"""Sidecar drain loop: the useful rows of the padded batch, in percent:
verdicts applied over batches served times the configuration's
`max_batch`."""

from lib import metrics


def read(obs):
    verdicts = metrics.delta(obs, {"native": "verdicts"})
    batches = metrics.delta(obs, {"registry": "pingoo_pipeline_batches_total",
                                  "labels": {"plane": "sidecar"}})
    if verdicts is None or not batches:
        return None
    return 100.0 * verdicts / (batches * int(obs["config"]["max_batch"]))
