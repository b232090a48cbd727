"""Sidecar drain loop: device arrays the loop materialised on the host
a batch, the mean over the window's batches:
Δ`pingoo_sidecar_host_copies_total` /
Δ`pingoo_pipeline_batches_total{plane="sidecar"}`. 1.0 is a loop that
brings a batch's lanes, cascade counts, attribution lane and Stage-A
counts home in one stacked array; a loop that copies the three apart
would read 3.0 with a prefilter and provenance on, and a batch the
interpreter served counts none. None where the program has no such
counter (a `.json` ratio would read 0 there)."""

from lib import metrics

COUNTER = "pingoo_sidecar_host_copies_total"


def read(obs):
    registry = (obs.get("after") or {}).get("registry") or []
    if not any(name == COUNTER for name, _, _ in registry):
        return None
    copies = metrics.delta(obs, {"registry": COUNTER,
                                 "labels": {"plane": "sidecar"}})
    batches = metrics.delta(obs, {"registry": "pingoo_pipeline_batches_total",
                                  "labels": {"plane": "sidecar"}})
    if copies is None or not batches:
        return None
    return copies / batches
