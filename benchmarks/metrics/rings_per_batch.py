"""Sidecar drain loop: how many of its rings gave rows to a batch, the
mean over the window's batches: Δ`pingoo_batch_rings_total` /
Δ`pingoo_pipeline_batches_total{plane="sidecar"}`. One ring a native
httpd worker: 1.0 is a loop that serves its workers in turn, the worker
count one that merges them all into every batch. None where the program
has no such counter (a `.json` ratio would read 0 there)."""

from lib import metrics

COUNTER = "pingoo_batch_rings_total"


def read(obs):
    registry = (obs.get("after") or {}).get("registry") or []
    if not any(name == COUNTER for name, _, _ in registry):
        return None
    rings = metrics.delta(obs, {"registry": COUNTER,
                                "labels": {"plane": "sidecar"}})
    batches = metrics.delta(obs, {"registry": "pingoo_pipeline_batches_total",
                                  "labels": {"plane": "sidecar"}})
    if rings is None or not batches:
        return None
    return rings / batches
