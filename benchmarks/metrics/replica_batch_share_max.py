"""Sidecar drain loop: of the batches launched over the window, the
busiest chip's share in percent, from
Δ`pingoo_sidecar_replica_batches_total{device}`: with `--replicas 4` 25
is even and 100 is one chip doing everything (the loop puts a batch on
the chip with the fewest in flight, ties round-robin). None where the
program has no such counter (a commit before `--replicas`), or no batch
was launched."""

from lib import metrics

COUNTER = "pingoo_sidecar_replica_batches_total"


def read(obs):
    registry = (obs.get("after") or {}).get("registry") or []
    chips = {labels.get("device") for name, labels, _ in registry
             if name == COUNTER and labels.get("plane") == "sidecar"}
    batches = [metrics.delta(obs, {"registry": COUNTER, "labels": {
        "plane": "sidecar", "device": chip}}) for chip in chips]
    if not batches or None in batches or sum(batches) <= 0:
        return None
    return 100.0 * max(batches) / sum(batches)
