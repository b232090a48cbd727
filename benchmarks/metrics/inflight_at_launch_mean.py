"""Sidecar drain loop: batches in flight on all chips at a launch, the
one launched included, the mean over the window's launches:
Δ`pingoo_sidecar_inflight_at_launch_total` /
Δ`pingoo_pipeline_batches_total{plane="sidecar"}`. Whether one loop
keeps its chips fed: 1.0 is a loop that launches into an empty pipeline
every time, the ceiling is `--replicas` x `PINGOO_PIPELINE_DEPTH` (12
with four chips at the default depth 3). None where the program has no
such counter (a commit before `--replicas`)."""

from lib import metrics

COUNTER = "pingoo_sidecar_inflight_at_launch_total"


def read(obs):
    registry = (obs.get("after") or {}).get("registry") or []
    if not any(name == COUNTER for name, _, _ in registry):
        return None
    inflight = metrics.delta(obs, {"registry": COUNTER,
                                   "labels": {"plane": "sidecar"}})
    batches = metrics.delta(obs, {"registry": "pingoo_pipeline_batches_total",
                                  "labels": {"plane": "sidecar"}})
    if inflight is None or not batches:
        return None
    return inflight / batches
