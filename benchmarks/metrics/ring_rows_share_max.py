"""Sidecar drain loop: of the request rows the loop dequeued over the
window, the busiest ring's share in percent, from
Δ`pingoo_ring_rows_total{ring}`: one ring a native httpd worker, so with
four workers 25 is even and 100 is one worker doing everything. None
where the program has no such counter (a commit before the drain loop
named its rings), or no row was dequeued."""

from lib import metrics

COUNTER = "pingoo_ring_rows_total"


def read(obs):
    registry = (obs.get("after") or {}).get("registry") or []
    rings = {labels.get("ring") for name, labels, _ in registry
             if name == COUNTER and labels.get("plane") == "sidecar"}
    rows = [metrics.delta(obs, {"registry": COUNTER, "labels": {
        "plane": "sidecar", "ring": ring}}) for ring in rings]
    if not rows or None in rows or sum(rows) <= 0:
        return None
    return 100.0 * max(rows) / sum(rows)
