"""Kernels: of the rows the cascade's banks saw over the window, the
share in percent that an APPROXIMATE DFA flagged and the bank's exact
scan re-scanned: Δ`pingoo_cascade_rows_total{stage="recheck"}` /
Δ`{stage="live"}`, all banks together (banks with an exact DFA or none
add live rows and no recheck). None where the program has no such
counter."""

from lib import metrics

COUNTER = "pingoo_cascade_rows_total"


def read(obs):
    registry = (obs.get("after") or {}).get("registry") or []
    if not any(name == COUNTER for name, _, _ in registry):
        return None
    recheck, live = (
        metrics.delta(obs, {"registry": COUNTER,
                            "labels": {"plane": "sidecar", "stage": stage}})
        for stage in ("recheck", "live"))
    if recheck is None or not live:
        return None
    return 100.0 * recheck / live
