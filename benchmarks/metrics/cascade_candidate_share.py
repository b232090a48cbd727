"""Kernels: of the rows the cascade's banks saw over the window, the
share in percent that Stage A left as candidates:
Δ`pingoo_cascade_rows_total{stage="candidate"}` / Δ`{stage="live"}`,
all banks together (a request counts once a bank). Counted by the lanes
program itself and folded by the sidecar where the batch resolves.
None where the program has no such counter."""

from lib import metrics

COUNTER = "pingoo_cascade_rows_total"


def read(obs):
    registry = (obs.get("after") or {}).get("registry") or []
    if not any(name == COUNTER for name, _, _ in registry):
        return None
    candidate, live = (
        metrics.delta(obs, {"registry": COUNTER,
                            "labels": {"plane": "sidecar", "stage": stage}})
        for stage in ("candidate", "live"))
    if candidate is None or not live:
        return None
    return 100.0 * candidate / live
