"""Kernels: how full the recheck ladder's buckets were over the window,
in percent: Δ`pingoo_cascade_rows_total{stage="recheck"}` /
Δ`pingoo_cascade_bucket_rows_total{ladder="recheck"}`, all banks
together. The exact re-scan walks the rows of the bucket it gathered
the flagged rows into (the smallest of the ladder 1024, 512, ..., 64
that holds them), so 100 less this is the padding the ladder pays. None
where the program has no such counter, or nothing was rechecked."""

from lib import metrics

ROWS = "pingoo_cascade_rows_total"
BUCKETS = "pingoo_cascade_bucket_rows_total"


def read(obs):
    registry = (obs.get("after") or {}).get("registry") or []
    if not any(name == BUCKETS for name, _, _ in registry):
        return None
    rows = metrics.delta(obs, {"registry": ROWS,
                               "labels": {"plane": "sidecar",
                                          "stage": "recheck"}})
    buckets = metrics.delta(obs, {"registry": BUCKETS,
                                  "labels": {"plane": "sidecar",
                                             "ladder": "recheck"}})
    if rows is None or not buckets:
        return None
    return 100.0 * rows / buckets
