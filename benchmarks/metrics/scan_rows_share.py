"""Kernels: of the rows of the padded batches, the share in percent that
the `dfa/*` and `pf/*` byte loops walk, over the window:
Δ`pingoo_scan_rows_total{kind="walked"}` / Δ`{kind="staged"}`, all
scanned fields together. `walked` is the device's own row extent, the
row tiles up to the batch's last row that has a byte (every row on a
mesh that shards batches), counted by the sidecar at encode from the
same lengths. None where the program has no such counter
(a commit whose loops walk every row of the padded batch reads 100 by
construction and does not say so)."""

from lib import metrics

COUNTER = "pingoo_scan_rows_total"


def read(obs):
    registry = (obs.get("after") or {}).get("registry") or []
    if not any(name == COUNTER for name, _, _ in registry):
        return None
    walked, staged = (
        metrics.delta(obs, {"registry": COUNTER,
                            "labels": {"plane": "sidecar", "kind": kind}})
        for kind in ("walked", "staged"))
    if walked is None or not staged:
        return None
    return 100.0 * walked / staged
