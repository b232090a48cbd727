"""Sidecar drain loop: of the packed batch rows over the window, the
share in percent the host shipped to the device:
Δ`pingoo_staged_rows_total{kind="uploaded"}` /
Δ`pingoo_staged_rows_total{kind="padded"}`. A batch of n live rows ships
its first 64, 256 or all of its rows (the smallest that holds the n) and
the chip pads them to the batch, so 6.25 is every batch of a 1,024-row
batch at the 64-row height and 100 a program that ships every row. None
where the program has no such counter (a `.json` ratio would read 0
there)."""

from lib import metrics

COUNTER = "pingoo_staged_rows_total"


def read(obs):
    registry = (obs.get("after") or {}).get("registry") or []
    if not any(name == COUNTER for name, _, _ in registry):
        return None
    uploaded = metrics.delta(obs, {"registry": COUNTER, "labels": {
        "plane": "sidecar", "kind": "uploaded"}})
    padded = metrics.delta(obs, {"registry": COUNTER, "labels": {
        "plane": "sidecar", "kind": "padded"}})
    if uploaded is None or not padded:
        return None
    return 100.0 * uploaded / padded
