"""Sidecar drain loop: of the batches completed over the window, the
share in percent that left because their device lanes were already
there when the loop looked (`how="ready"`), not because
`PINGOO_PIPELINE_DEPTH` batches were in flight (`depth`) or a pass
launched nothing (`drain`): Δ`pingoo_sidecar_completions_total
{how="ready"}` / Δ all three. Near 100 the host is the pace and a
verdict waits for the chip alone; near 0 the device is, and the depth
hides it. None where the program has no such counter (a loop that
completes by depth and drain alone does not say so)."""

from lib import metrics

COUNTER = "pingoo_sidecar_completions_total"


def read(obs):
    registry = (obs.get("after") or {}).get("registry") or []
    if not any(name == COUNTER for name, _, _ in registry):
        return None
    ready = metrics.delta(obs, {"registry": COUNTER, "labels": {
        "plane": "sidecar", "how": "ready"}})
    total = metrics.delta(obs, {"registry": COUNTER,
                                "labels": {"plane": "sidecar"}})  # every how
    if ready is None or not total:
        return None
    return 100.0 * ready / total
