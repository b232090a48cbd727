"""Device: milliseconds per batch the device ran nothing, inside the
traced interval (from the first to the last thing the trace saw of the
device or of the program's own spans), over the `lanes` programs that
ran there. Its split over the drain loop's phases goes to the run's
log and to `trace_spans.json`."""

from lib import xspans


def read(obs):
    out = xspans.spans(obs)
    if not out or not out["lanes_calls"]:
        return None
    return out["idle_s"] * 1e3 / out["lanes_calls"]
