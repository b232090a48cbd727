"""Device time of the jitted verdict programs per batch: the summed
device durations of the `lanes` and prefilter (`stage_a`) programs in
the traced part of the window (the trace's XLA Modules line), over the
number of `lanes` programs that ran there: one per batch."""

PROGRAMS = ("lanes", "prefilter", "stage_a")


def read(obs):
    reduced = (obs.get("trace") or {}).get("reduced")
    if not reduced:
        return None
    modules = reduced["modules"]
    seconds = sum(m["seconds"] for name, m in modules.items()
                  if any(p in name for p in PROGRAMS))
    batches = sum(m["calls"] for name, m in modules.items()
                  if "lanes" in name)
    if not seconds or not batches:
        return None
    return seconds * 1e3 / batches
