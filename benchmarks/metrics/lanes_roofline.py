"""The `lanes` program's share of its roofline, in percent, over the
traced part of the window: the least time the REQUESTS answered in
those seconds need (lib/geometry.py: their own field bytes over the
published HBM bandwidth, or one 8-bit compare per rule and byte over
the int8 peak, whichever is larger) over the device time the `lanes`
program took in the same seconds. The requests are the generator's own
records, those whose answer was whole inside the traced interval (the
profiler's start and length on the host's monotonic clock, which is the
generator's too), by their templates' real lengths; the rules are the
configuration's sources. Nothing is read from the program's counters,
and no "batch" enters: a program that splits or merges its calls does
the same work in the same seconds. Rows and bytes the program pads on
top count as time, not as work; what the share would read if the
padding were work goes to the log, not into a metric. A request
answered just inside the interval may have been scanned just before it
and the other way round at its end: with 7 calls in the interval that
is one part in seven either way, on a share that reads in millionths."""

import numpy as np

from lib import deploy, geometry


def lanes_in_trace(obs):
    """-> (device seconds, calls) of the `lanes` program in the trace."""
    reduced = (obs.get("trace") or {}).get("reduced")
    if not reduced:
        return None, 0
    lanes = [m for name, m in reduced["modules"].items() if "lanes" in name]
    return (sum(m["seconds"] for m in lanes) or None,
            sum(m["calls"] for m in lanes))


def answered_in_trace(obs):
    """The generator's records whose answer was whole while the
    profiler traced, lead-in and lead-out included; None when the
    interval is not known."""
    done = (obs.get("trace") or {}).get("done") or {}
    t0, rec = obs.get("gen_t0_mono"), obs.get("all_records")
    if t0 is None or rec is None or "traced_s" not in done:
        return None
    start = (done["started_mono"] + done["start_s"] - t0) * 1e9
    end = start + done["traced_s"] * 1e9
    return rec[(rec["outcome"] == 0) & (rec["done_ns"] >= start)
               & (rec["done_ns"] < end)]


def read(obs):
    seconds, calls = lanes_in_trace(obs)
    rec = answered_in_trace(obs)
    if not seconds or rec is None or not len(rec):
        return None
    kind = obs["device"]["kind"]
    per_field = geometry.rules_per_field(obs["sources"])
    work = np.array([geometry.request_work(t, per_field)
                     for t in obs["templates"]], np.float64)
    n_bytes, ops = work[rec["tmpl"]].sum(axis=0)
    least = geometry.least_seconds(n_bytes, ops, kind)
    note = (f"lanes: the {len(rec)} requests answered in the traced "
            f"{obs['trace']['done']['traced_s']:.3f} s need "
            f"{least['seconds'] * 1e6:.3f} us ({least['bound']} bound); "
            f"{calls} calls took {seconds * 1e3:.3f} ms of device time")
    widths = {ls.get("field"): v
              for n, ls, v in (obs.get("after") or {}).get("registry") or []
              if n == "pingoo_staging_field_cap"
              and ls.get("plane", "sidecar") == "sidecar"}
    if widths:
        padded = geometry.least_seconds(*geometry.padded_work(
            calls * int(obs["config"]["max_batch"]), widths, per_field), kind)
        note += (f"; as many padded batches ({widths}) would need "
                 f"{padded['seconds'] * 1e6:.3f} us")
    deploy.log(note)
    return 100.0 * least["seconds"] / seconds
