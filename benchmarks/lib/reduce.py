"""From what a run observed to its result line: the end-to-end metrics,
the comparison that decides `correct`, the per-layer metrics.

The yardstick: records of the generator (one per request due in the
window: due, sent, done, status, outcome) against the plain reference's
expected status of each template.
"""

from __future__ import annotations

import os

import numpy as np

from . import deploy, metrics as metrics_mod, xplane

DEGRADES = {"registry": "pingoo_degrade_total", "labels": {"plane": "sidecar"}}

ANSWERED, CONN_LOST, NO_ANSWER, UNSENT = 0, 1, 2, 3


def compare(records: np.ndarray, want: np.ndarray, releases=None,
            deadline_ms=None, at_once: bool = False) -> dict:
    """Every answer of the window against the reference's (`want`, one
    per record), each judged by what it says.

    The configuration states when the native plane lets a request
    through uninspected: no verdict `deadline_ms` after it was enqueued,
    or at once while the sidecar's heartbeat is stale or the ring is
    full (`at_once`: the native plane's own counters say it was in that
    state during these seconds). A response released so is late, or not
    inspected, and it is counted as failed; it is not a wrong verdict.
    The program marks no response as released, so each missed block is
    held against its own wait: it is a release's only if the client
    waited for it as long as the deadline (or `at_once` holds), and no
    more of them than the native plane counted (`releases`; None when
    its counters could not be read). Any other missed block, and every
    false block, is a wrong answer."""
    ok = records["outcome"] == ANSWERED
    got, want = records["status"][ok], want[ok]
    missed = (got == 200) & (want == 403)
    released = np.zeros(len(got), dtype=bool)
    if at_once:
        released[:] = True
    elif deadline_ms is not None:
        waited_ms = (records["done_ns"][ok] - records["sent_ns"][ok]) / 1e6
        released = waited_ms >= deadline_ms
    excused = int((missed & released).sum())
    if releases is not None:
        excused = min(excused, int(releases))
    never = (records["outcome"] == CONN_LOST) | (records["outcome"] == NO_ANSWER)
    return {"compared": int(ok.sum()), "right": int((got == want).sum()),
            "false_blocks": int(((got == 403) & (want == 200)).sum()),
            "missed_blocks": int(missed.sum()) - excused,
            "missed_released": excused,
            "other_statuses": int(((got != 200) & (got != 403)).sum()),
            "never_answered": int(never.sum()),
            "fail_open": int(releases or 0)}


def decide(cmp: dict) -> tuple:
    """-> (correct, {name: (value, limit)}). Exact comparisons: the
    limit of each is 0, and at least one answer has to have been
    compared."""
    compared = {name: (cmp[name], 0) for name in (
        "false_blocks", "missed_blocks", "other_statuses", "never_answered")}
    compared["answers_not_compared"] = (0 if cmp["compared"] else 1, 0)
    return all(v <= limit for v, limit in compared.values()), compared


def latencies_ms(records: np.ndarray, lost_latency_ms: float) -> np.ndarray:
    """Due time -> last byte of every record; one that never got a
    whole response counts at `lost_latency_ms`, the longest the
    generator waits."""
    lat = np.full(len(records), lost_latency_ms)
    ok = records["outcome"] == ANSWERED
    lat[ok] = (records["done_ns"][ok] - records["due_ns"][ok]) / 1e6
    return lat


def end_to_end(records: np.ndarray, want: np.ndarray, window_ns: tuple,
               seconds: float, fail_open_delta, lost_latency_ms: float,
               all_records=None, all_want=None) -> dict:
    """The user's numbers. A latency runs from when the request was DUE
    to the last byte of its response, over the requests due in the
    window. The rate is the right answers whose last byte arrived
    inside the window (whenever they were due: the lead-in's
    stragglers count, the window's own do not), less the fail-opens
    of the same seconds, over all the window's seconds."""
    out = {}
    if len(records):
        lat = latencies_ms(records, lost_latency_ms)
        out["latency_p50_ms"] = float(np.percentile(lat, 50))
        out["latency_p90_ms"] = float(np.percentile(lat, 90))
        every, every_want = ((records, want) if all_records is None
                             else (all_records, all_want))
        in_time = ((every["outcome"] == ANSWERED)
                   & (every["done_ns"] >= window_ns[0])
                   & (every["done_ns"] < window_ns[1]))
        right = every["status"][in_time] == every_want[in_time]
        inspected = max(0, int(right.sum()) - int(fail_open_delta or 0))
        out["inspected_rps"] = inspected / seconds
    return out


def latency_percentiles(records) -> dict:
    """More of the distribution than the line carries, for result.json."""
    if records is None or not len(records):
        return {}
    ok = records[records["outcome"] == ANSWERED]
    if not len(ok):
        return {}
    lat = (ok["done_ns"] - ok["due_ns"]) / 1e6
    return {f"p{q}": float(np.percentile(lat, q))
            for q in (50, 75, 90, 95, 99)} | {"mean": float(lat.mean())}


def latency_slices(records, window_ns, slice_s: float = 5.0) -> list:
    """[p50, p99] in ms of the answered requests due in each slice of
    the window: for result.json, to tell a run that drifts from runs
    that differ."""
    out = []
    if records is None or not window_ns or not len(records):
        return out
    ok = records[records["outcome"] == ANSWERED]
    lat = (ok["done_ns"] - ok["due_ns"]) / 1e6
    edge = window_ns[0]
    while edge < window_ns[1]:
        part = lat[(ok["due_ns"] >= edge)
                   & (ok["due_ns"] < edge + slice_s * 1e9)]
        if len(part):
            out.append([float(np.percentile(part, 50)),
                        float(np.percentile(part, 99))])
        edge += int(slice_s * 1e9)
    return out


def answered_slices(records, window_ns, slice_s: float = 5.0) -> list:
    """Whole answers a second in each slice of the window, by when
    their last byte came: for result.json, to tell a rate that drifts
    within a run from runs that differ."""
    out = []
    if records is None or not window_ns or not len(records):
        return out
    done = records["done_ns"][records["outcome"] == ANSWERED]
    step = int(slice_s * 1e9)
    for edge in range(window_ns[0], window_ns[1], step):
        out.append(float(((done >= edge) & (done < edge + step)).sum())
                   / slice_s)
    return out


def device_block(run, obs: dict, trace: dict) -> dict:
    dev = dict(run.device)
    stats = obs.get("memstats") or {}
    peaks = [p for p in stats.get("peak_bytes_in_use") or [] if p]
    dev["memory_peak_bytes"] = max(peaks) if peaks else None
    if trace:
        dev["busy_s"] = trace["busy_s"]
        dev["window_s"] = trace["window_s"]
    return dev


def lost_ms(seconds: float) -> float:
    """The latency a request with no whole response counts at, the
    longest wait: the window, its lead-out and the generator's drain."""
    return (seconds + 11.0) * 1e3


def empty_line(run) -> dict:
    return {"correct": False, "attempted": 0, "failed": 0, "metrics": {},
            "device": dict(run.device),
            "compared": {"answers_not_compared": {"value": 1, "limit": 0}}}


def releases(whole: dict, config: dict) -> tuple:
    """-> (how many requests the native plane let through uninspected,
    the configuration's deadline for it, whether it did so at once),
    over `whole`: from the window's start to the end of the drain, since
    the window's last requests meet their deadline after it has closed."""
    at_once = any(metrics_mod.delta(whole, {"native": counter})
                  for counter in ("degraded_entered", "ring.enqueue_full"))
    return (metrics_mod.delta(whole, {"native": "fail_open"}),
            config.get("fail_open_deadline_ms"), at_once)


def result_line(run, obs: dict) -> dict:
    cell = run.cell
    records = obs["records"]
    want = run.want(records)
    # what reached its client without a device verdict in the window's
    # seconds: the native plane's releases, and the sidecar's falls
    # down its degrade ladder
    fo = (metrics_mod.delta(obs, {"native": "fail_open"}) or 0) \
        + (metrics_mod.delta(obs, DEGRADES) or 0)
    whole = dict(obs, after=obs.get("after_drain") or obs.get("after"))
    cmp = compare(records, want, *releases(whole, cell.config))
    correct, compared = decide(cmp)
    attempted = int(obs.get("attempted", len(records)))
    cmp["sidecar_degrades"] = int(metrics_mod.delta(whole, DEGRADES) or 0)
    inspected_all = max(0, cmp["right"] - cmp["fail_open"]
                        - cmp["sidecar_degrades"])
    failed = max(0, attempted - inspected_all)

    trace = None
    if run.trace:
        trace = xplane.reduce_file(getattr(run, "trace_file", None),
                                   run.out_dir, run.seconds_left())
    units = {m["name"]: m["unit"]
             for m in cell.bench["end_to_end"] + cell.bench["per_layer"]}
    values: dict = {}
    if not run.rehearsal:
        if run.trace:
            # readers see the requests of the seconds the profiler
            # did not slow, like the counters (`all_records` has the
            # rest: a reader of the trace takes its own seconds)
            until = obs.get("counters_until_ns")
            quiet = records if until is None else \
                records[records["due_ns"] < until]
            lo, hi = obs.get("window_ns", (0, 0))
            reader_obs = dict(obs, records=quiet, compare=cmp,
                              window_ns=(lo, hi if until is None else until),
                              templates=run.templates,
                              sources=run.sources,
                              trace=dict(obs.get("trace") or {},
                                         reduced=trace),
                              cell=cell.cell, config=cell.config,
                              traffic=cell.traffic, device=run.device)
            metrics_dir = os.path.join(cell.bench_dir, "metrics")
            for name in cell.metric_names("per_layer"):
                try:
                    value = metrics_mod.load_reader(metrics_dir, name)(
                        reader_obs)
                except Exception as exc:   # one reader never costs the line
                    deploy.log(f"metric {name}: {exc!r}")
                    value = None
                if value is not None:
                    values[name] = float(value)
        else:
            every = obs.get("all_records")
            e2e = end_to_end(records, want, obs.get("window_ns", (0, 0)),
                             obs["seconds"], fo, lost_ms(obs["seconds"]),
                             every, None if every is None else run.want(every))
            e2e["setup_s"] = obs.get("setup_s")
            if attempted:
                e2e["inspected_share"] = 100.0 * (attempted - failed) \
                    / attempted
            for name in cell.metric_names("end_to_end"):
                if e2e.get(name) is not None:
                    values[name] = float(e2e[name])
    line = {"correct": bool(correct), "attempted": attempted,
            "failed": int(failed),
            "metrics": {k: {"value": v, "unit": units[k]}
                        for k, v in values.items()},
            "device": device_block(run, obs, trace)}
    if trace and trace.get("breakdown"):
        line["breakdown"] = trace["breakdown"]
    line["counts"] = dict(cmp, unsent=int((records["outcome"] == UNSENT).sum()),
                          lost=int((records["outcome"] == CONN_LOST).sum()),
                          no_answer=int((records["outcome"] == NO_ANSWER).sum()),
                          sequence_exhausted=obs.get("sequence_exhausted"),
                          warm_rounds=run.notes.get("warm_rounds"),
                          warm_clean=run.notes.get("warm_clean"))
    line["compared"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in compared.items()}
    return line
