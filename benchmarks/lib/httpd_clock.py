"""What the native httpd workers say about their own time: the loop
account and the per-request chain on the native JSON (`/__pingoo/metrics`), and each worker's ring of recent passes at
`/__pingoo/timeline`, placed here on the device trace's clock.

The counters (docs/OBSERVABILITY.md "Workers"), listener totals over the
workers:
  * `loop_us{phase}`: the workers' wall time, µs, cut at each event-loop
    pass's phase boundaries (wait, verdicts, supervise, accept, client,
    upstream, tls, close, sweep); the phases partition it;
  * `chain_us{segment}` and `chain_requests`: each answered request's
    residence cut by its stamps into read (first byte -> enqueued),
    verdict (-> applied), upstream (-> upstream response whole) and reply
    (-> last byte written); `chain_upstream_requests` have an upstream
    leg.

A program without them (the commit before they existed) reads as None
everywhere here, and nothing raises.

The pass rings. Each worker keeps its last 4,096 passes that did work,
each with the wait before it, in the shared block; any worker renders
all of them at `/__pingoo/timeline` as `httpd/<phase>` spans (pid 10 +
worker, µs on CLOCK_MONOTONIC). `join` puts them beside the device's
idle gaps of a traced run: the profiler's window is placed on the
monotonic clock through `lib/serve.py`'s `started_mono` + `start_s`
(the instant start_trace returned; the session began inside the
`start_s` before it, which is the alignment's error), and every idle
instant goes to a (sidecar phase, httpd phase) cell, each worker's
phase counting 1/workers of it. `python -m lib.httpd_clock <xplane.pb>
<out dir> <timeline.json> <anchor_mono> [<start_s>]` writes
`trace_spans.json` (lib/xspans.py's tables) with the join added.
"""

from __future__ import annotations

import bisect
import json
import os
import socket
import sys
from typing import Optional

from . import deploy, metrics as metrics_mod

SEGMENTS = ("read", "verdict", "upstream", "reply")
WAIT = "wait"
UNCOVERED = "uncovered"
PASS_PREFIX = "httpd/"
PASS_PID0 = 10          # a worker's pid in the timeline: 10 + worker


# -- the counters -------------------------------------------------------------


def _native(obs: dict, which: str) -> dict:
    return ((obs.get(which) or {}).get("native")) or {}


def _delta(obs: dict, dotted: str):
    return metrics_mod.delta(obs, {"native": dotted})


def wall_s(obs: dict) -> Optional[float]:
    """The seconds between the two snapshots of the window."""
    a = (obs.get("before") or {}).get("at_mono")
    b = (obs.get("after") or {}).get("at_mono")
    return None if a is None or b is None or b <= a else b - a


def counters(obs: dict) -> Optional[dict]:
    """The loop account and the chain over the window, read once a run
    (kept in `obs`); None where the program has no loop account."""
    if "_httpd_clock" in obs:
        return obs["_httpd_clock"]
    obs["_httpd_clock"] = out = None
    before, after = _native(obs, "before"), _native(obs, "after")
    if "loop_us" not in before or "loop_us" not in after:
        return None
    loop = {p: _delta(obs, f"loop_us.{p}") for p in after["loop_us"]}
    chain = {g: _delta(obs, f"chain_us.{g}") for g in SEGMENTS}
    if None in loop.values() or None in chain.values():
        return None
    out = {"loop_us": loop, "chain_us": chain,
           "passes": _delta(obs, "passes"),
           "empty_passes": _delta(obs, "empty_passes"),
           "chain_requests": _delta(obs, "chain_requests"),
           "chain_upstream_requests": _delta(obs, "chain_upstream_requests"),
           "workers": int(after.get("workers") or 1),
           "wall_s": wall_s(obs)}
    obs["_httpd_clock"] = out
    _log(out)
    return out


def chain_ms_mean(c: dict) -> Optional[dict]:
    """Each segment's mean in ms over the requests answered."""
    n = c["chain_requests"]
    if not n:
        return None
    return {g: c["chain_us"][g] / n / 1e3 for g in SEGMENTS}


def _log(c: dict) -> None:
    total = sum(c["loop_us"].values())
    if not total:
        return
    split = ", ".join(f"{p} {100.0 * us / total:.2f}%"
                      for p, us in sorted(c["loop_us"].items(),
                                          key=lambda kv: -kv[1]))
    wall = c["wall_s"]
    deploy.log(f"httpd: {c['workers']} worker(s), loop account "
               f"{total / 1e6:.3f} s over {wall if wall is None else round(wall, 3)}"
               f" s of wall: {split}; {c['passes']} passes, "
               f"{c['empty_passes']} empty")
    means = chain_ms_mean(c)
    if means:
        deploy.log("httpd: chain ms per request over "
                   f"{c['chain_requests']} requests ("
                   f"{c['chain_upstream_requests']} with an upstream leg): "
                   + ", ".join(f"{g} {v:.3f}" for g, v in means.items())
                   + f"; in httpd {sum(means.values()):.3f}")


# -- the pass rings -----------------------------------------------------------


def fetch_timeline(port: int, timeout: float = 10.0) -> Optional[dict]:
    """`/__pingoo/timeline` of the listener (whichever worker answers
    renders every worker's ring); None on any failure."""
    raw = (b"GET /__pingoo/timeline HTTP/1.1\r\nhost: bench\r\n"
           b"user-agent: bench-timeline\r\nconnection: close\r\n\r\n")
    chunks = []
    try:
        with socket.create_connection(("127.0.0.1", port),
                                      timeout=timeout) as s:
            s.sendall(raw)
            while True:
                chunk = s.recv(1 << 20)
                if not chunk:
                    break
                chunks.append(chunk)
        head, _, body = b"".join(chunks).partition(b"\r\n\r\n")
        if not head.startswith(b"HTTP/1.1 200"):
            return None
        return json.loads(body)
    except (OSError, ValueError):
        return None


def pass_spans(doc: Optional[dict]) -> dict:
    """{worker: [(start µs, end µs, phase)] sorted} of a timeline."""
    out: dict = {}
    for ev in (doc or {}).get("traceEvents") or []:
        name, pid = ev.get("name", ""), ev.get("pid", 0)
        if ev.get("ph") == "X" and pid >= PASS_PID0 and \
                name.startswith(PASS_PREFIX):
            out.setdefault(pid - PASS_PID0, []).append(
                (ev["ts"], ev["ts"] + ev["dur"], name[len(PASS_PREFIX):]))
    for spans in out.values():
        spans.sort()
    return out


def _pieces(spans: list, starts: list, a: int, b: int) -> list:
    """[(s, e, label)] covering [a, b) from sorted, non-overlapping
    `spans` [(s, e, label)] (`starts` their starts), the gaps between
    them `uncovered`."""
    out, t = [], a
    i = max(0, bisect.bisect_right(starts, a) - 1)
    while i < len(spans) and spans[i][0] < b:
        s, e, label = spans[i]
        i += 1
        if e <= t:
            continue
        if s > t:
            out.append((t, s, UNCOVERED))
            t = s
        out.append((t, min(e, b), label))
        t = min(e, b)
    if t < b:
        out.append((t, b, UNCOVERED))
    return out


def _label_at(pieces: list, cursor: list, t: int) -> str:
    while pieces[cursor[0]][1] <= t:
        cursor[0] += 1
    return pieces[cursor[0]][2]


def join_gaps(gaps: list, sidecar: list, workers: dict) -> dict:
    """{sidecar phase: {httpd phase: ns}} over the device's idle `gaps`
    [(g0, g1)], all on one clock: `sidecar` the drain loop's spans
    [(s, e, phase)], `workers` {worker: [(s, e, phase)]}. Every idle
    instant is counted once: under the sidecar phase that covers it and
    each worker's phase, for 1/workers of it."""
    table: dict = {}
    sc_starts = [s for s, _, _ in sidecar]
    w_lists = [(spans, [s for s, _, _ in spans])
               for _, spans in sorted(workers.items())]
    share = 1.0 / max(1, len(w_lists))
    for g0, g1 in gaps:
        if g1 <= g0:
            continue
        lists = [_pieces(sidecar, sc_starts, g0, g1)] + \
            [_pieces(spans, starts, g0, g1) for spans, starts in w_lists]
        cuts = sorted({t for pieces in lists for p in pieces for t in p[:2]})
        cursors = [[0] for _ in lists]
        for a, b in zip(cuts, cuts[1:]):
            sc = _label_at(lists[0], cursors[0], a)
            row = table.setdefault(sc, {})
            if not w_lists:
                row[UNCOVERED] = row.get(UNCOVERED, 0.0) + (b - a)
                continue
            for k in range(1, len(lists)):
                hp = _label_at(lists[k], cursors[k], a)
                row[hp] = row.get(hp, 0.0) + (b - a) * share
    return table


def idle_gaps(merged: list, lo: int, hi: int) -> list:
    """The stretches of [lo, hi) outside the merged busy intervals."""
    gaps, edge = [], lo
    for start, end in merged:
        if start > edge:
            gaps.append((edge, min(start, hi)))
        edge = max(edge, end)
    if hi > edge:
        gaps.append((edge, hi))
    return gaps


def join(trace: dict, timeline: Optional[dict], anchor_mono: float,
         start_s: Optional[float] = None) -> Optional[dict]:
    """The device's idle time of a trace (`xspans.extract`'s lists) by
    (sidecar phase, httpd phase), seconds averaged over the devices.
    The trace's first instant is placed at `anchor_mono` (monotonic s)."""
    from .xspans import self_times

    devices = trace.get("devices") or []
    spans = [tuple(s) for s in trace.get("spans") or []]
    if not devices:
        return None
    edges = [t for dev in devices for _, s, d in dev["ops"]
             for t in (s, s + d)]
    edges += [t for _, s, d, _ in spans for t in (s, s + d)]
    lo, hi = min(edges), max(edges)
    offset = lo - int(anchor_mono * 1e9)     # trace ns - monotonic ns
    workers = {w: [(s * 1000 + offset, e * 1000 + offset, p)
                   for s, e, p in ws]
               for w, ws in pass_spans(timeline).items()}
    sidecar = sorted((s, s + d, phase) for phase, s, d, _ in spans)
    table: dict = {}
    n = len(devices)
    for dev in devices:
        _, merged = self_times(dev["ops"])
        for sc, row in join_gaps(idle_gaps(merged, lo, hi), sidecar,
                                 workers).items():
            into = table.setdefault(sc, {})
            for hp, ns in row.items():
                into[hp] = into.get(hp, 0.0) + ns / n / 1e9
    by_httpd: dict = {}
    for row in table.values():
        for hp, s in row.items():
            by_httpd[hp] = by_httpd.get(hp, 0.0) + s
    covered = [(ws[0][0], ws[-1][1]) for ws in workers.values() if ws]
    return {
        "idle_by_sidecar_httpd": {
            sc: dict(sorted(row.items(), key=lambda kv: -kv[1]))
            for sc, row in sorted(table.items(),
                                  key=lambda kv: -sum(kv[1].values()))},
        "idle_by_httpd_phase": dict(sorted(by_httpd.items(),
                                           key=lambda kv: -kv[1])),
        "httpd_rings_s": [((a - lo) / 1e9, (b - lo) / 1e9)
                          for a, b in covered],
        "alignment_error_s": start_s,
        "anchor_mono": anchor_mono,
    }


def log_join(out: dict) -> None:
    j = out.get("httpd") or {}
    if not j:
        return
    deploy.log("spans: device idle ms by httpd phase (rings over "
               f"{j['httpd_rings_s']} s of the trace, alignment error "
               f"{j['alignment_error_s']} s): " + ", ".join(
                   f"{k} {v * 1e3:.3f}"
                   for k, v in j["idle_by_httpd_phase"].items()))
    for sc, row in list(j["idle_by_sidecar_httpd"].items())[:6]:
        deploy.log(f"spans: idle ms under sidecar/{sc}: " + ", ".join(
            f"{k} {v * 1e3:.3f}" for k, v in row.items()))


def _main(path: str, out_dir: str, timeline_path: str, anchor_mono: str,
          start_s: str = "") -> None:
    from . import xspans

    trace = xspans.extract(path)
    with open(timeline_path, encoding="utf-8") as f:
        timeline = json.load(f)
    out = xspans.reduce_spans(trace) or {}
    out["httpd"] = join(trace, timeline, float(anchor_mono),
                        float(start_s) if start_s else None)
    with open(os.path.join(out_dir, "trace_spans.json"), "w",
              encoding="utf-8") as f:
        json.dump(out, f)


if __name__ == "__main__":
    _main(*sys.argv[1:])
