"""From a profiler trace (`.xplane.pb`) to numbers.

Two steps, so that the arithmetic can be checked on a small recorded
trace without the profiler: `extract` (a child process, the only code
here that imports jax - for `jax.profiler.ProfileData`, on the CPU
platform, after the server has let go of the chip) turns the file into
plain intervals; `reduce_events` turns intervals into busy time, idle
gaps and time per program.

Device planes are named `/device:TPU:<n>`; their line `XLA Ops` holds
one event per operation that ran on the device, `XLA Modules` one per
jitted program. Host planes hold the threads' own events.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Optional

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
OP_NAME_CHARS = 96


def extract(path: str) -> dict:
    """The trace as plain lists (runs in the child)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, summary = [], [], []
    names: dict = {}    # an op's name is its whole HLO line: keep its head, once
    for plane in data.planes:
        lines = []
        if not list(plane.lines):
            summary.append({"plane": plane.name, "line": None, "events": 0})
        for line in plane.lines:
            events = []
            for ev in line.events:
                name = ev.name[:OP_NAME_CHARS]
                events.append((names.setdefault(name, name),
                               int(ev.start_ns), int(ev.duration_ns)))
            summary.append({"plane": plane.name, "line": line.name,
                            "events": len(events)})
            lines.append({"name": line.name, "events": events})
        if plane.name.startswith("/device:") and \
                any(ln["name"] == OPS_LINE for ln in lines):
            devices.append({"name": plane.name, "lines": [
                ln for ln in lines if ln["name"] in (OPS_LINE, MODULES_LINE)]})
        elif plane.name.startswith("/host:"):
            # the busiest threads only: enough to name an idle gap by
            lines.sort(key=lambda ln: -len(ln["events"]))
            host.append({"name": plane.name, "lines": [
                {"name": ln["name"], "events": ln["events"][:200000]}
                for ln in lines[:8]]})
    return {"devices": devices, "host": host, "summary": summary}


def _union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out: list = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def _host_name_at(host: list, t: int) -> str:
    """What the host was doing at time t: the innermost event of the
    busiest host thread that covers t, else "host"."""
    best = None
    for plane in host:
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if start <= t < start + dur and \
                        (best is None or dur < best[1]):
                    best = (name, dur)
    return best[0] if best else "host"


def reduce_events(trace: dict, top: int = 10) -> Optional[dict]:
    """Busy seconds (the union of the device's operation intervals,
    averaged over the devices), the traced window, time per program
    and per operation, the longest idle gaps. None when no operation
    ran on a device: there is nothing to read."""
    devices = trace.get("devices") or []
    spans, busy, ops_time, modules = [], [], {}, {}
    gaps: list = []
    for dev in devices:
        ops = next((ln["events"] for ln in dev["lines"]
                    if ln["name"] == OPS_LINE), [])
        if not ops:
            continue
        merged = _union([(s, s + d) for _, s, d in ops])
        busy.append(sum(e - s for s, e in merged))
        spans.append((merged[0][0], merged[-1][1]))
        for name, _, dur in ops:
            short = name[:OP_NAME_CHARS]
            ops_time[short] = ops_time.get(short, 0) + dur
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            gaps.append((s1 - e0, e0))
        for ln in dev["lines"]:
            if ln["name"] == MODULES_LINE:
                for name, _, dur in ln["events"]:
                    m = modules.setdefault(name, [0, 0])
                    m[0] += dur
                    m[1] += 1
    if not busy:
        return None
    # The window: from the first to the last thing the trace saw, on the
    # host or on a device (the device may idle at both ends).
    starts = [s for s, _ in spans]
    ends = [e for _, e in spans]
    for plane in trace.get("host") or []:
        for line in plane["lines"]:
            if line["events"]:
                starts.append(min(s for _, s, _ in line["events"]))
                ends.append(max(s + d for _, s, d in line["events"]))
    window_ns = max(ends) - min(starts)
    n_dev = len(busy)
    gaps.sort(reverse=True)
    host = trace.get("host") or []
    idle = {}
    for dur, start in gaps[:top]:
        name = _host_name_at(host, start + dur // 2)
        idle[name] = idle.get(name, 0) + dur
    device_ops = sorted(ops_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": sum(busy) / n_dev / 1e9,
        "window_s": window_ns / 1e9,
        "devices": n_dev,
        "modules": {k: {"seconds": v[0] / n_dev / 1e9, "calls": v[1]}
                    for k, v in modules.items()},
        "breakdown": {
            "device_ops": [[k, v / n_dev / 1e9] for k, v in device_ops],
            "idle_gaps": [[k, v / 1e9] for k, v in sorted(
                idle.items(), key=lambda kv: -kv[1])[:top]],
        },
    }


def reduce_file(path: Optional[str], out_dir: str,
                timeout: float = 240.0) -> Optional[dict]:
    """Extract and reduce in a child (CPU platform, the chip is free by
    now) that is given `timeout` seconds. None, with the reason logged,
    when anything is missing."""
    if not path or not os.path.isfile(path):
        print("[bench] trace: no .xplane.pb was written", file=sys.stderr)
        return None
    out = os.path.join(out_dir, "trace_reduced.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), path, out_dir],
            env=env, capture_output=True, text=True, timeout=timeout)
        if proc.returncode != 0:
            print(f"[bench] trace: extract rc={proc.returncode} "
                  f"{proc.stderr[-500:]}", file=sys.stderr)
            return None
        with open(out, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError, subprocess.TimeoutExpired) as exc:
        print(f"[bench] trace: {exc!r}", file=sys.stderr)
        return None


def _main(path: str, out_dir: str) -> None:
    """The child: extract, reduce, and leave three small files: the
    reduced numbers, the list of planes and lines, and the head of each
    line to read by eye (a few hundred KB; the events stay in memory)."""
    trace = extract(path)
    with open(os.path.join(out_dir, "trace_summary.json"), "w",
              encoding="utf-8") as f:
        json.dump(trace["summary"], f)
    with open(os.path.join(out_dir, "trace_sample.json"), "w",
              encoding="utf-8") as f:
        json.dump({kind: [{"name": p["name"], "lines": [
            {"name": ln["name"], "events": ln["events"][:150]}
            for ln in p["lines"]]} for p in trace[kind]]
            for kind in ("devices", "host")}, f)
    with open(os.path.join(out_dir, "trace_reduced.json"), "w",
              encoding="utf-8") as f:
        json.dump(reduce_events(trace), f)


if __name__ == "__main__":
    _main(sys.argv[1], sys.argv[2])
