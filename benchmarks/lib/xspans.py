"""What the program's own names say about a run: its scopes and spans in
the profiler's trace, and its drain loop's phase counters.

The served program names its device work (`jax.named_scope`, one fixed
vocabulary `<kind>/<key>`, docs/OBSERVABILITY.md) and its drain loop's
phases (`jax.profiler.TraceAnnotation("sidecar/<phase>", batch=, rows=)`,
and the counter `pingoo_sidecar_loop_ms_total{phase}`). This module is
a second, bounded reading of the `.xplane.pb` that `lib/xplane.py`
reduced already, for three tables:

  * device SELF time by scope. The device's `XLA Ops` events nest (a
    `while` holds its body's operations), so each instant goes to the
    innermost event; an event's scope is the deepest component of the
    vocabulary in its instruction's `op_name`, else its enclosing
    event's, else `unscoped`. The scopes add up to the device's busy
    time. On this libtpu neither the event's name (the HLO line,
    printed without metadata) nor any stat `ProfileData` shows carries
    the `op_name`; the profiler's `/host:metadata` plane does: it holds
    each executed module's optimized `HloProto`, and in it every
    instruction's `metadata.op_name` (read here straight off the
    protobuf wire: four nested messages, no schema needed).
  * the program's `sidecar/*` spans with their `batch`.
  * every device idle stretch inside the traced interval, split over
    the `sidecar/<phase>` spans that cover it (what no span covers is
    `uncovered`); the twenty longest gaps keep the phase and batch at
    their midpoint.

Like `xplane.reduce_file`, the file is read in a child on the CPU
platform (only the child imports jax). A program without these names
(the commit before they existed) reads as nothing: `spans()` still
gives the idle time, every scope is `unscoped`, no phase is found, and
the readers that need a name return None.
"""

from __future__ import annotations

import bisect
import heapq
import json
import os
import re
import subprocess
import sys
import time
from typing import Optional

from . import deploy, metrics as metrics_mod

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KEYED = ("pf", "nfa", "dfa", "win", "grp", "list")
BARE = ("unpack", "num", "bool", "act")
UNSCOPED = "unscoped"
UNCOVERED = "uncovered"
SPAN_PREFIX = "sidecar/"
SCAN_KINDS = ("nfa", "dfa", "win")
LOOP_COUNTER = "pingoo_sidecar_loop_ms_total"
BUSY_PHASES = ("poll", "encode", "prefilter", "dispatch", "host_rules",
               "resolve", "provenance", "bodies", "swap")
BATCHES = {"registry": "pingoo_pipeline_batches_total",
           "labels": {"plane": "sidecar"}}
_OP_NAME = re.compile(r'op_name="([^"]*)"')
MIN_CHILD_S = 20.0     # less than this left of the run: do not start


# -- names --------------------------------------------------------------------


def scope_of(op_name: str) -> str:
    """The deepest component of the vocabulary in an operation's
    `op_name` ("jit(lanes_packed)/act/bool/dfa/url/while/body/..." ->
    "dfa/url"), else `unscoped`."""
    parts = op_name.split("/")
    for i in range(len(parts) - 1, -1, -1):
        if parts[i] in BARE:
            return parts[i]
        if i and parts[i - 1] in KEYED:
            return f"{parts[i - 1]}/{parts[i]}"
    return UNSCOPED


def _event_op_name(name: str, stats: dict, hlo: dict) -> tuple:
    """-> (op_name, where it was found) of one device operation: the
    HLO line's own `metadata={op_name="..."}`, else a string stat that
    holds a path (the profiler's `tf_op`), else its instruction's entry
    in its module's HloProto (`hlo`: instruction name -> op_name)."""
    found = _OP_NAME.search(name)
    if found:
        return found.group(1), "hlo_line"
    for key, value in stats.items():
        if isinstance(value, str) and "/" in value and "jit(" in value:
            return value, f"stat:{key}"
    instruction = name[1:].split(" ", 1)[0] if name.startswith("%") else name
    if instruction in hlo:
        return hlo[instruction], "hlo_proto"
    return "", "none"


# -- the protobuf wire, as far as the op names need it ------------------------


def _varint(buf, i: int) -> tuple:
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: a varint as int, a
    length-delimited field as a view of its bytes (fixed-width fields
    are skipped: nothing here reads one)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire}")


def _sub(buf, number: int):
    """The length-delimited fields `number` of a message."""
    for field, value in _fields(buf):
        if field == number and not isinstance(value, int):
            yield value


def _text(buf, number: int) -> str:
    return next((bytes(v).decode("utf-8", "replace")
                 for v in _sub(buf, number)), "")


def hlo_op_names(path: str) -> dict:
    """{module event name: {instruction name: op_name}} from the
    HloProtos in the trace's `/host:metadata` plane. XSpace.planes=1;
    XPlane.name=2, .event_metadata=4 (map entry: value=2);
    XEventMetadata.name=2, .stats=5; XStat.bytes_value=6 (the HloProto);
    HloProto.hlo_module=1; HloModuleProto.computations=3;
    HloComputationProto.instructions=2; HloInstructionProto.name=1,
    .metadata=7; OpMetadata.op_name=2."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict = {}
    for plane in _sub(space, 1):
        if _text(plane, 2) != "/host:metadata":
            continue
        for entry in _sub(plane, 4):
            for meta in _sub(entry, 2):
                ops = out.setdefault(_text(meta, 2), {})
                for stat in _sub(meta, 5):
                    for proto in _sub(stat, 6):
                        for module in _sub(proto, 1):
                            for comp in _sub(module, 3):
                                for instr in _sub(comp, 2):
                                    ops[_text(instr, 1)] = next(
                                        (_text(m, 2)
                                         for m in _sub(instr, 7)), "")
    return out


def _module_ops(hlo: dict, module: str) -> dict:
    """A module event's instruction table: by its whole name
    (`jit_lanes_packed(<id>)`), else by its name without the id."""
    if module in hlo:
        return hlo[module]
    base = module.split("(", 1)[0]
    return next((ops for name, ops in hlo.items()
                 if name.split("(", 1)[0] == base), {})


# -- the child: the file as plain lists ---------------------------------------


def extract(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    hlo = hlo_op_names(path)
    devices, spans, sample = [], [], []
    scopes: dict = {}      # (module, event name) -> scope, once each
    found_in: dict = {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            # which program an operation belongs to: the module event
            # that holds it in time (instruction names repeat across
            # programs)
            modules = sorted(
                (int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                 ev.name) for ev in lines[MODULES_LINE].events) \
                if MODULES_LINE in lines else []
            starts = [m[0] for m in modules]
            ops = []
            for ev in lines[OPS_LINE].events:
                name, start = ev.name, int(ev.start_ns)
                at = bisect.bisect_right(starts, start) - 1
                module = modules[at][2] if at >= 0 \
                    and start < modules[at][1] else ""
                scope = scopes.get((module, name))
                if scope is None:
                    stats = dict(ev.stats)
                    op_name, where = _event_op_name(
                        name, stats, _module_ops(hlo, module))
                    scope = scopes[module, name] = scope_of(op_name)
                    found_in[where] = found_in.get(where, 0) + 1
                    if len(sample) < 60:
                        sample.append({"name": name[:300], "module": module,
                                       "op_name": op_name, "scope": scope})
                ops.append((scope, start, int(ev.duration_ns)))
            if ops:
                devices.append({
                    "name": plane.name, "ops": ops,
                    "lanes_calls": sum("lanes" in m[2] for m in modules)})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        batch = dict(ev.stats).get("batch")
                        spans.append((ev.name[len(SPAN_PREFIX):],
                                      int(ev.start_ns), int(ev.duration_ns),
                                      batch))
    return {"devices": devices, "spans": spans, "sample": sample,
            "op_name_found_in": found_in,
            "hlo_modules": {name: len(ops) for name, ops in hlo.items()}}


# -- the arithmetic -----------------------------------------------------------


def self_times(ops: list) -> tuple:
    """-> ({scope: self ns}, merged busy intervals) of one device's
    nested operations [(scope, start, dur)]: an operation's self time
    is its duration less what the operations directly inside it cover."""
    order = sorted(ops, key=lambda op: (op[1], -op[2]))
    by_scope: dict = {}
    stack: list = []       # [end, scope, self ns], innermost last
    merged: list = []

    def close(entry):
        by_scope[entry[1]] = by_scope.get(entry[1], 0) + max(0, entry[2])

    for scope, start, dur in order:
        end = start + dur
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        if stack:
            # what lies inside the parent is the parent's no longer; an
            # operation without a name of its own (a copy, a tuple, a
            # parameter the compiler put in) is its parent's
            stack[-1][2] -= min(end, stack[-1][0]) - start
            end = min(end, stack[-1][0])
            if scope == UNSCOPED:
                scope = stack[-1][1]
        elif merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
        stack.append([end, scope, end - start])
    while stack:
        close(stack.pop())
    return by_scope, merged


def idle_by_phase(merged: list, spans: list, lo: int, hi: int) -> tuple:
    """-> ({phase: idle ns}, [(gap ns, start, phase, batch)]) for one
    device: the stretches of [lo, hi) outside `merged`, split over the
    spans [(phase, start, dur, batch)] that cover them; each gap keeps
    the phase and batch at its midpoint."""
    gaps, edge = [], lo
    for start, end in merged:
        if start > edge:
            gaps.append((edge, min(start, hi)))
        edge = max(edge, end)
    if hi > edge:
        gaps.append((edge, hi))
    spans = sorted(spans, key=lambda s: s[1])
    by_phase: dict = {}
    named, first = [], 0
    for g0, g1 in gaps:
        if g1 <= g0:
            continue
        while first < len(spans) and \
                spans[first][1] + spans[first][2] <= g0:
            first += 1
        covered, mid, at_mid = 0, (g0 + g1) // 2, (UNCOVERED, None)
        for j in range(first, len(spans)):
            phase, s0, dur, batch = spans[j]
            if s0 >= g1:
                break
            over = min(g1, s0 + dur) - max(g0, s0)
            if over > 0:
                by_phase[phase] = by_phase.get(phase, 0) + over
                covered += over
            if s0 <= mid < s0 + dur:
                at_mid = (phase, batch)
        if g1 - g0 > covered:
            by_phase[UNCOVERED] = by_phase.get(UNCOVERED, 0) \
                + g1 - g0 - covered
        named.append((g1 - g0, g0, *at_mid))
    return by_phase, named


def reduce_spans(trace: dict, top: int = 20) -> Optional[dict]:
    """The three tables, seconds, averaged over the devices. None when
    no operation ran on a device."""
    devices = trace.get("devices") or []
    spans = [tuple(s) for s in trace.get("spans") or []]
    if not devices:
        return None
    edges = [t for dev in devices for _, s, d in dev["ops"]
             for t in (s, s + d)]
    edges += [t for _, s, d, _ in spans for t in (s, s + d)]
    lo, hi = min(edges), max(edges)
    n = len(devices)
    by_scope: dict = {}
    by_phase: dict = {}
    gaps: list = []
    busy = 0
    for dev in devices:
        scopes, merged = self_times(dev["ops"])
        for scope, ns in scopes.items():
            by_scope[scope] = by_scope.get(scope, 0) + ns / n / 1e9
        busy += sum(e - s for s, e in merged)
        phases, named = idle_by_phase(merged, spans, lo, hi)
        for phase, ns in phases.items():
            by_phase[phase] = by_phase.get(phase, 0) + ns / n / 1e9
        gaps += named
    by_kind: dict = {}
    for scope, seconds in by_scope.items():
        kind = scope.split("/", 1)[0]
        by_kind[kind] = by_kind.get(kind, 0) + seconds
    span_s: dict = {}
    for phase, _, dur, _ in spans:
        span_s[phase] = span_s.get(phase, 0) + dur / 1e9
    return {
        "interval_s": (hi - lo) / 1e9,
        "busy_s": busy / n / 1e9,
        "idle_s": sum(by_phase.values()),
        "lanes_calls": sum(dev["lanes_calls"] for dev in devices) / n,
        "by_scope": dict(sorted(by_scope.items(), key=lambda kv: -kv[1])),
        "by_kind": dict(sorted(by_kind.items(), key=lambda kv: -kv[1])),
        "idle_by_phase": dict(sorted(by_phase.items(),
                                     key=lambda kv: -kv[1])),
        "span_s": span_s,
        "batches_spanned": len({b for *_, b in spans if b is not None}),
        "longest_gaps": [{"seconds": ns / 1e9, "at_s": (start - lo) / 1e9,
                          "phase": phase, "batch": batch}
                         for ns, start, phase, batch in heapq.nlargest(
                             top, gaps, key=lambda g: g[:2])],
        "op_name_found_in": trace.get("op_name_found_in"),
    }


# -- the parent's side --------------------------------------------------------


def _trace_file(obs: dict) -> Optional[str]:
    request = (obs.get("trace") or {}).get("request") or {}
    found = None
    for base, _, names in os.walk(request.get("dir") or ""):
        for name in names:
            if name.endswith(".xplane.pb"):
                found = os.path.join(base, name)
    return found


def _out_dir(obs: dict) -> str:
    """The run's output directory, from the trace's: the run directory
    is `<cell>-<seed>-t<trace>-<pid>-<ns>`, the output `<cell>-...-t1`."""
    run_dir = os.path.dirname(os.path.dirname(
        obs["trace"]["request"]["dir"].rstrip("/")))
    tag = os.path.basename(run_dir).rsplit("-", 2)[0]
    out = os.path.join(deploy.WORK, "out", tag)
    return out if os.path.isdir(out) else run_dir


def _seconds_left() -> float:
    from . import harness

    return harness.RUN_LIMIT_S - (time.monotonic()
                                  - harness.T_PROCESS_START)


def spans(obs: dict) -> Optional[dict]:
    """`reduce_spans` of the run's trace, read once a run (kept in
    `obs`), within what is left of the run's limit; None when there is
    no trace, no time, or no device operation in it."""
    if "_xspans" in obs:
        return obs["_xspans"]
    obs["_xspans"] = out = None
    path = _trace_file(obs)
    left = _seconds_left() - 10.0
    if path is None:
        deploy.log("spans: no .xplane.pb to read")
    elif left < MIN_CHILD_S:
        deploy.log(f"spans: {left:.0f} s left of the run, not reading")
    else:
        out_dir = _out_dir(obs)
        t0 = time.monotonic()
        try:
            proc = subprocess.run(
                [sys.executable, "-m", "lib.xspans", path, out_dir],
                cwd=deploy.BENCH_DIR,
                env=dict(os.environ, JAX_PLATFORMS="cpu"),
                capture_output=True, text=True, timeout=left)
            if proc.returncode == 0:
                with open(os.path.join(out_dir, "trace_spans.json"),
                          encoding="utf-8") as f:
                    out = json.load(f)
            else:
                deploy.log(f"spans: child rc={proc.returncode} "
                           f"{proc.stderr[-500:]}")
        except (OSError, ValueError, subprocess.TimeoutExpired) as exc:
            deploy.log(f"spans: {exc!r}")
        if out:
            _log_tables(obs, out, time.monotonic() - t0)
    obs["_xspans"] = out
    return out


def _log_tables(obs: dict, out: dict, took_s: float) -> None:
    def table(d: dict) -> str:
        return ", ".join(f"{k} {v * 1e3:.3f}" for k, v in d.items())

    reduced = (obs.get("trace") or {}).get("reduced") or {}
    deploy.log(f"spans: read in {took_s:.1f} s; scopes' self time "
               f"{sum(out['by_scope'].values()):.6f} s, busy_s "
               f"{reduced.get('busy_s')} (lib/xplane.py), op_name from "
               f"{out['op_name_found_in']}")
    deploy.log(f"spans: device self ms by scope: {table(out['by_scope'])}")
    deploy.log(f"spans: device idle ms by phase ({out['idle_s'] * 1e3:.3f} "
               f"of {out['interval_s'] * 1e3:.3f}): "
               f"{table(out['idle_by_phase'])}")
    deploy.log("spans: longest gaps (ms, phase, batch): " + ", ".join(
        f"{g['seconds'] * 1e3:.3f} {g['phase']} {g['batch']}"
        for g in out["longest_gaps"][:8]))
    deploy.log(f"spans: sidecar/* span ms over {out['batches_spanned']} "
               f"batches: {table(out['span_s'])}")


def scoped_ms_per_batch(obs: dict, kinds: tuple) -> Optional[float]:
    """Device self time under the scopes of `kinds`, per `lanes` call;
    None where the trace holds no such scope."""
    out = spans(obs)
    if not out or not out["lanes_calls"]:
        return None
    seconds = [s for kind, s in out["by_kind"].items() if kind in kinds]
    if not seconds:
        return None
    return sum(seconds) * 1e3 / out["lanes_calls"]


def phase_ms_per_batch(obs: dict, phases: tuple) -> Optional[float]:
    """The drain loop's milliseconds in `phases` per batch served, over
    the window's counters; None where the program has no such counter."""
    registry = (obs.get("after") or {}).get("registry") or []
    if not any(name == LOOP_COUNTER for name, _, _ in registry):
        return None
    ms = metrics_mod.delta(obs, {"registry": LOOP_COUNTER,
                                 "labels": {"plane": "sidecar"},
                                 "any_of": {"phase": list(phases)}})
    batches = metrics_mod.delta(obs, BATCHES)
    if ms is None or not batches:
        return None
    return ms / batches


def _main(path: str, out_dir: str) -> None:
    trace = extract(path)
    with open(os.path.join(out_dir, "trace_spans_sample.json"), "w",
              encoding="utf-8") as f:
        json.dump({"op_name_found_in": trace["op_name_found_in"],
                   "hlo_modules": trace["hlo_modules"],
                   "ops": trace["sample"], "spans": trace["spans"][:200]}, f)
    with open(os.path.join(out_dir, "trace_spans.json"), "w",
              encoding="utf-8") as f:
        json.dump(reduce_spans(trace), f)


if __name__ == "__main__":
    _main(sys.argv[1], sys.argv[2])
