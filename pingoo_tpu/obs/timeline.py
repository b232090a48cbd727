"""Cross-plane span timeline (ISSUE 17): per-request and per-window
spans joined across the C++ listener, the shm ring, and both Python
planes, exported as Chrome-trace (catapult) JSON.

The join works because every plane already stamps the SAME clock:
native httpd `now_ms()`, the ring's `pingoo_ring_now_ms()` (both
clock_gettime(CLOCK_MONOTONIC), see pingoo_ring.cc), and Python's
`time.monotonic()` (CLOCK_MONOTONIC on Linux) are one timebase per
machine. So a ring slot's `enq_ms` (stamped by the native producer)
and the sidecar's `time.monotonic()` resolve stamp subtract directly —
no epoch conversion, no skew estimation. All spans are stored in
monotonic MICROseconds (Chrome-trace's native unit); the export
carries a `clock` block (monotonic now + wall now) so an offline
merger (tools/timeline_capture.py) can pin the trace to wall time.

Span layout (Perfetto rows):
  * pid = plane ("native" | "sidecar" | "python"): ring-wait spans are
    emitted under pid "native" because their start stamp is the native
    enqueue clock — that row IS the cross-plane join.
  * tid = per-request lane (derived from the trace id / ring ticket)
    for request/hold spans, or a per-plane "batch" lane for the batch
    pipeline span and its stage children. Stage children are clamped
    inside their parent's bounds, so nesting holds by construction.

Gating + hot-path contract: `PINGOO_TIMELINE_SAMPLE` (a rate in
(0, 1]; unset/0 = off) decides per BATCH with a deterministic stride
accumulator — no RNG, one float add + compare on the unsampled path.
The record methods below are registered hot in
tools/analyze/lint_config.py: pure float math over already-host stage
numbers, never an array allocation or a device sync. Retention is a
bounded deque (`PINGOO_TIMELINE_N` spans, default 4096); the export at
`/__pingoo/timeline` drains nothing (snapshot semantics).
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Optional

DEFAULT_SPAN_CAP = 4096
# Per-request lanes emitted per sampled batch (the batch-lane pipeline
# span always goes out; request lanes are the expensive part).
DEFAULT_ROWS_PER_BATCH = 8

_PLANES = ("python", "sidecar", "native")


def timeline_sample_rate() -> float:
    """PINGOO_TIMELINE_SAMPLE as a clamped rate; 0.0 = disabled."""
    raw = os.environ.get("PINGOO_TIMELINE_SAMPLE", "").strip()
    if not raw:
        return 0.0
    try:
        rate = float(raw)
    except ValueError:
        return 0.0
    if rate <= 0.0:
        return 0.0
    return min(rate, 1.0)


class Timeline:
    """Process-global bounded span store + deterministic batch sampler
    shared by the co-resident Python planes."""

    def __init__(self, rate: Optional[float] = None, registry=None):
        self.rate = timeline_sample_rate() if rate is None else rate
        self._acc = 0.0
        self._lock = threading.Lock()
        cap = int(os.environ.get("PINGOO_TIMELINE_N", DEFAULT_SPAN_CAP))
        self.spans: deque = deque(maxlen=max(64, cap))
        self.rows_per_batch = int(os.environ.get(
            "PINGOO_TIMELINE_ROWS", DEFAULT_ROWS_PER_BATCH))
        self._counters: dict[str, object] = {}
        self._registry = registry
        self._seq = 0

    @property
    def enabled(self) -> bool:
        return self.rate > 0.0

    def _reg(self):
        if self._registry is None:
            from . import REGISTRY

            self._registry = REGISTRY
        return self._registry

    def ensure_instruments(self, plane: str) -> None:
        """Create pingoo_timeline_spans_total{plane} at zero at boot
        (and the native series, which the join rows emit under)."""
        self._counter(plane)
        self._counter("native")

    def _counter(self, plane: str):
        ctr = self._counters.get(plane)
        if ctr is None:
            from . import schema

            ctr = self._reg().counter(
                "pingoo_timeline_spans_total",
                schema.PERF_METRICS["pingoo_timeline_spans_total"],
                labels={"plane": plane})
            self._counters[plane] = ctr
        return ctr

    def sample(self) -> bool:
        """Per-batch sampling decision — the ONLY per-batch work when
        a batch is not sampled: one add, one compare (stride sampling,
        deterministic, no RNG)."""
        if self.rate <= 0.0:
            return False
        self._acc += self.rate
        if self._acc >= 1.0:
            self._acc -= 1.0
            return True
        return False

    # ------------------------------------------------------------------
    # Span recording (only runs for SAMPLED batches).

    def add_span(self, plane: str, tid: str, name: str,
                 t0_us: float, dur_us: float,
                 trace_id: str = "", args: Optional[dict] = None) -> None:
        span = (plane, tid, name, float(t0_us), max(0.0, float(dur_us)),
                trace_id, args or {})
        self._counter(plane).inc()
        with self._lock:
            self.spans.append(span)

    def _stage_children(self, plane: str, tid: str, t0_us: float,
                        t_end_us: float, stages_us: list,
                        trace_id: str, args: dict) -> None:
        """Lay consecutive stage spans inside [t0_us, t_end_us] from
        (name, dur_us) pairs, clamped so nesting always holds."""
        cursor = t0_us
        for name, dur in stages_us:
            if dur <= 0.0:
                continue
            start = min(cursor, t_end_us)
            end = min(start + dur, t_end_us)
            self.add_span(plane, tid, name, start, end - start,
                          trace_id, args)
            cursor = end

    def batch_python(self, *, stages_ms: dict, t_launch: float,
                     t_resolve: float, t_end: float,
                     rows: Optional[list] = None,
                     args: Optional[dict] = None) -> None:
        """One sampled python-plane batch: the batch-lane pipeline
        span with stage children reconstructed from the already-stamped
        `<stage>_ms` wall times (engine/service's per-batch stage
        dict), an explicit resolve span, plus bounded per-request
        lanes.

        `rows` entries: (trace_id, t_enq_mono_s, t_admit_mono_s) — the
        request span covers enqueue -> batch end; sched_hold covers
        admit -> launch.
        """
        base_args = dict(args or {})
        t0_us = t_launch * 1e6
        t_end_us = t_end * 1e6
        with self._lock:
            self._seq += 1
            seq = self._seq
        tid = "python/batch"
        self.add_span("python", tid, "batch", t0_us,
                      max(0.0, t_end_us - t0_us), f"b-{seq}", base_args)
        order = ("encode", "prefilter", "device_dispatch",
                 "device_compute")
        stage_pairs = [
            (name, float(stages_ms.get(f"{name}_ms", 0.0)) * 1e3)
            for name in order]
        self._stage_children("python", tid, t0_us, t_resolve * 1e6,
                             stage_pairs, f"b-{seq}", base_args)
        if t_end > t_resolve:
            self.add_span("python", tid, "resolve", t_resolve * 1e6,
                          (t_end - t_resolve) * 1e6, f"b-{seq}",
                          base_args)
        for trace_id, t_enq, t_admit in (rows or [])[:self.rows_per_batch]:
            lane = f"python/req:{trace_id[-6:] if trace_id else seq}"
            enq_us = t_enq * 1e6
            self.add_span("python", lane, "request", enq_us,
                          max(0.0, t_end_us - enq_us), trace_id,
                          base_args)
            adm_us = t_admit * 1e6
            self.add_span("python", lane, "sched_hold", adm_us,
                          max(0.0, min(t0_us, t_end_us) - adm_us),
                          trace_id, base_args)

    def batch_sidecar(self, *, points: dict,
                      rows: Optional[list] = None,
                      args: Optional[dict] = None) -> None:
        """One sampled sidecar batch from the phase boundaries the drain
        loop's span source recorded for it (obs/pipeline.BatchSpans
        `points`: phase -> (t_start, t_end), time.monotonic() seconds):
        encode, prefilter, device_dispatch as recorded, device_compute
        from the dispatch's end to the device wait's, resolve as
        recorded.

        `rows` entries: (trace_id, enq_ms) with enq_ms the NATIVE
        producer's ring-clock stamp — the ring-wait span is emitted
        under pid "native" ending at the batch's start (sidecar pickup).
        Same monotonic timebase, so the subtraction is the cross-plane
        join.
        """
        base_args = dict(args or {})
        with self._lock:
            self._seq += 1
            seq = self._seq
        tid = "sidecar/batch"
        none = (0.0, 0.0)
        dispatch, wait = points.get("dispatch", none), \
            points.get("device_wait", none)
        resolve = points.get("resolve", none)
        t_end = resolve[1] or wait[1]
        t0 = points["encode"][0]
        t0_us = t0 * 1e6
        t_end_us = t_end * 1e6
        self.add_span("sidecar", tid, "batch", t0_us,
                      max(0.0, t_end_us - t0_us), f"b-{seq}", base_args)
        bounds = (("encode", *points.get("encode", none)),
                  ("prefilter", *points.get("prefilter", none)),
                  ("device_dispatch", *dispatch),
                  ("device_compute", dispatch[1], wait[1]),
                  ("resolve", *resolve))
        for name, a, b in bounds:
            if b > a > 0.0:
                self.add_span("sidecar", tid, name, a * 1e6,
                              (b - a) * 1e6, f"b-{seq}", base_args)
        for trace_id, enq_ms in (rows or [])[:self.rows_per_batch]:
            lane = f"ring/req:{trace_id[-6:] if trace_id else seq}"
            enq_us = float(enq_ms) * 1e3
            self.add_span("native", lane, "ring_wait", enq_us,
                          max(0.0, t0_us - enq_us), trace_id, base_args)
            self.add_span("sidecar", lane, "request", t0_us,
                          max(0.0, t_end_us - t0_us), trace_id,
                          base_args)

    # ------------------------------------------------------------------
    # Export.

    def chrome_trace(self) -> dict:
        """Chrome-trace (catapult) JSON object for /__pingoo/timeline:
        loads directly in Perfetto. `clock` pins the monotonic span
        timebase to wall time for offline merging."""
        with self._lock:
            spans = list(self.spans)
        pids = {}
        events = []
        for plane in _PLANES:
            pids[plane] = len(pids) + 1
            events.append({
                "ph": "M", "name": "process_name", "pid": pids[plane],
                "tid": 0, "args": {"name": f"pingoo:{plane}"},
            })
        tids: dict[tuple, int] = {}
        for plane, tid, name, t0_us, dur_us, trace_id, args in spans:
            pid = pids.setdefault(plane, len(pids) + 1)
            tkey = (plane, tid)
            if tkey not in tids:
                tids[tkey] = len(tids) + 1
                events.append({
                    "ph": "M", "name": "thread_name", "pid": pid,
                    "tid": tids[tkey], "args": {"name": tid},
                })
            ev_args = {"trace_id": trace_id}
            ev_args.update(args)
            events.append({
                "ph": "X", "pid": pid, "tid": tids[tkey], "name": name,
                "cat": plane, "ts": round(t0_us, 1),
                "dur": round(dur_us, 1), "args": ev_args,
            })
        return {
            "displayTimeUnit": "ms",
            "clock": {
                "unit": "monotonic_us",
                "monotonic_now_us": time.monotonic() * 1e6,
                "wall_now_s": time.time(),
            },
            "otherData": {
                "sample_rate": self.rate,
                "spans": len(spans),
                "cap": self.spans.maxlen,
            },
            "traceEvents": events,
        }

    def chrome_trace_json(self) -> str:
        return json.dumps(self.chrome_trace())

    def snapshot(self) -> dict:
        with self._lock:
            n = len(self.spans)
        return {"enabled": self.enabled, "rate": self.rate,
                "spans": n, "cap": self.spans.maxlen}


_TIMELINE: Optional[Timeline] = None
_TIMELINE_LOCK = threading.Lock()


def get_timeline() -> Timeline:
    global _TIMELINE
    if _TIMELINE is None:
        with _TIMELINE_LOCK:
            if _TIMELINE is None:
                _TIMELINE = Timeline()
    return _TIMELINE


def reset_timeline_for_tests() -> None:
    """Drop the singleton so a test can re-read the sampling env."""
    global _TIMELINE
    with _TIMELINE_LOCK:
        _TIMELINE = None
