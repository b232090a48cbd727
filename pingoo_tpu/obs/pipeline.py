"""Overlapped-executor pipeline telemetry (ISSUE 9, docs/EXECUTOR.md).

Both engine planes run the zero-copy pipelined executor — the Python
listener service (engine/service.py, plane="python") and the ring
sidecar (native_ring.RingSidecar, plane="sidecar") — and each owns one
`PipelineStats` bundle exporting the obs/schema.PIPELINE_METRICS
family on its plane label:

  * pingoo_pipeline_inflight{plane}: batches currently between stage
    entry and final resolve (the executor's live depth; bounded by
    PINGOO_PIPELINE_DEPTH).
  * pingoo_pipeline_depth{plane}: the configured in-flight bound.
  * pingoo_pipeline_stage_occupancy{plane,stage}: fraction of wall
    time each stage has been busy since boot — a stage near 1.0 is the
    pipeline's bottleneck, stages summing past 1.0 prove overlap.
  * pingoo_pipeline_overlap_ratio{plane}: EWMA fraction of each
    batch's device-compute window that a DIFFERENT in-flight batch
    spent in host-side encode/dispatch — the acceptance number for
    "batch N+1 encodes while batch N scans" (> 0 means the executor is
    actually overlapping, not just queueing).
  * pingoo_pipeline_batches_total{plane,mode}: batches served, split
    by executor mode (on = staged overlap, off = legacy lockstep), so
    an A/B drive can attribute throughput to the arm that produced it.

The ring sidecar also records its drain loop's stage boundaries here,
and nowhere else (docs/OBSERVABILITY.md "Spans and scopes"):
`stage(name, rec)` is a context manager that enters a
`jax.profiler.TraceAnnotation("sidecar/<phase>", batch=, rows=, rings=,
device=)` — so
the span lands in the profiler's own file, on the device trace's clock —
and on exit fans ONE pair of `time.monotonic()` stamps out to every
sink: the `pingoo_verdict_stage_ms` histogram, the occupancy/overlap
bookkeeping below, the scheduler's stage costs, the batch's recorded
points (which the sampled Timeline reads) and the loop-phase account:

  * pingoo_sidecar_loop_ms_total{plane,phase}: drain-thread wall time
    by LOOP_PHASES, a partition (every instant is in exactly one phase;
    `idle` is one coalesced span from the first empty pass to the next
    pass that gets rows). Flushed once per finished batch, or once a
    second while idle.
  * pingoo_sidecar_stall_total{plane,phase}: spans of a non-idle phase
    longer than STALL_MS, each logged once with its batch and the ring
    depth.
  * pingoo_sidecar_completions_total{plane,how}: batches completed, by
    the rule of `RingSidecar.run` that chose the moment (COMPLETIONS).
  * pingoo_sidecar_host_copies_total{plane}: device arrays the loop
    materialised on the host (`RingSidecar._to_host`): one a batch.
  * pingoo_sidecar_replica_batches_total{plane,device},
    pingoo_sidecar_inflight_at_launch_total{plane} and the
    pingoo_sidecar_replicas{plane} gauge: batches launched on each chip
    (`--replicas`), and the batches in flight on all of them summed at
    each launch (`note_launch`).

Interval bookkeeping is host-side float math on the plane's own
serial context (event loop / drain thread): no locks, no arrays, no
device access. Overlap is computed from (monotonic) stage wall
intervals kept in a small ring: whichever of the two intervals in an
(other-batch host stage, compute) pair is recorded second finds the
first, so each pair is counted exactly once.
"""

from __future__ import annotations

import logging
import os
import time
from collections import deque

# Executor stage names, in hot-path order. "encode" and "dispatch" are
# host-side (staging fill, jit call issue); "compute" is the device
# wall (the window other batches should overlap); "resolve" is the
# host-side fan-out after results land.
PIPELINE_EXEC_STAGES = ("encode", "dispatch", "compute", "resolve")

# Host-side stages whose wall overlapping a DIFFERENT batch's compute
# window is the overlap the executor exists to create.
_HOST_STAGES = frozenset(("encode", "dispatch"))

_EWMA_ALPHA = 0.2
_RECENT_INTERVALS = 32

# The drain loop's phases. `poll` is the loop's own turn while it has
# work (dequeue passes that got rows, the launch decision, the
# heartbeat); the `sched` stage label is an AGE, not a phase.
LOOP_PHASES = ("poll", "encode", "prefilter", "dispatch", "host_rules",
               "device_wait", "resolve", "provenance", "bodies", "swap",
               "idle")
# The pingoo_verdict_stage_ms{stage} series each phase's spans feed.
PHASE_STAGE = {"encode": "encode", "prefilter": "prefilter",
               "dispatch": "device_dispatch",
               "device_wait": "device_compute", "resolve": "resolve",
               "provenance": "provenance"}
# The executor stage (PIPELINE_EXEC_STAGES) a phase's end reports.
_PHASE_EXEC = {"encode": "encode", "dispatch": "dispatch",
               "device_wait": "compute", "resolve": "resolve"}
STALL_MS = 250.0
# Why the drain loop completed a batch when it did: its device lanes
# were ready, the in-flight bound was reached (the loop blocked on it),
# a pass launched nothing (also the flush and a swap boundary), or it
# held the staging buffers the next batch is encoded into (several
# chips: a batch on a slow chip outlasted the encoder's rotation).
COMPLETIONS = ("ready", "depth", "drain", "staging")
_IDLE_FLUSH_S = 1.0
# The drain thread's own scheduler readings (the calling thread's, read
# through fds opened on it): the run-queue wait in nanoseconds, the
# second field of schedstat, and delayacct_blkio_ticks, field 42 of stat.
SCHEDSTAT = "/proc/thread-self/schedstat"
THREAD_STAT = "/proc/thread-self/stat"
_RUNQUEUE_READ_S = 1.0

_log = logging.getLogger(__name__)


def _open_reading(path: str):
    try:
        return os.open(path, os.O_RDONLY | os.O_CLOEXEC)
    except OSError:
        return None


def read_runqueue_ns(fd) -> "int | None":
    """The run-queue wait (ns) from a schedstat fd, None without one."""
    if fd is None:
        return None
    try:
        return int(os.pread(fd, 128, 0).split()[1])
    except (OSError, IndexError, ValueError):
        return None


def read_blkio_ticks(fd) -> "int | None":
    """delayacct_blkio_ticks from a stat fd: the fields after the
    parenthesised name start at field 3."""
    if fd is None:
        return None
    try:
        data = os.pread(fd, 4096, 0)
        return int(data[data.rindex(b")") + 2:].split()[42 - 3])
    except (OSError, IndexError, ValueError):
        return None


CASCADE_STAGES = ("live", "candidate", "recheck")
CASCADE_LADDERS = ("candidate", "recheck")


class CascadeCounters:
    """`pingoo_cascade_rows_total{plane, bank, stage}` and
    `pingoo_cascade_bucket_rows_total{plane, bank, ladder}`
    (obs/schema.py): what the lanes program counted of its own cascade
    (engine/verdict.cascade_counts, already on the host with the
    batch's lanes), folded once a batch where the batch resolves."""

    def __init__(self, plane: str, banks: tuple):
        from . import REGISTRY as registry, schema

        self.banks = tuple(b.removeprefix("nfa_") for b in banks)
        self._rows = [
            [registry.counter(
                "pingoo_cascade_rows_total",
                schema.CASCADE_METRICS["pingoo_cascade_rows_total"],
                labels={"plane": plane, "bank": bank, "stage": stage})
             for stage in CASCADE_STAGES] for bank in self.banks]
        self._buckets = [
            [registry.counter(
                "pingoo_cascade_bucket_rows_total",
                schema.CASCADE_METRICS[
                    "pingoo_cascade_bucket_rows_total"],
                labels={"plane": plane, "bank": bank, "ladder": ladder})
             for ladder in CASCADE_LADDERS] for bank in self.banks]

    def fold(self, counts: list, n: int) -> None:
        """One batch of `n` requests; `counts` is [banks][(candidate,
        candidate_bucket, recheck, recheck_bucket)] host ints, a
        candidate of -1 meaning an ungated bank (every live row)."""
        for rows, buckets, (cand, cand_b, re, re_b) in zip(
                self._rows, self._buckets, counts):
            rows[0].inc(n)
            rows[1].inc(n if cand < 0 else cand)
            rows[2].inc(re)
            buckets[0].inc(cand_b)
            buckets[1].inc(re_b)

    def snapshot(self) -> dict:
        return {bank: {
            **{stage: c.value for stage, c in zip(CASCADE_STAGES, rows)},
            **{f"{ladder}_bucket": c.value
               for ladder, c in zip(CASCADE_LADDERS, buckets)}}
            for bank, rows, buckets in zip(self.banks, self._rows,
                                           self._buckets)}


class BatchSpans:
    """One batch's identity in the drain loop, and the phase boundaries
    recorded for it: rides the in-flight tuple from launch to resolve.
    `seq` is the loop's batch counter (the pipeline slot id, and the
    `batch` stat of every span the batch causes); `points` maps a phase
    to its (t_start, t_end) in time.monotonic() seconds; `rings` is how
    many of the sidecar's rings gave the batch rows; `device` the chip
    it runs on (its `--replicas` index); `staged` the StagingEncoder
    checkout its views are in (None off the staging encoder); `stats`
    is what the spans opened from then on carry besides (`blocked`,
    `recheck`: set once the batch's lanes are on the host)."""

    __slots__ = ("seq", "rows", "rings", "device", "staged", "stats",
                 "cascade", "lane_rows", "points", "tags", "compute_ms")

    def __init__(self, seq: int, rows: int, rings: int = 1):
        self.seq = seq
        self.rows = rows
        self.rings = rings
        self.device = 0
        self.staged = None      # its StagingEncoder checkout, if any
        self.stats: dict = {}
        self.cascade = None     # the CascadeCounters of its lanes program
        self.lane_rows = None   # and that program's row layout (LaneRows)
        self.points: dict[str, tuple] = {}
        self.tags: dict = {}
        self.compute_ms = 0.0

    def span_ms(self, first: str, last: str) -> float:
        """Milliseconds from `first`'s start to `last`'s end."""
        return (self.points[last][1] - self.points[first][0]) * 1e3


class _Stage:
    """PipelineStats.stage()'s context manager (one per with-block)."""

    __slots__ = ("_ps", "_name", "_rec")

    def __init__(self, ps, name, rec):
        self._ps, self._name, self._rec = ps, name, rec

    def __enter__(self):
        self._ps._push(self._name, self._rec)
        return self

    def next(self, name: str) -> float:
        """End the open phase and begin `name` at the same stamp;
        returns the seconds the ended phase lasted."""
        return self._ps._switch(name, self._rec)

    def __exit__(self, *exc) -> bool:
        self._ps._pop()
        return False


class PipelineStats:
    """One plane's pipeline instrument bundle + overlap bookkeeping.

    Created eagerly at plane boot (like sched.SchedMetrics) so the full
    PIPELINE_METRICS inventory exists from the first scrape; the mode
    counters are created lazily per observed mode label.
    """

    def __init__(self, plane: str, depth: int, registry=None):
        if registry is None:
            from . import REGISTRY as registry  # noqa: N813
        from . import schema

        self.plane = plane
        self._registry = registry
        labels = {"plane": plane}
        self.inflight = registry.gauge(
            "pingoo_pipeline_inflight",
            schema.PIPELINE_METRICS["pingoo_pipeline_inflight"],
            labels=labels)
        self.depth = registry.gauge(
            "pingoo_pipeline_depth",
            schema.PIPELINE_METRICS["pingoo_pipeline_depth"],
            labels=labels)
        self.depth.set(max(1, int(depth)))
        self.overlap_ratio = registry.gauge(
            "pingoo_pipeline_overlap_ratio",
            schema.PIPELINE_METRICS["pingoo_pipeline_overlap_ratio"],
            labels=labels)
        self._occupancy = {
            stage: registry.gauge(
                "pingoo_pipeline_stage_occupancy",
                schema.PIPELINE_METRICS["pingoo_pipeline_stage_occupancy"],
                labels={"plane": plane, "stage": stage})
            for stage in PIPELINE_EXEC_STAGES}
        self._batches: dict[str, object] = {}
        self._slot_seq = 0
        self._t_boot = time.monotonic()
        self._busy = dict.fromkeys(PIPELINE_EXEC_STAGES, 0.0)
        # (slot, stage, t_start, t_end) of recent stage walls; 32 spans
        # several pipeline depths of history on both planes.
        self._recent: deque = deque(maxlen=_RECENT_INTERVALS)
        self._overlap_ewma: float | None = None
        self.overlap_events = 0
        self._loop_ctr: dict[str, object] = {}  # filled by attach_loop

    # -- batch lifecycle (hot) ----------------------------------------------

    def enter(self, mode: str = "on") -> int:
        """A batch entered the executor; returns its pipeline slot id
        (monotonic per plane — flight-recorder rows carry it so an
        explain/debug session can line batches up against the overlap
        series)."""
        self._slot_seq += 1
        self.inflight.inc()
        counter = self._batches.get(mode)
        if counter is None:
            from . import schema

            counter = self._registry.counter(
                "pingoo_pipeline_batches_total",
                schema.PIPELINE_METRICS["pingoo_pipeline_batches_total"],
                labels={"plane": self.plane, "mode": mode})
            self._batches[mode] = counter
        counter.inc()
        return self._slot_seq

    def exit(self) -> None:
        self.inflight.dec()

    def note_stage(self, slot: int, stage: str, t_start: float,
                   t_end: float) -> None:
        """Record one stage's wall interval (monotonic seconds) for the
        given pipeline slot: updates the stage's occupancy gauge and,
        when the interval pairs with a different slot's interval of the
        opposite kind (host stage x compute), the overlap ratio."""
        dur = t_end - t_start
        if dur < 0.0:
            return
        busy = self._busy.get(stage)
        if busy is None:  # unknown stage: occupancy only tracks the
            return        # canonical four
        self._busy[stage] = busy + dur
        wall = t_end - self._t_boot
        if wall > 0.0:
            self._occupancy[stage].set(
                min(1.0, round(self._busy[stage] / wall, 6)))
        if stage == "compute":
            self._score_overlap(slot, t_start, t_end,
                                want_host=True, compute_dur=dur)
        elif stage in _HOST_STAGES:
            self._score_overlap(slot, t_start, t_end, want_host=False)
        self._recent.append((slot, stage, t_start, t_end))

    # -- overlap bookkeeping -------------------------------------------------

    def _score_overlap(self, slot: int, t0: float, t1: float,
                       want_host: bool,
                       compute_dur: float = 0.0) -> None:
        """Pair the just-finished interval against stored intervals of
        the opposite kind from OTHER slots; the ratio denominator is
        always the compute window (the thing being hidden)."""
        for o_slot, o_stage, o_t0, o_t1 in self._recent:
            if o_slot == slot:
                continue
            if want_host != (o_stage in _HOST_STAGES):
                continue
            ov = min(t1, o_t1) - max(t0, o_t0)
            if ov <= 0.0:
                continue
            denom = compute_dur if want_host else (o_t1 - o_t0)
            if denom <= 0.0:
                continue
            self._note_overlap(min(1.0, ov / denom))

    def _note_overlap(self, ratio: float) -> None:
        self.overlap_events += 1
        prev = self._overlap_ewma
        if prev is None:
            self._overlap_ewma = ratio
        else:
            self._overlap_ewma = prev + _EWMA_ALPHA * (ratio - prev)
        self.overlap_ratio.set(round(self._overlap_ewma, 6))


    # -- the drain loop's span source (sidecar) -------------------------------

    def attach_loop(self, stage_hist: dict, observe_cost, cost_size: int,
                    depth_fn=None) -> None:
        """Wire the sinks a stage boundary fans out to: `stage_hist`
        {stage label: pingoo_verdict_stage_ms histogram}, `observe_cost`
        (the scheduler's observe_stage_cost) at `cost_size` rows, and
        `depth_fn` (requests still queued, by ring, for the stall
        line)."""
        from jax.profiler import TraceAnnotation

        from . import schema

        self._annotate = TraceAnnotation
        self._span_names = {p: f"{self.plane}/{p}" for p in LOOP_PHASES}
        self._phase_hist = {p: stage_hist[s]
                            for p, s in PHASE_STAGE.items()}
        self._observe_cost = observe_cost
        self._cost_size = cost_size
        self._depth_fn = depth_fn
        self._acc = dict.fromkeys(LOOP_PHASES, 0.0)
        self._loop_ctr = {
            p: self._registry.counter(
                "pingoo_sidecar_loop_ms_total",
                schema.PIPELINE_METRICS["pingoo_sidecar_loop_ms_total"],
                labels={"plane": self.plane, "phase": p})
            for p in LOOP_PHASES}
        self._stall_ctr: dict[str, object] = {}
        self._runqueue_ctr = None   # made at loop_start, where readable
        self._sched_fd = self._stat_fd = None
        self._runqueue_ns = self._blkio_ticks = None
        self._t_runqueue = 0.0
        self._completion_ctr = {
            how: self._registry.counter(
                "pingoo_sidecar_completions_total",
                schema.PIPELINE_METRICS["pingoo_sidecar_completions_total"],
                labels={"plane": self.plane, "how": how})
            for how in COMPLETIONS}
        self.completions = dict.fromkeys(COMPLETIONS, 0)  # this loop's own
        self.host_copies = self._registry.counter(
            "pingoo_sidecar_host_copies_total",
            schema.PIPELINE_METRICS["pingoo_sidecar_host_copies_total"],
            labels={"plane": self.plane})
        self.replica_batches: list = []  # one counter a chip: replicas()
        self._stack: list = []      # enclosing with-blocks: (name, rec)
        self._base = "poll"         # what the loop falls back to
        self._cur = None            # the open span: name, rec, t0, ann
        self._cur_rec = None
        self._cur_t0 = 0.0
        self._cur_ann = None
        self._t_flush = 0.0

    def replicas(self, n: int) -> None:
        """The drain loop launches on `n` chips: one batch counter each
        (`device` = the chip's index), the in-flight-at-launch sum and
        the gauge."""
        from . import schema

        labels = {"plane": self.plane}
        self.replica_batches = [
            self._registry.counter(
                "pingoo_sidecar_replica_batches_total",
                schema.PIPELINE_METRICS[
                    "pingoo_sidecar_replica_batches_total"],
                labels={**labels, "device": str(chip)})
            for chip in range(n)]
        self.inflight_at_launch = self._registry.counter(
            "pingoo_sidecar_inflight_at_launch_total",
            schema.PIPELINE_METRICS[
                "pingoo_sidecar_inflight_at_launch_total"], labels=labels)
        self._registry.gauge(
            "pingoo_sidecar_replicas",
            schema.PIPELINE_METRICS["pingoo_sidecar_replicas"],
            labels=labels).set(n)

    def note_launch(self, chip: int, inflight: int) -> None:
        """A batch launched on `chip`, `inflight` batches now in flight
        on all chips (this one included)."""
        self.replica_batches[chip].inc()
        self.inflight_at_launch.inc(inflight)

    def loop_start(self) -> None:
        """The drain loop begins: everything until loop_stop() is
        accounted to exactly one phase. Called on the drain thread: the
        run-queue and blkio readings are this thread's."""
        from . import schema

        now = time.monotonic()
        self._close_readings()
        self._sched_fd = _open_reading(SCHEDSTAT)
        self._stat_fd = _open_reading(THREAD_STAT)
        self._runqueue_ns = read_runqueue_ns(self._sched_fd)
        self._blkio_ticks = read_blkio_ticks(self._stat_fd)
        self._t_runqueue = now
        if self._runqueue_ns is not None and self._runqueue_ctr is None:
            self._runqueue_ctr = self._registry.counter(
                "pingoo_sidecar_runqueue_us_total",
                schema.PIPELINE_METRICS["pingoo_sidecar_runqueue_us_total"],
                labels={"plane": self.plane})
        self._stack.clear()
        self._base = "poll"
        self._t_flush = now
        self.loop_stamps = [now, None]  # the account's first and last
        self._open("poll", None, now)

    def loop_stop(self) -> None:
        if self._cur is not None:
            now = time.monotonic()
            self._close(now)
            self._cur = None
            self._flush(now)
            self.loop_stamps[1] = now
        self._close_readings()

    def _close_readings(self) -> None:
        for fd in (self._sched_fd, self._stat_fd):
            if fd is not None:
                os.close(fd)
        self._sched_fd = self._stat_fd = None

    def begin(self, mode: str, rows: int, rings: int = 1) -> BatchSpans:
        """enter() for the drain loop: the batch's span record."""
        return BatchSpans(self.enter(mode), rows, rings)

    def note_completion(self, how: str) -> None:
        """The drain loop is about to complete its oldest batch, by the
        rule `how` (COMPLETIONS)."""
        self._completion_ctr[how].inc()
        self.completions[how] += 1

    def finish(self) -> None:
        """exit() for the drain loop; the phase account reaches the
        registry here, once per batch."""
        self.exit()
        self._flush(time.monotonic())

    def stage(self, name: str, rec: BatchSpans = None) -> _Stage:
        """`with pipe.stage("encode", rec) as sp:` — the phase lasts to
        the block's end or to `sp.next(phase)`; a block nested in it
        suspends it (spans never overlap)."""
        return _Stage(self, name, rec)

    def idle(self) -> None:
        """An empty pass with nothing in flight, or on several chips
        nothing in flight ready (the loop sleeps next): one clock read
        and a compare while already idle."""
        now = time.monotonic()
        if self._cur != "idle":
            self._close(now)
            self._base = "idle"
            self._open("idle", None, now)
        elif now - self._t_flush >= _IDLE_FLUSH_S:
            # fold the stretch so far; its span stays open
            self._acc["idle"] += (now - self._cur_t0) * 1e3
            self._cur_t0 = now
            self._flush(now)

    def wake(self) -> None:
        """A pass got rows: an idle stretch ends here (the dequeue that
        ended it counts to it)."""
        if self._cur == "idle":
            now = time.monotonic()
            self._close(now)
            self._base = "poll"
            self._open("poll", None, now)

    def _open(self, name: str, rec, t: float) -> None:
        if rec is None:
            ann = self._annotate(self._span_names[name])
        else:
            ann = self._annotate(self._span_names[name], batch=rec.seq,
                                 rows=rec.rows, rings=rec.rings,
                                 device=rec.device, **rec.stats)
        ann.__enter__()
        self._cur, self._cur_rec, self._cur_t0, self._cur_ann = \
            name, rec, t, ann

    def _close(self, t: float) -> float:
        """End the open span at `t`: the one place a stage boundary's
        stamps are recorded."""
        name, rec, t0 = self._cur, self._cur_rec, self._cur_t0
        self._cur_ann.__exit__(None, None, None)
        ms = (t - t0) * 1e3
        self._acc[name] += ms
        hist = self._phase_hist.get(name)
        if hist is not None:
            hist.observe(ms)
        if rec is not None:
            rec.points[name] = (t0, t)
            self._note_exec(name, rec, t0, t)
        if ms > STALL_MS and name != "idle":
            self._stall(name, ms, rec)
        return t - t0

    def _note_exec(self, name: str, rec: BatchSpans, t0: float,
                   t: float) -> None:
        """The executor's view of a phase: occupancy/overlap stage and
        the scheduler's stage cost. `dispatch` runs from the prefilter's
        issue to the lane program's; `compute` from the launch to the
        results."""
        stage = _PHASE_EXEC.get(name)
        if stage is None:
            return
        if stage == "dispatch":
            t0 = rec.points.get("prefilter", (t0,))[0]
        elif stage == "compute":
            t0 = rec.points.get("dispatch", (0, t0))[1]
            rec.compute_ms = (t - t0) * 1e3
        self.note_stage(rec.seq, stage, t0, t)
        if stage != "resolve":  # the cost model has no resolve stage
            self._observe_cost(stage, self._cost_size, (t - t0) * 1e3)

    def _push(self, name: str, rec) -> None:
        t = time.monotonic()
        self._close(t)
        self._base = "poll"
        self._stack.append((name, rec))
        self._open(name, rec, t)

    def _switch(self, name: str, rec) -> float:
        if name == self._cur:
            return 0.0
        t = time.monotonic()
        dur = self._close(t)
        self._stack[-1] = (name, rec)
        self._open(name, rec, t)
        return dur

    def _pop(self) -> None:
        t = time.monotonic()
        self._close(t)
        self._stack.pop()
        name, rec = self._stack[-1] if self._stack else (self._base, None)
        self._open(name, rec, t)

    def _flush(self, now: float) -> None:
        for phase, ms in self._acc.items():
            if ms:
                self._loop_ctr[phase].inc(ms)
                self._acc[phase] = 0.0
        self._t_flush = now
        if self._runqueue_ctr is not None and \
                now - self._t_runqueue >= _RUNQUEUE_READ_S:
            self._t_runqueue = now
            self._note_runqueue()

    def _note_runqueue(self) -> "int | None":
        """The run-queue wait since the last reading into the counter;
        -> it in ns, None where it cannot be read."""
        ns = read_runqueue_ns(self._sched_fd)
        if ns is None or self._runqueue_ns is None:
            return None
        waited = ns - self._runqueue_ns
        self._runqueue_ns = ns
        if self._runqueue_ctr is not None and waited > 0:
            self._runqueue_ctr.inc(waited / 1e3)
        return waited

    def _stall(self, name: str, ms: float, rec) -> None:
        from . import schema

        ctr = self._stall_ctr.get(name)
        if ctr is None:
            ctr = self._stall_ctr[name] = self._registry.counter(
                "pingoo_sidecar_stall_total",
                schema.PIPELINE_METRICS["pingoo_sidecar_stall_total"],
                labels={"plane": self.plane, "phase": name})
        ctr.inc()
        depths = self._depth_fn() if self._depth_fn else None
        # the wedge's witness: what this thread waited for meanwhile, a
        # run queue (a descheduled vCPU) or block I/O (writeback)
        waited = self._note_runqueue()
        ticks = read_blkio_ticks(self._stat_fd)
        blkio = None if ticks is None or self._blkio_ticks is None \
            else ticks - self._blkio_ticks
        self._blkio_ticks = ticks
        _log.warning("drain loop stalled", extra={"fields": {
            "phase": name, "ms": round(ms, 1),
            "batch": rec.seq if rec is not None else None,
            "ring_depth": sum(depths.values()) if depths else depths,
            "ring_depths": depths,
            "runqueue_ms": None if waited is None else waited / 1e6,
            "blkio_ticks": blkio}})

    def snapshot(self) -> dict:
        wall = max(time.monotonic() - self._t_boot, 1e-9)
        return {
            "plane": self.plane,
            "depth": self.depth.value,
            "inflight": self.inflight.value,
            "batches": {mode: c.value
                        for mode, c in sorted(self._batches.items())},
            "overlap_ratio": (round(self._overlap_ewma, 4)
                              if self._overlap_ewma is not None else None),
            "overlap_events": self.overlap_events,
            "stage_occupancy": {
                stage: round(self._busy[stage] / wall, 4)
                for stage in PIPELINE_EXEC_STAGES},
            "loop_ms": {p: round(c.value, 3)
                        for p, c in self._loop_ctr.items()},
        }
