"""Deadline-aware continuous-batching admission scheduler (ISSUE 6).

The pre-scheduler serving path admitted work through FIXED batch-
assembly windows: the Python collector waited `max_wait_us` after the
first request of every batch, and the ring sidecar dispatched whatever
one dequeue pass returned. Both couple latency to an arbitrary timer
instead of to the thing the north star actually budgets — each
request's remaining deadline slack (p99 < 2 ms end to end).

This module is the plane-agnostic admission core both engine planes
drive (engine/service.py collector, native_ring.RingSidecar drain):

  * every request carries its ADMIT timestamp and a latency budget
    (`PINGOO_DEADLINE_MS`, default the 2 ms north-star budget);
  * the scheduler keeps filling the in-flight batch while the OLDEST
    request's slack still covers the estimated dispatch+compute cost
    of serving the batch — "launch when full OR slack <= estimate";
  * the cost estimate is an EWMA per padded-batch-size bucket
    (`CostModel`), seeded from bench history (`BENCH_history.jsonl`
    p_batch_ms) so the very first batches after boot already launch
    against a plausible cost instead of a blind timer;
  * a request whose deadline is UNMEETABLE (remaining slack below the
    estimate even if launched immediately) can fail open per
    `PINGOO_SCHED_FAILOPEN`: `serve` (default — serve late, count the
    miss), `allow` (resolve immediately with the fail-open verdict),
    or `interpret` (evaluate on the host interpreter, off the device
    path);
  * every launch/resolve feeds the `pingoo_sched_*` metrics
    (obs/schema.SCHED_METRICS) on the plane's label.

`PINGOO_SCHED_MODE=fixed` keeps the legacy fixed-window assembly (the
A/B arm `bench.py --mesh` measures against); `continuous` is the
default. The admission loop and the EWMA update are registered hot in
the analyze-lint registries (tools/analyze/lint_config.py): nothing
here may allocate arrays or touch the device — it is pure float math
on the collector/drain thread between dispatch and resolve.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional

# The north-star latency budget (BASELINE.md: p99 added verdict
# latency < 2 ms) is the default per-request deadline.
DEFAULT_DEADLINE_MS = 2.0

# Default EWMA smoothing for the per-bucket cost model: heavy enough to
# converge within tens of batches after boot, light enough that one
# GC-hiccup outlier cannot triple the estimate.
DEFAULT_ALPHA = 0.2

# Fallback seed when neither PINGOO_SCHED_SEED_MS nor a bench-history
# entry is available: the measured full-batch verdict cost on a v5e
# (bench.py p_batch_ms ~1.4 at B=2048).
DEFAULT_SEED_MS = 1.5

SCHED_MODES = ("continuous", "fixed")
FAILOPEN_POLICIES = ("serve", "allow", "interpret")

# Per-stage cost decomposition for the overlapped executor (ISSUE 9,
# docs/EXECUTOR.md): once stages overlap across in-flight batches, the
# single encode->result wall double-counts the time a batch spent
# waiting on another batch's stage token, so the planes feed each
# stage's ACTIVE wall separately and the estimate is their sum.
PIPELINE_COST_STAGES = ("encode", "dispatch", "compute")

# How the affine seed splits across stages before any per-stage
# observation lands (fractions sum to 1.0 so a pure-seed estimate
# matches the legacy single-wall seed exactly).
STAGE_SEED_SPLIT = {"encode": 0.3, "dispatch": 0.2, "compute": 0.5}

# pingoo_sched_batch_size histogram bounds: pow2 ladder matching the
# padded launch sizes the engine actually compiles for.
BATCH_SIZE_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024,
                      2048, 4096)


@dataclass(frozen=True)
class SchedulerConfig:
    """Static admission policy for one plane's scheduler."""

    mode: str = "continuous"
    deadline_ms: float = DEFAULT_DEADLINE_MS
    failopen: str = "serve"
    max_batch: int = 1024

    @classmethod
    def from_env(cls, max_batch: int) -> "SchedulerConfig":
        mode = os.environ.get("PINGOO_SCHED_MODE", "continuous")
        if mode not in SCHED_MODES:
            mode = "continuous"
        try:
            deadline_ms = float(
                os.environ.get("PINGOO_DEADLINE_MS", DEFAULT_DEADLINE_MS))
        except ValueError:
            deadline_ms = DEFAULT_DEADLINE_MS
        failopen = os.environ.get("PINGOO_SCHED_FAILOPEN", "serve")
        if failopen not in FAILOPEN_POLICIES:
            failopen = "serve"
        return cls(mode=mode, deadline_ms=deadline_ms, failopen=failopen,
                   max_batch=max_batch)


def seed_from_bench_history(path: Optional[str] = None) -> Optional[float]:
    """Newest usable `p_batch_ms` from BENCH_history.jsonl (bench.py
    --history appends one JSON object per run). Best-effort: a missing
    or corrupt history just returns None and the static seed applies.
    Read back to front so the seed tracks the latest measurement."""
    import json

    path = path or os.environ.get("BENCH_HISTORY_FILE",
                                  "BENCH_history.jsonl")
    try:
        with open(path) as f:
            lines = f.readlines()
    except OSError:
        return None
    for line in reversed(lines):
        try:
            entry = json.loads(line)
        except ValueError:
            continue
        val = entry.get("p_batch_ms")
        if isinstance(val, (int, float)) and val > 0:
            return float(val)
    return None


def seed_stages_from_bench_history(
        path: Optional[str] = None) -> Optional[dict]:
    """Newest usable per-stage EWMA map from BENCH_history.jsonl
    (ISSUE 12 satellite): bench.py --history flattens the pipelined
    arm's cost snapshot as `pipeline_on_stage_ewma_ms` =
    {stage: {"<bucket>": ms}}. Returns {stage: {bucket:int -> ms}} or
    None. Best-effort like seed_from_bench_history — a missing/corrupt
    history leaves the affine STAGE_SEED_SPLIT fallback in charge, but
    when history exists the first launch decisions run against
    MEASURED dispatch/compute walls instead of the 1.5 ms seed."""
    import json

    path = path or os.environ.get("BENCH_HISTORY_FILE",
                                  "BENCH_history.jsonl")
    try:
        with open(path) as f:
            lines = f.readlines()
    except OSError:
        return None
    for line in reversed(lines):
        try:
            entry = json.loads(line)
        except ValueError:
            continue
        raw = entry.get("pipeline_on_stage_ewma_ms")
        if not isinstance(raw, dict):
            continue
        out: dict = {}
        for stage, buckets in raw.items():
            if stage not in STAGE_SEED_SPLIT \
                    or not isinstance(buckets, dict):
                continue
            per_bucket = {}
            for b, ms in buckets.items():
                try:
                    bucket = int(b)
                    val = float(ms)
                except (TypeError, ValueError):
                    continue
                if bucket > 0 and val > 0:
                    per_bucket[bucket] = val
            if per_bucket:
                out[stage] = per_bucket
        if out:
            return out
    return None


def _pow2_bucket(n: int, cap: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


def _pow2_kb_bucket(nbytes: int) -> int:
    """Staged-bytes bucket for the dispatch cost model (ISSUE 15):
    pow2 KB, floor 1 KB — coarse enough that one serving config lands
    in one bucket, fine enough that full (~4.6 KB/req) and compact
    (few hundred B/req) staging never share one."""
    kb = max(1, (max(0, int(nbytes)) + 1023) // 1024)
    b = 1
    while b < kb:
        b *= 2
    return b


class CostModel:
    """EWMA per-batch-size dispatch-cost estimates (milliseconds).

    Buckets follow the engine's pow2 batch padding — the cost of a
    batch is a function of its PADDED size, which is what the XLA
    program actually runs. Unobserved buckets fall back to an affine
    seed (half fixed dispatch cost, half size-proportional), so the
    model orders sizes sensibly before the first measurements land.

    `observe` runs per batch on the collector/drain hot path
    (registered in lint_config.HOT_FUNCTIONS): one dict probe and two
    float ops, no arrays, no device access.
    """

    def __init__(self, max_batch: int = 1024,
                 seed_ms: Optional[float] = None,
                 alpha: float = DEFAULT_ALPHA):
        self.max_batch = max(1, int(max_batch))
        seeded_from_env_or_arg = (
            seed_ms is not None
            or bool(os.environ.get("PINGOO_SCHED_SEED_MS")))
        if seed_ms is None:
            env = os.environ.get("PINGOO_SCHED_SEED_MS")
            if env:
                try:
                    seed_ms = float(env)
                except ValueError:
                    seed_ms = None
            if seed_ms is None:
                seed_ms = seed_from_bench_history()
            if seed_ms is None:
                seed_ms = DEFAULT_SEED_MS
        self.seed_ms = max(float(seed_ms), 1e-3)
        self.alpha = float(alpha)
        self._ewma: dict[int, float] = {}
        # Per-stage ACTIVE-wall EWMAs (ISSUE 9): stage -> bucket -> ms.
        # Populated by the overlapped executor; once any stage has
        # data, estimate() is the SUM of stage estimates — the single
        # encode->result wall includes stage-token waits under overlap
        # and would inflate should_launch's slack math.
        #
        # Boot-seeded from bench history (ISSUE 12 satellite, gated the
        # same way as the batch-cost seed: only when no explicit seed
        # was pinned) so the first launch decisions run on measured
        # dispatch/compute walls. Live observations EWMA-blend
        # over the seed from the first batch.
        self._stage_ewma: dict[str, dict[int, float]] = {}
        if seeded_from_env_or_arg is False:
            hist = seed_stages_from_bench_history()
            if hist:
                self._stage_ewma = {s: dict(b) for s, b in hist.items()}
        # Dispatch-stage EWMAs keyed by staged-BYTES bucket (ISSUE 15):
        # the dispatch wall is bytes-proportional host staging, so with
        # compact staging in play the pow2 row bucket alone conflates
        # full and compact batches of the same size. Bytes-keyed
        # observations take precedence in estimate_dispatch.
        self._dispatch_bytes_ewma: dict[int, float] = {}

    def _seed_for(self, bucket: int) -> float:
        cap = _pow2_bucket(self.max_batch, self.max_batch)
        return self.seed_ms * (0.5 + 0.5 * bucket / cap)

    def _baseline(self, bucket: int) -> float:
        """Whole-batch wall estimate for one bucket: the legacy EWMA
        when observed, the affine seed otherwise."""
        est = self._ewma.get(bucket)
        if est is None:
            return self._seed_for(bucket)
        return est

    def estimate(self, batch_size: int) -> float:
        """Expected dispatch+compute wall (ms) for a batch whose padded
        size covers `batch_size` rows. Stage-decomposed when the
        executor feeds per-stage costs; unobserved stages fall back to
        their STAGE_SEED_SPLIT share of the whole-batch baseline."""
        bucket = _pow2_bucket(max(1, batch_size), self.max_batch)
        if not self._stage_ewma:
            return self._baseline(bucket)
        base = self._baseline(bucket)
        total = 0.0
        for stage in PIPELINE_COST_STAGES:
            est = self._stage_ewma.get(stage, {}).get(bucket)
            if est is None:
                est = STAGE_SEED_SPLIT[stage] * base
            total += est
        return total

    def estimate_stage(self, stage: str, batch_size: int) -> float:
        """Expected ACTIVE wall (ms) of ONE executor stage — the
        per-stage fail-open budget checks size their remaining-work
        slack with this instead of the whole-batch estimate."""
        bucket = _pow2_bucket(max(1, batch_size), self.max_batch)
        est = self._stage_ewma.get(stage, {}).get(bucket)
        if est is None:
            split = STAGE_SEED_SPLIT.get(stage, 1.0)
            return split * self._baseline(bucket)
        return est

    def observe(self, batch_size: int, ms: float) -> None:
        """EWMA update from one served batch's measured cost (hot)."""
        if ms < 0:
            return
        bucket = _pow2_bucket(max(1, batch_size), self.max_batch)
        prev = self._ewma.get(bucket)
        if prev is None:
            self._ewma[bucket] = ms
        else:
            self._ewma[bucket] = prev + self.alpha * (ms - prev)

    def observe_stage(self, stage: str, batch_size: int,
                      ms: float) -> None:
        """EWMA update for one executor stage's ACTIVE wall (hot) —
        callers must exclude time spent waiting on stage tokens."""
        if ms < 0 or stage not in STAGE_SEED_SPLIT:
            return
        bucket = _pow2_bucket(max(1, batch_size), self.max_batch)
        stages = self._stage_ewma.get(stage)
        if stages is None:
            stages = self._stage_ewma[stage] = {}
        prev = stages.get(bucket)
        if prev is None:
            stages[bucket] = ms
        else:
            stages[bucket] = prev + self.alpha * (ms - prev)

    def estimate_dispatch(self, batch_size: int,
                          staged_bytes: Optional[int] = None) -> float:
        """Expected dispatch-stage wall (ms), preferring the staged-
        BYTES-bucket EWMA when that bucket has been observed (ISSUE 15:
        compact staging ships a fraction of full mode's bytes at the
        same row count, so row-bucket estimates conflate the two)."""
        if staged_bytes:
            est = self._dispatch_bytes_ewma.get(
                _pow2_kb_bucket(staged_bytes))
            if est is not None:
                return est
        return self.estimate_stage("dispatch", batch_size)

    def observe_dispatch_bytes(self, staged_bytes: int,
                               ms: float) -> None:
        """EWMA update for the dispatch stage keyed by the batch's
        staged-bytes pow2-KB bucket (hot)."""
        if ms < 0 or not staged_bytes or staged_bytes <= 0:
            return
        bucket = _pow2_kb_bucket(staged_bytes)
        prev = self._dispatch_bytes_ewma.get(bucket)
        if prev is None:
            self._dispatch_bytes_ewma[bucket] = ms
        else:
            self._dispatch_bytes_ewma[bucket] = \
                prev + self.alpha * (ms - prev)

    def snapshot(self) -> dict:
        return {"seed_ms": round(self.seed_ms, 4),
                "ewma_ms": {b: round(v, 4)
                            for b, v in sorted(self._ewma.items())},
                "stage_ewma_ms": {
                    stage: {b: round(v, 4)
                            for b, v in sorted(buckets.items())}
                    for stage, buckets in sorted(
                        self._stage_ewma.items())},
                "dispatch_bytes_ewma_ms": {
                    f"{kb}kb": round(v, 4)
                    for kb, v in sorted(
                        self._dispatch_bytes_ewma.items())}}

    def restore(self, snap: dict) -> bool:
        """Inverse of snapshot(): overwrite this model's state from a
        durable cost-ledger entry (ISSUE 17). Snapshot keys arrive
        JSON-round-tripped — int bucket keys are strings, bytes keys
        "<kb>kb" — so each map is re-parsed; unparseable entries are
        skipped, and the method returns True if ANY state was restored.
        Keys this model no longer keeps (`megastep_ewma_ms`,
        `megastep_first_ms` of a ledger written before the K-window was
        deleted) are ignored. Overwrite (not blend) semantics: a
        ledger measured on the actual backend beats both the static
        seed and the lossy BENCH_history p_batch_ms seeding this path
        replaces."""
        if not isinstance(snap, dict):
            return False
        restored = False
        seed = snap.get("seed_ms")
        if isinstance(seed, (int, float)) and seed > 0:
            self.seed_ms = max(float(seed), 1e-3)
            restored = True

        def _fbuckets(raw):
            out = {}
            if isinstance(raw, dict):
                for b, v in raw.items():
                    try:
                        bucket, val = int(b), float(v)
                    except (TypeError, ValueError):
                        continue
                    if bucket > 0 and val >= 0:
                        out[bucket] = val
            return out

        ewma = _fbuckets(snap.get("ewma_ms"))
        if ewma:
            self._ewma = ewma
            restored = True
        stage_raw = snap.get("stage_ewma_ms")
        if isinstance(stage_raw, dict):
            stage = {}
            for name, buckets in stage_raw.items():
                if name not in STAGE_SEED_SPLIT:
                    continue
                parsed = _fbuckets(buckets)
                if parsed:
                    stage[name] = parsed
            if stage:
                self._stage_ewma = stage
                restored = True

        disp_raw = snap.get("dispatch_bytes_ewma_ms")
        if isinstance(disp_raw, dict):
            disp = {}
            for key, v in disp_raw.items():
                try:
                    kb = int(str(key).rstrip("kb"))
                    val = float(v)
                except (TypeError, ValueError):
                    continue
                if kb > 0 and val >= 0:
                    disp[kb] = val
            if disp:
                self._dispatch_bytes_ewma = disp
                restored = True
        return restored


# ----------------------------------------------------------------------
# Durable cost ledger (ISSUE 17): CostModel snapshots persisted on
# drain and reloaded at boot, versioned per backend + ruleset
# fingerprint so the future autotuner only ever selects from costs
# measured on the ACTUAL backend under the ACTUAL plan. This replaces
# the lossy BENCH_history seeding path: a reload overwrites whatever
# seed the constructor derived.

COST_LEDGER_VERSION = 1
DEFAULT_COST_LEDGER = "COST_LEDGER.json"


def cost_ledger_path() -> Optional[str]:
    """PINGOO_COST_LEDGER: unset/empty -> the default path (the ledger
    is on by default — it is pure boot-time/drain-time IO, never hot);
    `0`/`off` -> disabled; anything else is the path."""
    raw = os.environ.get("PINGOO_COST_LEDGER", "").strip()
    if raw.lower() in ("0", "off", "false", "none"):
        return None
    if not raw or raw.lower() in ("1", "on", "true"):
        return DEFAULT_COST_LEDGER
    return raw


def _reload_counter(plane: str, result: str, registry=None):
    if registry is None:
        from ..obs import REGISTRY as registry  # noqa: N813
    from ..obs import schema

    return registry.counter(
        "pingoo_costmodel_reload_total",
        schema.PERF_METRICS["pingoo_costmodel_reload_total"],
        labels={"plane": plane, "result": result})


def load_cost_ledger(cost: CostModel, *, backend: str, fingerprint: str,
                     plane: str, path: Optional[str] = None,
                     registry=None) -> str:
    """Boot-time reload of this plane's persisted CostModel snapshot.
    Returns the counted result label: `ok` (EWMAs restored), `stale`
    (version or ruleset-fingerprint mismatch — discarded), `missing`
    (no file / no entry for this backend+plane), `error` (unreadable),
    or `disabled` (gated off, nothing counted)."""
    import json

    if path is None:
        path = cost_ledger_path()
    if path is None:
        return "disabled"
    # Eager zero-valued series so the inventory is scrapeable from
    # boot regardless of which result fires.
    for result in ("ok", "stale", "missing", "error"):
        _reload_counter(plane, result, registry)
    entry_key = f"{backend}|{plane}"
    try:
        if not os.path.exists(path):
            _reload_counter(plane, "missing", registry).inc()
            return "missing"
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        _reload_counter(plane, "error", registry).inc()
        return "error"
    if not isinstance(doc, dict) \
            or doc.get("version") != COST_LEDGER_VERSION:
        _reload_counter(plane, "stale", registry).inc()
        return "stale"
    entry = (doc.get("entries") or {}).get(entry_key)
    if not isinstance(entry, dict):
        _reload_counter(plane, "missing", registry).inc()
        return "missing"
    if entry.get("fingerprint") != fingerprint:
        _reload_counter(plane, "stale", registry).inc()
        return "stale"
    if not cost.restore(entry.get("cost") or {}):
        _reload_counter(plane, "error", registry).inc()
        return "error"
    _reload_counter(plane, "ok", registry).inc()
    return "ok"


def save_cost_ledger(cost: CostModel, *, backend: str, fingerprint: str,
                     plane: str, path: Optional[str] = None) -> bool:
    """Drain-time persist of this plane's CostModel snapshot:
    read-merge-write (other backend|plane entries survive), atomic via
    tmp+rename, best-effort — a failed save never blocks shutdown."""
    import json
    import time

    if path is None:
        path = cost_ledger_path()
    if path is None:
        return False
    doc: dict = {"version": COST_LEDGER_VERSION, "entries": {}}
    try:
        with open(path) as f:
            prior = json.load(f)
        if isinstance(prior, dict) \
                and prior.get("version") == COST_LEDGER_VERSION \
                and isinstance(prior.get("entries"), dict):
            doc["entries"] = prior["entries"]
    except (OSError, ValueError):
        pass
    doc["entries"][f"{backend}|{plane}"] = {
        "ts": round(time.time(), 3),
        "backend": backend,
        "plane": plane,
        "fingerprint": fingerprint,
        "cost": cost.snapshot(),
    }
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
        return True
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


class SchedMetrics:
    """The plane's `pingoo_sched_*` instruments (obs/schema.py
    SCHED_METRICS). Created eagerly so both planes expose the full
    inventory from boot (zero-valued until traffic moves them)."""

    def __init__(self, plane: str, registry=None):
        if registry is None:
            from ..obs import REGISTRY as registry  # noqa: N813
        from ..obs import schema

        labels = {"plane": plane}
        self.queue_depth = registry.gauge(
            "pingoo_sched_queue_depth",
            schema.SCHED_METRICS["pingoo_sched_queue_depth"],
            labels=labels)
        self.batch_size = registry.histogram(
            "pingoo_sched_batch_size",
            schema.SCHED_METRICS["pingoo_sched_batch_size"],
            buckets=BATCH_SIZE_BUCKETS, labels=labels)
        self.deadline_miss = registry.counter(
            "pingoo_sched_deadline_miss_total",
            schema.SCHED_METRICS["pingoo_sched_deadline_miss_total"],
            labels=labels)
        self.failopen = registry.counter(
            "pingoo_sched_failopen_total",
            schema.SCHED_METRICS["pingoo_sched_failopen_total"],
            labels=labels)
        self.mesh_devices = registry.gauge(
            "pingoo_mesh_devices",
            schema.SCHED_METRICS["pingoo_mesh_devices"], labels=labels)
        self.mesh_devices.set(1)


class Scheduler:
    """One plane's admission scheduler: launch-timing policy + deadline
    accounting over the shared cost model.

    All timestamps are `time.monotonic()` seconds on the Python plane;
    the sidecar converts the ring's `enq_ms` clock before calling in.
    The policy methods are pure float math (hot path — see module
    docstring); the metrics sinks are O(1) registry instruments.
    """

    def __init__(self, config: SchedulerConfig, plane: str = "python",
                 cost_model: Optional[CostModel] = None, registry=None):
        self.config = config
        self.plane = plane
        self.cost = cost_model or CostModel(max_batch=config.max_batch)
        self.metrics = SchedMetrics(plane, registry=registry)
        self.launches = 0
        self.deadline_misses = 0
        self.failopens = 0

    # -- launch policy (hot) -------------------------------------------------

    def wait_budget_s(self, n_pending: int, oldest_admit_s: float,
                      now_s: float) -> float:
        """How much longer the plane may keep assembling this batch
        (seconds, <= 0 means launch NOW): the oldest request's
        remaining deadline slack minus the estimated cost of serving
        the batch at its current size."""
        if n_pending >= self.config.max_batch:
            return 0.0
        deadline_at = oldest_admit_s + self.config.deadline_ms / 1e3
        est_s = self.cost.estimate(n_pending) / 1e3
        return (deadline_at - now_s) - est_s

    def should_launch(self, n_pending: int, oldest_admit_s: float,
                      now_s: float) -> bool:
        """Launch when full OR when the oldest request's slack no
        longer covers the dispatch estimate."""
        return (n_pending >= self.config.max_batch
                or self.wait_budget_s(n_pending, oldest_admit_s,
                                      now_s) <= 0.0)

    def unmeetable(self, admit_s: float, now_s: float,
                   batch_size: int) -> bool:
        """True when this request's deadline cannot be met even by an
        immediate launch — the fail-open trigger."""
        deadline_at = admit_s + self.config.deadline_ms / 1e3
        return now_s + self.cost.estimate(batch_size) / 1e3 > deadline_at

    # -- accounting sinks ----------------------------------------------------

    def note_launch(self, batch_size: int, queue_depth: int) -> None:
        """One batch left admission for the device (hot)."""
        self.launches += 1
        self.metrics.batch_size.observe(batch_size)
        self.metrics.queue_depth.set(queue_depth)

    def note_resolved(self, admit_s: float, resolve_s: float) -> bool:
        """Per-request deadline accounting at resolve time; returns
        True when the request missed its deadline."""
        missed = (resolve_s - admit_s) * 1e3 > self.config.deadline_ms
        if missed:
            self.deadline_misses += 1
            self.metrics.deadline_miss.inc()
        return missed

    def note_misses(self, n: int) -> None:
        """Batched deadline-miss accounting (the sidecar counts misses
        with one vectorized compare per batch)."""
        if n > 0:
            self.deadline_misses += n
            self.metrics.deadline_miss.inc(n)

    def note_failopen(self, n: int = 1) -> None:
        self.failopens += n
        self.metrics.failopen.inc(n)

    def observe_cost(self, batch_size: int, ms: float) -> None:
        self.cost.observe(batch_size, ms)

    def observe_stage_cost(self, stage: str, batch_size: int,
                           ms: float) -> None:
        """Per-stage ACTIVE-wall feed from the overlapped executor
        (hot; ISSUE 9) — keeps should_launch's slack estimate honest
        once stages overlap across in-flight batches."""
        self.cost.observe_stage(stage, batch_size, ms)

    def observe_dispatch_bytes(self, staged_bytes: int,
                               ms: float) -> None:
        """Dispatch-stage wall keyed by the batch's staged-bytes bucket
        (hot; ISSUE 15 compact staging)."""
        self.cost.observe_dispatch_bytes(staged_bytes, ms)

    def snapshot(self) -> dict:
        return {
            "mode": self.config.mode,
            "deadline_ms": self.config.deadline_ms,
            "failopen_policy": self.config.failopen,
            "launches": self.launches,
            "deadline_misses": self.deadline_misses,
            "failopens": self.failopens,
            "cost_model": self.cost.snapshot(),
        }
