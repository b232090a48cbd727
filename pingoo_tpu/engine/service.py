"""VerdictService: adaptive batching between the host data plane and the
TPU verdict engine.

The reference evaluates rules inline per request (http_listener.rs:
251-264). Here requests enqueue a RequestTuple and await a verdict; a
collector loop drains the queue into fixed-size batches under a latency
deadline (SURVEY.md §7 "Latency vs batching": adaptive window tuned
against the 2ms p99 budget), encodes them (engine/batch.py), runs the
jitted verdict, and resolves per-request futures with (matched_row,
first_action, bot_score).

Fail-open fallback (SURVEY.md §5 failure detection): if the device path
raises, the batch is evaluated on the host interpreter instead — same
verdicts (that is the parity contract), only slower — and the error is
counted on the metrics surface.
"""

from __future__ import annotations

import asyncio
import os
import time
from dataclasses import dataclass
from typing import Optional

import numpy as np

from ..backend import backend_info
from ..compiler.plan import RulesetPlan
from ..config.schema import Action
from ..expr import execute_as_bool
from ..obs.flightrecorder import (FlightRecorder, register_recorder,
                                  tuple_digest)
from ..obs.perf import (batch_leading_dim, get_compile_ledger,
                        instrument_jit, plan_fingerprint,
                        set_dispatch_context, staging_widths)
from ..obs.pipeline import PipelineStats
from ..obs.provenance import (ParityAuditor, PrefilterAttribution,
                              RuleAttribution, provenance_enabled)
from ..obs.timeline import get_timeline
from ..sched import MeshExecutor, MeshUnavailable, Scheduler, SchedulerConfig
from ..sched.scheduler import load_cost_ledger, save_cost_ledger
from .batch import (
    RequestBatch,
    RequestTuple,
    ScanColumnCounters,
    StagingEncoder,
    batch_to_contexts,
    bucket_arrays,
    encode_requests,
    pad_batch,
    pow2_batch_size,
    resolve_stage_caps,
    stage_overflow_thresholds,
    tuple_to_context,
)
from .verdict import (action_lanes, finish_batch,
                      make_packed_prefilter_fn, make_packed_verdict_fn,
                      make_prefilter_fn, make_verdict_fn)

# Per-stage slices of the PINGOO_DEADLINE_MS budget (ISSUE 9,
# docs/EXECUTOR.md): cumulative launch-relative fractions a batch may
# have consumed when each HOST stage finishes before the whole batch
# fails open through the PINGOO_SCHED_FAILOPEN route (an overrunning
# encode must not stall the collector into the device dispatch; the
# compute stage's budget is the remainder and is enforced by the
# scheduler's unmeetable/deadline-miss machinery). Only enforced when
# the failopen policy is not `serve` — `serve` (the default) keeps
# verdicts flowing bit-identically and just counts the misses.
PIPELINE_STAGE_BUDGET = {"encode": 0.45, "dispatch": 0.75}


class _PlanSwap:
    """Admission-queue sentinel carrying a prepared ruleset hot-swap
    (ISSUE 11, docs/RESILIENCE.md). It travels the SAME queue as
    requests, so its queue position IS the epoch boundary: requests
    admitted ahead of it resolve on the old plan, requests behind it on
    the new one — no request is dropped or resolved twice."""

    __slots__ = ("plan", "lists", "tenant", "state", "fut")

    def __init__(self, plan, lists, tenant, state, fut):
        self.plan = plan
        self.lists = lists
        self.tenant = tenant
        self.state = state
        self.fut = fut


class _StageBudgetExceeded(RuntimeError):
    """A pipeline stage blew its slice of the deadline budget; the
    batch reroutes through the fail-open machinery instead of holding
    its pipeline slot through a doomed device round trip."""

    def __init__(self, stage: str, elapsed_ms: float):
        super().__init__(
            f"pipeline stage {stage!r} blew its deadline slice "
            f"({elapsed_ms:.3f} ms since launch)")
        self.stage = stage
        self.elapsed_ms = elapsed_ms


@dataclass
class Verdict:
    action: int  # unverified-client lane: 0 none, 1 block, 2 captcha
    matched: np.ndarray  # [R] bool, original rule order
    bot_score: float = 0.0
    # Verified-client lane: the reference's action loop skips Captcha
    # actions for captcha-verified clients but still blocks on any
    # matched rule carrying Block (http_listener.rs:251-264).
    verified_block: bool = False
    # True when the engine failed and this verdict is the fail-open
    # placeholder: `matched` is all-False garbage, so consumers that
    # read non-action columns (service routing) must fall back to
    # interpretation instead of trusting it.
    degraded: bool = False
    # Ruleset hot-swap (ISSUE 11): which plan epoch evaluated this
    # request. Batches flip plans only at launch boundaries, so every
    # verdict in a batch carries the same epoch — the per-epoch
    # bit-exactness contract tests/test_hotswap.py asserts.
    epoch: int = 0

    @property
    def block(self) -> bool:
        return self.action == 1

    @property
    def captcha(self) -> bool:
        return self.action == 2

    def action_for(self, captcha_verified: bool) -> int:
        """0 none / 1 block / 2 captcha for this client's verification
        state — the decision the reference loop would reach."""
        if captcha_verified:
            return 1 if self.verified_block else 0
        return self.action


@dataclass
class ServiceStats:
    """Per-service counters + the shared-registry instruments.

    The pre-registry `verdict_ms` list grew to 65536 floats and then
    deleted half (unbounded resident memory, O(n) truncation on the hot
    path, and percentile math over a python list per scrape); the
    fixed-bucket registry histograms replace it — O(1) observe, O(1)
    snapshot — while `snapshot()` keeps returning the same percentile
    keys (now bucket-upper-bound estimates, the same convention the
    native plane's histogram percentiles use)."""

    batches: int = 0
    requests: int = 0
    device_errors: int = 0
    score_errors: int = 0
    host_fallback_batches: int = 0
    batch_occupancy_sum: int = 0
    # Batch dedup (ISSUE 4 satellite): identical RequestTuples inside
    # one collector batch are encoded/evaluated once, the verdict fanned
    # out to every duplicate's future.
    dedup_hits: int = 0
    # Literal-prefilter cascade counters (docs/PREFILTER.md).
    prefilter_candidate_rate: float = 0.0
    scan_banks_skipped: int = 0
    # Bitsplit-DFA dispatch counters (docs/DFA.md) — host-static per
    # plan+env, folded once per device batch.
    dfa_banks: int = 0
    dfa_rechecks: int = 0

    def __post_init__(self):
        from ..obs import REGISTRY
        from ..obs.registry import LATENCY_BUCKETS_MS, WAIT_BUCKETS_MS
        from ..obs.schema import DFA_METRICS, PREFILTER_METRICS, VERDICT_STAGES

        self.wait_hist = REGISTRY.histogram(
            "pingoo_verdict_wait_ms",
            "verdict wait: evaluate() -> resolve (ms)",
            buckets=WAIT_BUCKETS_MS, labels={"plane": "python"})
        self.stage_hist = {
            stage: REGISTRY.histogram(
                "pingoo_verdict_stage_ms",
                "verdict pipeline stage latency (ms)",
                buckets=LATENCY_BUCKETS_MS,
                labels={"plane": "python", "stage": stage})
            for stage in VERDICT_STAGES}
        self.pf_rate_gauge = REGISTRY.gauge(
            "pingoo_prefilter_candidate_rate",
            PREFILTER_METRICS["pingoo_prefilter_candidate_rate"],
            labels={"plane": "python"})
        self.pf_skip_counter = REGISTRY.counter(
            "pingoo_scan_banks_skipped_total",
            PREFILTER_METRICS["pingoo_scan_banks_skipped_total"],
            labels={"plane": "python"})
        self.dfa_banks_counter = {
            mode: REGISTRY.counter(
                "pingoo_dfa_banks_total",
                DFA_METRICS["pingoo_dfa_banks_total"],
                labels={"plane": "python", "mode": mode})
            for mode in ("auto", "force")}
        self.dfa_recheck_counter = REGISTRY.counter(
            "pingoo_dfa_recheck_total",
            DFA_METRICS["pingoo_dfa_recheck_total"],
            labels={"plane": "python"})
        # Compact staging (ISSUE 15): bytes actually staged to the
        # device per verdict batch, split by the PINGOO_STAGING arm —
        # the numerator of the dispatch-wall reduction this plane is
        # serving under.
        from ..obs.schema import STAGING_METRICS
        self.staged_bytes_counter = {
            mode: REGISTRY.counter(
                "pingoo_staged_bytes_total",
                STAGING_METRICS["pingoo_staged_bytes_total"],
                labels={"plane": "python", "mode": mode})
            for mode in ("full", "compact")}

    def observe_stage(self, stage: str, ms: float, n: int = 1) -> None:
        h = self.stage_hist[stage]
        if n == 1:
            h.observe(ms)
        else:
            h.observe_n(ms, n)

    def snapshot(self) -> dict:
        return {
            "batches": self.batches,
            "requests": self.requests,
            "device_errors": self.device_errors,
            "score_errors": self.score_errors,
            "host_fallback_batches": self.host_fallback_batches,
            "mean_occupancy": (self.batch_occupancy_sum / self.batches
                               if self.batches else 0.0),
            "dedup_hits": self.dedup_hits,
            "prefilter_candidate_rate": round(
                self.prefilter_candidate_rate, 4),
            "scan_banks_skipped": self.scan_banks_skipped,
            "dfa_banks": self.dfa_banks,
            "dfa_rechecks": self.dfa_rechecks,
            "verdict_p50_ms": self.wait_hist.percentile(0.50),
            "verdict_p99_ms": self.wait_hist.percentile(0.99),
            "stages": {
                stage: {"count": h.count,
                        "p50_ms": h.percentile(0.50),
                        "p99_ms": h.percentile(0.99),
                        "mean_ms": round(h.sum / h.count, 4)
                        if h.count else 0.0}
                for stage, h in self.stage_hist.items()},
        }


class VerdictService:
    """Async facade over the batched engine."""

    def __init__(
        self,
        plan: RulesetPlan,
        lists: dict,
        max_batch: int = 1024,
        max_wait_us: int = 300,
        device: Optional[object] = None,
        use_device: bool = True,
        bot_score_params: Optional[object] = None,
    ):
        self.plan = plan
        self.lists = lists
        self.max_batch = max_batch
        self.max_wait_s = max_wait_us / 1e6
        self.bot_score_params = bot_score_params
        self._score_fn = None
        self.stats = ServiceStats()
        self.use_device = use_device
        self._queue: asyncio.Queue = asyncio.Queue()
        self._task: Optional[asyncio.Task] = None
        self._verdict_fn = None
        self._tables = None
        self._pf_fn = None
        self._pf_gated_banks = 0
        self._pf_attr = None
        # Continuous-batching admission scheduler + serving mesh
        # (ISSUE 6, docs/SCHEDULER.md): the scheduler replaces the
        # fixed max_wait_us assembly window with a deadline-slack
        # launch policy (PINGOO_SCHED_MODE=fixed keeps the old
        # behavior); the mesh executor shards tables/batches when
        # PINGOO_MESH asks for more than one device.
        self.sched = Scheduler(SchedulerConfig.from_env(max_batch),
                               plane="python")
        # Perf ledger + cross-plane timeline + durable cost ledger
        # (ISSUE 17, docs/OBSERVABILITY.md): the compile ledger wraps
        # every jitted program this plane builds (zero hot-path delta
        # while PINGOO_PERF_LEDGER is off), the timeline samples
        # batches at PINGOO_TIMELINE_SAMPLE, and the scheduler's
        # CostModel reloads the prior run's measured EWMAs — keyed to
        # this backend + ruleset fingerprint — instead of re-seeding
        # from BENCH_history.
        self._plan_fp = plan_fingerprint(plan)
        self._perf = get_compile_ledger()
        self._perf.ensure_instruments("python")
        self._timeline = get_timeline()
        self._timeline.ensure_instruments("python")
        # What JAX gave this process (None on the interpreter-only
        # plane): logged at boot, served in the metrics JSON, and the
        # cost ledger's backend key.
        self.backend = backend_info() if use_device else None
        self._backend_label = \
            self.backend["platform"] if use_device else "host"
        self.cost_ledger_result = load_cost_ledger(
            self.sched.cost, backend=self._backend_label,
            fingerprint=self._plan_fp, plane="python")
        # Degradation ladder (ISSUE 10, docs/RESILIENCE.md): this
        # plane's scattered fallbacks (staging->legacy encode,
        # DFA->NFA, mesh->single-device, device->interpreter) report
        # through one state machine — demotions are counted per rung
        # and probed back with exponential backoff.
        from .ladder import DegradationLadder

        self.ladder = DegradationLadder("python")
        self._dfa_probe = False
        self._dfa_mode0 = getattr(plan, "dfa_default_mode", "auto")
        self.mesh: Optional[MeshExecutor] = None
        # Double-buffered dispatch: up to this many batches in flight,
        # so batch N+1 assembles/encodes while batch N computes (the
        # first slice of the ROADMAP's pipelined-executor item).
        self._pipeline_depth = max(1, int(
            os.environ.get("PINGOO_SCHED_PIPELINE", "2")))
        self._inflight: set = set()
        # Overlapped zero-copy executor (ISSUE 9, docs/EXECUTOR.md):
        # PINGOO_PIPELINE=on (the default) encodes into reused staging
        # buffers and runs the evaluate chain as token-guarded stages
        # — batch N+1's encode overlaps batch N's device compute, but
        # two batches never fill staging or issue device work at the
        # same time. =off keeps the legacy per-batch-allocating chain
        # (the bench A/B arm and the bit-identity oracle).
        # PINGOO_PIPELINE_DEPTH overrides the in-flight batch bound.
        mode = os.environ.get("PINGOO_PIPELINE", "on").strip().lower()
        self.pipeline_mode = "off" if mode in ("off", "0", "false") else "on"
        try:
            self._pipeline_depth = max(1, int(os.environ.get(
                "PINGOO_PIPELINE_DEPTH", str(self._pipeline_depth))))
        except ValueError:
            pass
        self._pipe = PipelineStats("python", self._pipeline_depth)
        # Compact staging (ISSUE 15, docs/EXECUTOR.md "Compact
        # staging"): PINGOO_STAGING=compact stages plan-capped field
        # prefixes into ONE packed buffer and ships it in a single
        # device_put; the jitted programs slice the fields back out on
        # device. `full` (the default) keeps the per-field staging path
        # byte-for-byte untouched — the bit-identity oracle.
        self._stage_caps: Optional[dict] = None
        self._packed_verdict_fn = None
        self._packed_pf_fn = None
        self._staging: Optional[StagingEncoder] = None
        if self.pipeline_mode == "on":
            # nbuf = depth + 1: every in-flight batch holds one buffer
            # set and the collector encodes the next into another.
            self._staging = self._make_staging(plan)
        import threading as _threading

        # Per-stage in-flight tokens: host stages are serialized ACROSS
        # batches (the staging encoder's rotating buffers are checked
        # out non-atomically; two concurrent encodes would also just
        # fight over the GIL), while a batch holding no token — i.e.
        # blocked on device compute — lets the next batch's host work
        # run. That asymmetry IS the overlap.
        self._stage_tokens = {
            "encode": _threading.Lock(),
            "dispatch": _threading.Lock(),
        }
        # Verdict provenance (ISSUE 5): per-rule attribution, the
        # flight recorder, and the shadow-parity auditor. PINGOO_
        # PROVENANCE=0 turns the whole layer off; the parity auditor
        # additionally samples nothing until PINGOO_PARITY_SAMPLE > 0.
        self._last_batch_stages: dict = {}
        self.flight_recorder = None
        self._attribution = None
        self.parity = None
        if provenance_enabled():
            self.flight_recorder = register_recorder(FlightRecorder(
                "python", rule_names=plan.rule_names))
            self._attribution = RuleAttribution(plan.rule_names,
                                                plane="python")
            self.parity = ParityAuditor(plan, lists, plane="python",
                                        recorder=self.flight_recorder)
        # Ruleset hot-swap (ISSUE 11, docs/RESILIENCE.md): the plan
        # epoch this plane is serving (0 = boot plan); swap_plan()
        # prepares a new engine state off the serving path and the
        # collector flips to it at a batch boundary.
        self.ruleset_epoch = 0
        self.tenant = "default"
        self._device_hint = device
        from .hotswap import set_epoch_gauge

        set_epoch_gauge("python", 0)
        if use_device:
            state = self._build_engine_state(plan, device)
            if state is None:
                self.use_device = False
            else:
                self._adopt_engine_state(state)

    def _build_engine_state(self, plan: RulesetPlan,
                            device: Optional[object] = None
                            ) -> Optional[dict]:
        """Compile the plan-derived engine bundle (jitted fns, placed
        tables, mesh, staging buffers) WITHOUT touching the serving
        references. Backs both boot and swap_plan — for a swap it runs
        off the serving path, so admissions never wait on a compile.
        Returns None after a boot/build failure (fail-open: SURVEY.md
        §5 failure detection — a broken accelerator backend degrades to
        the XLA CPU engine, and a broken XLA entirely to the
        interpreter; never crash the data plane)."""
        try:
            import jax

            # Donated request buffers (ISSUE 9): XLA recycles each
            # pipelined batch's upload in place — requested only on
            # real accelerator backends (no-op + warning on cpu).
            from .verdict import donate_batch_buffers

            state: dict = {"plan": plan}
            # Compile-ledger wrapping (ISSUE 17): every jitted program
            # this state holds goes through instrument_jit so each XLA
            # trace/compile becomes a counted, persisted event. The
            # wrapper composes AFTER jax.jit — donation/static_argnums
            # semantics untouched — and is a no-op passthrough while
            # PINGOO_PERF_LEDGER is off.
            fp = plan_fingerprint(plan)
            widths = staging_widths(plan)

            def _wrap(fn, name):
                return instrument_jit(fn, name, plane="python",
                                      fingerprint=fp, widths=widths)

            state["verdict_fn"] = _wrap(make_verdict_fn(
                plan, donate=donate_batch_buffers()), "verdict")
            # Stage-A prefilter as its own dispatch so the pipeline
            # stage is separately timeable (None when the plan has
            # no factors or PINGOO_PREFILTER=off).
            pf = make_prefilter_fn(plan)
            state["pf_fn"] = \
                _wrap(pf.fn, "prefilter") if pf is not None else None
            state["pf_gated_banks"] = \
                len(pf.gated) if pf is not None else 0
            state["pf_attr"] = (
                PrefilterAttribution(pf.masked, plane="python")
                if pf is not None and provenance_enabled() else None)
            # Compact staging (ISSUE 15): the packed twins trace the
            # SAME predicate bodies over unpack_staged's device-side
            # slices; built only under PINGOO_STAGING=compact, so the
            # default path compiles nothing new.
            state["stage_caps"] = resolve_stage_caps(plan)
            state["packed_verdict_fn"] = None
            state["packed_pf_fn"] = None
            if state["stage_caps"] is not None:
                state["packed_verdict_fn"] = _wrap(
                    make_packed_verdict_fn(
                        plan, donate=donate_batch_buffers()), "verdict")
                ppf = make_packed_prefilter_fn(plan)
                state["packed_pf_fn"] = \
                    _wrap(ppf.fn, "prefilter") if ppf is not None \
                    else None
            # Mesh BEFORE table materialization: tp padding must
            # land in plan.np_tables before device_tables() runs.
            mesh = self._build_mesh(plan)
            tables = plan.device_tables()
            if mesh.active:
                tables = mesh.place_tables(tables)
            elif device is not None:
                tables = jax.device_put(tables, device)
            state["mesh"] = mesh
            state["tables"] = tables
            state["scan_columns"] = ScanColumnCounters(
                "python", plan, rows_sharded=mesh.dp > 1)
            state["staging"] = (self._make_staging(plan)
                                if self.pipeline_mode == "on" else None)
            return state
        except Exception as exc:
            # Boot-time demotion is permanent for this service (no
            # tables to probe against), but still counted/logged
            # through the ladder's device rung.
            self.ladder.note_failure("device", exc)
            return None

    def _adopt_engine_state(self, state: dict) -> None:
        """Install a pre-built engine bundle as the serving references.
        Only called with no batch in flight (boot, or the collector's
        swap point after the drain), so nothing reads these mid-flip."""
        self._verdict_fn = state["verdict_fn"]
        self._pf_fn = state["pf_fn"]
        self._pf_gated_banks = state["pf_gated_banks"]
        self._pf_attr = state["pf_attr"]
        self.mesh = state["mesh"]
        self._tables = state["tables"]
        if state.get("staging") is not None:
            self._staging = state["staging"]
        # Compact staging (ISSUE 15): the packed fns + caps flip with
        # the plan at the same batch boundary the staging encoder does,
        # so every batch is encoded AND decoded under one cap set.
        self._stage_caps = state.get("stage_caps")
        self._packed_verdict_fn = state.get("packed_verdict_fn")
        self._packed_pf_fn = state.get("packed_pf_fn")
        self._scan_columns = state["scan_columns"]
        self._set_cap_gauges()

    def _make_staging(self, plan: RulesetPlan) -> StagingEncoder:
        """The staging encoder for a plan: plain rotating buffers under
        PINGOO_STAGING=full, packed one-copy layout under =compact
        (caps from the plan's compile-time staging pass, overflow
        thresholds keeping the rewrite set exact)."""
        caps = resolve_stage_caps(plan)
        if caps is None:
            return StagingEncoder(self.max_batch, plan.field_specs,
                                  nbuf=self._pipeline_depth + 1)
        return StagingEncoder(
            self.max_batch, plan.field_specs,
            nbuf=self._pipeline_depth + 1, stage_caps=caps,
            overflow_thresholds=stage_overflow_thresholds(plan, caps))

    def _set_cap_gauges(self) -> None:
        """Export the adopted plan's per-field staging caps (host-
        static per epoch; the observable half of the staged-bytes
        reduction)."""
        if not self._stage_caps:
            return
        from ..obs import REGISTRY
        from ..obs.schema import STAGING_METRICS

        for field, cap in self._stage_caps.items():
            REGISTRY.gauge(
                "pingoo_staging_field_cap",
                STAGING_METRICS["pingoo_staging_field_cap"],
                labels={"field": field}).set(int(cap))

    def _build_mesh(self, plan) -> MeshExecutor:
        """The serving mesh for this plane (PINGOO_MESH). Degrades to
        the inactive single-device executor — never crashes the data
        plane — when the spec is malformed or needs more devices than
        the backend has; the failure is logged and visible as
        pingoo_mesh_devices == 1."""
        try:
            return MeshExecutor(plan, plane="python",
                                metrics=self.sched.metrics)
        except (MeshUnavailable, ValueError) as exc:
            self.ladder.note_failure("mesh", exc)
            return MeshExecutor(plan, spec=(1, 1, 1), plane="python",
                                metrics=self.sched.metrics)

    # -- degradation ladder (ISSUE 10, docs/RESILIENCE.md) --------------------

    def _rebuild_verdict_fn(self, dfa_off: bool) -> None:
        """Re-trace the verdict fn with the lowered DFAs in or out
        (plan-level default — what `_resolve_dfa_mode` falls back to
        when PINGOO_DFA is unset). The next batch pays one re-jit."""
        from .verdict import donate_batch_buffers

        self.plan.dfa_default_mode = "off" if dfa_off else self._dfa_mode0
        fp = plan_fingerprint(self.plan)
        widths = staging_widths(self.plan)
        self._verdict_fn = instrument_jit(
            make_verdict_fn(self.plan, donate=donate_batch_buffers()),
            "verdict", plane="python", fingerprint=fp, widths=widths)
        if self._packed_verdict_fn is not None:
            # The packed twin embeds the same DFA dispatch decision;
            # keep it in lockstep with the per-batch program.
            self._packed_verdict_fn = instrument_jit(
                make_packed_verdict_fn(
                    self.plan, donate=donate_batch_buffers()),
                "verdict", plane="python", fingerprint=fp,
                widths=widths)

    def _dfa_rung_tick(self) -> None:
        """Demoted-dfa probe: when the backoff window opens, restore
        the lowered-DFA dispatch for one batch; the device success /
        failure report then promotes or re-demotes."""
        if not self.use_device:
            return
        if not self.ladder.healthy("dfa") and not self._dfa_probe \
                and self.ladder.try_rung("dfa"):
            self._rebuild_verdict_fn(dfa_off=False)
            self._dfa_probe = True

    def _note_device_failure(self, exc: BaseException) -> None:
        """Cheapest-rung-first demotion: a device error with lowered
        DFAs active drops them back to the exact NFA scan before
        giving up on the device; only a failure with the DFAs already
        out (or pinned by PINGOO_DFA) demotes the device rung to the
        host interpreter."""
        from .verdict import dfa_dispatch_counts

        if self._dfa_probe:
            self.ladder.note_failure("dfa", exc)
            self._rebuild_verdict_fn(dfa_off=True)
            self._dfa_probe = False
        elif self.ladder.healthy("dfa") \
                and not os.environ.get("PINGOO_DFA") \
                and dfa_dispatch_counts(self.plan)[1] > 0:
            self.ladder.note_failure("dfa", exc)
            self._rebuild_verdict_fn(dfa_off=True)
        else:
            self.ladder.note_failure("device", exc)

    def _note_device_success(self) -> None:
        if self._dfa_probe:
            self.ladder.note_success("dfa")
            self._dfa_probe = False
        self.ladder.note_success("device")

    async def start(self) -> None:
        if self._task is None:
            self._task = asyncio.create_task(self._collector())
            # Warm the XLA program off the serving path so the first real
            # request doesn't pay the compile.
            if self.use_device:
                loop = asyncio.get_running_loop()
                await loop.run_in_executor(
                    None, self._evaluate_sync, [RequestTuple()])
            # Device-level tracing (SURVEY.md §5 tracing/profiling): the
            # structured logs + per-batch verdict timings are always on;
            # PINGOO_PROFILE_DIR additionally captures a jax.profiler
            # trace of the serving window for offline kernel analysis
            # (viewable in TensorBoard / xprof).
            profile_dir = os.environ.get("PINGOO_PROFILE_DIR")
            if profile_dir and self.use_device:
                try:
                    import jax

                    jax.profiler.start_trace(profile_dir)
                    self._tracing = True
                except Exception:
                    self._tracing = False

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
        # Drain the double-buffered in-flight batches: their futures
        # must resolve (fail-open at worst) before callers tear down.
        if self._inflight:
            await asyncio.gather(*list(self._inflight),
                                 return_exceptions=True)
        task = getattr(self, "_profile_task", None)
        if task is not None and not task.done():
            task.cancel()
            try:
                await task
            except asyncio.CancelledError:
                pass
            self._profile_task = None
        self.ensure_trace_stopped()
        if self.parity is not None:
            self.parity.stop()
        if self._attribution is not None:
            self._attribution.close()
        # Durable cost ledger (ISSUE 17): persist the measured EWMAs on
        # drain so the next boot estimates from THIS run's costs.
        self.persist_cost_ledger()

    def persist_cost_ledger(self) -> bool:
        """Snapshot the scheduler's CostModel into the durable cost
        ledger (PINGOO_COST_LEDGER). Idempotent + best-effort: also
        safe from the SIGTERM drain path after a blown graceful-stop
        deadline."""
        try:
            return save_cost_ledger(
                self.sched.cost, backend=self._backend_label,
                fingerprint=self._plan_fp, plane="python")
        except Exception:
            return False

    def ensure_trace_stopped(self) -> None:
        """Flush any live jax.profiler trace (the boot-time
        PINGOO_PROFILE_DIR capture or an on-demand /__pingoo/profile
        window). Idempotent and synchronous so the SIGTERM drain path
        can call it even when the graceful-stop deadline expired —
        without the explicit stop_trace the trace files are simply
        never written (the profiler buffers in memory)."""
        if getattr(self, "_tracing", False):
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception:
                pass
            self._tracing = False

    async def capture_profile(self, seconds: float,
                              out_dir: Optional[str] = None) -> dict:
        """On-demand bounded jax.profiler window (the /__pingoo/profile
        endpoint): generalizes the boot-only PINGOO_PROFILE_DIR hook to
        any serving moment. One capture at a time; the window is capped
        at 30 s so a forgotten curl cannot leave tracing overhead on."""
        seconds = max(0.1, min(float(seconds), 30.0))
        if getattr(self, "_tracing", False):
            return {"error": "a profiler trace is already active"}
        out_dir = out_dir or os.environ.get("PINGOO_PROFILE_DIR")
        if not out_dir:
            import tempfile

            out_dir = tempfile.mkdtemp(prefix="pingoo-profile-")
        try:
            import jax

            jax.profiler.start_trace(out_dir)
        except Exception as exc:
            return {"error": f"profiler unavailable: {exc!r}"}
        self._tracing = True

        async def _stop_after_window():
            try:
                await asyncio.sleep(seconds)
            finally:
                # Cancellation (service stop) must still flush.
                self.ensure_trace_stopped()

        self._profile_task = asyncio.create_task(_stop_after_window())
        return {"profiling": True, "dir": out_dir, "seconds": seconds}

    async def evaluate(self, req: RequestTuple) -> Verdict:
        """Await the verdict for one request (the per-request hot call)."""
        fut: asyncio.Future = asyncio.get_running_loop().create_future()
        await self._queue.put((req, fut, time.monotonic()))
        return await fut

    # -- batching loop -------------------------------------------------------

    async def _collector(self) -> None:
        """Admission loop (ISSUE 6): pop -> assemble under the
        scheduler's launch policy -> hand the batch to a double-
        buffered runner task, so batch N+1 assembles and encodes while
        batch N computes. In `continuous` mode the assembly window is
        the oldest request's remaining deadline slack minus the EWMA
        dispatch estimate — not a fixed timer; `fixed` keeps the
        legacy max_wait_us window (the bench A/B arm)."""
        sched = self.sched
        continuous = sched.config.mode == "continuous"
        sem = asyncio.Semaphore(self._pipeline_depth)
        while True:
            item = await self._queue.get()
            if isinstance(item, _PlanSwap):
                await self._apply_swap(item)
                continue
            t_first = time.monotonic()
            self.stats.observe_stage(
                "queue_wait", (t_first - item[2]) * 1e3)
            # Pending entries are (req, fut, t_enq, t_admit): t_enq
            # anchors the request's deadline (evaluate() entry — the
            # <2 ms budget is end to end), t_admit its collector pop.
            pending = [(item[0], item[1], item[2], t_first)]
            oldest_enq = item[2]
            fixed_deadline = t_first + self.max_wait_s
            # A swap sentinel popped mid-assembly closes the batch: the
            # requests admitted so far launch on the old plan, the flip
            # happens right after the launch (and drains it), and the
            # requests still queued behind the sentinel admit next
            # iteration on the new plan.
            swap = None
            while len(pending) < self.max_batch:
                now = time.monotonic()
                if continuous:
                    timeout = sched.wait_budget_s(
                        len(pending), oldest_enq, now)
                else:
                    timeout = fixed_deadline - now
                if timeout <= 0:
                    break
                try:
                    item = await asyncio.wait_for(self._queue.get(), timeout)
                except asyncio.TimeoutError:
                    break
                if isinstance(item, _PlanSwap):
                    swap = item
                    break
                t_adm = time.monotonic()
                self.stats.observe_stage(
                    "queue_wait", (t_adm - item[2]) * 1e3)
                pending.append((item[0], item[1], item[2], t_adm))
            # Greedy tail drain: whatever is ALREADY queued rides this
            # launch for free (burst traffic batches even when the
            # oldest request's slack is exhausted — launching
            # singletons under overload would only make every
            # follower later).
            while swap is None and len(pending) < self.max_batch:
                try:
                    item = self._queue.get_nowait()
                except asyncio.QueueEmpty:
                    break
                if isinstance(item, _PlanSwap):
                    swap = item
                    break
                t_adm = time.monotonic()
                self.stats.observe_stage(
                    "queue_wait", (t_adm - item[2]) * 1e3)
                pending.append((item[0], item[1], item[2], t_adm))
            t_launch = time.monotonic()
            # Scheduler hold time: first admit -> launch decision.
            self.stats.observe_stage("sched", (t_launch - t_first) * 1e3)
            # ISSUE 6 satellite (fairness fix): batch_assembly is
            # stamped PER REQUEST from its own admit timestamp — the
            # old single (t_launch - t_first) observation under-
            # reported queue wait for requests admitted late into a
            # large batch.
            for _, _, _, t_adm in pending:
                self.stats.observe_stage(
                    "batch_assembly", (t_launch - t_adm) * 1e3)
            sched.note_launch(len(pending), self._queue.qsize())
            await sem.acquire()
            task = asyncio.create_task(
                self._run_batch_guarded(pending, t_launch, sem))
            self._inflight.add(task)
            task.add_done_callback(self._inflight.discard)
            if swap is not None:
                await self._apply_swap(swap)

    # -- ruleset hot-swap (ISSUE 11, docs/RESILIENCE.md) ----------------------

    async def swap_plan(self, plan: RulesetPlan,
                        lists: Optional[dict] = None,
                        tenant: str = "default") -> dict:
        """Hot-swap the serving ruleset at the next batch boundary.

        The new plan's engine state (jitted programs, placed tables,
        staging buffers) is built and warmed HERE, off the serving path
        — compile-ahead; with the artifact cache / TenantPlanStore the
        plan itself was typically already compiled. Then a sentinel
        rides the admission queue: the collector launches everything
        admitted ahead of it on the old plan, awaits the in-flight
        batches, flips the references, and bumps `ruleset_epoch`. The
        returned dict carries {epoch, tenant, pause_ms}; pause_ms is
        the drain+flip wall (the admission stall the swap cost — the
        number bench_regress tracks as swap_pause_p99_ms)."""
        from .hotswap import note_swap

        if self._task is None:
            raise RuntimeError("swap_plan requires a started service")
        loop = asyncio.get_running_loop()
        state = None
        if self.use_device:
            state = await loop.run_in_executor(
                None, self._build_engine_state, plan, self._device_hint)
            if state is None:
                note_swap("python", tenant, "rejected")
                raise RuntimeError(
                    f"hot-swap rejected for tenant {tenant!r}: engine "
                    f"state build failed (old plan keeps serving)")
            # Warm the jitted programs off-path so the first post-swap
            # batch doesn't pay an XLA compile inside its deadline.
            await loop.run_in_executor(None, self._warm_state, state)
        fut: asyncio.Future = loop.create_future()
        await self._queue.put(_PlanSwap(plan, lists, tenant, state, fut))
        return await fut

    def _warm_state(self, state: dict) -> None:
        """Trace/compile the new state's device programs on a dummy
        row (best-effort — a warm failure surfaces later through the
        normal ladder machinery, not as a rejected swap)."""
        try:
            plan = state["plan"]
            batch = encode_requests([RequestTuple()], plan.field_specs)
            fast = pad_batch(
                RequestBatch(size=1, arrays=bucket_arrays(batch.arrays)),
                1)
            dev_arrays = fast.arrays
            mesh = state["mesh"]
            if mesh is not None and mesh.active:
                dev_arrays = mesh.shard_batch(dev_arrays)
            pf_hits = None
            if state["pf_fn"] is not None:
                pf_hits, _ = state["pf_fn"](state["tables"], dev_arrays)
            state["verdict_fn"](state["tables"], dev_arrays, pf_hits)
            # Compact staging (ISSUE 15): warm the packed twins on the
            # new plan's layout rung too — a swap that widens a cap
            # must not pay its re-trace inside a serving deadline.
            if (state.get("packed_verdict_fn") is not None
                    and state.get("staging") is not None):
                import jax

                pb = state["staging"].encode_requests(
                    [RequestTuple()], pad_to=1)
                if pb.packed is not None and not (
                        mesh is not None and mesh.active):
                    dev_packed = jax.device_put(pb.packed)
                    pf_hits = None
                    if state.get("packed_pf_fn") is not None:
                        pf_hits, _ = state["packed_pf_fn"](
                            state["tables"], dev_packed, pb.layout)
                    state["packed_verdict_fn"](
                        state["tables"], dev_packed, pb.layout, pf_hits)
        except Exception:
            pass

    async def _apply_swap(self, swap: _PlanSwap) -> None:
        """The epoch flip, in collector context at a batch boundary.
        Awaiting the in-flight set first is what makes it atomic:
        _run_batch reads self.plan/_tables/_verdict_fn when it runs, so
        no launched batch can observe a half-installed state — and no
        future is dropped (every pending request launched) or resolved
        twice (each launched exactly once)."""
        from .hotswap import note_swap, set_epoch_gauge

        t0 = time.monotonic()
        while self._inflight:
            await asyncio.gather(*list(self._inflight),
                                 return_exceptions=True)
        try:
            self._install_plan(swap)
        except Exception as exc:
            note_swap("python", swap.tenant, "rejected")
            if not swap.fut.done():
                swap.fut.set_exception(exc)
            return
        self.ruleset_epoch += 1
        self.tenant = swap.tenant
        pause_ms = (time.monotonic() - t0) * 1e3
        set_epoch_gauge("python", self.ruleset_epoch)
        note_swap("python", swap.tenant, "ok")
        self.stats.observe_stage("sched", pause_ms)
        if not swap.fut.done():
            swap.fut.set_result({"epoch": self.ruleset_epoch,
                                 "tenant": swap.tenant,
                                 "pause_ms": round(pause_ms, 3)})

    def _install_plan(self, swap: _PlanSwap) -> None:
        plan = swap.plan
        if self.use_device:
            if swap.state is None:
                raise RuntimeError("hot-swap with no prepared state")
            self._adopt_engine_state(swap.state)
        self.plan = plan
        if swap.lists is not None:
            self.lists = swap.lists
        self._dfa_mode0 = getattr(plan, "dfa_default_mode", "auto")
        self._dfa_probe = False
        # Provenance follows the plan: rule names/indices changed, so
        # attribution, the parity oracle, and flight-record annotation
        # restart on the new plan's shape (counters are cumulative
        # across epochs; the per-rule label sets re-seed).
        if self._attribution is not None:
            self._attribution.close()
            self._attribution = RuleAttribution(plan.rule_names,
                                                plane="python")
        if self.parity is not None:
            self.parity.stop()
            self.flight_recorder = register_recorder(FlightRecorder(
                "python", rule_names=plan.rule_names))
            self.parity = ParityAuditor(plan, self.lists, plane="python",
                                        recorder=self.flight_recorder)

    async def _run_batch_guarded(self, pending, t_launch, sem) -> None:
        try:
            await self._run_batch(pending, t_launch)
        except asyncio.CancelledError:
            raise
        except Exception:
            # The runner must never strand futures: resolve this batch
            # fail-open (no-match) and keep serving.
            self.stats.device_errors += 1
            R = len(self.plan.rules)
            for _, fut, _t, _a in pending:
                if not fut.done():
                    fut.set_result(Verdict(
                        action=0, matched=np.zeros(R, dtype=bool),
                        degraded=True, epoch=self.ruleset_epoch))
        finally:
            sem.release()

    @staticmethod
    def _dedup_key(req: RequestTuple) -> tuple:
        # Everything a verdict can depend on; trace_id deliberately
        # excluded (it never reaches the device arrays).
        return (req.method, req.path, req.url, req.host, req.user_agent,
                req.ip, req.remote_port, req.asn, req.country)

    async def _run_batch(self, pending: list, t_launch: float) -> None:
        # Unmeetable deadlines fail open FIRST (per PINGOO_SCHED_
        # FAILOPEN) so a hopeless request never occupies device budget.
        if self.sched.config.failopen != "serve":
            pending = await self._apply_failopen(pending)
            if not pending:
                return
        reqs = [r for r, _, _, _ in pending]
        # Batch dedup: replayed/bursty traffic repeats identical tuples
        # (same method/path/headers/ip); encode + evaluate each distinct
        # tuple once and fan the verdict out to every duplicate.
        seen: dict[tuple, int] = {}
        uniq_rows: list[int] = []
        row_of: list[int] = []
        for i, req in enumerate(reqs):
            key = self._dedup_key(req)
            j = seen.get(key)
            if j is None:
                j = len(uniq_rows)
                seen[key] = j
                uniq_rows.append(i)
            row_of.append(j)
        dups = len(reqs) - len(uniq_rows)
        eval_reqs = [reqs[i] for i in uniq_rows] if dups else reqs
        loop = asyncio.get_running_loop()
        stages: dict = {}  # per-batch (double-buffered batches overlap)
        # The pipeline slot id rides the batch's stage dict into the
        # evaluate chain (note_stage pairing) and every flight record
        # (which batch-in-flight a request's timings belong to).
        pipe_slot = self._pipe.enter(self.pipeline_mode)
        stages["pipeline_slot"] = pipe_slot
        try:
            t_eval = time.monotonic()
            try:
                matched, scores = await loop.run_in_executor(
                    None, self._evaluate_with_scores, eval_reqs, stages,
                    t_launch)
            except _StageBudgetExceeded:
                # A host stage blew its slice of the deadline budget:
                # the whole batch reroutes through the PINGOO_SCHED_
                # FAILOPEN route instead of riding the device.
                await self._failopen_batch(pending)
                return
            # Feed the EWMA cost model the measured encode->result wall
            # for this padded size — what the launch policy trades slack
            # against — plus the per-stage decomposition (ISSUE 9) so
            # wait_budget_s can price encode+dispatch+compute instead of
            # one opaque wall.
            psize = self._pow2_size(len(eval_reqs))
            self.sched.observe_cost(psize,
                                    (time.monotonic() - t_eval) * 1e3)
            if "encode_ms" in stages:
                self.sched.observe_stage_cost(
                    "encode", psize, stages["encode_ms"])
            if "device_dispatch_ms" in stages:
                self.sched.observe_stage_cost(
                    "dispatch", psize,
                    stages.get("prefilter_ms", 0.0)
                    + stages["device_dispatch_ms"])
            if "compute_wall_ms" in stages:
                # Dispatch-end -> results-ready: the honest remaining
                # wall a row's deadline must still cover after launch
                # (NOT the residual block at sync, which goes to ~0
                # exactly when the overlap works).
                self.sched.observe_stage_cost(
                    "compute", psize, stages["compute_wall_ms"])
            if dups:
                self.stats.dedup_hits += dups
                matched = matched[row_of]  # fan out to duplicate rows
                scores = scores[row_of]
            t_resolve = time.monotonic()
            actions, verified_block = action_lanes(self.plan, matched)
            self.stats.batches += 1
            self.stats.requests += len(reqs)
            self.stats.batch_occupancy_sum += len(reqs)
            for i, (_, fut, t_enq, _t_adm) in enumerate(pending):
                # The shared verdict-wait histogram measures the full
                # evaluate() -> resolve wall per REQUEST (queue wait
                # included) — the <2ms p99 budget is about this number.
                self.stats.wait_hist.observe((t_resolve - t_enq) * 1e3)
                self.sched.note_resolved(t_enq, t_resolve)
                if not fut.done():
                    fut.set_result(
                        Verdict(action=int(actions[i]), matched=matched[i],
                                bot_score=float(scores[i]),
                                verified_block=bool(verified_block[i]),
                                epoch=self.ruleset_epoch))
            t_res_end = time.monotonic()
            self.stats.observe_stage(
                "resolve", (t_res_end - t_resolve) * 1e3)
            self._pipe.note_stage(pipe_slot, "resolve",
                                  t_resolve, t_res_end)
            # Provenance AFTER future resolution: attribution fold +
            # flight records + the parity sampling decision never sit
            # between the device result and the waiting requests.
            t_prov = time.monotonic()
            if self._attribution is not None:
                self._observe_provenance(reqs, pending, matched, actions,
                                         t_resolve, t_launch, stages)
            self.stats.observe_stage(
                "provenance", (time.monotonic() - t_prov) * 1e3)
            # Cross-plane timeline (ISSUE 17): per-batch cost while
            # unsampled is the one add+compare inside sample().
            if self._timeline.sample():
                self._timeline.batch_python(
                    stages_ms=stages, t_launch=t_launch,
                    t_resolve=t_resolve, t_end=t_res_end,
                    rows=[(reqs[i].trace_id or "", pending[i][2],
                           pending[i][3])
                          for i in range(
                              min(len(pending),
                                  self._timeline.rows_per_batch))],
                    args={"pipeline_slot": pipe_slot})
        finally:
            self._pipe.exit()

    async def _failopen_batch(self, pending: list) -> None:
        """Resolve a whole batch through the PINGOO_SCHED_FAILOPEN
        route after a pipeline stage blew its slice of the deadline
        budget (docs/EXECUTOR.md): `allow` answers every future with
        the degraded no-match verdict immediately; `interpret` gives a
        real verdict off the device path. Only reachable when failopen
        != serve — `serve` never raises _StageBudgetExceeded."""
        self.sched.note_failopen(len(pending))
        R = len(self.plan.rules)
        if self.sched.config.failopen == "interpret":
            loop = asyncio.get_running_loop()
            late_reqs = [r for r, _, _, _ in pending]
            matched = await loop.run_in_executor(
                None, lambda: np.stack(
                    [self._interpret_row(r) for r in late_reqs]))
            acts, vblk = action_lanes(self.plan, matched)
            t_res = time.monotonic()
            for i, (_, fut, t_enq, _t_adm) in enumerate(pending):
                self.stats.wait_hist.observe((t_res - t_enq) * 1e3)
                self.sched.note_resolved(t_enq, t_res)
                if not fut.done():
                    fut.set_result(Verdict(
                        action=int(acts[i]), matched=matched[i],
                        verified_block=bool(vblk[i]),
                        epoch=self.ruleset_epoch))
            return
        t_res = time.monotonic()
        for _, fut, t_enq, _t_adm in pending:
            self.stats.wait_hist.observe((t_res - t_enq) * 1e3)
            self.sched.note_resolved(t_enq, t_res)
            if not fut.done():
                fut.set_result(Verdict(
                    action=0, matched=np.zeros(R, dtype=bool),
                    degraded=True, epoch=self.ruleset_epoch))

    async def _apply_failopen(self, pending: list) -> list:
        """Fail open the requests whose deadline is unmeetable even by
        an immediate launch (sched.unmeetable): `allow` resolves them
        with the fail-open verdict at once; `interpret` evaluates them
        on the host interpreter off the device path. Returns the
        requests that still ride the device batch."""
        now = time.monotonic()
        keep: list = []
        late: list = []
        for item in pending:
            if self.sched.unmeetable(item[2], now, len(pending)):
                late.append(item)
            else:
                keep.append(item)
        if not late:
            return pending
        self.sched.note_failopen(len(late))
        R = len(self.plan.rules)
        if self.sched.config.failopen == "allow":
            t_res = time.monotonic()
            for _, fut, t_enq, _t_adm in late:
                self.stats.wait_hist.observe((t_res - t_enq) * 1e3)
                self.sched.note_resolved(t_enq, t_res)
                if not fut.done():
                    fut.set_result(Verdict(
                        action=0, matched=np.zeros(R, dtype=bool),
                        degraded=True, epoch=self.ruleset_epoch))
            return keep
        # interpret: a real verdict, just off the device path — the
        # same degradation rung the watchdog fallback uses.
        loop = asyncio.get_running_loop()
        late_reqs = [r for r, _, _, _ in late]
        matched = await loop.run_in_executor(
            None, lambda: np.stack(
                [self._interpret_row(r) for r in late_reqs]))
        acts, vblk = action_lanes(self.plan, matched)
        t_res = time.monotonic()
        for i, (_, fut, t_enq, _t_adm) in enumerate(late):
            self.stats.wait_hist.observe((t_res - t_enq) * 1e3)
            self.sched.note_resolved(t_enq, t_res)
            if not fut.done():
                fut.set_result(Verdict(
                    action=int(acts[i]), matched=matched[i],
                    verified_block=bool(vblk[i]),
                    epoch=self.ruleset_epoch))
        return keep

    def _observe_provenance(self, reqs, pending, matched, actions,
                            t_resolve, t_launch, batch_stages) -> None:
        """Per-batch provenance: fold per-rule hit counters, flight-
        record each request, and hand the batch to the parity sampler.
        Runs on the collector path per batch — registered hot in the
        analyze-lint registries, so any device sync creeping in here
        fails `make analyze` (the matrix is already host-resident)."""
        self._attribution.fold_batch(matched.sum(axis=0))
        recorder = self.flight_recorder
        n = len(reqs)
        # Matched-rule ids per row from ONE nonzero pass (per-row
        # nonzero would be n small kernel launches' worth of overhead).
        rows, cols = np.nonzero(matched)
        per_row: dict[int, list] = {}
        # pingoo: allow(sync-tolist): host-resident numpy index vectors
        for r, c in zip(rows.tolist(), cols.tolist()):
            per_row.setdefault(r, []).append(c)
        # Recording more rows than the ring holds is pure wrap-around
        # churn; keep the LAST capacity rows of the batch.
        start = max(0, n - recorder.capacity)
        for i in range(start, n):
            req = reqs[i]
            stages = dict(batch_stages)
            stages["wait_ms"] = round(
                (t_resolve - pending[i][2]) * 1e3, 3)
            # ISSUE 6: admit -> launch slack per request (the share of
            # its wait the SCHEDULER chose, vs. queue/device time).
            stages["admit_to_launch_ms"] = round(
                (t_launch - pending[i][3]) * 1e3, 3)
            recorder.record(
                trace_id=req.trace_id,
                digest=tuple_digest(req.method, req.host, req.path,
                                    req.url, req.user_agent, req.ip),
                stages=stages,
                matched_rules=per_row.get(i, ()),
                action=int(actions[i]))
        if self.parity is not None:
            self.parity.submit_matrix(reqs, matched)

    def _evaluate_with_scores(self, reqs: list[RequestTuple],
                              stages: Optional[dict] = None,
                              t_launch: Optional[float] = None):
        """-> (matched [B, R], bot scores [B]). Scores ride the same
        encoded batch (BASELINE config 5: the vectorized bot head).
        `stages` collects this batch's per-stage timings — a PER-BATCH
        dict, because double-buffered dispatch (ISSUE 6) overlaps two
        batches' evaluations. With PINGOO_PIPELINE=on the encode runs
        into reused staging buffers under the encode token (ISSUE 9):
        already bucketed + padded, value-identical to the legacy
        encode->bucket->pad chain (tests/test_pipeline.py holds the
        bit-identity line)."""
        if stages is None:
            stages = {}
        self._last_batch_stages = stages  # latest batch (introspection)
        pipe_slot = stages.get("pipeline_slot")
        n = len(reqs)
        batch = None
        staged = False
        if self._staging is not None and self.ladder.try_rung("pipeline"):
            try:
                with self._stage_tokens["encode"]:
                    t0 = time.monotonic()
                    batch = self._staging.encode_requests(
                        reqs, pad_to=self._pow2_size(n))
                    t1 = time.monotonic()
                staged = True
                self.ladder.note_success("pipeline")
                if pipe_slot is not None:
                    self._pipe.note_stage(pipe_slot, "encode", t0, t1)
            except Exception as exc:
                # Ladder pipeline rung: a broken staging encoder
                # demotes this plane to the legacy encode chain below
                # (bit-identical, tests/test_pipeline.py) until a
                # backoff probe re-promotes it.
                self.ladder.note_failure("pipeline", exc)
                batch = None
        if batch is None:
            t0 = time.monotonic()
            batch = encode_requests(reqs, self.plan.field_specs)
            t1 = time.monotonic()
        self._batch_stage("encode", (t1 - t0) * 1e3, stages)
        self._check_stage_budget("encode", t_launch)
        # DISPATCH the scorer before the verdict runs: jax dispatch is
        # async, so the bot head computes while the verdict path does
        # its host work + device round trip, instead of serializing
        # after it (analyze-lint surfaced the old ordering, which
        # blocked on the scorer only once the verdict was already done).
        score_dev = None
        if self.bot_score_params is not None:
            try:
                if self._score_fn is None:
                    import jax

                    from ..models import botscore

                    self._score_fn = instrument_jit(
                        jax.jit(botscore.score), "score",
                        plane="python", fingerprint=self._plan_fp)
                # Pad to the same pow2 shape the verdict uses so the
                # jitted scorer compiles once per bucket, not per
                # occupancy.
                padded = pad_batch(batch, self._pow2_size(n))
                set_dispatch_context(batch=self._pow2_size(n))
                score_dev = self._score_fn(self.bot_score_params,
                                           padded.arrays)
            except Exception:
                # Scoring is advisory and never blocks verdicts, but a
                # broken scorer must show up on the metrics surface.
                self.stats.score_errors += 1
        matched = self._evaluate_sync(reqs, batch, stages, t_launch,
                                      staged=staged)
        # pingoo: allow(hot-alloc): [B] f32 default score vector
        scores = np.zeros(n, dtype=np.float32)
        if score_dev is not None:
            try:
                # pingoo: allow(sync-asarray-hot): scores materialize
                scores = np.asarray(  # after overlapping the verdict
                    score_dev, dtype=np.float32)[:n]
            except Exception:
                self.stats.score_errors += 1
        return matched, scores

    def pipeline_snapshot(self) -> dict:
        """Pipelined-executor introspection (ISSUE 9): mode, depth,
        in-flight count, per-stage occupancy and the overlap ratio —
        the JSON twin of the pingoo_pipeline_* registry gauges."""
        snap = self._pipe.snapshot()
        snap["mode"] = self.pipeline_mode
        return snap

    def _pow2_size(self, n: int) -> int:
        """Padded launch size: the shared pow2 ladder, dp-aligned when
        a serving mesh is active (the batch axis must shard evenly)."""
        multiple = self.mesh.dp if self.mesh is not None else 1
        return pow2_batch_size(n, self.max_batch, multiple=multiple)

    def _batch_stage(self, stage: str, ms: float,
                     stages: Optional[dict] = None) -> None:
        """Observe a pipeline stage AND stash it in the batch's stage
        dict the flight recorder attaches to every record (the dict is
        per batch: double-buffered batches overlap)."""
        self.stats.observe_stage(stage, ms)
        if stages is not None:
            stages[f"{stage}_ms"] = round(ms, 3)

    def _check_stage_budget(self, stage: str,
                            t_launch: Optional[float]) -> None:
        """Per-stage fail-open budget (ISSUE 9, docs/EXECUTOR.md):
        after each HOST stage, check the launch-relative elapsed time
        against that stage's cumulative slice of the deadline
        (PIPELINE_STAGE_BUDGET x PINGOO_DEADLINE_MS) and raise
        _StageBudgetExceeded to reroute the batch through the fail-open
        machinery. No-op under the default `serve` policy — serving
        bit-identical verdicts beats enforcing the budget."""
        if t_launch is None or self.sched.config.failopen == "serve":
            return
        frac = PIPELINE_STAGE_BUDGET.get(stage)
        if frac is None:
            return
        elapsed_ms = (time.monotonic() - t_launch) * 1e3
        if elapsed_ms > frac * self.sched.config.deadline_ms:
            raise _StageBudgetExceeded(stage, elapsed_ms)

    def _evaluate_sync(self, reqs: list[RequestTuple],
                       batch: Optional[RequestBatch] = None,
                       stages: Optional[dict] = None,
                       t_launch: Optional[float] = None,
                       staged: bool = False) -> np.ndarray:
        from contextlib import nullcontext

        n = len(reqs)
        if batch is None:
            batch = encode_requests(reqs, self.plan.field_specs)
            staged = False
        pipe_slot = (stages or {}).get("pipeline_slot")
        matched = None
        # Ladder device rung: while demoted, skip the dispatch entirely
        # (the host interpreter serves below) except for backoff probes;
        # a device exception demotes instead of staying an anonymous
        # device_errors increment.
        self._dfa_rung_tick()
        if self.use_device and self.ladder.try_rung("device"):
            try:
                if staged:
                    # Staging path (ISSUE 9): the encoder already
                    # bucketed the field axes and padded the batch axis
                    # — reusing its views IS the zero-copy win.
                    fast = batch
                else:
                    # Stabilize BOTH shape axes: bucket field lengths,
                    # and pad the batch axis to a power of two so
                    # arbitrary collector occupancies don't each
                    # compile a fresh XLA program.
                    arrays = bucket_arrays(batch.arrays)
                    fast = pad_batch(
                        RequestBatch(size=batch.size, arrays=arrays),
                        self._pow2_size(n))
                self._scan_columns.note(fast.arrays)
                # The dispatch token serializes device issue across
                # in-flight batches (program order stays deterministic)
                # while leaving compute token-free: batch N+1 encodes
                # and dispatches while batch N blocks on its result.
                tok = (self._stage_tokens["dispatch"]
                       if self._staging is not None else nullcontext())
                # True padded launch batch for the compile ledger's
                # surface check (the packed blob hides the batch axis
                # from arg-shape inspection).
                set_dispatch_context(batch=batch_leading_dim(fast.arrays))
                td0 = time.monotonic()
                with tok:
                    # Mesh placement (ISSUE 6): the device programs
                    # read the dp-sharded view; `fast` itself stays
                    # host-resident for the host-rule overlap +
                    # overflow re-interpretation.
                    dev_arrays = fast.arrays
                    if self.mesh is not None and self.mesh.active:
                        dev_arrays = self.mesh.shard_batch(dev_arrays)
                    # Compact staging (ISSUE 15): one device_put of the
                    # packed buffer replaces the per-field transfers —
                    # the bytes-proportional slice of the dispatch
                    # wall. Mesh stays on the per-field path (the
                    # shard plan addresses named arrays).
                    use_packed = (
                        staged and batch.packed is not None
                        and self._packed_verdict_fn is not None
                        and not (self.mesh is not None
                                 and self.mesh.active))
                    if stages is not None:
                        # Flight-row staging mode (ISSUE 17 satellite).
                        stages["staging_mode"] = \
                            "compact" if use_packed else "full"
                    if use_packed:
                        import jax
                        dev_packed = jax.device_put(batch.packed)
                    pf_hits = pf_aux = None
                    if self._pf_fn is not None:
                        # Stage A (always-on, whole batch): factor hits
                        # feed the verdict program's bank gating; the
                        # aux lanes feed the candidate-rate/skip
                        # metrics after the batch's sync point.
                        t0 = time.monotonic()
                        if use_packed and self._packed_pf_fn is not None:
                            pf_hits, pf_aux = self._packed_pf_fn(
                                self._tables, dev_packed, batch.layout)
                        else:
                            pf_hits, pf_aux = self._pf_fn(self._tables,
                                                          dev_arrays)
                        self._batch_stage(
                            "prefilter", (time.monotonic() - t0) * 1e3,
                            stages)
                    t0 = time.monotonic()
                    if use_packed:
                        dev = self._packed_verdict_fn(
                            self._tables, dev_packed, batch.layout,
                            pf_hits)
                    else:
                        dev = self._verdict_fn(self._tables, dev_arrays,
                                               pf_hits)
                    # jax dispatch is async: this stage is issue +
                    # host->device transfer; the on-device execution
                    # residual is timed inside finish_batch via
                    # block_until_ready, AFTER the host-interpreted
                    # rules overlapped it.
                    self._batch_stage(
                        "device_dispatch", (time.monotonic() - t0) * 1e3,
                        stages)
                td1 = time.monotonic()
                # Staged-bytes accounting (ISSUE 15): the transfer
                # volume behind this dispatch window, on the metrics
                # surface AND into the scheduler's bytes-keyed
                # dispatch EWMA.
                if batch.staged_bytes:
                    self.stats.staged_bytes_counter[
                        "compact" if batch.packed is not None
                        else "full"].inc(batch.staged_bytes)
                    self.sched.observe_dispatch_bytes(
                        batch.staged_bytes, (td1 - td0) * 1e3)
                if pipe_slot is not None:
                    self._pipe.note_stage(pipe_slot, "dispatch", td0, td1)
                self._check_stage_budget("dispatch", t_launch)
                matched = finish_batch(
                    self.plan, dev, fast, self.lists,
                    on_device_wait=lambda ms: self._batch_stage(
                        "device_compute", ms, stages))[:n]
                tc1 = time.monotonic()
                # The pipeline's compute window is dispatch-end ->
                # results-ready (the overlap denominator AND the
                # per-stage cost fed to the scheduler) — NOT the
                # residual block at sync, which goes to ~0 exactly
                # when the overlap works.
                if pipe_slot is not None:
                    self._pipe.note_stage(pipe_slot, "compute", td1, tc1)
                if stages is not None:
                    stages["compute_wall_ms"] = round(
                        (tc1 - td1) * 1e3, 3)
                if pf_aux is not None:
                    self._observe_prefilter(pf_aux, fast.size)
                self._observe_dfa()
                self._note_device_success()
            except _StageBudgetExceeded:
                raise
            except Exception as exc:
                self.stats.device_errors += 1
                self._note_device_failure(exc)
                matched = None
        if matched is None:
            self.stats.host_fallback_batches += 1
            # [:n]: the staging batch carries pow2 padding rows the
            # host interpreter evaluates too — slice them off.
            matched = self._evaluate_host(batch)[:n]
        return self._rewrite_overflow_rows(reqs, batch, matched)

    def _observe_prefilter(self, pf_aux, batch_rows: int) -> None:
        """Fold the Stage-A aux lanes into the metrics surface
        (obs/schema.py PREFILTER_METRICS). Called AFTER finish_batch's
        sync point — the aux vector was computed before the verdict even
        dispatched, so this materialization never waits on the device."""
        try:
            # pingoo: allow(sync-asarray-hot): aux int32 lanes resolved
            vals = np.asarray(pf_aux)  # long before the batch's sync
            cand_rows, skipped = int(vals[0]), int(vals[1])
        except Exception:
            return
        denom = batch_rows * self._pf_gated_banks
        self.stats.prefilter_candidate_rate = (
            cand_rows / denom if denom else 0.0)
        self.stats.scan_banks_skipped += skipped
        self.stats.pf_rate_gauge.set(self.stats.prefilter_candidate_rate)
        self.stats.pf_skip_counter.inc(skipped)
        if self._pf_attr is not None:
            # Per-bank candidate-rate/skip attribution (ISSUE 5).
            self._pf_attr.observe(vals, batch_rows)

    def _observe_dfa(self) -> None:
        """Bitsplit-DFA dispatch accounting (obs/schema.py DFA_METRICS):
        how many banks this batch ran through a lowered DFA under the
        resolved PINGOO_DFA mode, and how many of those took the
        approximate-lowering recheck path. Host-static per plan+env
        (engine/verdict.dfa_dispatch_counts), so this never waits on the
        device."""
        from .verdict import dfa_dispatch_counts

        mode, banks, rechecks = dfa_dispatch_counts(self.plan)
        if not banks:
            return
        self.stats.dfa_banks += banks
        self.stats.dfa_rechecks += rechecks
        ctr = self.stats.dfa_banks_counter.get(mode)
        if ctr is not None:
            ctr.inc(banks)
        if rechecks:
            self.stats.dfa_recheck_counter.inc(rechecks)

    def _rewrite_overflow_rows(self, reqs, batch, matched: np.ndarray):
        """Rows whose fields exceeded device capacity are re-evaluated on
        the host interpreter over the UNTRUNCATED strings — the reference
        matches full path/url (pingoo/rules.rs:37-51), so parity for
        over-long requests cannot be defined over the truncated view."""
        overflow = batch.overflow
        if overflow is None or not overflow[: len(reqs)].any():
            return matched
        from .verdict import interpret_rules_row

        for i in np.nonzero(overflow[: len(reqs)])[0]:
            ctx = tuple_to_context(reqs[i], self.lists)
            matched[i, :] = interpret_rules_row(self.plan, ctx)
        return matched

    # -- provenance introspection (the /__pingoo/explain endpoint) -----------

    def _interpret_row(self, req: RequestTuple) -> np.ndarray:
        from .verdict import interpret_rules_row

        return interpret_rules_row(
            self.plan, tuple_to_context(req, self.lists))

    async def explain(self, req: RequestTuple) -> dict:
        """Re-run ONE request end to end (the real batched device path)
        AND through the host interpreter oracle, returning the per-rule
        / per-stage provenance picture (the /__pingoo/explain payload,
        validated against the interpreter's rule trace in tests)."""
        verdict = await self.evaluate(req)
        loop = asyncio.get_running_loop()
        want = await loop.run_in_executor(None, self._interpret_row, req)
        rules = []
        mismatched = []
        for rule in self.plan.rules:
            dev_hit = bool(verdict.matched[rule.index]) \
                if not verdict.degraded else None
            interp_hit = bool(want[rule.index])
            if dev_hit is not None and dev_hit != interp_hit:
                mismatched.append(rule.name)
            rules.append({
                "name": rule.name,
                "index": rule.index,
                "host": rule.host,
                "always": rule.always,
                "actions": [a.value for a in rule.actions],
                "device": dev_hit,
                "interpreter": interp_hit,
            })
        # The flight record for this trace id lands in the provenance
        # stage, AFTER the future resolves — poll briefly for it.
        stages = None
        if self.flight_recorder is not None and req.trace_id:
            for _ in range(10):
                entry = next(
                    (e for e in self.flight_recorder.snapshot()
                     if e["trace_id"] == req.trace_id), None)
                if entry is not None:
                    stages = entry["stages_ms"]
                    break
                await asyncio.sleep(0.01)
        return {
            "trace_id": req.trace_id,
            "digest": tuple_digest(req.method, req.host, req.path,
                                   req.url, req.user_agent, req.ip),
            "request": {
                "method": req.method, "host": req.host,
                "path": req.path, "url": req.url,
                "user_agent": req.user_agent, "ip": req.ip,
                "asn": req.asn, "country": req.country,
            },
            "action": verdict.action,
            "verified_block": verdict.verified_block,
            "bot_score": verdict.bot_score,
            "degraded": verdict.degraded,
            "matched_rules": [
                r.name for r in self.plan.rules
                if bool(want[r.index] if verdict.degraded
                        else verdict.matched[r.index])],
            "rules": rules,
            "parity": {"consistent": not mismatched,
                       "mismatched_rules": mismatched},
            "stages_ms": stages,
        }

    def _evaluate_host(self, batch: RequestBatch) -> np.ndarray:
        """Interpreter path: the CPU engine (also the watchdog fallback)."""
        contexts = batch_to_contexts(batch, self.lists)
        R = len(self.plan.rules)
        out = np.zeros((batch.size, R), dtype=bool)
        for rule in self.plan.rules:
            if rule.always:
                out[:, rule.index] = True
                continue
            prog = rule.program
            for i, ctx in enumerate(contexts):
                try:
                    out[i, rule.index] = execute_as_bool(prog, ctx)
                except Exception:
                    out[i, rule.index] = False  # fail-open, always
        return out
