"""Configuration for the JAX hot-path linter (tools/analyze/lint.py).

Registries are repo-relative `path::qualname` strings; a method's
qualname is `Class.method`, nested functions join with dots
(`outer.inner`). docs/STATIC_ANALYSIS.md documents how to extend them.
"""

# Directories the linter walks (repo-relative). These hold the code
# that runs per batch on the serving path; host/ and the offline
# tooling are deliberately out of scope.
LINT_DIRS = (
    "pingoo_tpu/engine",
    "pingoo_tpu/ops",
    "pingoo_tpu/compiler",
    # The provenance layer (ISSUE 5) folds device aux lanes per batch;
    # its hot functions are registered below so a bare host-device sync
    # there fails `make analyze`.
    "pingoo_tpu/obs",
    # The admission scheduler + mesh executor (ISSUE 6) sit between
    # the queues and the compiled programs on every batch.
    "pingoo_tpu/sched",
)

# Never descend into these directory names, and never read non-.py
# files: caches and build outputs are not source (ISSUE 3 satellite —
# grep-based tools must not trip over __pycache__/ or binaries).
EXCLUDE_DIRS = frozenset({
    "__pycache__", ".git", ".pytest_cache", "build", "dist",
    ".mypy_cache", ".ruff_cache", "node_modules",
})

# Functions REGISTERED AS HOT: they run per batch with the request
# latency budget on the line, so host-device syncs (sync-asarray-hot)
# and fresh numpy allocations (hot-alloc) inside them must be either
# eliminated or individually justified with an inline suppression.
HOT_FUNCTIONS = frozenset({
    "pingoo_tpu/engine/service.py::VerdictService._evaluate_sync",
    "pingoo_tpu/engine/service.py::VerdictService._evaluate_with_scores",
    "pingoo_tpu/engine/service.py::VerdictService._run_batch",
    "pingoo_tpu/engine/service.py::VerdictService._observe_prefilter",
    # Bitsplit-DFA dispatch accounting (ISSUE 8): host-static counter
    # folds per batch — pure int math, no arrays, never a device sync.
    "pingoo_tpu/engine/service.py::VerdictService._observe_dfa",
    "pingoo_tpu/engine/verdict.py::finish_batch",
    "pingoo_tpu/engine/verdict.py::merge_lanes",
    # Verdict provenance (ISSUE 5): the attribution fold runs per batch
    # on the collector/drain path (the one sanctioned materialization of
    # the device aux lane is suppressed inline), and the parity
    # sampler's submit side must stay a pure sampling-decision +
    # queue-put — the interpreter re-evaluation belongs on the audit
    # worker thread, never the dispatch hot path.
    "pingoo_tpu/engine/service.py::VerdictService._observe_provenance",
    "pingoo_tpu/obs/provenance.py::RuleAttribution.fold_batch",
    "pingoo_tpu/obs/provenance.py::ParityAuditor.submit_matrix",
    "pingoo_tpu/obs/provenance.py::ParityAuditor.submit_lanes",
    "pingoo_tpu/obs/flightrecorder.py::FlightRecorder.record",
    # Continuous-batching scheduler (ISSUE 6): the launch policy and
    # the EWMA cost update run per batch on the collector/drain
    # threads between dispatch and resolve — pure float math, no
    # arrays, and NEVER a host-device sync. The mesh executor's batch
    # placement runs per batch too: async device_put issues only.
    "pingoo_tpu/sched/scheduler.py::Scheduler.wait_budget_s",
    "pingoo_tpu/sched/scheduler.py::Scheduler.should_launch",
    "pingoo_tpu/sched/scheduler.py::Scheduler.note_launch",
    "pingoo_tpu/sched/scheduler.py::CostModel.observe",
    "pingoo_tpu/sched/scheduler.py::CostModel.estimate",
    "pingoo_tpu/sched/mesh_exec.py::MeshExecutor.shard_batch",
    # Zero-copy pipelined executor (ISSUE 9): the staging encoders run
    # per batch under the encode token — they must FILL the reused
    # buffers, never allocate fresh ones; the per-stage budget check
    # and the stage cost/telemetry feeds are pure float math between
    # dispatch and resolve.
    "pingoo_tpu/engine/batch.py::StagingEncoder.encode_requests",
    "pingoo_tpu/engine/batch.py::StagingEncoder.encode_slots",
    "pingoo_tpu/engine/service.py::VerdictService._check_stage_budget",
    "pingoo_tpu/sched/scheduler.py::CostModel.observe_stage",
    "pingoo_tpu/sched/scheduler.py::CostModel.estimate_stage",
    "pingoo_tpu/sched/scheduler.py::Scheduler.observe_stage_cost",
    "pingoo_tpu/obs/pipeline.py::PipelineStats.note_stage",
    # Compact staging (ISSUE 15): the packed encoders fill the single
    # reused [B, width] staging buffer per batch (one strided copy per
    # field into REUSED memory, never a fresh matrix), and the meta
    # tail pack is pure byte stores into the same buffer.
    "pingoo_tpu/engine/batch.py::StagingEncoder._encode_requests_packed",
    "pingoo_tpu/engine/batch.py::StagingEncoder._encode_slots_packed",
    "pingoo_tpu/engine/batch.py::StagingEncoder._pack_meta",
    # Perf ledger + timeline (ISSUE 17): the compile probe wraps EVERY
    # jitted dispatch (two O(1) cache-size calls per invocation; event
    # assembly only on the rare compile branch), the stride sampler is
    # one float add+compare per batch, and the span-record methods are
    # pure float math over already-host stage numbers into a bounded
    # deque — no arrays, never a device sync.
    "pingoo_tpu/obs/perf.py::_InstrumentedJit.__call__",
    "pingoo_tpu/obs/timeline.py::Timeline.sample",
    "pingoo_tpu/obs/timeline.py::Timeline.add_span",
    "pingoo_tpu/obs/timeline.py::Timeline.batch_python",
    "pingoo_tpu/obs/timeline.py::Timeline.batch_sidecar",
    # The drain loop's span source (ISSUE 28): entered and left about
    # ten times a batch, and `idle` once per empty pass — one
    # TraceAnnotation, one monotonic stamp and float math into the
    # sinks, never an array, never a device sync.
    "pingoo_tpu/obs/pipeline.py::_Stage.__enter__",
    "pingoo_tpu/obs/pipeline.py::_Stage.next",
    "pingoo_tpu/obs/pipeline.py::_Stage.__exit__",
    "pingoo_tpu/obs/pipeline.py::PipelineStats.stage",
    "pingoo_tpu/obs/pipeline.py::PipelineStats.begin",
    "pingoo_tpu/obs/pipeline.py::PipelineStats.finish",
    "pingoo_tpu/obs/pipeline.py::PipelineStats.idle",
    "pingoo_tpu/obs/pipeline.py::PipelineStats.wake",
    "pingoo_tpu/obs/pipeline.py::PipelineStats._open",
    "pingoo_tpu/obs/pipeline.py::PipelineStats._close",
    "pingoo_tpu/obs/pipeline.py::PipelineStats._note_exec",
    "pingoo_tpu/obs/pipeline.py::PipelineStats._push",
    "pingoo_tpu/obs/pipeline.py::PipelineStats._switch",
    "pingoo_tpu/obs/pipeline.py::PipelineStats._pop",
    "pingoo_tpu/obs/pipeline.py::PipelineStats._flush",
    # The cascade's row counters (ISSUE 35): folded once a batch where
    # the batch resolves, from host ints that came with the lanes' own
    # copy — counter adds, never an array, never a device sync.
    "pingoo_tpu/obs/pipeline.py::CascadeCounters.fold",
})

# Functions traced by jax.jit that the AST cannot see are jitted (they
# are CALLED from a jit-decorated function rather than decorated
# themselves). Their bodies execute at trace time: jnp.asarray of a
# captured host constant there is re-staged on every retrace
# (recompile-const-upload). Nested defs inherit traced-ness.
TRACED_FUNCTIONS = frozenset({
    "pingoo_tpu/engine/verdict.py::_matched_cols",
    "pingoo_tpu/engine/verdict.py::_eval_leaves",
    "pingoo_tpu/engine/verdict.py::_eval_bool",
    "pingoo_tpu/engine/verdict.py::_eval_num",
    # Stage-A prefilter kernel (ISSUE 4): traced per batch from the
    # verdict/lane programs and from make_prefilter_fn.
    "pingoo_tpu/ops/prefilter.py::prefilter_scan",
    "pingoo_tpu/ops/prefilter.py::_fused_prefilter",
    # Bitsplit-DFA byte ladder (ISSUE 8): traced from the verdict
    # program's bank dispatch (engine/verdict run_packed_scans).
    "pingoo_tpu/ops/bitsplit_dfa.py::dfa_scan",
    "pingoo_tpu/ops/bitsplit_dfa.py::_fused_dfa",
    # The byte loops' shared driver (ISSUE 29): traced from both of the
    # above through their *_scan_chunk.
    "pingoo_tpu/ops/live_columns.py::scan_live_columns",
})

# The explicit blessing list for block_until_ready: the ONE deliberate
# device sync point per plane. Everything else must go through these.
# (_await_device is the wait primitive finish_batch routes its single
# sanctioned sync through.)
BLOCK_UNTIL_READY_ALLOW = frozenset({
    "pingoo_tpu/engine/verdict.py::_await_device",
})

# Attribute/function names that hold jitted dispatch callables: casting
# their result to a Python scalar (float()/int()/bool()) forces a
# blocking device round-trip per call (sync-scalar-cast).
JITTED_DISPATCH_NAMES = frozenset({
    "_verdict_fn", "_score_fn", "_lane_fn", "_pf_fn", "verdict_fn",
    "lane_fn",
})

# Registered shape quantizers (unbounded-compile-axis): the ONLY
# sanctioned routes from a raw size (len(x), arr.shape) to a jitted
# dispatch argument. Each lands its input on a closed rung ladder, so
# the reachable compile set stays inside the statically-proved
# COMPILE_SURFACE.json bound (tools/analyze/surface.py).
SHAPE_QUANTIZERS = frozenset({
    "pow2_batch_size",   # engine/batch.py: pow2 batch ladder, floor 8
    "bucket_len",        # engine/batch.py: field-axis length buckets
    "bucket_arrays",     # engine/batch.py: bucket every field axis
    "pad_batch",         # engine/batch.py: pad batch axis to a rung
    "quantize_stage_cap",  # compiler/plan.py: staging-width rungs
    "upload_rows",       # engine/batch.py: packed upload-height rungs
    "_pow2_size",        # service wrapper over pow2_batch_size
})

# numpy allocators flagged inside hot functions (hot-alloc).
NP_ALLOCATORS = frozenset({
    "zeros", "ones", "empty", "full", "zeros_like", "ones_like",
    "empty_like", "full_like", "concatenate", "stack", "vstack",
    "hstack", "tile", "repeat",
})

# numpy materializers that force a device->host copy when handed a jax
# array (sync-asarray-hot, flagged inside hot functions).
NP_MATERIALIZERS = frozenset({"asarray", "array", "ascontiguousarray"})
