"""The documented metric inventory — the parity contract between the
three telemetry surfaces (Python listener, native httpd, ring sidecar).

WAFFLED (PAPERS.md) turns parsing discrepancies between WAF planes into
bypasses; the counter-measure on the telemetry side is that both planes
export the SAME metric names for shared concepts so divergence (e.g.
native `requests` minus sidecar `processed`) is one subtraction on one
scrape, not a join across incompatible schemas. tests/test_obs.py and
tools/check_metrics_schema.py enforce this inventory against the actual
expositions; docs/OBSERVABILITY.md is the human-readable copy.
"""

from __future__ import annotations

# Metric names every plane that handles requests must expose (with a
# `plane` label distinguishing the source: python | native).
SHARED_METRICS = {
    "pingoo_requests_total": "requests entering the WAF hot path",
    "pingoo_blocked_total": "requests answered 403 by a verdict",
    "pingoo_captcha_total": "captcha challenges served/redirected",
    "pingoo_fail_open_total":
        "requests released without a verdict (ring full, verdict "
        "deadline, engine error)",
}

# Shared verdict-wait histogram: identical bucket upper bounds (ms) on
# every surface. Native plane: enqueue -> verdict-apply wall time
# (httpd.cc record_wait). Python plane: evaluate() -> resolve wall time
# (the pre-registry `verdict_ms`). Ring telemetry block: enqueue ->
# verdict-post (pingoo_ring.cc record_waits).
SHARED_WAIT_HISTOGRAM = "pingoo_verdict_wait_ms"
SHARED_WAIT_BUCKETS_MS = (1, 2, 5, 10, 50, 100, 1000)

# Python-plane verdict pipeline stages, in hot-path order
# (engine/service.py): each is a pingoo_verdict_stage_ms{stage=...}
# histogram.
VERDICT_STAGES = (
    "queue_wait",      # evaluate() enqueue -> collector pop
    "batch_assembly",  # PER-REQUEST admit -> batch launch (ISSUE 6:
                       # stamped from each request's own admit
                       # timestamp, not the batch's first pop)
    "sched",           # scheduler hold: first admit -> launch decision
    "encode",          # RequestTuple list -> fixed-shape arrays
    "prefilter",       # Stage-A factor pass dispatch (async; ISSUE 4)
    "device_dispatch", # jitted call issue (async) incl. host->device
    "device_compute",  # block_until_ready on the device result
    "resolve",         # lanes/actions + future resolution
    "provenance",      # attribution fold + flight record + parity submit
)

# Literal-prefilter cascade metrics (docs/PREFILTER.md): exported by
# every plane that runs the batched verdict engine — the Python
# listener plane (engine/service.py, plane="python") and the ring
# sidecar serving the native plane (native_ring.py, plane="sidecar").
# The "prefilter" entry in VERDICT_STAGES above is the matching
# prefilter_ms stage histogram.
PREFILTER_METRICS = {
    "pingoo_prefilter_candidate_rate":
        "fraction of request x gated-NFA-bank pairs the literal "
        "prefilter left as candidates in the last batch",
    "pingoo_scan_banks_skipped_total":
        "NFA bank scans skipped because no request in the batch held "
        "any of the bank's necessary literal factors",
}

# The cascade's second half, row by row (ISSUE 35, docs/PREFILTER.md
# "What the cascade counts"): counted by the lanes program itself
# (engine/verdict.cascade_counts: they ride the lanes' one device->host
# copy) and folded by the sidecar where the batch resolves
# (obs/pipeline.CascadeCounters), per Stage-A-gated or approximate-DFA
# bank. plane="sidecar" only: the Python listener plane runs the
# verdict matrix, not the lanes program.
CASCADE_METRICS = {
    "pingoo_cascade_rows_total":
        "{bank, stage}: rows a bank saw: live (the batch's requests), "
        "candidate (rows Stage A left it), recheck (rows its "
        "approximate DFA flagged, re-scanned by the exact bank)",
    "pingoo_cascade_bucket_rows_total":
        "{bank, ladder}: rows of the gather-ladder bucket taken, "
        "summed: candidate (the Stage-A compaction) and recheck (the "
        "exact re-scan); over the stage's rows it is the padding paid",
}

# Bitsplit-DFA lowering metrics (ISSUE 8, docs/DFA.md): exported by
# every plane that runs the batched verdict engine (plane="python"
# listener service, plane="sidecar" ring drainer). Both are host-static
# per plan+env — counted once per batch from the plan's scan_plans and
# the resolved PINGOO_DFA mode (engine/verdict.dfa_dispatch_counts),
# not from device results. `pingoo_dfa_banks_total` carries a `mode`
# label (auto | force) naming how the dispatch was selected.
DFA_METRICS = {
    "pingoo_dfa_banks_total":
        "NFA bank evaluations dispatched to a lowered bitsplit DFA "
        "(mode label: auto = cost-model selected, force = env pinned)",
    "pingoo_dfa_recheck_total":
        "DFA bank dispatches that took the approximate-lowering path "
        "(merged states) and rechecked candidate rows through the "
        "exact NFA bank",
}

# Verdict-provenance metrics (ISSUE 5, docs/OBSERVABILITY.md
# Provenance/Parity sections): exported by every plane that runs the
# batched verdict engine (plane="python" listener service,
# plane="sidecar" ring drainer). Per-rule families carry a `rule` label
# bounded to the top-K hitters (PINGOO_ATTR_TOP_K) plus one "_overflow"
# series so a 500-rule plan cannot blow up Prometheus cardinality;
# per-bank families carry a `bank` label (one per gated scan bank — at
# most a handful per ruleset by construction).
PROVENANCE_METRICS = {
    "pingoo_rule_hits_total":
        "requests matching each rule (top-K labelled series + the "
        "\"_overflow\" remainder bucket)",
    "pingoo_prefilter_bank_candidate_rate":
        "fraction of the last batch's rows Stage A left as candidates "
        "for this gated scan bank",
    "pingoo_scan_bank_skipped_total":
        "batches in which this gated scan bank was skipped entirely",
    "pingoo_flightrecorder_records_total":
        "requests written into the in-memory flight-recorder ring",
}

# Shadow-parity auditor metrics (ISSUE 5): the always-on sampler that
# re-evaluates PINGOO_PARITY_SAMPLE of live batches through the host
# expression interpreter off the hot path and diffs the verdicts.
PARITY_METRICS = {
    "pingoo_parity_checked_total":
        "requests re-evaluated by the shadow-parity auditor",
    "pingoo_parity_mismatch_total":
        "audited requests whose device verdict diverged from the host "
        "interpreter",
    "pingoo_parity_rule_mismatch_total":
        "per-rule breakdown of parity divergences (bounded rule label "
        "+ \"_overflow\")",
    "pingoo_parity_dropped_total":
        "sampled batches dropped because the audit queue was full",
}

# Overlapped-executor pipeline metrics (ISSUE 9, docs/EXECUTOR.md):
# exported by every plane that runs the batched verdict engine
# (plane="python" listener service, plane="sidecar" ring drainer). The
# instrument bundle lives in obs/pipeline.PipelineStats — both planes
# construct one at boot, which is what makes the pingoo_pipeline_*
# series exist under both plane labels. `stage_occupancy` carries a
# `stage` label over obs/pipeline.PIPELINE_EXEC_STAGES;
# `batches_total` carries a `mode` label (on = staged overlap,
# off = legacy lockstep — the PINGOO_PIPELINE A/B arms).
PIPELINE_METRICS = {
    "pingoo_pipeline_inflight":
        "batches currently in flight in the overlapped executor "
        "(bounded by PINGOO_PIPELINE_DEPTH)",
    "pingoo_pipeline_depth":
        "configured executor in-flight bound (PINGOO_PIPELINE_DEPTH)",
    "pingoo_pipeline_stage_occupancy":
        "fraction of wall time this executor stage has been busy "
        "since boot (stages summing past 1.0 prove overlap)",
    "pingoo_pipeline_overlap_ratio":
        "EWMA fraction of each batch's device-compute window that a "
        "different in-flight batch spent in host-side encode/dispatch",
    "pingoo_pipeline_batches_total":
        "batches served by the executor, split by mode (on = staged "
        "overlap, off = legacy lockstep)",
    # The sidecar drain loop's phase account (obs/pipeline.LOOP_PHASES):
    # every instant of the drain thread is in exactly one `phase`, so
    # the phases' deltas add up to the wall time between two scrapes.
    "pingoo_sidecar_loop_ms_total":
        "drain-thread wall time (ms) by loop phase (poll|encode|"
        "prefilter|dispatch|host_rules|device_wait|resolve|provenance|"
        "bodies|swap|idle); the phases partition the loop's time",
    "pingoo_sidecar_runqueue_us_total":
        "drain thread's wait on a CPU run queue (us; the second field of "
        "/proc/thread-self/schedstat), read once a second; absent where "
        "that file is missing",
    "pingoo_sidecar_stall_total":
        "spans of one non-idle drain-loop phase longer than 250 ms, by "
        "phase (each also logs its batch and the ring depth)",
    "pingoo_sidecar_completions_total":
        "batches the drain loop completed, by the rule that chose the "
        "moment (how=ready: its device lanes were already there; depth: "
        "PINGOO_PIPELINE_DEPTH batches in flight, the loop blocked on "
        "the oldest; drain: a pass that launched nothing, the flush, a "
        "swap boundary; staging: it held the staging buffers the next "
        "batch is encoded into)",
    "pingoo_sidecar_host_copies_total":
        "device arrays the drain loop materialised on the host to "
        "complete its batches: one a batch (lanes, cascade counts, "
        "attribution lane and Stage-A counts in one stacked array), "
        "none for a batch the interpreter served",
    "pingoo_sidecar_replica_batches_total":
        "batches the drain loop launched on each chip (device = the "
        "chip's index among the --replicas local devices; 0 alone on "
        "one chip)",
    "pingoo_sidecar_inflight_at_launch_total":
        "batches in flight on all chips, the one launched included, "
        "summed over launches: over pingoo_pipeline_batches_total it is "
        "the mean in flight at a launch (at most --replicas x "
        "PINGOO_PIPELINE_DEPTH)",
    "pingoo_sidecar_replicas":
        "chips the drain loop launches batches on (--replicas), each "
        "holding a whole copy of the plan's device tables",
}

# Continuous-batching scheduler + serving-mesh metrics (ISSUE 6,
# docs/SCHEDULER.md): exported by every plane that runs the batched
# verdict engine (plane="python" listener service, plane="sidecar"
# ring drainer). `pingoo_sched_batch_size` is a histogram over the
# pow2 launch-size ladder (sched/scheduler.BATCH_SIZE_BUCKETS); the
# rest are counters/gauges. The matching `sched` entry in
# VERDICT_STAGES above is the scheduler's hold-time stage histogram.
SCHED_METRICS = {
    "pingoo_sched_queue_depth":
        "requests waiting in the admission queue at the last launch",
    "pingoo_sched_batch_size":
        "per-launch batch occupancy (histogram over the pow2 ladder)",
    "pingoo_sched_deadline_miss_total":
        "requests resolved after their PINGOO_DEADLINE_MS budget",
    "pingoo_sched_failopen_total":
        "requests failed open by the scheduler because their deadline "
        "was unmeetable (PINGOO_SCHED_FAILOPEN policy)",
    "pingoo_mesh_devices":
        "devices in this plane's serving mesh (dp*tp*sp; 1 = "
        "single-device)",
}

# Ring telemetry block metrics (source: the shm header's atomic
# telemetry block, pingoo_ring.h PingooRingTelemetry), exported by BOTH
# the native httpd (it maps the ring) and the sidecar drainer (so the
# Python control-plane scrape carries native-plane queue state).
RING_METRICS = {
    "pingoo_ring_enqueued_total": "request slots enqueued",
    "pingoo_ring_dequeued_total": "request slots dequeued",
    "pingoo_ring_enqueue_full_total":
        "enqueue attempts refused because the request ring was full",
    "pingoo_ring_verdicts_posted_total": "verdict slots posted",
    "pingoo_ring_verdict_post_full_total":
        "verdict posts that hit a full verdict ring (retried)",
    "pingoo_ring_depth": "request slots currently queued",
    "pingoo_ring_depth_hwm": "high-water mark of queued request slots",
}

# The drain loop names its rings (ISSUE 31): one ring a native worker,
# all of them drained by one sidecar in a merged pass. Sidecar-only.
SIDECAR_RING_METRICS = {
    "pingoo_ring_rows_total":
        "sidecar counter {ring=<ring file's name>}: request rows the "
        "drain loop dequeued from each of its rings",
    "pingoo_batch_rings_total":
        "sidecar counter: over the batches launched, how many rings "
        "gave rows to each (over pingoo_pipeline_batches_total: the "
        "mean rings a batch)",
}

# Sidecar supervision + degradation-ladder metrics (ISSUE 10,
# docs/RESILIENCE.md). The liveness trio (sidecar_up / degraded_mode /
# sidecar_epoch) is exported by BOTH planes from the same ring-header
# liveness block (v5): the native httpd reads it to decide the
# degraded fast-path, the sidecar writes it. pingoo_degrade_total is
# the ladder's per-rung demotion counter (engine/ladder.py), exported
# wherever a ladder runs (plane="python" and plane="sidecar");
# reattach/chaos counters are sidecar-plane.
RESILIENCE_METRICS = {
    "pingoo_sidecar_up":
        "1 while a sidecar heartbeat is fresh (0 before any sidecar "
        "ever attached AND while degraded — both alert the same way)",
    "pingoo_degraded_mode":
        "1 while the native plane bypasses the ring (stale heartbeat "
        "past PINGOO_SIDECAR_TIMEOUT_MS): every request fails open",
    "pingoo_sidecar_epoch":
        "monotonic sidecar attach count from the ring header (a bump "
        "= a sidecar restart; reconciliation ran)",
    "pingoo_degraded_entered_total":
        "degraded-mode entries (each one failed every awaiting ticket "
        "open at once)",
    # Release witness (ISSUE 30, native/httpd.cc note_release): what
    # let requests through uninspected; the tickets add up to
    # pingoo_fail_open_total.
    "pingoo_release_events_total":
        "native counter {cause=deadline|degraded|bypass|ring_full}: "
        "release events (a deadline sweep that expired tickets, a "
        "degraded entry, an episode of bypassed or ring-full arrivals)",
    "pingoo_released_total":
        "native counter {cause}: requests proxied uninspected, by what "
        "released them",
    "pingoo_sidecar_heartbeat_age_max_ms":
        "native gauge: the oldest sidecar heartbeat this plane has seen "
        "(degraded mode starts past PINGOO_SIDECAR_TIMEOUT_MS)",
    "pingoo_sidecar_heartbeat_late_total":
        "native counter: times the heartbeat's age crossed HALF the "
        "liveness window (a near miss of degraded mode)",
    "pingoo_native_loop_gap_max_ms":
        "native gauge: the longest gap between two passes of the "
        "httpd event loop (its own stalls)",
    "pingoo_sidecar_sync_overdue_total":
        "sidecar counter {ready=true|false}: device->host syncs of the "
        "drain loop still blocked after 250 ms, by whether the arrays "
        "waited for were ready on the device when probed (each logs "
        "`device sync overdue` with the probe's own round trip)",
    "pingoo_reattach_reconciled_total":
        "tickets a restarting sidecar reconciled from the dead epoch, "
        "by action (reeval = slot bytes intact, re-evaluated; "
        "failopen = bytes recycled, allow posted)",
    "pingoo_degrade_total":
        "degradation-ladder demotions by rung (pipeline|dfa|mesh|"
        "device|body; engine/ladder.py)",
    "pingoo_chaos_injected_total":
        "faults injected by the PINGOO_CHAOS harness, by fault "
        "(obs/chaos.py; absent in production)",
}

# Ruleset hot-swap + differential-fuzzer metrics (ISSUE 11,
# docs/RESILIENCE.md Hot-swap section / docs/FUZZING.md). The epoch
# gauge and swap counter are exported by every plane that runs the
# batched verdict engine (plane="python" listener service,
# plane="sidecar" ring drainer): the epoch is the count of plan swaps
# this plane has applied (0 = the boot plan; every verdict is
# attributable to exactly one epoch), and the swap counter carries
# {tenant, result} labels (result: ok | rejected). The fuzz counter is
# emitted by the differential fuzzer (tools/analyze/fuzz.py) when a
# run's registry is scraped — absent in production serving.
HOTSWAP_METRICS = {
    "pingoo_ruleset_epoch":
        "ruleset plan epoch on this plane (bumps once per applied "
        "hot-swap; in-flight batches always finish on their epoch)",
    "pingoo_ruleset_swap_total":
        "ruleset hot-swap attempts by {tenant, result} (ok = flipped "
        "at a batch boundary, rejected = build/validation failed)",
    "pingoo_fuzz_discrepancy_total":
        "differential-fuzzer parse discrepancies by class (not a "
        "documented known-delta; tools/analyze/fuzz.py)",
}

# Streaming body-inspection metrics (ISSUE 13, docs/BODY_STREAMING.md
# / docs/OBSERVABILITY.md). Exported by BOTH planes when
# PINGOO_BODY_INSPECT=on: the sidecar (plane="sidecar") runs the
# windowed scanner over ring body slots, the Python listener
# (plane="python") over its buffered bodies, and the native httpd
# (plane="native") counts the producer side — windows enqueued, flows
# failed open, h2 streams skipped.
BODY_METRICS = {
    "pingoo_body_windows_total":
        "body windows scanned (sidecar/python) or enqueued (native)",
    "pingoo_body_flows_active":
        "flows with live carry-over state in the scanner table",
    "pingoo_body_carry_depth":
        "windows a finished flow's verdict waited for, i.e. carry-over "
        "chain length (histogram)",
    "pingoo_body_bytes_total": "body payload bytes scanned",
    "pingoo_body_degrade_total":
        "flows degraded to metadata-only verdicts, by reason (evict = "
        "state-table pressure, ttl = stalled flow reaped, gap = window "
        "sequence gap, abort = client reset, ring_full = body ring "
        "back-pressure, ladder = body rung demoted, h2 = native h2 "
        "stream not inspected this PR)",
}

# Compact-staging metrics (ISSUE 15, docs/EXECUTOR.md "Compact
# staging"). Exported by every plane that runs the batched verdict
# engine (plane="python" listener service, plane="sidecar" ring
# drainer). `staged_bytes_total` carries a `mode` label over the
# PINGOO_STAGING arms (full = per-field staging, compact = packed
# one-copy buffer) so the bytes-per-request reduction is one division
# on one scrape; `staging_field_cap` is host-static per adopted plan —
# the plan-derived per-field staging width (equal to the field spec
# under PINGOO_STAGING=full or when the ruleset pins the field).
STAGING_METRICS = {
    "pingoo_staged_bytes_total":
        "request bytes shipped to the device for verdict batches, by "
        "mode (full = per-field arrays, compact = packed buffer)",
    # Upload height (docs/EXECUTOR.md "Compact staging"), sidecar only:
    # per packed batch, the rows shipped and the rows the chip padded
    # them to.
    "pingoo_staged_rows_total":
        "packed batch rows, by kind (uploaded = the rows shipped to the "
        "device, padded = the batch rows the device padded them to)",
    "pingoo_staging_field_cap":
        "per-field staging width in bytes under the adopted plan "
        "(plan-derived cap, quantized to the pow2 rung ladder)",
    # Live-column walk (ISSUE 29, ops/live_columns.py): per batch and
    # field a contains/regex rule scans, {plane, field, kind}.
    "pingoo_scan_columns_total":
        "byte columns per batch and scanned field, by kind (staged = "
        "the field's staged width, walked = the columns the dfa/pf "
        "byte loops walk: the batch's longest row, rounded up to 8)",
    # Live-row walk (ISSUE 32): the same loops' row axis.
    "pingoo_scan_rows_total":
        "batch rows per batch and scanned field, by kind (staged = the "
        "padded batch's rows, walked = the rows the dfa/pf byte loops "
        "walk: the row tiles up to the last row that has a byte; every "
        "row on a mesh that shards batches)",
}

# Perf ledger + cross-plane timeline + durable cost ledger (ISSUE 17,
# docs/OBSERVABILITY.md "Compile ledger"/"Timeline"/"Cost ledger").
# Exported by every plane that runs the batched verdict engine
# (plane="python" listener service, plane="sidecar" ring drainer).
# `pingoo_compile_total` carries {plane, fn, kind} — fn over
# obs/perf.COMPILE_FN_KINDS (verdict|lanes|prefilter|pad|score;
# the packed-staging twins report under the same fn label), kind
# cold|warm (warm = a retrace under live traffic, the recompile-storm
# alert series); `pingoo_compile_ms` is a {plane, fn} histogram over
# obs/perf.COMPILE_BUCKETS_MS. `pingoo_timeline_spans_total{plane}`
# counts spans the sampler actually recorded (plane also takes the
# value "native" for ring-wait spans stamped from native enqueue
# clocks). `pingoo_costmodel_reload_total{plane, result}` counts boot
# reload attempts of the durable cost ledger (result: ok | stale |
# missing | error).
PERF_METRICS = {
    "pingoo_compile_total":
        "XLA trace/compile events observed by the compile ledger, by "
        "{fn, kind} (cold = a wrapper's first compile, warm = a later "
        "retrace — the recompile-storm signal)",
    "pingoo_compile_ms":
        "wall time of observed XLA trace/compile events (histogram "
        "per {plane, fn})",
    "pingoo_timeline_spans_total":
        "spans recorded by the cross-plane timeline sampler "
        "(PINGOO_TIMELINE_SAMPLE-gated; bounded in-memory ring)",
    "pingoo_costmodel_reload_total":
        "durable cost-ledger reload attempts at boot, by result (ok = "
        "EWMAs restored, stale = fingerprint/version mismatch "
        "discarded, missing = no snapshot for this backend+plane, "
        "error = unreadable file)",
    "pingoo_compile_unexpected_total":
        "compile events OUTSIDE the statically-proved admissible "
        "surface (COMPILE_SURFACE.json via PINGOO_COMPILE_SURFACE), by "
        "{plane, fn} — any nonzero value means an unquantized shape "
        "axis reached a jitted dispatch; fails make timeline-smoke",
}

# Native-plane-only counters (httpd.cc Stats), exported with
# plane="native" under these names.
NATIVE_METRICS = {
    "pingoo_ua_rejected_total": "empty/oversized UA pre-ring 403s",
    "pingoo_no_service_total": "route bits said no service (404)",
    "pingoo_upstream_fail_total": "upstream connect/response failures (502)",
    "pingoo_upstream_tls_fail_total":
        "upstream TLS handshake/verify failures",
    "pingoo_verdicts_total": "verdict bytes applied",
    # The accept and block-and-reconnect path (ISSUE 35).
    "pingoo_accepted_total": "connections accepted on the listener",
    "pingoo_closed_after_block_total":
        "HTTP/1 connections closed because httpd answered 403",
    "pingoo_connections": "open client connections",
    "pingoo_pooled_upstreams": "idle pooled upstream connections",
    # One counter surface for N workers (ISSUE 31): every native series
    # above is the LISTENER's (the sum, or the maximum for a high-water
    # mark, over its --native-workers processes, whichever of them
    # answers the scrape); these carry each worker's own share.
    "pingoo_native_workers": "httpd worker processes on this listener",
    "pingoo_native_answered_by": "index of the worker that answered",
    "pingoo_worker_requests_total": "{worker}: requests it parsed",
    "pingoo_worker_verdicts_total": "{worker}: verdict bytes it applied",
    "pingoo_worker_fail_open_total":
        "{worker}: requests it proxied uninspected",
    "pingoo_worker_accepted_total": "{worker}: connections it accepted",
    "pingoo_worker_ring_depth": "{worker}: its ring's queued slots",
    "pingoo_worker_ring_depth_hwm":
        "{worker}: its ring's high-water mark",
    "pingoo_worker_loop_gap_max_ms":
        "{worker}: its event loop's longest pass-to-pass gap",
}

# JSON back-compat keys (the pre-registry schemas, still served under
# Accept: application/json). Maps JSON key -> metric name, per plane.
PYTHON_JSON_KEYS = {
    "requests": "pingoo_requests_total",
    "blocked": "pingoo_blocked_total",
    "captcha_served": "pingoo_captcha_total",
}
NATIVE_JSON_KEYS = {
    "workers": "pingoo_native_workers",
    "answered_by": "pingoo_native_answered_by",
    "per_worker": "pingoo_worker_requests_total",
    "requests": "pingoo_requests_total",
    "blocked": "pingoo_blocked_total",
    "captcha": "pingoo_captcha_total",
    "fail_open": "pingoo_fail_open_total",
    "accepted": "pingoo_accepted_total",
    "closed_after_block": "pingoo_closed_after_block_total",
    "verdict_wait_ms_hist": "pingoo_verdict_wait_ms",
}


def all_metric_names() -> set[str]:
    return (set(SHARED_METRICS) | set(RING_METRICS) | set(NATIVE_METRICS)
            | set(SIDECAR_RING_METRICS)
            | set(PREFILTER_METRICS) | set(CASCADE_METRICS)
            | set(DFA_METRICS)
            | set(PROVENANCE_METRICS)
            | set(PARITY_METRICS) | set(SCHED_METRICS)
            | set(PIPELINE_METRICS) | set(RESILIENCE_METRICS)
            | set(HOTSWAP_METRICS) | set(BODY_METRICS)
            | set(STAGING_METRICS) | set(PERF_METRICS)
            | {SHARED_WAIT_HISTOGRAM, "pingoo_verdict_stage_ms"})
