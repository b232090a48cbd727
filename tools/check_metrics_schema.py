#!/usr/bin/env python
"""Metrics-schema parity audit (make audit; ISSUE 2 satellite).

Fast, no-accelerator checks that the three telemetry surfaces agree on
the documented inventory (pingoo_tpu/obs/schema.py):

  1. The native plane's C++ exposition (native/httpd.cc) emits every
     shared/native/ring metric name and keeps the legacy JSON keys —
     checked against the SOURCE (the exposition is string literals, so
     a renamed or dropped metric is visible without booting the plane).
  2. The Python listener (host/httpd.py) and sidecar (native_ring.py)
     reference the same names through obs/schema.py.
  3. A synthetic registry populated with the full inventory passes the
     Prometheus exposition lint (obs/registry.lint_prometheus_text).
  4. docs/OBSERVABILITY.md documents every inventory name.

Exit 0 clean, 1 with a problem list on stderr. The live-boot version of
this check is `make metrics-smoke` (tools/metrics_smoke.py).
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from pingoo_tpu.obs import schema  # noqa: E402
from pingoo_tpu.obs.registry import (  # noqa: E402
    MetricRegistry,
    WAIT_BUCKETS_MS,
    lint_prometheus_text,
)


def _read(rel):
    with open(os.path.join(REPO, rel)) as f:
        return f.read()


def main() -> int:
    problems = []

    native_src = _read("pingoo_tpu/native/httpd.cc")
    native_names = (set(schema.SHARED_METRICS) | set(schema.RING_METRICS)
                    | set(schema.NATIVE_METRICS)
                    | {schema.SHARED_WAIT_HISTOGRAM})
    for name in sorted(native_names):
        if f'"{name}' not in native_src and name not in native_src:
            problems.append(f"native/httpd.cc: missing metric {name}")
    for key in schema.NATIVE_JSON_KEYS:
        if f'"{key}"' not in native_src:
            problems.append(
                f"native/httpd.cc: missing legacy JSON key {key!r}")

    py_listener = _read("pingoo_tpu/host/httpd.py")
    for name in schema.SHARED_METRICS:
        if name not in py_listener:
            problems.append(f"host/httpd.py: missing metric {name}")
    for key in schema.PYTHON_JSON_KEYS:
        if f'"{key}"' not in py_listener:
            problems.append(
                f"host/httpd.py: missing legacy JSON key {key!r}")

    sidecar_src = _read("pingoo_tpu/native_ring.py")
    for name in list(schema.RING_METRICS) + list(
            schema.SIDECAR_RING_METRICS):   # the second: the drain loop's
        if name not in sidecar_src:         # rings by name (ISSUE 31)
            problems.append(f"native_ring.py: missing metric {name}")

    service_src = _read("pingoo_tpu/engine/service.py")
    if schema.SHARED_WAIT_HISTOGRAM not in service_src:
        problems.append("engine/service.py: missing shared wait histogram")
    for stage in schema.VERDICT_STAGES:
        if f'"{stage}"' not in service_src:
            problems.append(
                f"engine/service.py: stage {stage!r} not instrumented")

    # Prefilter cascade metrics: both engine planes (the Python listener
    # service and the ring sidecar backing the native plane) must export
    # the documented names.
    for name in schema.PREFILTER_METRICS:
        if name not in service_src:
            problems.append(f"engine/service.py: missing metric {name}")
        if name not in sidecar_src:
            problems.append(f"native_ring.py: missing metric {name}")

    # The cascade's row counters (ISSUE 35): the name literals live with
    # their fold, obs/pipeline.CascadeCounters; the sidecar wires it.
    pipeline_src = _read("pingoo_tpu/obs/pipeline.py")
    for name in schema.CASCADE_METRICS:
        if name not in pipeline_src:
            problems.append(f"obs/pipeline.py: missing metric {name}")
    if "CascadeCounters" not in sidecar_src:
        problems.append("native_ring.py: cascade wiring missing "
                        "CascadeCounters")

    # Bitsplit-DFA dispatch metrics (ISSUE 8): like the prefilter
    # family, both engine planes must export the documented names (the
    # counts themselves are host-static, engine/verdict
    # dfa_dispatch_counts).
    for name in schema.DFA_METRICS:
        if name not in service_src:
            problems.append(f"engine/service.py: missing metric {name}")
        if name not in sidecar_src:
            problems.append(f"native_ring.py: missing metric {name}")

    # Streaming body inspection (ISSUE 13, docs/BODY_STREAMING.md): the
    # scanner-side metric-name literals live in engine/bodyscan.py
    # (attach_metrics, shared by both scanning planes); the native
    # plane exports the producer-side subset as C++ string literals
    # (the carry-depth histogram is scanner-only); both consuming
    # planes must wire a BodyScanner — the sidecar drains ring body
    # slots, the Python listener scans its buffered bodies through
    # scan_buffered.
    body_src = _read("pingoo_tpu/engine/bodyscan.py")
    for name in schema.BODY_METRICS:
        if name not in body_src:
            problems.append(f"engine/bodyscan.py: missing metric {name}")
    for name in ("pingoo_body_windows_total", "pingoo_body_bytes_total",
                 "pingoo_body_flows_active", "pingoo_body_degrade_total"):
        if name not in native_src:
            problems.append(f"native/httpd.cc: missing metric {name}")
    for plane_src, label in ((py_listener, "host/httpd.py"),
                             (sidecar_src, "native_ring.py")):
        if "BodyScanner" not in plane_src:
            problems.append(f"{label}: body wiring missing BodyScanner")
    if "scan_buffered" not in py_listener:
        problems.append("host/httpd.py: body wiring missing scan_buffered")
    if "PINGOO_BODY_INSPECT" not in native_src:
        problems.append("native/httpd.cc: missing PINGOO_BODY_INSPECT gate")

    # Verdict provenance (ISSUE 5): the metric-name literals live in
    # obs/provenance.py + obs/flightrecorder.py (shared by both engine
    # planes), so check those sources for the names and both plane
    # sources for the wiring symbols.
    prov_src = (_read("pingoo_tpu/obs/provenance.py")
                + _read("pingoo_tpu/obs/flightrecorder.py"))
    for name in {**schema.PROVENANCE_METRICS, **schema.PARITY_METRICS}:
        if name not in prov_src:
            problems.append(f"obs provenance layer: missing metric {name}")
    for symbol in ("RuleAttribution", "ParityAuditor", "FlightRecorder"):
        if symbol not in service_src:
            problems.append(f"engine/service.py: provenance wiring "
                            f"missing {symbol}")
        if symbol not in sidecar_src:
            problems.append(f"native_ring.py: provenance wiring "
                            f"missing {symbol}")

    # Continuous-batching scheduler + serving mesh (ISSUE 6): the
    # metric-name literals live in sched/scheduler.py (shared by both
    # engine planes; the mesh gauge is set through the same
    # SchedMetrics bundle), and both planes must wire the Scheduler —
    # the Python listener service and the ring sidecar each construct
    # one, which is what makes the pingoo_sched_* series exist under
    # both plane labels.
    sched_src = _read("pingoo_tpu/sched/scheduler.py")
    for name in schema.SCHED_METRICS:
        if name not in sched_src:
            problems.append(f"sched/scheduler.py: missing metric {name}")
    for plane_src, label in ((service_src, "engine/service.py"),
                             (sidecar_src, "native_ring.py")):
        for symbol in ("Scheduler", "SchedulerConfig", "MeshExecutor"):
            if symbol not in plane_src:
                problems.append(
                    f"{label}: scheduler wiring missing {symbol}")

    # Compact staging (ISSUE 15): both engine planes must export the
    # staged-bytes counter and the per-field cap gauge — the counter is
    # what makes the full-vs-compact byte savings visible per plane,
    # and the gauge publishes the adopted plan's staging widths.
    # The scan-columns and scan-rows counters' literals live in
    # engine/batch.py (ScanColumnCounters, shared); both planes must
    # construct one.
    scan_extents = ("pingoo_scan_columns_total", "pingoo_scan_rows_total")
    batch_src = _read("pingoo_tpu/engine/batch.py")
    for name in scan_extents:
        if name not in batch_src:
            problems.append(f"engine/batch.py: missing metric {name}")
    for name in schema.STAGING_METRICS:
        if name in scan_extents:
            name = "ScanColumnCounters"
        # The upload height is the sidecar's alone: the Python plane
        # ships every packed row and counts none.
        if name not in service_src and name != "pingoo_staged_rows_total":
            problems.append(f"engine/service.py: missing metric {name}")
        if name not in sidecar_src:
            problems.append(f"native_ring.py: missing metric {name}")

    # Pipelined-executor telemetry (ISSUE 9): the metric-name literals
    # live in obs/pipeline.py (shared by both engine planes), and both
    # planes must construct a PipelineStats — that is what makes the
    # pingoo_pipeline_* series exist under both plane labels.
    pipe_src = _read("pingoo_tpu/obs/pipeline.py")
    for name in schema.PIPELINE_METRICS:
        if name not in pipe_src:
            problems.append(f"obs/pipeline.py: missing metric {name}")
    for plane_src, label in ((service_src, "engine/service.py"),
                             (sidecar_src, "native_ring.py")):
        if "PipelineStats" not in plane_src:
            problems.append(
                f"{label}: pipeline wiring missing PipelineStats")

    # Sidecar supervision (ISSUE 10, docs/RESILIENCE.md): the liveness
    # gauges/counter are C++ string literals in the native exposition;
    # the reattach/epoch names live in the sidecar, the ladder counter
    # in engine/ladder.py, the chaos counter in obs/chaos.py. Both
    # engine planes must wire a DegradationLadder — that is what makes
    # the pingoo_degrade_total series exist under both plane labels —
    # and the native plane must carry the liveness detector itself.
    for name in ("pingoo_sidecar_up", "pingoo_degraded_mode",
                 "pingoo_sidecar_epoch", "pingoo_degraded_entered_total",
                 # the release witness (ISSUE 30)
                 "pingoo_release_events_total", "pingoo_released_total",
                 "pingoo_sidecar_heartbeat_age_max_ms",
                 "pingoo_sidecar_heartbeat_late_total",
                 "pingoo_native_loop_gap_max_ms"):
        if name not in native_src:
            problems.append(f"native/httpd.cc: missing metric {name}")
    if "check_sidecar_liveness" not in native_src:
        problems.append(
            "native/httpd.cc: liveness detector check_sidecar_liveness "
            "missing")
    for name in ("pingoo_reattach_reconciled_total",
                 "pingoo_sidecar_epoch",
                 "pingoo_sidecar_sync_overdue_total"):
        if name not in sidecar_src:
            problems.append(f"native_ring.py: missing metric {name}")
    ladder_src = _read("pingoo_tpu/engine/ladder.py")
    if "pingoo_degrade_total" not in ladder_src:
        problems.append(
            "engine/ladder.py: missing metric pingoo_degrade_total")
    chaos_src = _read("pingoo_tpu/obs/chaos.py")
    if "pingoo_chaos_injected_total" not in chaos_src:
        problems.append(
            "obs/chaos.py: missing metric pingoo_chaos_injected_total")
    for plane_src, label in ((service_src, "engine/service.py"),
                             (sidecar_src, "native_ring.py")):
        if "DegradationLadder" not in plane_src:
            problems.append(
                f"{label}: ladder wiring missing DegradationLadder")
    if "ChaosInjector" not in sidecar_src:
        problems.append(
            "native_ring.py: chaos wiring missing ChaosInjector")

    # Perf ledger + timeline (ISSUE 17): the compile/timeline metric
    # literals live in obs/perf.py + obs/timeline.py, the cost-ledger
    # reload counter in sched/scheduler.py; both engine planes must
    # wire the instrumentation (instrument_jit for compile tracking,
    # get_timeline for span emission, load_cost_ledger for the durable
    # cost reload) — that is what makes the series exist under both
    # plane labels.
    perf_src = (_read("pingoo_tpu/obs/perf.py")
                + _read("pingoo_tpu/obs/timeline.py"))
    for name in ("pingoo_compile_total", "pingoo_compile_ms",
                 "pingoo_timeline_spans_total"):
        if name not in perf_src:
            problems.append(f"obs perf layer: missing metric {name}")
    if "pingoo_costmodel_reload_total" not in sched_src:
        problems.append("sched/scheduler.py: missing metric "
                        "pingoo_costmodel_reload_total")
    for plane_src, label in ((service_src, "engine/service.py"),
                             (sidecar_src, "native_ring.py")):
        for symbol in ("instrument_jit", "get_timeline",
                       "load_cost_ledger", "save_cost_ledger"):
            if symbol not in plane_src:
                problems.append(
                    f"{label}: perf wiring missing {symbol}")

    # Flight-recorder + explain endpoints: the Python listener serves
    # both; the native plane serves its own flightrecorder dump (the
    # C++ exposition is string literals, so the source is the schema).
    for endpoint in ("/__pingoo/flightrecorder", "/__pingoo/explain",
                     "/__pingoo/compileledger", "/__pingoo/timeline"):
        if endpoint not in py_listener:
            problems.append(f"host/httpd.py: missing endpoint {endpoint}")
    for endpoint in ("/__pingoo/flightrecorder", "/__pingoo/timeline"):
        if endpoint not in native_src:
            problems.append(
                f"native/httpd.cc: missing endpoint {endpoint}")

    docs = _read("docs/OBSERVABILITY.md") if os.path.exists(
        os.path.join(REPO, "docs/OBSERVABILITY.md")) else ""
    if not docs:
        problems.append("docs/OBSERVABILITY.md missing")
    else:
        for name in sorted(schema.all_metric_names()):
            if name not in docs:
                problems.append(f"docs/OBSERVABILITY.md: undocumented {name}")

    # Synthetic full-inventory registry must pass the exposition lint.
    reg = MetricRegistry()
    for name, help_text in {**schema.SHARED_METRICS,
                            **schema.RING_METRICS,
                            **schema.PREFILTER_METRICS,
                            **schema.DFA_METRICS,
                            **schema.PROVENANCE_METRICS,
                            **schema.PARITY_METRICS,
                            **schema.SCHED_METRICS,
                            **schema.PIPELINE_METRICS,
                            **schema.RESILIENCE_METRICS,
                            **schema.BODY_METRICS,
                            **schema.STAGING_METRICS,
                            **schema.PERF_METRICS}.items():
        if name == "pingoo_compile_ms":
            from pingoo_tpu.obs.perf import COMPILE_BUCKETS_MS

            hb = reg.histogram(name, help_text,
                               buckets=COMPILE_BUCKETS_MS,
                               labels={"plane": "audit", "fn": "verdict"})
            for v in (0.5, 120, 9500):
                hb.observe(v)
        elif name == "pingoo_body_carry_depth":
            hb = reg.histogram(name, help_text,
                               buckets=(1, 2, 4, 8, 16, 64, 256),
                               labels={"plane": "audit"})
            for v in (1, 3, 500):
                hb.observe(v)
        elif name == "pingoo_sched_batch_size":
            # The one histogram in the sched family: lint it with its
            # real pow2 bucket ladder.
            from pingoo_tpu.sched import BATCH_SIZE_BUCKETS

            hb = reg.histogram(name, help_text,
                               buckets=BATCH_SIZE_BUCKETS,
                               labels={"plane": "audit"})
            for v in (1, 64, 2048, 100000):
                hb.observe(v)
        elif name.endswith("_total"):
            reg.counter(name, help_text, labels={"plane": "audit"}).inc()
        else:
            reg.gauge(name, help_text, labels={"plane": "audit"}).set(1)
    # The rule/bank-labelled provenance families must lint with their
    # real label shapes too (a rule name can carry exposition-hostile
    # characters; the formatter escapes them).
    reg.counter("pingoo_rule_hits_total", "", labels={
        "plane": "audit", "rule": 'r"quoted\\rule'}).inc()
    reg.gauge("pingoo_prefilter_bank_candidate_rate", "", labels={
        "plane": "audit", "bank": "nfa_url@short"}).set(0.5)
    reg.counter("pingoo_dfa_banks_total", "", labels={
        "plane": "audit", "mode": "auto"}).inc()
    reg.gauge("pingoo_pipeline_stage_occupancy", "", labels={
        "plane": "audit", "stage": "encode"}).set(0.5)
    reg.counter("pingoo_pipeline_batches_total", "", labels={
        "plane": "audit", "mode": "on"}).inc()
    reg.counter("pingoo_reattach_reconciled_total", "", labels={
        "plane": "audit", "action": "reeval"}).inc()
    reg.counter("pingoo_degrade_total", "", labels={
        "plane": "audit", "rung": "device"}).inc()
    reg.counter("pingoo_released_total", "", labels={
        "plane": "audit", "cause": "deadline"}).inc()
    reg.counter("pingoo_chaos_injected_total", "", labels={
        "plane": "audit", "fault": "verdict_full"}).inc()
    reg.counter("pingoo_body_degrade_total", "", labels={
        "plane": "audit", "reason": "ring_full"}).inc()
    reg.counter("pingoo_staged_bytes_total", "", labels={
        "plane": "audit", "mode": "compact"}).inc()
    reg.gauge("pingoo_staging_field_cap", "", labels={
        "field": "url"}).set(256)
    reg.counter("pingoo_scan_columns_total", "", labels={
        "plane": "audit", "field": "url", "kind": "walked"}).inc()
    reg.counter("pingoo_scan_rows_total", "", labels={
        "plane": "audit", "field": "url", "kind": "walked"}).inc()
    reg.counter("pingoo_compile_total", "", labels={
        "plane": "audit", "fn": "verdict", "kind": "cold"}).inc()
    reg.counter("pingoo_timeline_spans_total", "", labels={
        "plane": "audit"}).inc()
    reg.counter("pingoo_costmodel_reload_total", "", labels={
        "plane": "audit", "result": "stale"}).inc()
    h = reg.histogram(schema.SHARED_WAIT_HISTOGRAM, "wait",
                      buckets=WAIT_BUCKETS_MS, labels={"plane": "audit"})
    for v in (0.5, 3, 70, 2000):
        h.observe(v)
    problems += [f"lint: {p}" for p in
                 lint_prometheus_text(reg.prometheus_text())]

    if problems:
        print("metrics schema audit FAILED:", file=sys.stderr)
        for p in problems:
            print(f"  - {p}", file=sys.stderr)
        return 1
    print(f"metrics schema audit OK "
          f"({len(schema.all_metric_names())} inventory names)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
