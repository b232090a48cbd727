#!/usr/bin/env python
"""Perf-ledger + timeline smoke (make timeline-smoke; ISSUE 17).

Proves, offline and in ~a minute, that the observability tentpole
actually observes:

  * python plane: VerdictService under PINGOO_TIMELINE_SAMPLE=1 emits
    batch spans whose stage children NEST inside the batch span, the
    Chrome-trace export parses and carries the clock-pin block, and the
    compile ledger recorded the warm-up compiles with the JSONL file
    agreeing line-for-line with the in-memory totals;
  * sidecar plane: RingSidecar over a real shm ring emits sidecar spans
    plus the cross-plane ring-wait join rows under pid "native" (this
    half skips with a warning when the native toolchain is missing);
  * durable cost ledger: persist -> fresh CostModel reload round-trips
    the measured EWMAs bit-for-bit (result "ok"), and a fingerprint
    mismatch is discarded as "stale";
  * hot-path overhead: the measured cost of recording one sampled
    batch's spans is <2% of the mean live batch wall, and the
    unsampled-path cost (one sample() call) is nanoseconds.

Offline-safe like staging-smoke: when jax is unavailable the smoke
SKIPS WITH A WARNING (exit 0). The work happens in a re-exec'd child
under a controlled environment so a parent shell's perf/timeline knobs
cannot skew the run.
"""

import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

FAILURES: list = []

N_PY = 64       # python-plane requests
N_RING = 64     # sidecar-plane requests
MAX_BATCH = 16
OVERHEAD_ITERS = 400


def check(ok, what):
    print(("  ok  " if ok else "  FAIL") + f" {what}")
    if not ok:
        FAILURES.append(what)


def parent() -> int:
    try:
        import jax  # noqa: F401
    except Exception as exc:
        print(f"timeline smoke SKIPPED: jax unavailable ({exc!r})")
        return 0
    tmp = tempfile.mkdtemp(prefix="pingoo-timeline-smoke-")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PINGOO_TIMELINE_SAMPLE"] = "1"
    env["PINGOO_PERF_LEDGER"] = os.path.join(tmp, "COMPILE_LEDGER.jsonl")
    env["PINGOO_COST_LEDGER"] = os.path.join(tmp, "COST_LEDGER.json")
    env["PINGOO_COMPILE_SURFACE"] = os.path.join(
        tmp, "COMPILE_SURFACE.json")
    for k in ("PINGOO_TIMELINE_N", "PINGOO_TIMELINE_ROWS",
              "PINGOO_PERF_LEDGER_N", "PINGOO_STAGING", "PINGOO_PIPELINE",
              "PINGOO_MESH", "PINGOO_CHAOS", "PINGOO_PARITY_SAMPLE",
              "PINGOO_PROFILE_DIR"):
        env.pop(k, None)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child"],
        env=env, cwd=REPO, timeout=900)
    return proc.returncode


def _nesting_holds(spans, batch_tid) -> tuple:
    """Every stage child on the batch lane must lie inside one of that
    lane's batch spans (1 us slack for float rounding)."""
    batches = [(t0, t0 + dur) for plane, tid, name, t0, dur, *_ in spans
               if tid == batch_tid and name == "batch"]
    children = [(name, t0, t0 + dur)
                for plane, tid, name, t0, dur, *_ in spans
                if tid == batch_tid and name != "batch"]
    orphans = [name for name, a, b in children
               if not any(a >= b0 - 1.0 and b <= b1 + 1.0
                          for b0, b1 in batches)]
    return len(batches), len(children), orphans


def _python_plane() -> dict:
    import asyncio
    import random

    sys.path.insert(0, os.path.join(REPO, "tests"))
    from pingoo_tpu.compiler import compile_ruleset
    from pingoo_tpu.engine.service import VerdictService
    from pingoo_tpu.obs.perf import get_compile_ledger
    from pingoo_tpu.obs.timeline import Timeline, get_timeline
    from pingoo_tpu.sched.scheduler import CostModel, load_cost_ledger
    from test_parity import LISTS, RULE_SOURCES, make_rules, \
        random_requests

    reqs = random_requests(random.Random(1701), N_PY)
    plan = compile_ruleset(make_rules(RULE_SOURCES), LISTS)
    svc = VerdictService(plan, LISTS, use_device=True, max_batch=32)
    check(svc.cost_ledger_result == "missing",
          f"cost ledger: first boot reload is 'missing' "
          f"(got {svc.cost_ledger_result!r})")

    async def flow():
        await svc.start()
        t0 = time.monotonic()
        try:
            await asyncio.gather(*[svc.evaluate(r) for r in reqs])
        finally:
            elapsed = time.monotonic() - t0
            await svc.stop()
        return elapsed

    serve_wall_s = asyncio.run(flow())

    # -- timeline: export parses, spans nest ---------------------------
    tl = get_timeline()
    check(tl.enabled and tl.rate == 1.0, "timeline sampling enabled")
    trace = json.loads(tl.chrome_trace_json())
    xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    check(bool(xs), f"chrome trace parses with spans ({len(xs)})")
    check("clock" in trace and trace["clock"]["unit"] == "monotonic_us",
          "chrome trace carries the monotonic clock-pin block")
    with tl._lock:
        spans = list(tl.spans)
    n_b, n_c, orphans = _nesting_holds(spans, "python/batch")
    check(n_b > 0 and n_c > 0 and not orphans,
          f"python batch spans nest ({n_c} children in {n_b} batches, "
          f"orphans={orphans[:3]})")
    check(any(tid.startswith("python/req:")
              for _, tid, *_ in spans),
          "per-request lanes emitted on the python plane")

    # -- compile ledger: warm-up compiles + JSONL cross-check ----------
    ledger = get_compile_ledger()
    snap = ledger.snapshot()
    check(snap["enabled"], "compile ledger enabled")
    check(snap["totals"].get("python/verdict/cold", 0) >= 1,
          f"verdict warm-up compile on the ledger "
          f"(totals={snap['totals']})")
    with open(ledger.path) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    check(len(lines) == snap["compiles_total"] and not snap["io_errors"],
          f"COMPILE_LEDGER.jsonl agrees with in-memory totals "
          f"({len(lines)} == {snap['compiles_total']})")
    check(all(ln.get("fingerprint") == svc._plan_fp for ln in lines
              if ln.get("plane") == "python"),
          "ledger events stamped with the plan fingerprint")

    # -- durable cost ledger: persist -> reload round trip -------------
    check(svc.persist_cost_ledger(), "cost ledger persisted on stop")
    fresh = CostModel()
    result = load_cost_ledger(
        fresh, backend=svc._backend_label, fingerprint=svc._plan_fp,
        plane="python")
    check(result == "ok", f"cost ledger reload result 'ok' "
                          f"(got {result!r})")
    check(fresh.snapshot() == svc.sched.cost.snapshot(),
          "reloaded CostModel EWMAs bit-identical to the live model")
    stale = CostModel()
    result = load_cost_ledger(
        stale, backend=svc._backend_label, fingerprint="deadbeef0000",
        plane="python")
    check(result == "stale" and stale.snapshot() == CostModel().snapshot(),
          f"fingerprint mismatch discarded as 'stale' (got {result!r})")

    # -- hot-path overhead ---------------------------------------------
    launches = max(1, svc.sched.launches)
    mean_batch_ms = serve_wall_s * 1e3 / launches
    probe = Timeline(rate=1.0)
    stages = {"encode_ms": 0.2, "prefilter_ms": 0.1,
              "device_dispatch_ms": 0.1, "device_compute_ms": 1.0}
    rows = [(f"trace{i}", 1.0, 1.5) for i in range(probe.rows_per_batch)]
    t0 = time.perf_counter()
    for i in range(OVERHEAD_ITERS):
        probe.batch_python(stages_ms=stages, t_launch=2.0, t_resolve=3.0,
                           t_end=3.5, rows=rows)
    record_ms = (time.perf_counter() - t0) * 1e3 / OVERHEAD_ITERS
    off = Timeline(rate=0.0)
    t0 = time.perf_counter()
    for i in range(OVERHEAD_ITERS * 100):
        off.sample()
    off_us = (time.perf_counter() - t0) * 1e6 / (OVERHEAD_ITERS * 100)
    check(record_ms < 0.02 * mean_batch_ms,
          f"sampled record path <2% of mean batch wall "
          f"({record_ms:.4f} ms vs batch {mean_batch_ms:.2f} ms)")
    check(off_us < 5.0,
          f"sampling-off path is one add+compare ({off_us:.3f} us/call)")
    return {"mean_batch_ms": round(mean_batch_ms, 3),
            "record_ms_per_batch": round(record_ms, 4),
            "compiles_total": snap["compiles_total"]}


def _sidecar_plane() -> dict:
    import threading

    from pingoo_tpu.compiler import compile_ruleset
    from pingoo_tpu.config.schema import Action, RuleConfig
    from pingoo_tpu.expr import compile_expression
    from pingoo_tpu.native_ring import Ring, RingSidecar
    from pingoo_tpu.obs.perf import get_compile_ledger
    from pingoo_tpu.obs.timeline import get_timeline
    from pingoo_tpu.sched.scheduler import CostModel, load_cost_ledger

    rules = [RuleConfig(name="blk", actions=(Action.BLOCK,),
                        expression=compile_expression(
                            'http_request.path.starts_with("/evil")'))]
    plan = compile_ruleset(rules, {})

    with tempfile.TemporaryDirectory() as tmp:
        ring = Ring(os.path.join(tmp, "ring"), capacity=256, create=True)
        sidecar = RingSidecar(ring, plan, {}, max_batch=MAX_BATCH)
        for i in range(N_RING):
            path = (f"/evil/{i}" if i % 3 == 0 else f"/fine/{i}").encode()
            ring.enqueue(method=b"GET", host=b"tl.test", path=path,
                         url=path, user_agent=b"ua",
                         ip=b"\x00" * 15 + bytes([i % 251 + 1]))
        worker = threading.Thread(
            target=sidecar.run, kwargs={"max_requests": N_RING},
            daemon=True)
        worker.start()
        got = 0
        deadline = time.time() + 240
        while time.time() < deadline and got < N_RING:
            if ring.poll_verdict() is None:
                time.sleep(0.001)
                continue
            got += 1
        sidecar.stop()
        worker.join(timeout=30)
        ring.close()
    check(got == N_RING, f"sidecar served all verdicts ({got}/{N_RING})")

    tl = get_timeline()
    with tl._lock:
        spans = list(tl.spans)
    n_b, n_c, orphans = _nesting_holds(spans, "sidecar/batch")
    check(n_b > 0 and n_c > 0 and not orphans,
          f"sidecar batch spans nest ({n_c} children in {n_b} batches, "
          f"orphans={orphans[:3]})")
    joins = [s for s in spans
             if s[0] == "native" and s[2] == "ring_wait"]
    check(bool(joins),
          f"cross-plane ring-wait join rows under pid native "
          f"({len(joins)})")
    check(all(dur >= 0.0 for _, _, _, _, dur, *_ in joins),
          "ring-wait durations non-negative (shared monotonic clock)")

    snap = get_compile_ledger().snapshot()
    check(snap["totals"].get("sidecar/lanes/cold", 0) >= 1,
          f"sidecar lane warm-up compile on the ledger "
          f"(totals={snap['totals']})")

    # Sidecar cost ledger rode the same file under its own plane key.
    fresh = CostModel()
    result = load_cost_ledger(
        fresh, backend=sidecar._backend_label,
        fingerprint=sidecar._plan_fp, plane="sidecar")
    check(result == "ok",
          f"sidecar cost-ledger entry reloads 'ok' (got {result!r})")
    return {"ring_join_spans": len(joins)}


def _surface_checks(summary: dict) -> None:
    """ISSUE 18: every ledger compile event must lie inside the
    statically-proved admissible surface, and an injected out-of-
    surface compile must be detected."""
    from pingoo_tpu.obs import REGISTRY
    from pingoo_tpu.obs.perf import event_in_surface, \
        get_compile_ledger, load_compile_surface

    ledger = get_compile_ledger()
    surface = load_compile_surface(os.environ["PINGOO_COMPILE_SURFACE"])
    snap = ledger.snapshot()
    check(surface is not None and snap["surface_loaded"],
          "compile surface loaded by the ledger")
    escapes = [(e["plane"], e["fn"], event_in_surface(e, surface))
               for e in snap["events"]
               if event_in_surface(e, surface)]
    check(snap["compiles_total"] > 0 and not escapes
          and snap["unexpected_total"] == 0,
          f"all {snap['compiles_total']} compile events inside "
          f"COMPILE_SURFACE.json (escapes={escapes[:3]})")
    # Inject an out-of-surface compile: the detector must bite.
    ledger.note(plane="python", fn="verdict", kind="cold", wall_ms=0.1,
                shapes=[(65, 128)])  # 65 is on no pow2 rung
    snap2 = ledger.snapshot()
    check(snap2["unexpected_total"] == 1,
          f"injected out-of-surface compile detected "
          f"(unexpected_total={snap2['unexpected_total']})")
    check("pingoo_compile_unexpected_total"
          in REGISTRY.prometheus_text(),
          "scrape exposes pingoo_compile_unexpected_total")
    summary["surface_events_checked"] = snap["compiles_total"]


def child() -> int:
    from pingoo_tpu import native_ring
    from pingoo_tpu.obs import REGISTRY
    from pingoo_tpu.obs.registry import lint_prometheus_text

    # The admissible compile surface must exist BEFORE the first
    # compile event — the ledger resolves PINGOO_COMPILE_SURFACE once.
    from tools.analyze import surface as surface_mod
    surface_mod.write_surface(surface_mod.build_surface(),
                              os.environ["PINGOO_COMPILE_SURFACE"])

    summary = _python_plane()
    if native_ring.ensure_built():
        summary.update(_sidecar_plane())
    else:
        print("  note sidecar plane skipped: native toolchain "
              "unavailable")
    _surface_checks(summary)

    text = REGISTRY.prometheus_text()
    problems = lint_prometheus_text(text)
    check(not problems, f"prometheus lint clean {problems[:3]}")
    for name in ("pingoo_compile_total", "pingoo_compile_ms",
                 "pingoo_timeline_spans_total",
                 "pingoo_costmodel_reload_total"):
        check(name in text, f"scrape exposes {name}")

    if FAILURES:
        print(f"\ntimeline smoke FAILED ({len(FAILURES)} problems)")
        return 1
    print(json.dumps(summary))
    print("\ntimeline smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(child() if "--child" in sys.argv else parent())
