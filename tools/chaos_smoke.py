#!/usr/bin/env python
"""Sidecar supervision chaos smoke (make chaos-smoke; ISSUE 10).

Drives the liveness/reattach protocol (docs/RESILIENCE.md) through
REAL failures, offline and in ~a minute:

  * SIGKILL the sidecar process mid-batch (PINGOO_CHAOS=kill) with
    batches in flight, restart it, and prove crash-reattach
    reconciliation: every orphaned ticket (dequeued by the dead epoch,
    never answered) resolves EXACTLY once, with the verdict the rules
    demand — zero lost tickets, zero double-posts, p99
    enqueue->resolution bounded through the outage
    (`degraded_failopen_p99_ms`);
  * heartbeat freeze (PINGOO_CHAOS=heartbeat_freeze): the ring
    heartbeat goes stale within the detection window while the drain
    loop itself keeps serving — the liveness detector reads the
    protocol, not process existence;
  * injected device failure + verdict-ring-full stalls
    (PINGOO_CHAOS=xla_error,verdict_full): the degradation ladder
    demotes instead of crashing, every verdict still bit-exact;
  * ruleset swap storm (PINGOO_CHAOS=swap_storm; ISSUE 11): hot-swaps
    hammered at batch boundaries under live load, plus explicit
    multi-tenant request_swap calls racing the storm — zero lost or
    double-posted verdicts, bit-exact across every epoch, swap pause
    p99 inside the configured deadline budget (`swap_pause_p99_ms`).

Offline-safe like mesh-smoke: skips with a warning (exit 0) when jax
or the native toolchain is unavailable. The work happens in a
re-exec'd child under a controlled environment; the killable sidecar
runs as its OWN process (`--sidecar`) so SIGKILL exercises the real
no-cleanup crash path.

With BENCH_HISTORY=1 the summary appends to BENCH_history.jsonl under
backend "chaos-cpu", so tools/bench_regress.py gates
degraded_failopen_p99_ms across runs.
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

FAILURES: list = []

N_KILL = 64        # scenario A requests
N_LADDER = 48      # scenario C requests
N_SWAP = 96        # scenario D requests
MAX_BATCH = 16
P99_BOUND_MS = 30000.0  # hard outage bound (CI CPU: jit + restart)
# Swap-pause budget for CI CPU: the drain of in-flight batches inside
# the pause window runs jit'd computations on the host; on a real
# accelerator the default PINGOO_DEADLINE_MS (2ms) is the bound.
SWAP_P99_BOUND_MS = 1000.0


def check(ok, what):
    print(("  ok  " if ok else "  FAIL") + f" {what}")
    if not ok:
        FAILURES.append(what)


def make_plan():
    """The shared ruleset BOTH sidecar generations compile — verdicts
    are deterministic, so the smoke can assert exact actions without a
    reference run."""
    from pingoo_tpu.compiler import compile_ruleset
    from pingoo_tpu.config.schema import Action, RuleConfig
    from pingoo_tpu.expr import compile_expression

    rules = [
        RuleConfig(name="blk", actions=(Action.BLOCK,),
                   expression=compile_expression(
                       'http_request.path.starts_with("/evil")')),
        RuleConfig(name="ua", actions=(Action.BLOCK,),
                   expression=compile_expression(
                       'http_request.user_agent.contains("chaosbot")')),
    ]
    return compile_ruleset(rules, {})


def req_fields(i: int) -> dict:
    evil = i % 3 == 0
    bot = i % 7 == 0
    path = (f"/evil/{i}" if evil else f"/fine/{i}").encode()
    return {"method": b"GET", "host": b"chaos.test", "path": path,
            "url": path, "user_agent": b"chaosbot" if bot else b"ua",
            "ip": b"\x00" * 15 + bytes([i % 251 + 1])}


def want_action(i: int) -> int:
    return 1 if (i % 3 == 0 or i % 7 == 0) else 0


def parent() -> int:
    try:
        import jax  # noqa: F401
    except Exception as exc:
        print(f"chaos smoke SKIPPED: jax unavailable ({exc!r})")
        return 0
    from pingoo_tpu import native_ring

    if not native_ring.ensure_built():
        print("chaos smoke SKIPPED: native toolchain unavailable")
        return 0
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PINGOO_PARITY_SAMPLE"] = "1"
    for k in ("PINGOO_CHAOS", "PINGOO_DFA", "PINGOO_MESH",
              "PINGOO_DEADLINE_MS", "PINGOO_SCHED_MODE",
              "PINGOO_SCHED_FAILOPEN", "PINGOO_PIPELINE",
              "PINGOO_PIPELINE_DEPTH"):
        env.pop(k, None)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child"],
        env=env, cwd=REPO, timeout=900)
    return proc.returncode


def sidecar_main(ring_path: str, ready_path: str) -> int:
    """The killable sidecar generation: attach to the existing ring,
    signal readiness, drain until PINGOO_CHAOS kills the process."""
    from pingoo_tpu.native_ring import Ring, RingSidecar

    ring = Ring(ring_path, capacity=256, create=False)
    plan = make_plan()
    sidecar = RingSidecar(ring, plan, {}, max_batch=MAX_BATCH)
    with open(ready_path, "w") as f:
        f.write(f"epoch={sidecar.epoch}\n")
    sidecar.run()  # no request cap: PINGOO_CHAOS=kill ends this
    return 0


def _poller(ring, got: dict, stop, need: int):
    """Continuous verdict consumer: ticket -> list of (action, t_mono)
    so arrival latency is measured at arrival, and a double-post would
    surface as a second entry."""
    while not stop() and sum(len(v) for v in got.values()) < need:
        v = ring.poll_verdict()
        if v is None:
            time.sleep(0.001)
            continue
        got.setdefault(v[0], []).append((v[1], time.monotonic()))


def scenario_kill_reattach(tmp: str) -> dict:
    """SIGKILL mid-batch -> restart -> reconciliation, exactly once."""
    import threading

    from pingoo_tpu.native_ring import Ring, RingSidecar

    print("-- scenario: sidecar kill mid-batch + crash-reattach --")
    ring_path = os.path.join(tmp, "ring")
    ready_path = os.path.join(tmp, "ready")
    ring = Ring(ring_path, capacity=256, create=True)
    env = dict(os.environ)
    # pause briefly then SIGKILL after the first completed batch: the
    # run loop dispatches batch 2 BEFORE completing batch 1, so the
    # kill always strands dequeued-but-unposted tickets.
    env["PINGOO_CHAOS"] = "pause:100:1,kill:1"
    env["PINGOO_PIPELINE_DEPTH"] = "2"
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--sidecar",
         ring_path, ready_path], env=env, cwd=REPO)
    deadline = time.time() + 300
    while not os.path.exists(ready_path) and time.time() < deadline:
        if proc.poll() is not None:
            break
        time.sleep(0.05)
    check(os.path.exists(ready_path), "victim sidecar came up (epoch 1)")

    got: dict = {}
    stop_poll = False
    poll = threading.Thread(target=_poller,
                            args=(ring, got, lambda: stop_poll, N_KILL),
                            daemon=True)
    poll.start()
    enq_t = {}
    for i in range(N_KILL):
        tk = ring.enqueue(**req_fields(i))
        if tk is None:
            check(False, f"enqueue {i} hit a full ring")
            continue
        enq_t[tk] = time.monotonic()
    proc.wait(timeout=240)
    check(proc.returncode == -9,
          f"victim sidecar died by SIGKILL (rc={proc.returncode})")
    lv = ring.liveness()
    orphans = lv["req_tail"] - lv["posted_floor"]
    check(lv["epoch"] == 1, f"epoch 1 before reattach ({lv['epoch']})")
    check(orphans >= 1,
          f"kill stranded dequeued-but-unposted tickets ({orphans})")

    # Restart: a new epoch reconciles the orphans in __init__, then
    # serves the still-queued remainder.
    plan = make_plan()
    sidecar = RingSidecar(ring, plan, {}, max_batch=MAX_BATCH)
    check(sidecar.epoch == 2, f"reattach bumped epoch ({sidecar.epoch})")
    rec = dict(sidecar.reconciled)
    check(rec["reeval"] + rec["failopen"] == orphans,
          f"reconciled exactly the orphan window ({rec} vs {orphans})")
    check(rec["reeval"] == orphans,
          f"orphan bytes survived -> re-evaluated, not failed open "
          f"({rec})")
    remaining = N_KILL - lv["req_tail"]
    worker = threading.Thread(target=sidecar.run,
                              kwargs={"max_requests": remaining},
                              daemon=True)
    worker.start()
    deadline = time.time() + 240
    while time.time() < deadline and \
            sum(len(v) for v in got.values()) < N_KILL:
        time.sleep(0.01)
    stop_poll = True
    poll.join(timeout=5)
    sidecar.stop()
    worker.join(timeout=30)

    lost = [t for t in enq_t if t not in got]
    doubles = {t: [a for a, _ in v] for t, v in got.items() if len(v) > 1}
    check(not lost, f"zero lost tickets ({len(lost)} lost: {lost[:5]})")
    check(not doubles, f"zero double-posted tickets ({doubles})")
    wrong = [t for t, v in got.items()
             if (v[0][0] & 3) != want_action(t)]
    check(not wrong,
          f"verdicts bit-exact across crash+reattach ({wrong[:5]})")
    if sidecar.parity is not None:
        check(sidecar.parity.flush(30), "parity auditor drained")
        check(sidecar.parity.mismatch_total.value == 0,
              "parity clean over post-reattach batches")
    lats = sorted((v[0][1] - enq_t[t]) * 1e3 for t, v in got.items()
                  if t in enq_t)
    p99 = lats[max(0, int(len(lats) * 0.99) - 1)] if lats else -1.0
    check(0 < p99 < P99_BOUND_MS,
          f"p99 enqueue->resolution bounded through the outage "
          f"({p99:.0f}ms < {P99_BOUND_MS:.0f}ms)")
    ring.close()
    return {"orphans": orphans, "reconciled": rec,
            "degraded_failopen_p99_ms": round(p99, 1)}


def scenario_heartbeat_freeze(tmp: str) -> dict:
    """Frozen heartbeat goes stale within the detection window while
    the drain loop keeps serving — liveness is protocol, not ps."""
    import threading

    from pingoo_tpu.native_ring import Ring, RingSidecar

    print("-- scenario: heartbeat freeze detection --")
    ring = Ring(os.path.join(tmp, "ring_hb"), capacity=64, create=True)
    os.environ["PINGOO_CHAOS"] = "heartbeat_freeze"
    try:
        plan = make_plan()
        sidecar = RingSidecar(ring, plan, {}, max_batch=MAX_BATCH)
    finally:
        del os.environ["PINGOO_CHAOS"]
    t0 = time.monotonic()
    worker = threading.Thread(target=sidecar.run, daemon=True)
    worker.start()
    for i in range(8):
        ring.enqueue(**req_fields(i))
    got: dict = {}
    deadline = time.time() + 120
    while time.time() < deadline and len(got) < 8:
        v = ring.poll_verdict()
        if v is not None:
            got[v[0]] = v[1]
        time.sleep(0.005)
    check(len(got) == 8, f"frozen-heartbeat sidecar still serves "
                         f"({len(got)}/8)")
    detect_ms = None
    deadline = time.time() + 30
    while time.time() < deadline:
        lv = ring.liveness()
        age = lv["now_ms"] - lv["heartbeat_ms"]
        if age > 500:  # the PINGOO_SIDECAR_TIMEOUT_MS default
            detect_ms = (time.monotonic() - t0) * 1e3
            break
        time.sleep(0.02)
    check(detect_ms is not None,
          f"heartbeat went stale past the 500ms detection window "
          f"({detect_ms and round(detect_ms)}ms after attach)")
    sidecar.stop()
    worker.join(timeout=30)
    ring.close()
    return {"heartbeat_detect_ms": round(detect_ms or -1, 1)}


def scenario_ladder(tmp: str) -> dict:
    """Injected device failure + verdict-ring-full: the ladder demotes
    (counted), the posts retry, every verdict stays exact."""
    import threading

    from pingoo_tpu.native_ring import Ring, RingSidecar

    print("-- scenario: ladder demotion under injected faults --")
    ring = Ring(os.path.join(tmp, "ring_lad"), capacity=64, create=True)
    os.environ["PINGOO_CHAOS"] = "xla_error:1,verdict_full:2"
    try:
        plan = make_plan()
        sidecar = RingSidecar(ring, plan, {}, max_batch=MAX_BATCH)
    finally:
        del os.environ["PINGOO_CHAOS"]
    enq = {}
    for i in range(N_LADDER):
        enq[ring.enqueue(**req_fields(i))] = i
    worker = threading.Thread(target=sidecar.run,
                              kwargs={"max_requests": N_LADDER},
                              daemon=True)
    worker.start()
    got: dict = {}
    deadline = time.time() + 240
    while time.time() < deadline and len(got) < N_LADDER:
        v = ring.poll_verdict()
        if v is not None:
            got.setdefault(v[0], []).append(v[1])
        time.sleep(0.001)
    sidecar.stop()
    worker.join(timeout=30)
    snap = sidecar.ladder.snapshot()
    errs = {r: s["errors"] for r, s in snap.items() if s["errors"]}
    check("xla" in sidecar.chaos._fired,
          "chaos injected the device failure")
    check(sidecar.chaos.verdict_full_budget == 0,
          "verdict-ring-full stalls were exercised")
    check(sum(errs.values()) >= 1,
          f"ladder counted the demotion ({errs})")
    check(len(got) == N_LADDER and all(len(v) == 1 for v in got.values()),
          f"all verdicts, exactly once ({len(got)}/{N_LADDER})")
    wrong = [t for t, v in got.items()
             if (v[0] & 3) != want_action(enq[t])]
    check(not wrong, f"verdicts bit-exact through demotion ({wrong[:5]})")
    ring.close()
    return {"ladder_errors": errs,
            "ladder_demoted_rungs": sidecar.ladder.demoted()}


def scenario_swap_storm(tmp: str) -> dict:
    """PINGOO_CHAOS=swap_storm hammers hot-swaps at batch boundaries
    under live load, racing explicit multi-tenant request_swap calls.
    Every swap installs the SAME compiled plan, so any verdict drift
    is a swap-protocol bug by construction."""
    import threading

    from pingoo_tpu.native_ring import Ring, RingSidecar

    print("-- scenario: ruleset swap storm under live load --")
    ring = Ring(os.path.join(tmp, "ring_swap"), capacity=256,
                create=True)
    os.environ["PINGOO_CHAOS"] = "swap_storm:2"
    try:
        plan = make_plan()
        sidecar = RingSidecar(ring, plan, {}, max_batch=MAX_BATCH)
    finally:
        del os.environ["PINGOO_CHAOS"]
    worker = threading.Thread(target=sidecar.run, daemon=True)
    worker.start()
    got: dict = {}
    stop_poll = False
    poll = threading.Thread(target=_poller,
                            args=(ring, got, lambda: stop_poll, N_SWAP),
                            daemon=True)
    poll.start()
    tenants = ("acme", "globex", "initech", "umbrella")
    enq = {}
    swaps = []
    for i in range(N_SWAP):
        tk = ring.enqueue(**req_fields(i))
        if tk is None:
            check(False, f"enqueue {i} hit a full ring")
            continue
        enq[tk] = i
        if i and i % 24 == 0:
            # Explicit cross-tenant swaps racing the storm's implicit
            # ones — the engine state builds HERE (requester thread,
            # compile-ahead), never in the drain loop.
            swaps.append(sidecar.request_swap(
                plan, tenant=tenants[(i // 24) % len(tenants)]))
        time.sleep(0.002)
    for h in swaps:
        check(h.wait(120) and h.result == "ok",
              f"explicit tenant swap applied ({h.tenant}: {h.result})")
    deadline = time.time() + 240
    while time.time() < deadline and \
            sum(len(v) for v in got.values()) < N_SWAP:
        time.sleep(0.01)
    stop_poll = True
    poll.join(timeout=5)
    sidecar.stop()
    worker.join(timeout=30)

    lost = [t for t in enq if t not in got]
    doubles = {t: v for t, v in got.items() if len(v) > 1}
    check(not lost, f"zero lost tickets across swaps ({len(lost)} lost)")
    check(not doubles,
          f"zero double-posted tickets ({len(doubles)} doubled)")
    wrong = [t for t, v in got.items()
             if (v[0][0] & 3) != want_action(enq[t])]
    check(not wrong,
          f"verdicts bit-exact across every swap epoch ({wrong[:5]})")
    nswaps = len(sidecar.swap_pauses_ms)
    check(sidecar.ruleset_epoch >= 3,
          f"storm + explicit swaps applied ({sidecar.ruleset_epoch} "
          f"epochs over {sidecar.batches} batches)")
    check(nswaps == sidecar.ruleset_epoch,
          f"every applied swap recorded a pause ({nswaps} vs epoch "
          f"{sidecar.ruleset_epoch})")
    pauses = sorted(sidecar.swap_pauses_ms)
    p99 = pauses[max(0, int(len(pauses) * 0.99) - 1)] if pauses else -1.0
    check(0 <= p99 < SWAP_P99_BOUND_MS,
          f"swap pause p99 within budget ({p99:.1f}ms < "
          f"{SWAP_P99_BOUND_MS:.0f}ms)")
    ring.close()
    return {"swap_epochs": sidecar.ruleset_epoch,
            "swap_pause_p99_ms": round(p99, 2)}


def child() -> int:
    import tempfile

    summary = {"backend": "chaos-cpu"}
    with tempfile.TemporaryDirectory() as tmp:
        summary.update(scenario_kill_reattach(tmp))
        summary.update(scenario_heartbeat_freeze(tmp))
        summary.update(scenario_ladder(tmp))
        summary.update(scenario_swap_storm(tmp))

    from pingoo_tpu.obs import REGISTRY
    from pingoo_tpu.obs.registry import lint_prometheus_text

    text = REGISTRY.prometheus_text()
    problems = lint_prometheus_text(text)
    check(not problems, f"prometheus lint clean {problems[:3]}")
    for name in ("pingoo_sidecar_epoch", "pingoo_reattach_reconciled_total",
                 "pingoo_degrade_total", "pingoo_chaos_injected_total",
                 "pingoo_ruleset_epoch", "pingoo_ruleset_swap_total"):
        check(name in text, f"scrape exposes {name}")

    if FAILURES:
        print(f"\nchaos smoke FAILED ({len(FAILURES)} problems)")
        return 1
    print(json.dumps(summary))
    if os.environ.get("BENCH_HISTORY") == "1":
        summary["ts"] = time.time()
        path = os.environ.get("BENCH_HISTORY_FILE",
                              "BENCH_history.jsonl")
        with open(path, "a") as f:
            f.write(json.dumps(summary) + "\n")
    print("\nchaos smoke OK")
    return 0


if __name__ == "__main__":
    if "--sidecar" in sys.argv:
        i = sys.argv.index("--sidecar")
        sys.exit(sidecar_main(sys.argv[i + 1], sys.argv[i + 2]))
    sys.exit(child() if "--child" in sys.argv else parent())
