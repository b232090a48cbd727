#!/usr/bin/env python
"""Compact-staging smoke (make staging-smoke; ISSUE 15).

Proves, offline and in ~a minute, that compact staging (docs/EXECUTOR.md
"Compact staging") is a transport change and never a semantic one — on
BOTH planes:

  * python plane: VerdictService verdicts under PINGOO_STAGING=compact
    are bit-identical to PINGOO_STAGING=full (the per-field oracle),
    with the ParityAuditor sampling the compact path and finding it
    clean, and the compact arm staging FEWER bytes per request than
    full on a long-URL stream;
  * sidecar plane: RingSidecar over a real shm ring, the same
    full-vs-compact bit-identity (this half skips with a warning when
    the native toolchain is unavailable);
  * the `pingoo_staged_bytes_total` / `pingoo_staging_field_cap`
    series export through the shared registry and the exposition
    passes the Prometheus lint.

Offline-safe like mesh-smoke: when jax is unavailable the smoke
SKIPS WITH A WARNING (exit 0) instead of failing the gate. The work
happens in a re-exec'd child under a controlled environment so a parent
shell pinning PINGOO_STAGING cannot skew the A/B.
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

FAILURES: list = []

N_PY = 72       # python-plane requests
N_RING = 96     # sidecar-plane requests
MAX_BATCH = 16


def check(ok, what):
    print(("  ok  " if ok else "  FAIL") + f" {what}")
    if not ok:
        FAILURES.append(what)


def parent() -> int:
    try:
        import jax  # noqa: F401
    except Exception as exc:
        print(f"staging smoke SKIPPED: jax unavailable ({exc!r})")
        return 0
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    for k in ("PINGOO_STAGING", "PINGOO_STAGING_DEPTH", "PINGOO_PIPELINE",
              "PINGOO_PIPELINE_DEPTH", "PINGOO_MESH", "PINGOO_DFA",
              "PINGOO_DEADLINE_MS", "PINGOO_SCHED_MODE",
              "PINGOO_SCHED_FAILOPEN", "PINGOO_CHAOS"):
        env.pop(k, None)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child"],
        env=env, cwd=REPO, timeout=900)
    return proc.returncode


def _staged_bytes(svc, mode):
    return float(svc.stats.staged_bytes_counter[mode]._value)


def _python_plane() -> dict:
    """VerdictService full-vs-compact bit-identity + auditor + byte
    savings on a long-URL-tail stream."""
    import asyncio
    import dataclasses
    import random

    import numpy as np

    sys.path.insert(0, os.path.join(REPO, "tests"))
    from pingoo_tpu.compiler import compile_ruleset
    from pingoo_tpu.engine.service import VerdictService
    from test_parity import LISTS, RULE_SOURCES, make_rules, \
        random_requests

    reqs = random_requests(random.Random(1501), N_PY)
    # A long-URL tail: the rows that make full mode's content
    # bucketing balloon while compact stays at the clamped cap.
    for i in range(0, N_PY, 24):
        reqs[i] = dataclasses.replace(
            reqs[i], url="/deep?q=" + "x" * 1500,
            path="/deep/" + "y" * 1500)

    def serve(mode):
        os.environ["PINGOO_STAGING"] = mode
        os.environ["PINGOO_STAGING_DEPTH"] = "256"
        os.environ["PINGOO_PIPELINE"] = "on"
        os.environ["PINGOO_PARITY_SAMPLE"] = "1"
        os.environ["PINGOO_PROVENANCE"] = "1"
        try:
            plan = compile_ruleset(make_rules(RULE_SOURCES), LISTS)
            svc = VerdictService(plan, LISTS, use_device=True,
                                 max_batch=32)

            async def flow():
                await svc.start()
                try:
                    return await asyncio.gather(
                        *[svc.evaluate(r) for r in reqs])
                finally:
                    await svc.stop()

            verdicts = asyncio.run(flow())
            parity = svc.parity
            if parity is not None:
                parity.flush(30)
            return svc, verdicts
        finally:
            for k in ("PINGOO_STAGING", "PINGOO_STAGING_DEPTH",
                      "PINGOO_PIPELINE", "PINGOO_PARITY_SAMPLE",
                      "PINGOO_PROVENANCE"):
                del os.environ[k]

    svc_f, want = serve("full")
    full_bytes = _staged_bytes(svc_f, "full")
    svc_c, got = serve("compact")
    compact_bytes = _staged_bytes(svc_c, "compact")
    identical = all(
        w.action == g.action and w.verified_block == g.verified_block
        and np.array_equal(w.matched, g.matched)
        for w, g in zip(want, got))
    check(identical,
          "python-plane verdicts bit-identical (compact vs full oracle)")
    check(full_bytes > 0 and compact_bytes > 0,
          f"both modes accounted staged bytes "
          f"(full={full_bytes:.0f} compact={compact_bytes:.0f})")
    check(compact_bytes < full_bytes,
          f"compact staged FEWER bytes ({compact_bytes:.0f} < "
          f"{full_bytes:.0f})")
    parity = svc_c.parity
    if parity is not None:
        check(parity.checked_total.value > 0,
              "auditor sampled the compact path")
        check(parity.mismatch_total.value == 0,
              "auditor found the compact path clean")
    return {"python_full_bytes": full_bytes,
            "python_compact_bytes": compact_bytes}


def _sidecar_plane() -> dict:
    """RingSidecar full-vs-compact bit-identity over a real shm ring."""
    import tempfile
    import threading

    from pingoo_tpu.compiler import compile_ruleset
    from pingoo_tpu.config.schema import Action, RuleConfig
    from pingoo_tpu.expr import compile_expression
    from pingoo_tpu.native_ring import Ring, RingSidecar

    rules = [
        RuleConfig(name="blk", actions=(Action.BLOCK,),
                   expression=compile_expression(
                       'http_request.path.starts_with("/evil")')),
        RuleConfig(name="ua", actions=(Action.BLOCK,),
                   expression=compile_expression(
                       'http_request.user_agent.contains("stagebot")')),
    ]
    plan = compile_ruleset(rules, {})

    def fields(i):
        if i % 11 == 0:  # long-URL tail rows
            path = (b"/fine/%d/" % i) + b"q" * 1500
        else:
            path = (f"/evil/{i}" if i % 3 == 0
                    else f"/fine/{i}").encode()
        return {"method": b"GET", "host": b"stage.test", "path": path,
                "url": path,
                "user_agent": b"stagebot" if i % 7 == 0 else b"ua",
                "ip": b"\x00" * 15 + bytes([i % 251 + 1])}

    def drive(tmp, mode):
        os.environ["PINGOO_STAGING"] = mode
        os.environ["PINGOO_STAGING_DEPTH"] = "256"
        try:
            ring = Ring(os.path.join(tmp, f"ring_{mode}"),
                        capacity=256, create=True)
            sidecar = RingSidecar(ring, plan, {}, max_batch=MAX_BATCH)
        finally:
            del os.environ["PINGOO_STAGING"]
            del os.environ["PINGOO_STAGING_DEPTH"]
        enq = {}
        for i in range(N_RING):
            enq[ring.enqueue(**fields(i))] = i
        worker = threading.Thread(
            target=sidecar.run, kwargs={"max_requests": N_RING},
            daemon=True)
        worker.start()
        got: dict = {}
        deadline = time.time() + 240
        while time.time() < deadline and len(got) < N_RING:
            v = ring.poll_verdict()
            if v is None:
                time.sleep(0.001)
                continue
            got.setdefault(v[0], []).append(v[1])
        sidecar.stop()
        worker.join(timeout=30)
        staged = float(sidecar._staged_bytes_counter[mode]._value)
        ring.close()
        check(len(got) == N_RING
              and all(len(v) == 1 for v in got.values()),
              f"{mode}: all verdicts exactly once ({len(got)}/{N_RING})")
        return {enq[t]: v[0] & 3 for t, v in got.items()}, staged

    with tempfile.TemporaryDirectory() as tmp:
        full, fb = drive(tmp, "full")
        compact, cb = drive(tmp, "compact")
    check(full == compact,
          "sidecar-plane verdicts bit-identical (compact vs full oracle)")
    check(fb > 0 and cb > 0,
          f"sidecar staged-bytes accounted (full={fb:.0f} "
          f"compact={cb:.0f})")
    return {"sidecar_full_bytes": fb, "sidecar_compact_bytes": cb}


def child() -> int:
    from pingoo_tpu import native_ring
    from pingoo_tpu.obs import REGISTRY
    from pingoo_tpu.obs.registry import lint_prometheus_text

    summary = _python_plane()
    if native_ring.ensure_built():
        summary.update(_sidecar_plane())
    else:
        print("  note sidecar plane skipped: native toolchain "
              "unavailable")

    text = REGISTRY.prometheus_text()
    problems = lint_prometheus_text(text)
    check(not problems, f"prometheus lint clean {problems[:3]}")
    for name in ("pingoo_staged_bytes_total", "pingoo_staging_field_cap"):
        check(name in text, f"scrape exposes {name}")

    if FAILURES:
        print(f"\nstaging smoke FAILED ({len(FAILURES)} problems)")
        return 1
    print(json.dumps(summary))
    print("\nstaging smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(child() if "--child" in sys.argv else parent())
