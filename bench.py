#!/usr/bin/env python
"""Headline benchmark: WAF-evaluated requests/sec/chip @ 500 rules.

BASELINE.md north star: >= 1,000,000 req/s/chip on a 500-rule
OWASP-CRS-style ruleset at p99 added verdict latency < 2 ms (TPU v5e-1).
The reference publishes no numbers (BASELINE.md: `published` is {});
`vs_baseline` is measured against the 1M req/s target.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "req/s", "vs_baseline": N, ...}

Besides the headline on-chip kernel number, the same line carries:
  * blocklist_*: BASELINE config 3 — membership lookups/s against a
    1M-entry IP/CIDR blocklist (sorted-prefix-bucket kernel,
    ops/cidr.py), measured with the same chained-loop method.
  * e2e_*: the served path — native loadgen_http -> native httpd ->
    shared-memory ring -> Python sidecar -> device lane verdict ->
    403/proxy -> native pong, over real sockets.
  * dataplane_*: the same serving path with the DEVICE OUT of the loop
    (canned verdicts) — the data plane + ring transport capacity of
    this host, independent of the chip.

One owner of the chip: THIS process never initialises a JAX backend.
Every arm that uses JAX runs as a child (`_device_bench_child` for the
kernel loop and everything that shares its plan; the sched / pipeline /
staging children), one at a time, on the ambient platform, with the
persistent compile cache placed by pingoo_tpu.backend. Every arm's
JSON names `platform`, `device_kind` and `device_count`. There is no
CPU fallback: a backend that fails to initialise, or an arm that
raises, makes the exit code non-zero (the partial line still prints).
A `JAX_PLATFORMS=cpu` run is a correctness/count run — its rates are
labelled `platform: "cpu"` and are not device numbers.

Method: UNFILTERED 500-rule CRS-style ruleset (pingoo_tpu/utils/crs.py;
includes \\b and >31-position multi-word patterns — whatever the
compiler cannot lower is host-interpreted and reported via
`device_residency`) + 128k-entry IP blocklist + 4k ASN bitset;
replayed-log-style traffic at 5% attack rate. Timing uses a device-side
chained loop (each iteration's verdict feeds a carried checksum, and the
checksum salts EVERY input column of the next iteration, so XLA's
while-loop invariant code motion cannot hoist any of the verdict out of
the loop) with an empty-loop floor subtracted, so the per-batch figure
is verdict time over the device-resident rules with dispatch overhead
out; `p_batch_ms` is also the added verdict latency for a full batch
(the <2 ms budget).
"""

import json
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))


def _child_backend() -> dict:
    """First thing every JAX child does: place the persistent compile
    cache, then take the backend JAX gives it. Returns the platform /
    device_kind / device_count triple every arm's JSON carries."""
    from pingoo_tpu.backend import backend_info, place_compile_cache

    place_compile_cache()
    return backend_info()


def _run_child(fn: str, timeout: int, env: dict | None = None) -> dict:
    """Run `bench.<fn>()` in a process of its own and return the JSON
    object on its last stdout line. Children run one at a time and
    inherit the ambient platform: the parent never holds a backend, so
    each child is the sole owner of the chip while it runs."""
    out = _run_tracked(
        [sys.executable, "-c", f"import bench; bench.{fn}()"],
        capture_output=True, text=True, timeout=timeout,
        env=dict(os.environ) if env is None else env, cwd=REPO)
    lines = (out.stdout or "").strip().splitlines()
    if out.returncode != 0 or not lines:
        raise RuntimeError(
            f"{fn} rc={out.returncode}: {(out.stderr or '')[-300:]}")
    return json.loads(lines[-1])


def bench_blocklist_1m(iters: int = 50, batch: int = 8192) -> dict:
    """BASELINE config 3: 1M-entry IP/CIDR blocklist membership on HBM
    (reference lists.rs:48-125 loads these into a bel array the
    interpreter scans per request)."""
    import jax
    import jax.numpy as jnp

    from pingoo_tpu.ops.cidr import (
        build_cidr_table,
        index_v4_buckets,
        v4_buckets_contains,
    )

    rng = np.random.default_rng(20260729)
    addrs = np.unique(rng.integers(
        0x01000000, 0xDF000000, size=960_000, dtype=np.uint32))
    nets24 = np.unique(rng.integers(
        0x010000, 0xDF0000, size=70_000, dtype=np.uint32))
    n_entries = int(len(addrs) + len(nets24))
    nmax = max(len(addrs), len(nets24))
    keys = np.full((2, nmax), 0xFFFFFFFF, dtype=np.uint32)
    keys[0, : len(nets24)] = np.sort(nets24)
    keys[1, : len(addrs)] = np.sort(addrs)
    buckets = index_v4_buckets(
        keys,
        np.array([24, 32], dtype=np.int32),
        np.array([len(nets24), len(addrs)], dtype=np.int32),
        build_cidr_table([]),
    )

    # ~10% member probes, v6-mapped words.
    probes_v4 = rng.integers(0x01000000, 0xDF000000, size=batch,
                             dtype=np.uint32)
    members = rng.choice(addrs, size=batch // 10, replace=False)
    probes_v4[: len(members)] = members
    probes = np.zeros((batch, 4), dtype=np.uint32)
    probes[:, 2] = 0xFFFF
    probes[:, 3] = probes_v4
    ips = jax.device_put(probes)

    @jax.jit
    def run_n(buckets, ips, n):
        def body(i, acc):
            # Salt depends on the carried checksum (defeats dead-code
            # elimination) AND the loop index (alternates even if the
            # hit-count parity sticks, so inputs are never invariant).
            salted = ips.at[:, 3].set(
                ips[:, 3] + ((acc + i) % 2).astype(jnp.uint32))
            hit = v4_buckets_contains(buckets, salted)
            return acc + hit.sum().astype(jnp.int64)
        return jax.lax.fori_loop(0, n, body, jnp.int64(0))

    @jax.jit
    def floor_loop(ips, n):
        def body(i, acc):
            return acc + ips[:, 3].sum().astype(jnp.int64) + i
        return jax.lax.fori_loop(0, n, body, jnp.int64(0))

    int(run_n(buckets, ips, 2))
    int(floor_loop(ips, 2))
    t0 = time.time()
    int(floor_loop(ips, iters))
    floor = time.time() - t0
    t0 = time.time()
    checksum = int(run_n(buckets, ips, iters))
    full = time.time() - t0
    per_batch = max((full - floor) / iters, 1e-9)
    return {
        "blocklist_entries": n_entries,
        "blocklist_lookups_per_s": round(batch / per_batch, 1),
        "blocklist_checksum": checksum,
    }


def autotune_scan_strategies(plan, tables, arrays, iters: int = 30) -> dict:
    """Micro-autotune hook: measure the per-LOOP-ITERATION cost of each
    NFA scan strategy (lax.scan single/pair, fused Pallas single/pair)
    on the LIVE backend with the same chained-salted-loop method as the
    headline bench, on the widest bank (it dominates the verdict).
    Returns a DEFAULT_STEP_COSTS-shaped dict (relative to "scan") for
    compiler.plan.reselect_scan_strategies; {} when there is no bank."""
    import jax
    import jax.numpy as jnp

    from pingoo_tpu.ops.nfa_scan import (extract_slots, init_scan_state,
                                         scan_chunk)

    keys = [k for k in plan.scan_plans if k in tables]
    if not keys:
        return {}
    key = max(keys, key=lambda k: int(tables[k].opt.shape[0]))
    bank = tables[key]
    field = key[len("nfa_"):]
    data = arrays[f"{field}_bytes"]
    lens = arrays[f"{field}_len"]
    B, L = data.shape
    W = int(bank.opt.shape[0])
    variants = {
        "scan": (None, None),
        "pair": ("pair", None),
        "pallas": (None, "pallas"),
        "pallas_pair": ("pair", "pallas"),
    }
    raw = {}
    for name, (lookup, backend) in variants.items():
        loop_iters = (L + 1) // 2 if lookup == "pair" else L

        @jax.jit
        def run_n(data, lens, n, lookup=lookup, backend=backend):
            def body(i, acc):
                # salt from the carried checksum + loop index: no
                # loop-invariant inputs for XLA to hoist (see the
                # headline bench's measurement notes).
                salted = data ^ ((acc + i) % 2).astype(jnp.uint8)
                state = scan_chunk(bank, salted, lens,
                                   init_scan_state(B, W), 0,
                                   lookup=lookup, backend=backend)
                hits = extract_slots(bank, state, lens)
                return acc + hits.sum().astype(jnp.int64)

            return jax.lax.fori_loop(0, n, body, jnp.int64(0))

        @jax.jit
        def floor_loop(data, n):
            def body(i, acc):
                return acc + data.sum().astype(jnp.int64) + i

            return jax.lax.fori_loop(0, n, body, jnp.int64(0))

        try:
            int(run_n(data, lens, 2))
            int(floor_loop(data, 2))
            t0 = time.time()
            int(floor_loop(data, iters))
            floor = time.time() - t0
            t0 = time.time()
            int(run_n(data, lens, iters))
            full = time.time() - t0
        except Exception:
            continue  # a strategy that fails to compile is never selected
        raw[name] = max(full - floor, 1e-9) / iters / loop_iters
    base = raw.get("scan")
    if not base:
        return {}
    return {k: v / base for k, v in raw.items()}


def bench_prefilter_modes(plan, tables, arrays, verdict_body,
                          iters: int = 30) -> dict:
    """ISSUE 4: per-mode verdict throughput for the literal-prefilter
    cascade (PINGOO_PREFILTER=off|banks|compact) with the same
    chained-salted-loop method as the headline bench, plus the Stage-A
    candidate statistics (rate, banks skipped) on the bench traffic.
    Selects the fastest mode into plan.prefilter.default_mode (persisted
    by the caller via the artifact cache) and writes the
    BENCH_prefilter.json trajectory artifact."""
    import jax
    import jax.numpy as jnp

    out: dict = {"modes": {}}
    batch = int(arrays["asn"].shape[0])
    prev = os.environ.get("PINGOO_PREFILTER")
    try:
        for mode in ("off", "banks", "compact"):
            os.environ["PINGOO_PREFILTER"] = mode

            # Fresh jit per mode: the mode is read at trace time.
            @jax.jit
            def run_n(tables, arrays, n):
                def body(i, acc):
                    m = verdict_body(tables, arrays, (acc + i) % 2)
                    return acc + m.sum().astype(jnp.int64)
                return jax.lax.fori_loop(0, n, body, jnp.int64(0))

            @jax.jit
            def floor_loop(arrays, n):
                def body(i, acc):
                    return acc + arrays["asn"].sum() + i
                return jax.lax.fori_loop(0, n, body, jnp.int64(0))

            try:
                t0 = time.time()
                checksum = int(run_n(tables, arrays, 2))
                int(floor_loop(arrays, 2))
                compile_s = time.time() - t0
                t0 = time.time()
                int(floor_loop(arrays, iters))
                floor = time.time() - t0
                t0 = time.time()
                checksum = int(run_n(tables, arrays, iters))
                full = time.time() - t0
            except Exception as exc:
                out["modes"][mode] = {"error": repr(exc)[:200]}
                continue
            per_batch_s = max((full - floor) / iters, 1e-9)
            out["modes"][mode] = {
                "req_per_s": round(batch / per_batch_s, 1),
                "p_batch_ms": round(per_batch_s * 1000, 3),
                "compile_s": round(compile_s, 1),
                "checksum": checksum,
            }
    finally:
        if prev is None:
            os.environ.pop("PINGOO_PREFILTER", None)
        else:
            os.environ["PINGOO_PREFILTER"] = prev

    # Stage-A candidate statistics on the (unsalted) bench traffic.
    try:
        from pingoo_tpu.engine.verdict import make_prefilter_fn

        os.environ["PINGOO_PREFILTER"] = "banks"
        try:
            pf = make_prefilter_fn(plan)
        finally:
            if prev is None:
                os.environ.pop("PINGOO_PREFILTER", None)
            else:
                os.environ["PINGOO_PREFILTER"] = prev
        if pf is not None:
            pf_fn, n_gated = pf.fn, len(pf.gated)
            _, aux = pf_fn(tables, arrays)
            aux = np.asarray(aux)
            out["banks_gated"] = n_gated
            out["banks_skipped_per_batch"] = int(aux[1])
            out["candidate_rate"] = (
                round(int(aux[0]) / (batch * n_gated), 4) if n_gated
                else 0.0)
        pfp = getattr(plan, "prefilter", None)
        if pfp is not None:
            out["factors"] = {f: ff.num_factors
                              for f, ff in pfp.fields.items()}
    except Exception as exc:
        out["stats_error"] = repr(exc)[:200]

    base = out["modes"].get("off", {}).get("req_per_s")
    best_mode, best_rps = "off", base or 0
    for mode, row in out["modes"].items():
        rps = row.get("req_per_s")
        if base:
            row["speedup_vs_off"] = round(rps / base, 3) if rps else None
        if rps and rps > best_rps:
            best_mode, best_rps = mode, rps
    out["selected"] = best_mode
    if getattr(plan, "prefilter", None) is not None:
        plan.prefilter.default_mode = best_mode

    try:
        with open("BENCH_prefilter.json", "w") as f:
            json.dump({
                "metric": "prefilter_cascade_modes",
                **_child_backend(),
                "batch_size": batch,
                **out,
            }, f, indent=2)
    except OSError:
        pass
    return out


def bench_dfa_modes(plan, tables, arrays, verdict_body,
                    iters: int = 30) -> dict:
    """ISSUE 8: per-mode verdict throughput for the bitsplit-DFA
    lowering (PINGOO_DFA=off|auto|force) with the same chained-salted-
    loop method as the headline bench, plus the per-bank lowering
    summary (state counts, exact vs approximate). Selects the fastest
    mode into plan.dfa_default_mode (persisted by the caller via the
    artifact cache) and writes the BENCH_dfa.json trajectory artifact.
    The off mode is the PR 4 compact-cascade baseline, so
    speedup_vs_off is the ISSUE 8 acceptance number."""
    import jax
    import jax.numpy as jnp

    out: dict = {"modes": {}}
    batch = int(arrays["asn"].shape[0])
    prev = os.environ.get("PINGOO_DFA")
    try:
        for mode in ("off", "auto", "force"):
            os.environ["PINGOO_DFA"] = mode

            # Fresh jit per mode: the mode is read at trace time.
            @jax.jit
            def run_n(tables, arrays, n):
                def body(i, acc):
                    m = verdict_body(tables, arrays, (acc + i) % 2)
                    return acc + m.sum().astype(jnp.int64)
                return jax.lax.fori_loop(0, n, body, jnp.int64(0))

            @jax.jit
            def floor_loop(arrays, n):
                def body(i, acc):
                    return acc + arrays["asn"].sum() + i
                return jax.lax.fori_loop(0, n, body, jnp.int64(0))

            try:
                t0 = time.time()
                checksum = int(run_n(tables, arrays, 2))
                int(floor_loop(arrays, 2))
                compile_s = time.time() - t0
                t0 = time.time()
                int(floor_loop(arrays, iters))
                floor = time.time() - t0
                t0 = time.time()
                checksum = int(run_n(tables, arrays, iters))
                full = time.time() - t0
            except Exception as exc:
                out["modes"][mode] = {"error": repr(exc)[:200]}
                continue
            per_batch_s = max((full - floor) / iters, 1e-9)
            out["modes"][mode] = {
                "req_per_s": round(batch / per_batch_s, 1),
                "p_batch_ms": round(per_batch_s * 1000, 3),
                "compile_s": round(compile_s, 1),
                "checksum": checksum,
            }
    finally:
        if prev is None:
            os.environ.pop("PINGOO_DFA", None)
        else:
            os.environ["PINGOO_DFA"] = prev

    # Per-bank lowering summary (host-static, from the plan).
    try:
        banks = {}
        for key, e in plan.scan_plans.items():
            if not e.dfa_key or e.dfa_key not in plan.np_tables:
                continue
            dtab = plan.np_tables[e.dfa_key]
            banks[key] = {
                "states": int(dtab.num_states),
                "classes": int(dtab.num_classes),
                "exact": bool(dtab.exact),
                "auto": bool(e.dfa_auto),
            }
        for key, dkey in getattr(plan, "win_dfa", {}).items():
            if dkey not in plan.np_tables:
                continue
            dtab = plan.np_tables[dkey]
            banks[key] = {
                "states": int(dtab.num_states),
                "classes": int(dtab.num_classes),
                "exact": bool(dtab.exact),
                # Window DFAs dispatch on the row-work-bound CPU
                # backend under auto (engine/verdict._dfa_win_active).
                "auto": "cpu-only",
            }
        out["banks"] = banks
    except Exception as exc:
        out["stats_error"] = repr(exc)[:200]

    base = out["modes"].get("off", {}).get("req_per_s")
    best_mode, best_rps = "off", base or 0
    for mode, row in out["modes"].items():
        rps = row.get("req_per_s")
        if base:
            row["speedup_vs_off"] = round(rps / base, 3) if rps else None
        if rps and rps > best_rps:
            best_mode, best_rps = mode, rps
    out["selected"] = best_mode
    plan.dfa_default_mode = best_mode

    try:
        with open("BENCH_dfa.json", "w") as f:
            json.dump({
                "metric": "bitsplit_dfa_modes",
                **_child_backend(),
                "batch_size": batch,
                **out,
            }, f, indent=2)
    except OSError:
        pass
    return out


def _mesh_arg() -> str | None:
    """`--mesh dpxtpxsp` (or BENCH_MESH) selects the serving-mesh shape
    the scheduler bench runs under; None disables the bench unless
    BENCH_SCHED=1 asks for the 1x1x1 scheduler A/B alone."""
    if "--mesh" in sys.argv:
        i = sys.argv.index("--mesh")
        if i + 1 < len(sys.argv):
            return sys.argv[i + 1]
    return os.environ.get("BENCH_MESH") or None


def bench_sched(mesh_spec: str) -> dict:
    """ISSUE 6 satellite: measure the admission SCHEDULER modes
    (fixed-window vs continuous, docs/SCHEDULER.md) and the serving
    mesh by driving a bursty request stream through a live
    VerdictService. Runs in a SUBPROCESS so the dp*tp*sp virtual CPU
    devices can be forced before jax initializes (the same shape
    `make mesh-smoke` and tests/test_mesh_serving.py use) — a CPU-mesh
    dry-run, labelled `sched_backend: "cpu-mesh-dryrun"`. Returns flattened
    `sched_*` keys for the result line — tools/bench_regress.py tracks
    continuous throughput, p99, slack, and the deadline-miss rate."""
    dims = [int(x) for x in mesh_spec.lower().split("x")]
    if len(dims) != 3 or any(d < 1 for d in dims):
        raise ValueError(f"bad --mesh spec {mesh_spec!r}")
    ndev = dims[0] * dims[1] * dims[2]
    # The ONE arm that pins a platform: forced virtual devices exist
    # only on the CPU backend, so this is a CPU-mesh dry-run by
    # construction and its keys say so (`sched_backend`).
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={max(ndev, 2)}")
    env["XLA_FLAGS"] = " ".join(flags)
    env["PINGOO_MESH"] = mesh_spec
    child = _run_child("_sched_bench_child", timeout=1200, env=env)
    res = {"sched_backend": "cpu-mesh-dryrun",
           "sched_platform": child.get("platform"),
           "sched_device_kind": child.get("device_kind"),
           "sched_device_count": child.get("device_count"),
           "sched_mesh": mesh_spec, "sched_mesh_devices": ndev,
           "sched_deadline_ms": child.get("deadline_ms"),
           "sched_batch": child.get("max_batch")}
    for mode, row in child.get("modes", {}).items():
        for key, val in row.items():
            res[f"sched_{mode}_{key}"] = val
    cont = child.get("modes", {}).get("continuous", {})
    # The regress-tracked aliases (direction-aware, bench_regress.py).
    if "req_per_s" in cont:
        res["sched_continuous_req_per_s"] = cont["req_per_s"]
        res["sched_continuous_p99_ms"] = cont.get("p99_wait_ms")
        res["sched_deadline_miss_rate"] = cont.get("deadline_miss_rate")
        res["sched_p99_slack_ms"] = cont.get("p99_slack_ms")
    return res


def _sched_bench_child() -> None:
    """Child body of bench_sched (forced-device-count subprocess): boot
    VerdictService per scheduler mode, serve a bursty replayed-traffic
    stream, emit one JSON line with per-mode throughput/latency/miss
    statistics. Per-request latency is measured around evaluate() in
    the driver (the registry's wait histogram is process-global and
    would mix the two modes)."""
    import asyncio
    import time as _time

    from pingoo_tpu.compiler import compile_ruleset
    from pingoo_tpu.engine.service import VerdictService
    from pingoo_tpu.utils.crs import generate_ruleset, generate_traffic

    backend = _child_backend()
    n_rules = int(os.environ.get("BENCH_SCHED_RULES", "60"))
    n_reqs = int(os.environ.get("BENCH_SCHED_REQUESTS", "1024"))
    burst = int(os.environ.get("BENCH_SCHED_BURST", "64"))
    max_batch = int(os.environ.get("BENCH_SCHED_BATCH", "256"))
    rules, lists = generate_ruleset(n_rules, with_lists=True,
                                    list_sizes=(4096, 512))
    plan = compile_ruleset(rules, lists)
    reqs = generate_traffic(n_reqs, lists=lists, seed=7)
    result: dict = {**backend, "modes": {}, "max_batch": max_batch,
                    "rules": n_rules, "requests": n_reqs}

    for mode in ("fixed", "continuous"):
        os.environ["PINGOO_SCHED_MODE"] = mode
        svc = VerdictService(plan, lists, use_device=True,
                             max_batch=max_batch, max_wait_us=300)
        result["deadline_ms"] = svc.sched.config.deadline_ms
        waits: list[float] = []

        async def timed(svc=svc, waits=waits, r=None):
            t0 = _time.monotonic()
            v = await svc.evaluate(r)
            waits.append((_time.monotonic() - t0) * 1e3)
            return v

        async def drive(svc=svc, waits=waits):
            await svc.start()
            # Warm the per-bucket XLA programs off the measured run (a
            # first-burst compile would otherwise own the p99).
            await asyncio.gather(*[svc.evaluate(r)
                                   for r in reqs[:burst]])
            miss0 = svc.sched.deadline_misses
            launch0 = svc.sched.launches
            t0 = _time.monotonic()
            for i in range(0, n_reqs, burst):
                await asyncio.gather(*[
                    timed(svc, waits, r) for r in reqs[i:i + burst]])
            elapsed = _time.monotonic() - t0
            await svc.stop()
            return elapsed, miss0, launch0

        elapsed, miss0, launch0 = asyncio.run(drive())
        waits.sort()
        p99 = waits[min(len(waits) - 1, int(0.99 * len(waits)))]
        deadline_ms = svc.sched.config.deadline_ms
        launches = svc.sched.launches - launch0
        result["modes"][mode] = {
            "req_per_s": round(n_reqs / elapsed, 1),
            "p50_wait_ms": round(waits[len(waits) // 2], 3),
            "p99_wait_ms": round(p99, 3),
            "p99_slack_ms": round(deadline_ms - p99, 3),
            "deadline_miss_rate": round(
                (svc.sched.deadline_misses - miss0) / n_reqs, 4),
            "launches": launches,
            "mean_launch_occupancy": round(
                n_reqs / launches, 1) if launches else 0.0,
        }
    print(json.dumps(result), flush=True)


def bench_body() -> dict:
    """ISSUE 13 satellite: throughput of the streaming body scanner
    (engine/bodyscan.py) over interleaved multi-flow window streams —
    the shape the ring sidecar actually drains — A/B'd against the
    contiguous one-shot scan of the same payloads. Verdict equality
    across both framings and the interpreter oracle is enforced:
    streaming is a framing change, never a semantic one. Writes
    BENCH_body.json; tools/bench_regress.py tracks the streamed
    throughput."""
    import random as _random

    from pingoo_tpu.engine import bodyscan

    n_flows = int(os.environ.get("BENCH_BODY_FLOWS", "192"))
    plan = bodyscan.compile_body_plan()
    window = bodyscan.body_window_bytes()
    rng = _random.Random(1306)
    # Filler alphabet free of rule-literal bytes (space, quotes, <, .,
    # /, parens) so only the planted literals can match.
    alpha = b"abcdefghijklmnop0123456789=&"
    lits = [r.pattern.encode() for r in bodyscan.DEFAULT_BODY_RULES]
    payloads = []
    for i in range(n_flows):
        body = bytes(rng.choices(alpha, k=rng.randint(256, 3 * window)))
        if i % 3 == 0:  # a third carry a literal at a random offset
            lit = lits[i % len(lits)]
            at = rng.randint(0, len(body))
            body = body[:at] + lit + body[at:]
        payloads.append(body)
    total_bytes = sum(map(len, payloads))

    def make_windows():
        """Round-robin interleave the flows' windows, the arrival
        order a busy listener actually produces."""
        per_flow = []
        for fid, payload in enumerate(payloads):
            parts = bodyscan.split_payload(payload, window)
            per_flow.append([bodyscan.BodyWindow(
                flow_id=fid, win_seq=s, data=d,
                final=(s == len(parts) - 1))
                for s, d in enumerate(parts)])
        rounds, depth = [], max(map(len, per_flow))
        for r in range(depth):
            rounds.append([w[r] for w in per_flow if len(w) > r])
        return rounds

    def stream_pass():
        scanner = bodyscan.BodyScanner(plan)
        out = {}
        for batch in make_windows():
            for v in scanner.scan_windows(batch):
                out[v.flow_id] = v
        return out

    stream_pass()  # warm the chunk kernels off the clock
    t0 = time.time()
    streamed = stream_pass()
    stream_s = time.time() - t0

    scanner = bodyscan.BodyScanner(plan)
    t0 = time.time()
    contig = {fid: scanner.scan_buffered(p)
              for fid, p in enumerate(payloads)}
    contig_s = time.time() - t0

    mismatches = 0
    for fid, payload in enumerate(payloads):
        unv, vb, _ = bodyscan.body_lanes_oracle(plan, payload)
        sv, cv = streamed.get(fid), contig[fid]
        if (sv is None or sv.degraded or cv.degraded
                or sv.unverified != unv or cv.unverified != unv
                or sv.verified_block != vb or cv.verified_block != vb):
            mismatches += 1
    child = {
        "flows": n_flows,
        "bytes_total": total_bytes,
        "window_bytes": window,
        "body_stream_mb_per_s": round(total_bytes / stream_s / 1e6, 2),
        "body_contig_mb_per_s": round(total_bytes / contig_s / 1e6, 2),
        "body_verdict_mismatches": mismatches,
    }
    if contig_s > 0 and stream_s > 0:
        child["stream_vs_contig"] = round(contig_s / stream_s, 3)
    try:
        with open("BENCH_body.json", "w") as f:
            json.dump({"metric": "body_streaming_scan",
                       **_child_backend(), **child}, f, indent=2)
    except OSError:
        pass
    if mismatches:
        raise RuntimeError(
            f"body bench: {mismatches} verdict mismatch(es) between "
            f"streamed / contiguous / oracle")
    return child


def bench_pipeline() -> dict:
    """ISSUE 9 satellite: A/B the zero-copy pipelined executor
    (PINGOO_PIPELINE=off vs on, docs/EXECUTOR.md) by driving the same
    seeded traffic stream through a live ring + RingSidecar per mode in
    a SUBPROCESS (fresh jit caches per run, on the ambient platform).
    Verdict checksums must be identical across both modes — the
    pipeline is a scheduling change, never a semantic one. Writes
    BENCH_pipeline.json and returns flattened `pipeline_*` keys for the
    result line; tools/bench_regress.py tracks on-mode throughput and
    p99."""
    child = _run_child("_pipeline_bench_child", timeout=1800)
    on = child["modes"].get("on", {})
    off = child["modes"].get("off", {})
    child["checksum_match"] = (on.get("checksum") == off.get("checksum")
                               and on.get("checksum") is not None)
    if off.get("req_per_s") and on.get("req_per_s"):
        child["speedup"] = round(on["req_per_s"] / off["req_per_s"], 3)
    try:
        with open("BENCH_pipeline.json", "w") as f:
            json.dump({"metric": "pipelined_executor_modes", **child},
                      f, indent=2)
    except OSError:
        pass
    res = {"pipeline_platform": child.get("platform"),
           "pipeline_checksum_match": child["checksum_match"],
           "pipeline_speedup": child.get("speedup")}
    for mode, row in child["modes"].items():
        for key, val in row.items():
            if key != "checksum":
                res[f"pipeline_{mode}_{key}"] = val
    # The regress-tracked aliases (direction-aware, bench_regress.py).
    res["pipeline_on_req_per_s"] = on.get("req_per_s")
    res["pipeline_on_p99_ms"] = on.get("p99_wait_ms")
    res["pipeline_overlap_ratio"] = on.get("overlap_ratio")
    return res


def _pipeline_bench_child() -> None:
    """Child body of bench_pipeline: per PINGOO_PIPELINE mode, boot a
    fresh shm ring + RingSidecar, drive the same seeded traffic with
    INTERLEAVED verdict polling (both rings are finite — a driver that
    enqueues the whole stream before polling wedges against the
    sidecar's full-verdict-ring retry loop), and emit one JSON line
    with per-mode throughput / p99 / verdict checksum plus the on-mode
    overlap telemetry."""
    import socket as _socket
    import tempfile
    import time as _time
    import zlib

    from pingoo_tpu import native_ring
    from pingoo_tpu.compiler import compile_ruleset
    from pingoo_tpu.native_ring import Ring, RingSidecar
    from pingoo_tpu.utils.crs import generate_ruleset, generate_traffic

    backend = _child_backend()
    if not native_ring.ensure_built():
        raise RuntimeError("native build failed (make -C pingoo_tpu/native)")
    n_rules = int(os.environ.get("BENCH_PIPELINE_RULES", "500"))
    # 8 full batches at the default B=2048: with only 4 the A/B delta
    # sits below the GIL/scheduler jitter floor on shared CPU hosts.
    n_reqs = int(os.environ.get("BENCH_PIPELINE_REQUESTS", "16384"))
    max_batch = int(os.environ.get("BENCH_PIPELINE_BATCH", "2048"))
    depth = int(os.environ.get("BENCH_PIPELINE_DEPTH", "3"))
    rules, lists = generate_ruleset(n_rules, with_lists=True,
                                    list_sizes=(4096, 512))
    plan = compile_ruleset(rules, lists)

    def _pack(reqs):
        packed = []
        for r in reqs:
            try:
                ip = (b"\x00" * 10 + b"\xff\xff"
                      + _socket.inet_aton(r.ip))  # v6-mapped, ABI order
            except OSError:
                ip = b"\x00" * 16
            packed.append((r.method.encode(), r.host.encode(),
                           r.path.encode(), r.url.encode(),
                           r.user_agent.encode(), ip, r.remote_port,
                           r.asn, r.country.encode()))
        return packed

    # Warm with the SAME request count as the measured drive: batch
    # shapes form from whatever backlog the sidecar sees at dequeue
    # time, so a short warm stream leaves pow2 buckets uncompiled and
    # a multi-second jit compile lands inside the measured window —
    # which is an arm-order lottery, not an executor comparison.
    warm = _pack(generate_traffic(n_reqs, lists=lists, seed=12))
    traffic = _pack(generate_traffic(n_reqs, lists=lists, seed=11))
    result: dict = {**backend, "modes": {}, "max_batch": max_batch,
                    "rules": n_rules, "requests": n_reqs, "depth": depth}

    def drive(ring, stream, record=None):
        """Enqueue `stream` with interleaved polling; -> wall seconds.
        `record` (ticket -> stream index map + per-request waits)
        collects checksum/latency inputs for the measured run."""
        t_enq: dict[int, float] = {}
        idx_of: dict[int, int] = {}
        actions: dict[int, int] = {}
        waits: list[float] = []
        done = 0
        i = 0
        t0 = _time.monotonic()
        while done < len(stream):
            # Burst-enqueue up to a batch per poll round: one request
            # per iteration drip-feeds the ring, so the sidecar's
            # dequeue pass drains it dry and every arm serves
            # artificial near-empty backlogs instead of the deep-queue
            # regime the executor batches against.
            burst = 0
            while i < len(stream) and burst < 64:
                m, h, p, u, ua, ip, port, asn, cc = stream[i]
                t = ring.enqueue(method=m, host=h, path=p, url=u,
                                 user_agent=ua, ip=ip, port=port,
                                 asn=asn, country=cc)
                if t is None:
                    break
                idx_of[t] = i
                t_enq[t] = _time.monotonic()
                i += 1
                burst += 1
            v = ring.poll_verdict()
            while v is not None:
                ticket, action, _score = v
                now = _time.monotonic()
                waits.append((now - t_enq.pop(ticket, now)) * 1e3)
                actions[idx_of.pop(ticket, -1)] = action
                done += 1
                v = ring.poll_verdict()
        elapsed = _time.monotonic() - t0
        if record is not None:
            record["waits"] = waits
            record["checksum"] = zlib.crc32(
                bytes(actions[j] for j in sorted(actions)))
        return elapsed

    for mode in ("off", "on"):
        os.environ["PINGOO_PIPELINE"] = mode
        tmp = tempfile.mkdtemp(prefix="pingoo-pipe-bench-")
        # 16384 matches the e2e/dataplane benches: several batches of
        # backlog, so the executor's depth fills from real pressure.
        ring = Ring(os.path.join(tmp, "ring"), capacity=16384,
                    create=True)
        sidecar = RingSidecar(ring, plan, lists, max_batch=max_batch,
                              pipeline_depth=depth)
        th = threading.Thread(target=sidecar.run, daemon=True)
        th.start()
        drive(ring, warm)  # compile the hot pow2 buckets off the clock
        # Best-of-2 measured drives: the stream is identical, so the
        # checksum is too, and the faster wall isolates executor
        # behavior from scheduler-jitter outliers on shared CPU.
        rec: dict = {}
        elapsed = drive(ring, traffic, record=rec)
        rec2: dict = {}
        elapsed2 = drive(ring, traffic, record=rec2)
        if elapsed2 < elapsed:
            elapsed, rec = elapsed2, rec2
        snap = sidecar.stats().get("pipeline", {})
        cost = sidecar.sched.cost.snapshot()
        sidecar.stop()
        ring.close()
        waits = sorted(rec["waits"])
        row = {
            "req_per_s": round(n_reqs / elapsed, 1),
            "p50_wait_ms": round(waits[len(waits) // 2], 3),
            "p99_wait_ms": round(
                waits[min(len(waits) - 1, int(0.99 * len(waits)))], 3),
            "checksum": rec["checksum"],
            "overlap_ratio": snap.get("overlap_ratio"),
            "overlap_events": snap.get("overlap_events"),
            "stage_occupancy": snap.get("stage_occupancy"),
        }
        if mode == "on":
            row["stage_ewma_ms"] = cost.get("stage_ewma_ms")
        result["modes"][mode] = row
    print(json.dumps(result), flush=True)


def bench_staging() -> dict:
    """ISSUE 15 satellite: A/B compact staging (PINGOO_STAGING=full vs
    compact, docs/EXECUTOR.md) by driving the same seeded traffic —
    with a long-URL tail, the regime that makes full-mode per-batch
    width bucketing balloon to the field spec — through a live ring +
    RingSidecar per mode in a SUBPROCESS. Both arms run under the
    PINGOO_STAGING_DEPTH=256 operator clamp (a no-op for `full`, which
    ignores caps); verdict checksums must be identical — compact
    staging is a transport change, never a semantic one (depth-overflow
    rows re-serve from full slot bytes). Writes BENCH_staging.json;
    tools/bench_regress.py tracks compact throughput (higher-better)
    and staged bytes/request (lower-better)."""
    child = _run_child("_staging_bench_child", timeout=1800)
    full = child["modes"].get("full", {})
    compact = child["modes"].get("compact", {})
    child["checksum_match"] = (
        full.get("checksum") == compact.get("checksum")
        and full.get("checksum") is not None)
    if full.get("staged_bytes_per_req") and compact.get(
            "staged_bytes_per_req"):
        child["bytes_reduction"] = round(
            full["staged_bytes_per_req"] / compact["staged_bytes_per_req"],
            2)
    if full.get("req_per_s") and compact.get("req_per_s"):
        child["speedup"] = round(
            compact["req_per_s"] / full["req_per_s"], 3)
    try:
        with open("BENCH_staging.json", "w") as f:
            json.dump({"metric": "compact_staging_modes", **child},
                      f, indent=2)
    except OSError:
        pass
    if not child["checksum_match"]:
        raise RuntimeError(
            f"staging checksum mismatch: full={full.get('checksum')} "
            f"compact={compact.get('checksum')}")
    res = {"staging_platform": child.get("platform"),
           "staging_checksum_match": child["checksum_match"],
           "staging_speedup": child.get("speedup"),
           "staging_bytes_reduction": child.get("bytes_reduction")}
    for mode, row in child["modes"].items():
        for key, val in row.items():
            if key != "checksum":
                res[f"staging_{mode}_{key}"] = val
    # The regress-tracked aliases (direction-aware, bench_regress.py).
    res["staging_compact_req_per_s"] = compact.get("req_per_s")
    res["staged_bytes_per_req"] = compact.get("staged_bytes_per_req")
    return res


def _staging_bench_child() -> None:
    """Child body of bench_staging: per PINGOO_STAGING mode, boot a
    fresh shm ring + RingSidecar, drive the same seeded long-URL-tail
    traffic with interleaved polling, and emit one JSON line with
    per-mode throughput / p99 / staged bytes per request / dispatch
    EWMA / verdict checksum."""
    import dataclasses
    import socket as _socket
    import tempfile
    import time as _time
    import zlib

    from pingoo_tpu import native_ring
    from pingoo_tpu.compiler import compile_ruleset
    from pingoo_tpu.native_ring import Ring, RingSidecar
    from pingoo_tpu.utils.crs import generate_ruleset, generate_traffic

    backend = _child_backend()
    if not native_ring.ensure_built():
        raise RuntimeError("native build failed (make -C pingoo_tpu/native)")
    n_rules = int(os.environ.get("BENCH_STAGING_RULES", "500"))
    n_reqs = int(os.environ.get("BENCH_STAGING_REQUESTS", "8192"))
    max_batch = int(os.environ.get("BENCH_STAGING_BATCH", "2048"))
    depth = int(os.environ.get("BENCH_STAGING_PIPE_DEPTH", "3"))
    # Both arms share the operator clamp: `full` ignores caps entirely
    # (the bit-exact oracle), `compact` caps url/path at 256 and
    # re-serves the rare deeper-dependent row from full slot bytes.
    os.environ.setdefault("PINGOO_STAGING_DEPTH", "256")
    rules, lists = generate_ruleset(n_rules, with_lists=True,
                                    list_sizes=(4096, 512))
    plan = compile_ruleset(rules, lists)

    def _tail(reqs, rng_seed):
        """Give ~0.5% of the stream near-spec-width url/path values:
        the long-tail shape (search queries, encoded payloads) under
        which full-mode content bucketing stages the whole batch at
        the 2048 field spec while compact stays at the clamped cap."""
        import random as _random
        rng = _random.Random(rng_seed)
        out = list(reqs)
        for i in range(0, len(out), 200):
            j = min(len(out) - 1, i + rng.randrange(200))
            r = out[j]
            pad = "".join(rng.choice("abcdefgh") for _ in range(1800))
            out[j] = dataclasses.replace(
                r, url=(r.path + "?q=" + pad)[:2040],
                path=(r.path + "/" + pad)[:2040])
        return out

    def _pack(reqs):
        packed = []
        for r in reqs:
            try:
                ip = (b"\x00" * 10 + b"\xff\xff"
                      + _socket.inet_aton(r.ip))  # v6-mapped, ABI order
            except OSError:
                ip = b"\x00" * 16
            packed.append((r.method.encode(), r.host.encode(),
                           r.path.encode(), r.url.encode(),
                           r.user_agent.encode(), ip, r.remote_port,
                           r.asn, r.country.encode()))
        return packed

    warm = _pack(_tail(generate_traffic(n_reqs, lists=lists, seed=22), 2))
    traffic = _pack(_tail(generate_traffic(n_reqs, lists=lists, seed=21), 1))
    result: dict = {**backend, "modes": {}, "max_batch": max_batch,
                    "rules": n_rules, "requests": n_reqs,
                    "staging_depth": os.environ["PINGOO_STAGING_DEPTH"]}

    def drive(ring, stream, record=None):
        t_enq: dict[int, float] = {}
        idx_of: dict[int, int] = {}
        actions: dict[int, int] = {}
        waits: list[float] = []
        done = 0
        i = 0
        t0 = _time.monotonic()
        while done < len(stream):
            burst = 0
            while i < len(stream) and burst < 64:
                m, h, p, u, ua, ip, port, asn, cc = stream[i]
                t = ring.enqueue(method=m, host=h, path=p, url=u,
                                 user_agent=ua, ip=ip, port=port,
                                 asn=asn, country=cc)
                if t is None:
                    break
                idx_of[t] = i
                t_enq[t] = _time.monotonic()
                i += 1
                burst += 1
            v = ring.poll_verdict()
            while v is not None:
                ticket, action, _score = v
                now = _time.monotonic()
                waits.append((now - t_enq.pop(ticket, now)) * 1e3)
                actions[idx_of.pop(ticket, -1)] = action
                done += 1
                v = ring.poll_verdict()
        elapsed = _time.monotonic() - t0
        if record is not None:
            record["waits"] = waits
            record["checksum"] = zlib.crc32(
                bytes(actions[j] & 0xFF for j in sorted(actions)))
        return elapsed

    for mode in ("full", "compact"):
        os.environ["PINGOO_STAGING"] = mode
        tmp = tempfile.mkdtemp(prefix="pingoo-staging-bench-")
        ring = Ring(os.path.join(tmp, "ring"), capacity=16384,
                    create=True)
        sidecar = RingSidecar(ring, plan, lists, max_batch=max_batch,
                              pipeline_depth=depth)
        th = threading.Thread(target=sidecar.run, daemon=True)
        th.start()
        drive(ring, warm)  # compile the hot shapes off the clock
        counter = sidecar._staged_bytes_counter[mode]
        bytes0 = float(counter._value)
        rec: dict = {}
        elapsed = drive(ring, traffic, record=rec)
        rec2: dict = {}
        elapsed2 = drive(ring, traffic, record=rec2)
        staged = float(counter._value) - bytes0
        if elapsed2 < elapsed:
            elapsed, rec = elapsed2, rec2
        cost = sidecar.sched.cost.snapshot()
        overflow_rows = sidecar.depth_overflow_rows
        sidecar.stop()
        ring.close()
        waits = sorted(rec["waits"])
        result["modes"][mode] = {
            "req_per_s": round(n_reqs / elapsed, 1),
            "p50_wait_ms": round(waits[len(waits) // 2], 3),
            "p99_wait_ms": round(
                waits[min(len(waits) - 1, int(0.99 * len(waits)))], 3),
            "checksum": rec["checksum"],
            "staged_bytes_per_req": round(staged / (2 * n_reqs), 1),
            "dispatch_ewma_ms": (cost.get("stage_ewma_ms") or {}).get(
                "dispatch"),
            "dispatch_bytes_ewma_ms": cost.get("dispatch_bytes_ewma_ms"),
            "depth_overflow_rows": overflow_rows,
        }
    print(json.dumps(result), flush=True)


def bench_e2e(plan, lists, n_requests: int = 100_000) -> dict:
    """Committed end-to-end drive: loadgen_http -> httpd -> ring ->
    sidecar (device lane verdict) -> 403 / proxy -> pong."""
    import tempfile

    from pingoo_tpu import native_ring
    from pingoo_tpu.native_ring import Ring, RingSidecar

    if not native_ring.ensure_built():
        raise RuntimeError("native build failed (make -C pingoo_tpu/native)")
    ndir = native_ring.NATIVE_DIR

    tmp = tempfile.mkdtemp(prefix="pingoo-bench-")
    ring_path = os.path.join(tmp, "ring")
    ring = Ring(ring_path, capacity=16384, create=True)
    sidecar = RingSidecar(ring, plan, lists, max_batch=1024,
                          pipeline_depth=3)
    threading.Thread(target=sidecar.run, daemon=True).start()
    pong = subprocess.Popen([os.path.join(ndir, "pong"), "0"],
                            stdout=subprocess.PIPE)
    _CHILDREN.append(pong)
    pport = json.loads(pong.stdout.readline())["listening"]
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    hport = s.getsockname()[1]
    s.close()
    httpd = subprocess.Popen(
        [os.path.join(ndir, "httpd"), str(hport), ring_path, "127.0.0.1",
         str(pport)], stdout=subprocess.PIPE)
    _CHILDREN.append(httpd)
    httpd.stdout.readline()
    time.sleep(0.3)
    try:
        lg_bin = os.path.join(ndir, "loadgen_http")
        # Warm the jitted lane program off the measurement run.
        _run_tracked([lg_bin, str(hport), "8192", "1024", "100"],
                     capture_output=True, timeout=300)
        out = _run_tracked(
            [lg_bin, str(hport), str(n_requests), "4096", "100"],
            capture_output=True, text=True, timeout=300)
        res = json.loads(out.stdout.strip())
        # The native plane's own counters explain the block/fail-open
        # split: verdicts that miss the 3 s deadline (a compile on the
        # serving path) fail open — attacks pass rather than stall — so
        # e2e_blocked alone under-reports the WAF (e2e_fail_open says
        # how many requests the timeout released).
        stats = _scrape_metrics_json(hport)
        # Per-stage sidecar latency + shm ring telemetry: the registry
        # snapshot rides the artifact so a perf run carries its own
        # stage breakdown (queue/encode/dispatch/compute/post).
        from pingoo_tpu.obs import REGISTRY

        stage_latency = REGISTRY.stage_snapshot()
        ring_tel = sidecar.ring_telemetry()
        ladder = sidecar.ladder.snapshot()
    finally:
        pong.kill()
        httpd.kill()
        sidecar.stop()
        ring.close()
    # The degradation ladder is the runtime safety net, never a quiet
    # outcome: a drive any rung of which demoted measured a fallback.
    demoted = {rung: row["last_error"] for rung, row in ladder.items()
               if row["demotions"] or row["errors"]}
    if demoted:
        raise RuntimeError(f"e2e drive degraded: {demoted}")
    p50, p99 = _hist_percentiles(stats.get("verdict_wait_ms_hist"))
    return {
        "e2e_stage_latency": stage_latency,
        "e2e_ring_telemetry": ring_tel,
        "e2e_req_per_s": res["req_per_s"],
        "e2e_added_p50_ms": res["p50_ms"],
        "e2e_added_p99_ms": res["p99_ms"],
        "serving_p50_ms_le": p50,
        "serving_p99_ms_le": p99,
        "e2e_completed": res["completed"],
        "e2e_blocked": res["blocked"],
        "e2e_fail_open": stats.get("fail_open"),
        "e2e_verdicts": stats.get("verdicts"),
        "e2e_errors": res["errors"],
    }


def _scrape_metrics_json(port: int) -> dict:
    """Scrape /__pingoo/metrics in its JSON form. The endpoint now
    content-negotiates (Prometheus text by default, ISSUE 2), so the
    legacy-schema consumer must ask for application/json explicitly."""
    try:
        import urllib.request

        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/__pingoo/metrics",
            headers={"accept": "application/json"})
        with urllib.request.urlopen(req, timeout=5) as resp:
            return json.loads(resp.read())
    except Exception:
        return {}


def _hist_percentiles(hist):
    """(p50, p99) upper bounds from the data plane's enqueue->verdict
    wall-time histogram (httpd.cc verdict_wait_ms_hist) — the serving-
    path latency the <2 ms budget is about; kernel time alone cannot
    see ring/batching/transport waits. ">100" for the unbounded bucket:
    Infinity is not valid JSON and would break the driver's parse."""
    if not hist:
        return None, None
    edges = [("le1", 1.0), ("le2", 2.0), ("le5", 5.0), ("le10", 10.0),
             ("le50", 50.0), ("le100", 100.0), ("inf", float("inf"))]
    total = sum(hist.get(k, 0) for k, _ in edges)
    if not total:
        return None, None

    def pct(q):
        need = q * total
        run = 0
        for k, edge in edges:
            run += hist.get(k, 0)
            if run >= need:
                return edge if edge != float("inf") else ">100"
        return ">100"

    return pct(0.50), pct(0.99)


def bench_dataplane(n_requests: int = 200_000) -> dict:
    """Data-plane capacity with the DEVICE OUT OF THE LOOP: loadgen_http
    -> native httpd -> shared-memory ring -> NATIVE canned-verdict drain
    (native/drain.cc: memmem content check + batched verdict post; no
    accelerator, no Python in the loop) -> 403/proxy -> pong. This
    isolates the non-chip half of the serving path: it answers whether
    the C++ plane + ring transport can carry the request rates the
    chip can verdict (VERDICT r2 item 2; r3 item 5 moved the drain
    native). Runs in the bench parent: nothing here touches JAX."""
    import tempfile

    from pingoo_tpu import native_ring
    from pingoo_tpu.native_ring import Ring

    if not native_ring.ensure_built():
        raise RuntimeError("native build failed (make -C pingoo_tpu/native)")
    ndir = native_ring.NATIVE_DIR

    # Defaults tuned for THIS 1-CPU host (nproc == 1): one worker and
    # c=128 measured fastest (~23k req/s, p99 <= 10 ms with the native
    # drain; the old Python drain measured 14.1k); more workers just
    # time-share the core. On a multi-core host raise BENCH_DP_WORKERS /
    # BENCH_DP_LOADGENS to exercise the SO_REUSEPORT + ring-per-worker
    # sharding this bench is built on.
    workers = int(os.environ.get("BENCH_DP_WORKERS", "1"))
    loadgens = int(os.environ.get("BENCH_DP_LOADGENS", "1"))
    tmp = tempfile.mkdtemp(prefix="pingoo-dpbench-")
    rings = [Ring(os.path.join(tmp, f"ring{i}"), capacity=16384, create=True)
             for i in range(workers)]
    # Native drain process: C++ memmem + batched verdict post over all
    # worker rings (one consumer: the request queue pop is destructive
    # and the scratch batch is per-process).
    drain = subprocess.Popen(
        [os.path.join(ndir, "drain")]
        + [os.path.join(tmp, f"ring{i}") for i in range(workers)],
        stdout=subprocess.PIPE)
    _CHILDREN.append(drain)
    assert b"draining" in drain.stdout.readline()
    pong = subprocess.Popen([os.path.join(ndir, "pong"), "0"],
                            stdout=subprocess.PIPE)
    _CHILDREN.append(pong)
    pport = json.loads(pong.stdout.readline())["listening"]
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    hport = s.getsockname()[1]
    s.close()
    # N workers share the port via SO_REUSEPORT (the kernel load-
    # balances accepted connections), each with its own verdict ring —
    # the per-core sharding a production deployment uses (verdicts must
    # return on the worker's own ring: the verdict queue is MPMC, so
    # co-consuming workers would steal each other's tickets).
    httpds = []
    for i in range(workers):
        h = subprocess.Popen(
            [os.path.join(ndir, "httpd"), str(hport),
             os.path.join(tmp, f"ring{i}"), "127.0.0.1", str(pport)],
            stdout=subprocess.PIPE)
        _CHILDREN.append(h)
        h.stdout.readline()
        httpds.append(h)
    time.sleep(0.2)
    try:
        lg_bin = os.path.join(ndir, "loadgen_http")
        _run_tracked([lg_bin, str(hport), "8192", "256", "100"],
                     capture_output=True, timeout=120)  # warm-up
        per_lg = n_requests // loadgens
        conc = int(os.environ.get("BENCH_DP_CONC", "128")) // loadgens
        procs = [subprocess.Popen(
            [lg_bin, str(hport), str(per_lg), str(conc), "100"],
            stdout=subprocess.PIPE, text=True) for _ in range(loadgens)]
        _CHILDREN.extend(procs)
        results = []
        for p in procs:
            out, _ = p.communicate(timeout=300)
            results.append(json.loads(out.strip()))
        dp_stats = _scrape_metrics_json(hport)
    finally:
        drain.terminate()
        try:
            drain.wait(timeout=10)
        except subprocess.TimeoutExpired:
            drain.kill()
        pong.kill()
        for h in httpds:
            h.kill()
        for ring in rings:
            ring.close()
    completed = sum(r["completed"] for r in results)
    elapsed = max(r["elapsed_s"] for r in results)
    # The metrics scrape lands on ONE SO_REUSEPORT worker; with several
    # workers its histogram covers only that worker's share, so the
    # serving percentiles are only published when they describe the
    # whole plane (workers == 1).
    dp50 = dp99 = None
    if workers == 1:
        dp50, dp99 = _hist_percentiles(
            dp_stats.get("verdict_wait_ms_hist"))
    return {
        "dataplane_req_per_s": round(completed / elapsed, 1),
        "dataplane_serving_p50_ms_le": dp50,
        "dataplane_serving_p99_ms_le": dp99,
        "dataplane_p50_ms": round(
            sum(r["p50_ms"] for r in results) / len(results), 3),
        "dataplane_p99_ms": round(max(r["p99_ms"] for r in results), 3),
        "dataplane_completed": completed,
        "dataplane_blocked": sum(r["blocked"] for r in results),
        "dataplane_errors": sum(r["errors"] for r in results),
        "dataplane_workers": workers,
        "dataplane_note": (
            "device out of the loop (canned verdicts): loadgen -> C++ "
            "httpd workers (SO_REUSEPORT, one verdict ring each) -> ring "
            "-> NATIVE drain (native/drain.cc) -> proxy/403; no Python "
            "anywhere in the loop. LIMIT ANALYSIS: this host has ONE "
            "cpu (nproc=1); loadgen + httpd + drain + upstream "
            "time-share it, so the absolute number is the single-core "
            "harness ceiling — per-core sharding (SO_REUSEPORT + one "
            "verdict ring per worker) is in place and scales with cores "
            "on real hosts"),
    }


_CHILDREN: list = []  # every child process, so the watchdog can reap them

# Exactly ONE result line ever reaches stdout, no matter which thread
# (main, watchdog) wins: the driver parses the last line, and two racing
# print() calls can interleave their write()s into an unparseable blob.
_EMIT_LOCK = threading.Lock()
_EMITTED = False


def _history_enabled() -> bool:
    return "--history" in sys.argv or os.environ.get("BENCH_HISTORY") == "1"


def _history_path() -> str:
    return os.environ.get("BENCH_HISTORY_FILE", "BENCH_history.jsonl")


_GIT_COMMIT: list = []  # one-shot cache: [] = unprobed, [str|None] = probed


def _git_commit():
    """Best-effort short commit hash for history provenance; None when
    git/tree is unavailable (history append must never fail the run)."""
    if not _GIT_COMMIT:
        commit = None
        try:
            out = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                timeout=5, cwd=REPO)
            if out.returncode == 0:
                commit = out.stdout.decode().strip() or None
        except Exception:
            commit = None
        _GIT_COMMIT.append(commit)
    return _GIT_COMMIT[0]


def _append_history(line: str) -> None:
    """Bench trajectory (ISSUE 5 satellite): append THE emitted result
    line (success or error — a failed run is trajectory too) to
    BENCH_history.jsonl with a wall-clock stamp, so
    tools/bench_regress.py can diff consecutive runs. Best-effort: a
    read-only tree must not turn a finished bench into rc=1.

    ISSUE 17 satellite: every line also carries a history schema
    version, the backend, and the git commit, so bench_regress.py can
    refuse cross-backend comparisons explicitly instead of silently
    diffing a CPU run against a TPU baseline."""
    try:
        entry = {"ts": round(time.time(), 3), **json.loads(line)}
        entry.setdefault("history_schema", 2)
        entry.setdefault("backend", entry.get("platform") or os.environ.get(
            "PINGOO_BENCH_BACKEND", "unknown"))
        commit = _git_commit()
        if commit:
            entry.setdefault("git_commit", commit)
        with open(_history_path(), "a") as f:
            f.write(json.dumps(entry) + "\n")
    except Exception:
        pass


def _emit_once(line: str) -> bool:
    global _EMITTED
    with _EMIT_LOCK:
        if _EMITTED:
            return False
        _EMITTED = True
        print(line, flush=True)
        if _history_enabled():
            _append_history(line)
        return True


def _run_tracked(argv, capture_output=False, text=None, timeout=None,
                 check=False, **kw):
    """Like subprocess.run, but the child is registered in _CHILDREN for
    the watchdog: a watchdog os._exit during an in-flight run() would
    otherwise orphan the child (probe shims, make, loadgen runs)."""
    if capture_output:
        kw["stdout"] = subprocess.PIPE
        kw["stderr"] = subprocess.PIPE
    p = subprocess.Popen(argv, text=text, **kw)
    _CHILDREN.append(p)
    try:
        out, err = p.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        p.kill()
        p.communicate()
        raise
    if check and p.returncode != 0:
        raise subprocess.CalledProcessError(p.returncode, argv, out, err)
    return subprocess.CompletedProcess(argv, p.returncode, out, err)


def _emit_error_line(result: dict, error: str) -> None:
    """The driver must ALWAYS get one parseable JSON line, even when the
    device is unreachable or the run dies mid-way: emit whatever partial
    results exist plus the error."""
    out = {
        "metric": "waf_requests_per_sec_per_chip_500rules",
        "value": 0,
        "unit": "req/s",
        "vs_baseline": 0.0,
    }
    try:
        out.update(dict(result))
        out["error"] = error[:500]
        line = json.dumps(out)
    except Exception:
        # The main thread may be mutating `result` mid-copy; a partial
        # snapshot is not worth losing the line over.
        line = json.dumps({
            "metric": "waf_requests_per_sec_per_chip_500rules",
            "value": 0, "unit": "req/s", "vs_baseline": 0.0,
            "error": error[:500],
        })
    _emit_once(line)


def main() -> int:
    # NOTHING runs outside this guard: env parsing, a child that dies —
    # any exception anywhere must still yield the one JSON line (round
    # 3's parsed=null came from an unguarded crash), with rc != 0.
    result: dict = {}
    try:
        return _main_guarded(result)
    except Exception as exc:
        _emit_error_line(result, repr(exc))
        return 1


def _failed(result: dict) -> bool:
    """An arm that raised left an `*_error` key behind (here or in the
    device child's partial line): the run is not a result."""
    return any(k == "error" or k.endswith("_error") for k in result)


def _main_guarded(result: dict) -> int:
    # Watchdog: if anything later (a wedged child, the e2e drive, ...)
    # runs past the deadline, print the partial-result error line and
    # hard-exit — the driver records a parsed line instead of a timeout.
    deadline_s = int(os.environ.get("BENCH_WATCHDOG_S", "2400"))
    done = threading.Event()

    def _watchdog():
        if not done.wait(deadline_s):
            if done.is_set() or _EMITTED:
                return  # main finished right at the deadline: not a hang
            try:
                _emit_error_line(result,
                                 f"bench watchdog fired after {deadline_s}s; "
                                 f"partial results only")
                for child in _CHILDREN:  # do not orphan native processes
                    try:
                        if child.poll() is None:
                            child.kill()
                    except Exception:
                        pass
            finally:
                os._exit(2)

    threading.Thread(target=_watchdog, daemon=True).start()
    try:
        _run_arms(result)
    except Exception as exc:
        done.set()
        _emit_error_line(result, repr(exc))
        return 1
    finally:
        done.set()
    # The emit-once gate, not print(): a watchdog that timed out a
    # microsecond before done.set() must not interleave with this line.
    _emit_once(json.dumps(result))
    return 1 if _failed(result) else 0


def _run_arms(result: dict) -> None:
    """The bench parent: no JAX here. The device child runs first (a
    backend that fails to initialise raises out of it — there is no
    CPU fallback), then the remaining arms, one child at a time."""
    result.update(_run_child(
        "_device_bench_child",
        timeout=int(os.environ.get("BENCH_WATCHDOG_S", "2400"))))
    # Scheduler-mode + serving-mesh A/B (ISSUE 6): runs when --mesh
    # dpxtpxsp (or BENCH_MESH) is given, or under BENCH_SCHED=1 for the
    # single-device scheduler comparison alone. A CPU-mesh dry-run.
    mesh_spec = _mesh_arg()
    if mesh_spec is None and os.environ.get("BENCH_SCHED") == "1":
        mesh_spec = "1x1x1"
    if mesh_spec is not None and os.environ.get("BENCH_SKIP_SCHED") != "1":
        try:
            result.update(bench_sched(mesh_spec))
        except Exception as exc:
            result["sched_error"] = repr(exc)[:200]
    # Zero-copy pipelined executor A/B (ISSUE 9): PINGOO_PIPELINE
    # off vs on over the same ring-driven traffic, identical-verdict-
    # checksum enforced.
    if ("--pipeline" in sys.argv
            or os.environ.get("BENCH_SKIP_PIPELINE") != "1"):
        try:
            result.update(bench_pipeline())
        except Exception as exc:
            result["pipeline_error"] = repr(exc)[:200]
    # Compact staging A/B (ISSUE 15): PINGOO_STAGING full vs compact
    # over the same long-URL-tail ring traffic, identical-verdict-
    # checksum asserted.
    if ("--staging" in sys.argv
            or os.environ.get("BENCH_SKIP_STAGING") != "1"):
        try:
            result.update(bench_staging())
        except Exception as exc:
            result["staging_error"] = repr(exc)[:200]
    if os.environ.get("BENCH_SKIP_DATAPLANE") != "1":
        try:
            result.update(bench_dataplane())
        except Exception as exc:
            result["dataplane_error"] = repr(exc)[:200]


def _device_bench_child() -> None:
    """The kernel loop and every arm that shares its plan (prefilter /
    DFA modes, autotune, body scan, blocklist, the served e2e drive),
    in ONE child: it is the sole owner of the chip while it runs.
    Prints one JSON line — partial, with `*_error` keys, when an arm
    raises; the parent turns those into a non-zero exit."""
    result: dict = dict(_child_backend())
    # 2048 keeps the full-batch verdict inside the 2 ms latency budget on
    # a v5e-1 while giving up only ~5% throughput vs 4096.
    batch_size = int(os.environ.get("BENCH_BATCH", "2048"))
    num_rules = int(os.environ.get("BENCH_RULES", "500"))
    iters = int(os.environ.get("BENCH_ITERS", "200"))

    import jax
    import jax.numpy as jnp

    from pingoo_tpu.compiler import compile_ruleset
    from pingoo_tpu.engine import encode_requests
    from pingoo_tpu.engine.batch import bucket_arrays
    from pingoo_tpu.engine.verdict import _eval_bool, _eval_leaves
    from pingoo_tpu.utils.crs import generate_ruleset, generate_traffic

    dev = jax.devices()[0]
    t0 = time.time()
    rules, lists = generate_ruleset(
        num_rules, with_lists=True, list_sizes=(131072, 4096))
    plan = compile_ruleset(rules, lists)
    build_s = time.time() - t0
    residency = plan.stats["device_rules"] / plan.stats["rules"]
    device_rules = [r for r in plan.rules if not r.host]

    tables = jax.device_put(plan.device_tables(), dev)
    reqs = generate_traffic(batch_size, lists=lists, seed=100)
    arrays = jax.device_put(bucket_arrays(encode_requests(reqs).arrays), dev)

    def verdict_body(tables, arrays, salt):
        B = arrays["asn"].shape[0]
        a = dict(arrays)
        # Salt EVERY input column so no per-batch work is loop-invariant:
        # XLA's while-loop code motion hoists computations whose inputs
        # don't change across iterations, and an asn-only salt (the r1/r2
        # bench) let it hoist the NFA scans — the dominant cost — out of
        # the timed loop, overstating throughput ~2x. With the byte
        # tensors and numeric columns all salted by the carried checksum,
        # every iteration re-runs the full verdict. The salt itself mixes
        # the LOOP INDEX in (see run_n): a checksum-parity-only salt can
        # stick at 0 when the match count stays even, which would make
        # the inputs invariant after all.
        a["asn"] = a["asn"] + salt
        for k in list(a):
            if k.endswith("_bytes"):
                a[k] = a[k] ^ salt.astype(jnp.uint8)
            elif k != "asn" and not k.endswith("_len") and \
                    jnp.issubdtype(a[k].dtype, jnp.integer):
                a[k] = a[k] + salt.astype(a[k].dtype)
        leaves = _eval_leaves(plan, tables, a, B)
        eff = [None] * len(plan.leaves)
        for leaf_id, (v, e) in leaves.items():
            eff[leaf_id] = v & ~e
        base = eff + [jnp.ones((B,), dtype=bool), jnp.zeros((B,), dtype=bool)]
        extra, rule_col = [], []
        from pingoo_tpu.compiler.lowering import BConst, BErrConst, BLeaf

        for rule in device_rules:
            if rule.always:
                rule_col.append(len(plan.leaves))
            elif isinstance(rule.ir, BLeaf):
                rule_col.append(rule.ir.leaf_id)
            elif isinstance(rule.ir, BConst):
                rule_col.append(len(plan.leaves) if rule.ir.value
                                else len(plan.leaves) + 1)
            elif isinstance(rule.ir, BErrConst):
                rule_col.append(len(plan.leaves) + 1)
            else:
                v, e = _eval_bool(rule.ir, leaves, B)
                rule_col.append(len(base) + len(extra))
                extra.append(v & ~e)
        allmat = jnp.stack(base + extra, axis=1)
        return jnp.take(allmat, jnp.asarray(rule_col, dtype=jnp.int32), axis=1)

    @jax.jit
    def run_n(tables, arrays, n):
        def body(i, acc):
            m = verdict_body(tables, arrays, (acc + i) % 2)
            return acc + m.sum().astype(jnp.int64)
        return jax.lax.fori_loop(0, n, body, jnp.int64(0))

    @jax.jit
    def floor_loop(arrays, n):
        def body(i, acc):
            return acc + arrays["asn"].sum() + i
        return jax.lax.fori_loop(0, n, body, jnp.int64(0))

    t0 = time.time()
    int(run_n(tables, arrays, 2))
    int(floor_loop(arrays, 2))
    compile_s = time.time() - t0

    t0 = time.time()
    int(floor_loop(arrays, iters))
    floor_a = time.time() - t0
    t0 = time.time()
    checksum = int(run_n(tables, arrays, iters))
    full = time.time() - t0
    t0 = time.time()
    int(floor_loop(arrays, iters))
    floor_b = time.time() - t0

    per_batch_s = (full - (floor_a + floor_b) / 2) / iters
    rps = batch_size / per_batch_s
    result.update({
        "metric": "waf_requests_per_sec_per_chip_500rules",
        "value": round(rps, 1),
        "unit": "req/s",
        "vs_baseline": round(rps / 1_000_000.0, 4),
        "batch_size": batch_size,
        "rules": num_rules,
        "device_rules": plan.stats["device_rules"],
        "device_residency": round(residency, 4),
        "p_batch_ms": round(per_batch_s * 1000, 3),
        "latency_budget_ms": 2.0,
        "device": str(dev),
        "checksum": checksum,
        "build_s": round(build_s, 1),
        "compile_s": round(compile_s, 1),
    })
    # Literal-prefilter cascade (ISSUE 4): per-mode throughput + Stage-A
    # candidate stats; the fastest mode becomes the plan's default and
    # rides the artifact cache like the scan-strategy autotune below.
    if os.environ.get("BENCH_SKIP_PREFILTER") != "1":
        try:
            pf_res = bench_prefilter_modes(
                plan, tables, arrays, verdict_body,
                iters=min(iters, int(os.environ.get(
                    "BENCH_PREFILTER_ITERS", "30"))))
            result["prefilter"] = pf_res
            cache_dir = os.environ.get("PINGOO_CACHE_DIR")
            if cache_dir and pf_res.get("selected"):
                from pingoo_tpu.compiler.cache import update_cached_plan

                update_cached_plan(rules, lists, plan, cache_dir)
        except Exception as exc:
            result["prefilter_error"] = repr(exc)[:200]
    # Bitsplit-DFA lowering (ISSUE 8): off/auto/force A/B over the PR 4
    # compact baseline; the fastest mode becomes the plan's default and
    # rides the artifact cache like the prefilter selection above.
    if "--dfa" in sys.argv or os.environ.get("BENCH_SKIP_DFA") != "1":
        try:
            dfa_res = bench_dfa_modes(
                plan, tables, arrays, verdict_body,
                iters=min(iters, int(os.environ.get(
                    "BENCH_DFA_ITERS", "30"))))
            result["dfa"] = dfa_res
            auto_rps = dfa_res["modes"].get("auto", {}).get("req_per_s")
            if auto_rps:
                result["dfa_auto_req_per_s"] = auto_rps
            cache_dir = os.environ.get("PINGOO_CACHE_DIR")
            if cache_dir and dfa_res.get("selected"):
                from pingoo_tpu.compiler.cache import update_cached_plan

                update_cached_plan(rules, lists, plan, cache_dir)
        except Exception as exc:
            result["dfa_error"] = repr(exc)[:200]
    # Micro-autotune: replace the plan's default cost-model strategy
    # selection with MEASURED per-iteration costs, and persist the tuned
    # plan into the artifact cache when one is configured — runs on an
    # accelerator backend by default (the CPU backend inverts the
    # relative costs; BENCH_AUTOTUNE=force measures anyway, =0 skips).
    autotune = os.environ.get("BENCH_AUTOTUNE", "auto")
    if autotune != "0" and (result["platform"] != "cpu"
                            or autotune == "force"):
        try:
            from pingoo_tpu.compiler.plan import reselect_scan_strategies

            costs = autotune_scan_strategies(plan, tables, arrays)
            if costs:
                reselect_scan_strategies(plan, costs)
                result["autotune_costs"] = {
                    k: round(v, 4) for k, v in costs.items()}
                result["autotune_selected"] = {
                    k: e.strategy.kind + ("+pair" if e.strategy.pair else "")
                    for k, e in plan.scan_plans.items()}
                cache_dir = os.environ.get("PINGOO_CACHE_DIR")
                if cache_dir:
                    from pingoo_tpu.compiler.cache import update_cached_plan

                    update_cached_plan(rules, lists, plan, cache_dir)
        except Exception as exc:
            result["autotune_error"] = repr(exc)[:200]
    # Streaming body-scan arm (ISSUE 13): interleaved multi-flow window
    # streams vs the contiguous one-shot over identical payloads, with
    # verdict equality (and the interpreter oracle) enforced.
    if "--body" in sys.argv or os.environ.get("BENCH_SKIP_BODY") != "1":
        try:
            result.update(bench_body())
        except Exception as exc:
            result["body_error"] = repr(exc)[:200]
    if os.environ.get("BENCH_SKIP_BLOCKLIST") != "1":
        try:
            result.update(bench_blocklist_1m())
        except Exception as exc:  # a failing side-bench must not kill the line
            result["blocklist_error"] = repr(exc)[:200]
    if os.environ.get("BENCH_SKIP_E2E") != "1":
        try:
            result.update(bench_e2e(plan, lists))
        except Exception as exc:
            result["e2e_error"] = repr(exc)[:200]
    # Whole-run stage-latency snapshot (ISSUE 2): whatever verdict
    # pipeline stages ran in this process (the e2e sidecar, any engine
    # warm-up) ride the artifact for offline breakdowns.
    from pingoo_tpu.obs import REGISTRY

    stages = REGISTRY.stage_snapshot()
    if stages:
        result["stage_latency"] = stages
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    sys.exit(main())
