"""pingoo-prove: machine-checked lowering soundness (make prove).

One offline-safe entry point over the three ISSUE-18 pillars
(docs/STATIC_ANALYSIS.md "Prove"):

  plan proof      compiler/obligations.py discharges every lowering
                  obligation on the deterministic 500-rule CRS seed
                  plan (prefilter necessity, approximate-DFA
                  containment + exactness, staging caps, footprint
                  extension) and on the streaming body plan (table
                  reconstruction, tail cap, lazy gate, cross-window
                  carry closure). These are the SAME checks the
                  artifact cache runs at compile time (cache.py v12);
                  running them here proves the prover itself still
                  discharges on the seed corpus in bounded wall time.
  compile surface surface.py re-walks the jit entry points and
                  refreshes the committed COMPILE_SURFACE.json.
  ring protocol   ringcheck.py explores every interleaving of the ring
                  + body models up to the bound; all properties hold.

Mutation self-tests (on by default; --skip-mutations): five deliberate
regressions must each FAIL their checker, proving the gates bite —
a weakened prefilter factor, approximate DFA tables flipped to
exact=True, a narrowed staging cap, an unquantized jit argument
(lint unbounded-compile-axis), and a broken reclaim ordering
(ringcheck floor_before_post) plus the body silent-gap twin.

Offline-safe: when jax is unavailable the pass SKIPS WITH A WARNING
(exit 0) — the plan proof needs the compiler stack, and the surface /
ring pillars alone would be a green that proved the wrong thing.

`--history` appends prove_wall_s to BENCH_history.jsonl under
backend="prove-<jax backend>" so tools/bench_regress.py tracks the
proof budget like any other measured cost.
"""

from __future__ import annotations

import copy
import dataclasses
import json
import os
import time

from . import REPO_ROOT, note_skip, ringcheck, surface


def _check(ok: bool, what: str, failures: list) -> None:
    print(("  ok  " if ok else "  FAIL") + f" {what}")
    if not ok:
        failures.append(what)


def _mutation_weakened_factor(plan, ob):
    """Append a bogus 'ZZZ' necessary factor and repoint a gated slot
    at it: the necessity proof must find an accepting run that never
    completes the factor (and the mask recompute must disagree)."""
    mplan = copy.copy(plan)
    pf = copy.deepcopy(plan.prefilter)
    key = next(k for k, cs in pf.slot_codes.items()
               if any(c >= 0 for c in cs) and "@" not in k)
    field = pf.bank_field[key]
    ff = pf.fields[field]
    bogus = (frozenset({0x5A}),) * 3  # "ZZZ"
    pf.fields[field] = dataclasses.replace(
        ff, num_factors=ff.num_factors + 1, factors=ff.factors + (bogus,))
    codes = list(pf.slot_codes[key])
    codes[next(i for i, c in enumerate(codes) if c >= 0)] = ff.num_factors
    pf.slot_codes = dict(pf.slot_codes)
    pf.slot_codes[key] = tuple(codes)
    mplan.prefilter = pf
    return not ob.prove_plan(mplan).ok


def _mutation_approx_as_exact(plan, ob):
    """Flip a REAL approximate (budget-merged) DFA bank to exact=True:
    the post-fixpoint exactness pass must catch the merged subset
    masks. Returns None when the seed plan has no approximate bank
    (it does — treat that as a failure upstream, the self-test would
    be vacuous)."""
    banks, _ = ob.bank_source_patterns(plan)
    for key, entry in plan.scan_plans.items():
        if not entry.dfa_key:
            continue
        t = plan.np_tables[entry.dfa_key]
        if not bool(t.exact):
            lied = dataclasses.replace(t, exact=True)
            return bool(ob.check_dfa_containment(banks[key], lied))
    return None


def _mutation_narrowed_cap(plan, ob):
    m2 = copy.copy(plan)
    m2.staging_caps = dict(plan.staging_caps)
    f = next(f for f, c in m2.staging_caps.items() if c > 16)
    m2.staging_caps[f] = 16 if plan.staging_required[f] > 16 else 8
    return not ob.prove_plan(m2).ok


def _mutation_unquantized_arg():
    from . import lint
    src = ("class S:\n"
           "    def go(self, data, x):\n"
           "        return self._verdict_fn(data, len(x))\n")
    findings, _ = lint.lint_source(src, "pingoo_tpu/engine/service.py")
    return any(f.rule == "unbounded-compile-axis" for f in findings)


def _append_history(wall_s: float, backend: str) -> None:
    """Mirror bench.py _append_history's schema-2 stamping; the
    backend is namespaced so prove runs only compare to prove runs."""
    path = os.environ.get("BENCH_HISTORY_FILE",
                          os.path.join(REPO_ROOT, "BENCH_history.jsonl"))
    entry = {"ts": round(time.time(), 3), "history_schema": 2,
             "backend": f"prove-{backend}",
             "prove_wall_s": round(wall_s, 3)}
    try:
        with open(path, "a") as f:
            f.write(json.dumps(entry) + "\n")
    except OSError:
        pass  # read-only tree must not fail a finished prove


def run(history: bool = False, mutations: bool = True) -> int:
    t_start = time.perf_counter()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        import jax
    except Exception as exc:
        note_skip("prove", "jax unavailable")
        print(f"analyze-prove: SKIP — jax unavailable ({exc!r}); the "
              "lowering obligations need the compiler stack (tier-1 "
              "stays green; run in the jax container for the full "
              "gate)")
        return 0

    from pingoo_tpu.compiler import obligations as ob
    from pingoo_tpu.compiler.plan import compile_ruleset
    from pingoo_tpu.engine.bodyscan import compile_body_plan
    from pingoo_tpu.utils import crs

    failures: list = []

    # -- pillar 1: plan proofs on the seed corpus ----------------------
    rules, lists = crs.generate_ruleset(500)
    plan = compile_ruleset(rules, lists)
    t0 = time.perf_counter()
    proof = ob.prove_plan(plan)
    plan_s = time.perf_counter() - t0
    counts = proof.counts()
    _check(proof.ok,
           f"seed 500-rule plan: {counts.get('proved', 0)} obligations "
           f"proved in {plan_s:.2f}s "
           + (f"(failures: {[o.name for o in proof.failures()][:3]})"
              if not proof.ok else ""), failures)

    bplan = compile_body_plan()
    bproof = ob.prove_body_plan(bplan)
    _check(bproof.ok,
           f"body plan: {bproof.counts().get('proved', 0)} obligations "
           f"proved (windowed carry closure over every seam)"
           + (f" FAILURES {[o.name for o in bproof.failures()][:3]}"
              if not bproof.ok else ""), failures)

    # -- pillar 2: compile surface -------------------------------------
    try:
        surf = surface.build_surface()
        surface.write_surface(surf)
        _check(True, f"compile surface: "
                     f"{len(surf['entry_points'])} entry points all "
                     f"registered -> COMPILE_SURFACE.json", failures)
    except ValueError as exc:
        _check(False, f"compile surface: {exc}", failures)

    # -- pillar 3: ring-protocol model checker -------------------------
    _check(ringcheck.run(quiet=True) == 0,
           "ring + body protocol models: all properties hold over "
           "every interleaving", failures)

    # -- mutation self-tests: every checker must bite ------------------
    if mutations:
        _check(_mutation_weakened_factor(plan, ob),
               "mutation: weakened prefilter factor refused", failures)
        got = _mutation_approx_as_exact(plan, ob)
        _check(bool(got),
               "mutation: approximate DFA flipped exact=True refused"
               + ("" if got is not None
                  else " (NO approximate bank in seed plan — "
                       "self-test vacuous)"), failures)
        _check(_mutation_narrowed_cap(plan, ob),
               "mutation: narrowed staging cap refused", failures)
        _check(_mutation_unquantized_arg(),
               "mutation: unquantized jit argument flagged "
               "(unbounded-compile-axis)", failures)
        _check(ringcheck.run(mutate="floor_before_post",
                             quiet=True) != 0,
               "mutation: broken reclaim ordering caught by the model "
               "checker", failures)
        _check(ringcheck.run(mutate="silent_gap", quiet=True) != 0,
               "mutation: silent body-scan gap caught by the model "
               "checker", failures)

    wall_s = time.perf_counter() - t_start
    if history:
        _append_history(wall_s, jax.default_backend())
    if failures:
        print(f"analyze-prove: FAIL — {len(failures)} problem(s) in "
              f"{wall_s:.2f}s")
        return 1
    print(f"analyze-prove: OK ({wall_s:.2f}s wall; plan proof "
          f"{plan_s:.2f}s)")
    return 0


if __name__ == "__main__":
    raise SystemExit(run())
