"""Bitsplit-DFA lowering tests (ISSUE 8).

Covers the whole pipeline: subset-construction equivalence against the
bit-parallel NFA oracle (exact when no merging, superset under forced
approximate merging), the three-way kernel differential (numpy oracle /
lax.scan ladder / fused Pallas kernel in interpret mode), end-to-end
verdict bit-identity across PINGOO_DFA=off|auto|force and against the
host interpreter, the state-budget fallback, the artifact-cache
round-trip under the bumped FORMAT_VERSION, the cost-model
forward-compat fix (`_kind_cost`), the lint/metrics registrations, and
the acceptance mutation: breaking prune-only soundness in the
approximate-DFA recheck must surface in the shadow-parity auditor.
"""

import os
import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from pingoo_tpu.compiler import compile_ruleset  # noqa: E402
from pingoo_tpu.compiler.nfa import (  # noqa: E402
    MAX_SCAN_BITS,
    build_bank,
    lower_bank_to_dfa,
    scan_bits_needed,
)
from pingoo_tpu.compiler.nfa import scan_numpy as nfa_scan_numpy  # noqa: E402
from pingoo_tpu.compiler.plan import (  # noqa: E402
    DEFAULT_STEP_COSTS,
    DFA_KIND,
    ScanStrategy,
    _kind_cost,
    reselect_scan_strategies,
    select_dfa_strategy,
    select_scan_strategy,
    strategy_steps,
)
from pingoo_tpu.compiler.repat import compile_regex  # noqa: E402
from pingoo_tpu.compiler.repat import literal_pattern  # noqa: E402
from pingoo_tpu.config.schema import Action, RuleConfig  # noqa: E402
from pingoo_tpu.engine import (  # noqa: E402
    RequestTuple,
    encode_requests,
    evaluate_batch,
    make_verdict_fn,
)
from pingoo_tpu.engine.batch import RequestBatch, bucket_arrays  # noqa: E402
from pingoo_tpu.expr import compile_expression  # noqa: E402
from pingoo_tpu.ops.bitsplit_dfa import (  # noqa: E402
    _fused_dfa,
    dfa_row_candidates,
    dfa_scan,
    dfa_skip_hits,
    dfa_to_tables,
)
from pingoo_tpu.ops.bitsplit_dfa import scan_numpy as dfa_scan_numpy  # noqa: E402
from pingoo_tpu.utils.crs import (  # noqa: E402
    LFI_RCE_CORES,
    SQLI_CORES,
    XSS_CORES,
    generate_ruleset,
    generate_traffic,
)

CORPUS_PATTERNS = SQLI_CORES + XSS_CORES + LFI_RCE_CORES


def _corpus_bank_patterns(limit=28):
    """LinearPatterns from the CRS corpus that fit a scan bank — the
    same population compiler/plan feeds build_bank."""
    pats = []
    for src in CORPUS_PATTERNS:
        try:
            alts = compile_regex(src)
        except Exception:
            continue
        for lp in alts:
            if lp.never_match:
                continue
            if scan_bits_needed(lp) > MAX_SCAN_BITS:
                continue
            pats.append(lp)
            if len(pats) >= limit:
                return pats
    return pats


def _random_rows(rng, patterns, n_rows, L):
    """[n, L] data biased to exercise the banks: random noise rows plus
    rows seeded with per-position class members of random patterns."""
    data = np.zeros((n_rows, L), dtype=np.uint8)
    lens = np.zeros((n_rows,), dtype=np.int32)
    for i in range(n_rows):
        kind = rng.random()
        if kind < 0.15:
            lens[i] = 0
            continue
        row = bytearray()
        if kind < 0.45:
            row += bytes(rng.randrange(32, 127)
                         for _ in range(rng.randrange(1, L)))
        else:
            lp = rng.choice(patterns)
            if not lp.anchor_start and rng.random() < 0.5:
                row += bytes(rng.randrange(32, 127)
                             for _ in range(rng.randrange(0, 6)))
            for pos in lp.positions:
                choices = sorted(pos.bytes)
                if not choices:
                    continue
                reps = rng.randrange(0, 3)
                if pos.quant.name == "ONE":
                    reps = 1
                elif pos.quant.name == "PLUS":
                    reps = rng.randrange(1, 3)
                row += bytes(rng.choice(choices) for _ in range(reps))
            if rng.random() < 0.5:
                row += bytes(rng.randrange(32, 127)
                             for _ in range(rng.randrange(0, 6)))
        row = bytes(row)[:L]
        data[i, :len(row)] = np.frombuffer(row, dtype=np.uint8)
        lens[i] = len(row)
    return data, lens


@pytest.fixture(scope="module")
def corpus_bank():
    pats = _corpus_bank_patterns()
    assert len(pats) >= 16
    pats.append(literal_pattern(b"union select", case_insensitive=True))
    return pats, build_bank(pats)


class TestSubsetConstruction:
    def test_exact_dfa_matches_nfa_oracle(self, corpus_bank):
        """Property: with no merging and an ample budget the DFA is
        bit-identical to the bit-parallel NFA on every (row, slot)."""
        pats, bank = corpus_bank
        dfa = lower_bank_to_dfa(pats, state_budget=65536, merge_depths=())
        assert dfa is not None and dfa.exact and dfa.merge_depth == 0
        rng = random.Random(20260804)
        data, lens = _random_rows(rng, pats, 220, 48)
        ref = nfa_scan_numpy(bank, data, lens)
        got = dfa_scan_numpy(dfa, data, lens)
        np.testing.assert_array_equal(got, ref)
        assert ref.any() and not ref.all()  # both polarities exercised

    def test_approximate_dfa_is_sound_superset(self, corpus_bank):
        """Forced merging: the quotient DFA must shrink below the exact
        state count and may only OVER-approximate per slot (candidates
        ⊇ matches) — never lose a hit."""
        pats, bank = corpus_bank
        exact = lower_bank_to_dfa(pats, state_budget=65536, merge_depths=())
        assert exact is not None
        approx = lower_bank_to_dfa(pats, state_budget=exact.num_states - 1,
                                   merge_depths=(8, 4, 2, 1))
        assert approx is not None, "merge ladder should fit under budget"
        assert not approx.exact and approx.merge_depth >= 1
        assert approx.num_states < exact.num_states
        rng = random.Random(77)
        data, lens = _random_rows(rng, pats, 220, 48)
        ref = nfa_scan_numpy(bank, data, lens)
        got = dfa_scan_numpy(approx, data, lens)
        missing = ref & ~got
        assert not missing.any(), "approximate DFA dropped a true match"

    def test_budget_fallback_returns_none(self, corpus_bank):
        pats, _ = corpus_bank
        assert lower_bank_to_dfa(pats, state_budget=2,
                                 merge_depths=()) is None


class TestKernelDifferential:
    def test_three_way_differential(self, corpus_bank):
        """numpy oracle == lax.scan gather ladder == fused Pallas kernel
        (interpret mode — the same kernel program a TPU compiles)."""
        pats, _ = corpus_bank
        dfa = lower_bank_to_dfa(pats, state_budget=65536, merge_depths=())
        tables = dfa_to_tables(dfa)
        rng = random.Random(5150)
        for n_rows, L in ((97, 48), (3, 17), (128, 48)):
            data, lens = _random_rows(rng, pats, n_rows, L)
            ref = dfa_scan_numpy(dfa, data, lens)
            jd, jl = jnp.asarray(data), jnp.asarray(lens)
            got_scan = np.asarray(dfa_scan(tables, jd, jl))
            got_pallas = np.asarray(_fused_dfa(tables, jd, jl,
                                               interpret=True))
            np.testing.assert_array_equal(got_scan, ref)
            np.testing.assert_array_equal(got_pallas, ref)

    def test_staged_width_length_patterns(self, corpus_bank, live_lengths):
        """ISSUE 29: the ladder stops at the batch's longest row; at the
        staged width of 2,048 it equals the oracle, which walks every
        column, on every edge of that bound (conftest's patterns)."""
        pats, _ = corpus_bank
        dfa = lower_bank_to_dfa(pats, state_budget=65536, merge_depths=())
        tables = dfa_to_tables(dfa)
        lens, stage = live_lengths
        rng = random.Random(2929)
        L = 2048
        fill = np.zeros((len(lens), L), dtype=np.uint8)
        for i in range(len(lens)):
            row = bytearray()
            while len(row) < L:  # pattern-seeded fragments, no NULs
                frag, n = _random_rows(rng, pats, 1, 48)
                row += bytes(b or 0x2F for b in frag[0, :n[0]]) + b"/"
            fill[i] = np.frombuffer(bytes(row[:L]), dtype=np.uint8)
        data = stage(fill)
        ref = dfa_scan_numpy(dfa, data, lens)
        got = np.asarray(jax.jit(dfa_scan)(
            tables, jnp.asarray(data), jnp.asarray(lens)))
        np.testing.assert_array_equal(got, ref)
        if lens.max() >= 8:
            assert ref[lens >= 8].any() and not ref.all()

    def test_skip_hits_and_row_candidates(self, corpus_bank):
        """dfa_skip_hits is the zero-input base; dfa_row_candidates is
        exactly 'hits exceed the base' — the prune-only gate."""
        pats, _ = corpus_bank
        dfa = lower_bank_to_dfa(pats, state_budget=65536, merge_depths=())
        tables = dfa_to_tables(dfa)
        rng = random.Random(31337)
        data, lens = _random_rows(rng, pats, 64, 48)
        jd, jl = jnp.asarray(data), jnp.asarray(lens)
        hits = dfa_scan(tables, jd, jl)
        base = np.asarray(dfa_skip_hits(tables, jl))
        zero_ref = dfa_scan_numpy(dfa, np.zeros_like(data)[:, :0],
                                  np.zeros_like(lens))
        # The base equals a scan of nothing for len-0 rows...
        np.testing.assert_array_equal(base[lens == 0],
                                      zero_ref[lens == 0])
        cand = np.asarray(dfa_row_candidates(tables, hits, jl))
        np.testing.assert_array_equal(
            cand, (np.asarray(hits) & ~base).any(axis=1))


@pytest.fixture(scope="module")
def crs_plan():
    rules, lists = generate_ruleset(120, with_lists=True,
                                    list_sizes=(256, 64))
    plan = compile_ruleset(rules, lists)
    reqs = generate_traffic(160, lists=lists, seed=9, attack_fraction=0.3)
    batch = encode_requests(reqs)
    b2 = RequestBatch(size=batch.size, arrays=bucket_arrays(batch.arrays))
    return rules, lists, plan, reqs, b2


class TestVerdictParity:
    def test_crs_plan_lowers_banks(self, crs_plan):
        _, _, plan, _, _ = crs_plan
        assert plan.stats["dfa_banks"] >= 1
        lowered = [e for e in plan.scan_plans.values() if e.dfa_key]
        assert lowered
        for e in lowered:
            dtab = plan.np_tables[e.dfa_key]
            assert dtab.num_states <= 65536
            assert e.dfa_strategy is not None
            assert e.dfa_strategy.kind == DFA_KIND

    def test_off_auto_force_bit_identical(self, crs_plan, monkeypatch):
        """The acceptance property: verdict matrices bit-identical
        across every PINGOO_DFA mode, composed with every prefilter
        mode, and equal to the host interpreter."""
        from pingoo_tpu.engine.batch import batch_to_contexts
        from pingoo_tpu.engine.verdict import interpret_rules_row

        rules, lists, plan, _, batch = crs_plan
        tables = plan.device_tables()
        outs = {}
        for mode in ("off", "auto", "force"):
            monkeypatch.setenv("PINGOO_DFA", mode)
            outs[mode] = evaluate_batch(plan, make_verdict_fn(plan),
                                        tables, batch, lists)
        np.testing.assert_array_equal(outs["off"], outs["auto"])
        np.testing.assert_array_equal(outs["off"], outs["force"])
        assert outs["off"].any(), "corpus traffic must match something"
        monkeypatch.setenv("PINGOO_DFA", "force")
        for pf_mode in ("off", "banks", "compact"):
            monkeypatch.setenv("PINGOO_PREFILTER", pf_mode)
            got = evaluate_batch(plan, make_verdict_fn(plan),
                                 tables, batch, lists)
            np.testing.assert_array_equal(outs["off"], got)
        contexts = batch_to_contexts(batch, lists)
        for i in (0, 7, 31, 63, 100, 159):
            want = interpret_rules_row(plan, contexts[i])
            np.testing.assert_array_equal(outs["off"][i], want)

    def test_parity_across_seeds_and_odd_batches(self, monkeypatch):
        """Fresh rulesets + odd batch sizes so the compact recheck
        ladder hits its degenerate shapes."""
        for seed, nreq in ((101, 40), (2027, 33)):
            rules, lists = generate_ruleset(60, with_lists=True,
                                            list_sizes=(64, 16))
            reqs = generate_traffic(nreq, lists=lists, seed=seed + 1,
                                    attack_fraction=0.4)
            batch = encode_requests(reqs)
            b2 = RequestBatch(size=batch.size,
                              arrays=bucket_arrays(batch.arrays))
            plan = compile_ruleset(rules, lists)
            outs = {}
            for mode in ("off", "force"):
                monkeypatch.setenv("PINGOO_DFA", mode)
                outs[mode] = evaluate_batch(plan, make_verdict_fn(plan),
                                            plan.device_tables(), b2,
                                            lists)
            np.testing.assert_array_equal(outs["off"], outs["force"])

    def test_no_knob_reaches_the_fused_kernels(self, crs_plan,
                                               monkeypatch):
        """ISSUE 21: Mosaic refuses the fused kernels on the chip, so
        the *_KERNEL knobs that routed the served verdict through them
        are gone — with them set, the traced program holds no
        pallas_call and the default cost model never picks the Pallas
        NFA kernel. (The kernels keep their direct interpret-mode
        parity tests: TestKernelDifferential here, test_pallas_scan,
        test_prefilter.)"""
        import jax

        rules, lists, plan, _, batch = crs_plan
        assert all(e.strategy.kind != "pallas"
                   for e in plan.scan_plans.values())
        tables = plan.device_tables()
        monkeypatch.setenv("PINGOO_DFA", "force")
        monkeypatch.setenv("PINGOO_DFA_KERNEL", "pallas")
        monkeypatch.setenv("PINGOO_PREFILTER_KERNEL", "pallas")
        monkeypatch.setenv("PINGOO_PREFILTER", "banks")
        b2 = bucket_arrays(batch.arrays)
        jaxpr = jax.make_jaxpr(
            lambda t, a: make_verdict_fn(plan)(t, a))(tables, b2)
        assert "pallas_call" not in str(jaxpr)
        monkeypatch.setenv("PINGOO_SCAN_STRATEGY", "pallas")
        with pytest.raises(ValueError, match="PINGOO_SCAN_STRATEGY"):
            make_verdict_fn(plan)(tables, b2)

    def test_state_budget_fallback_keeps_nfa(self, monkeypatch):
        """PINGOO_DFA_STATES=2: nothing lowers, force mode degrades to
        the plain NFA path bit-identically."""
        monkeypatch.setenv("PINGOO_DFA_STATES", "2")
        rules, lists = generate_ruleset(60, with_lists=True,
                                        list_sizes=(64, 16))
        plan = compile_ruleset(rules, lists)
        assert plan.stats["dfa_banks"] == 0
        assert all(e.dfa_key is None for e in plan.scan_plans.values())
        reqs = generate_traffic(48, lists=lists, seed=3,
                                attack_fraction=0.4)
        batch = encode_requests(reqs)
        b2 = RequestBatch(size=batch.size,
                          arrays=bucket_arrays(batch.arrays))
        monkeypatch.setenv("PINGOO_DFA", "force")
        got = evaluate_batch(plan, make_verdict_fn(plan),
                             plan.device_tables(), b2, lists)
        monkeypatch.setenv("PINGOO_DFA", "off")
        want = evaluate_batch(plan, make_verdict_fn(plan),
                              plan.device_tables(), b2, lists)
        np.testing.assert_array_equal(want, got)


class TestWindowLowering:
    """ISSUE 8 window-bank lowering: the MXU conv banks' source
    patterns are fixed-shape literal-ish, so the subset construction
    is small and exact — and on the row-work-bound CPU backend the
    DFA gather ladder replaces the conv (engine/verdict
    ._dfa_win_active)."""

    def test_window_banks_lower_exact(self, crs_plan):
        _, _, plan, _, _ = crs_plan
        assert plan.win_dfa, "CRS plan must lower its window banks"
        for key, dkey in plan.win_dfa.items():
            assert key.startswith("win_") and dkey == f"dfa_{key}"
            dtab = plan.np_tables[dkey]
            assert dtab.exact, "window sources are literal-ish"
            assert dtab.num_slots == \
                plan.np_tables[key].kernel.shape[0]

    def test_window_dfa_matches_conv(self, crs_plan):
        """Direct bank-level differential: the lowered DFA's hit
        matrix is bit-identical to the window conv's on real encoded
        traffic, for every lowered field."""
        from pingoo_tpu.ops.window_match import window_hits

        _, _, plan, _, batch = crs_plan
        tables = plan.device_tables()
        for key, dkey in plan.win_dfa.items():
            field = key[len("win_"):]
            data = batch.arrays[f"{field}_bytes"]
            lens = batch.arrays[f"{field}_len"]
            want = np.asarray(window_hits(tables[key],
                                          jnp.asarray(data),
                                          jnp.asarray(lens)))
            got = np.asarray(dfa_scan(tables[dkey],
                                      jnp.asarray(data),
                                      jnp.asarray(lens)))
            np.testing.assert_array_equal(want, got, err_msg=key)

    def test_win_active_policy(self, crs_plan):
        from pingoo_tpu.engine.verdict import _dfa_win_active

        _, _, plan, _, _ = crs_plan
        key = next(iter(plan.win_dfa))
        assert not _dfa_win_active(plan, key, "off")
        assert _dfa_win_active(plan, key, "force")
        on_cpu = jax.default_backend() == "cpu"
        assert _dfa_win_active(plan, key, "auto") == on_cpu
        assert not _dfa_win_active(plan, "win_nope", "force")


class TestPruneOnlyMutation:
    def test_broken_recheck_gate_fails_parity_auditor(self, crs_plan,
                                                      monkeypatch):
        """ISSUE 8 acceptance mutation: if the approximate-DFA recheck
        gate prunes rows it must not (candidates forced empty — the
        prune-only soundness invariant broken), verdicts drop real
        matches and the shadow-parity auditor reports the divergence."""
        import pingoo_tpu.engine.verdict as verdict_mod
        from pingoo_tpu.obs.provenance import ParityAuditor
        from pingoo_tpu.obs.registry import MetricRegistry

        rules, lists, plan, reqs, batch = crs_plan
        approx = [e for e in plan.scan_plans.values()
                  if e.dfa_key and not plan.np_tables[e.dfa_key].exact]
        assert approx, "CRS banks must exercise the approximate path"
        monkeypatch.setenv("PINGOO_DFA", "force")

        def audit(matched):
            aud = ParityAuditor(plan, lists, plane="t_dfa",
                                registry=MetricRegistry(), sample=1.0)
            try:
                assert aud.submit_matrix(reqs, matched)
                assert aud.flush(30)
                return aud.mismatch_total.value
            finally:
                aud.stop()

        clean = evaluate_batch(plan, make_verdict_fn(plan),
                               plan.device_tables(), batch, lists)
        assert audit(clean) == 0

        monkeypatch.setattr(
            verdict_mod, "dfa_row_candidates",
            lambda tables, hits, lengths:
            jnp.zeros((hits.shape[0],), dtype=bool))
        broken = evaluate_batch(plan, make_verdict_fn(plan),
                                plan.device_tables(), batch, lists)
        assert (clean != broken).any(), \
            "the mutation must actually change verdicts"
        assert audit(broken) > 0


class TestCostModelForwardCompat:
    def test_kind_cost_unknown_kind_defaults(self):
        # The satellite fix: a closed cost dict must not KeyError on a
        # kind it predates — schema'd default, then 1.0.
        assert _kind_cost({}, "dfa") == DEFAULT_STEP_COSTS["dfa"]
        assert _kind_cost({"dfa": 0.5}, "dfa") == 0.5
        assert _kind_cost({}, "some_future_kind") == 1.0
        assert _kind_cost({"scan": 2.0}, "some_future_kind", 7.0) == 7.0

    def test_select_with_partial_cost_dict(self):
        class _T:
            halo_ok = False

        # Measured dicts from old bench artifacts carry no "dfa"/"pallas"
        # keys; selection must not raise.
        strat = select_scan_strategy(_T(), costs={"scan": 1.0})
        assert strat.kind in ("scan", "pallas")
        dstrat = select_dfa_strategy(costs={"scan": 1.0})
        assert dstrat.kind == DFA_KIND
        assert dstrat.cost == DEFAULT_STEP_COSTS["dfa"]

    def test_reselect_with_measured_costs_covers_dfa(self, crs_plan):
        import copy

        _, _, plan, _, _ = crs_plan
        clone = copy.deepcopy(plan)
        # A measured dict that predates the dfa kind entirely.
        reselect_scan_strategies(clone, {"scan": 3.0, "pair": 2.0,
                                         "pallas": 1.0,
                                         "pallas_pair": 0.9})
        for key, e in clone.scan_plans.items():
            if e.dfa_key:
                assert e.dfa_strategy is not None
                assert e.dfa_strategy.kind == DFA_KIND
                # Default dfa cost (0.15) still beats the measured best
                # (0.45/iter for pallas_pair), so auto stays on.
                assert e.dfa_auto

    def test_strategy_steps_dfa_is_plain_length(self, crs_plan):
        _, _, plan, _, _ = crs_plan
        for key, e in plan.scan_plans.items():
            if e.split is not None:
                continue
            tab = plan.np_tables[key]
            assert strategy_steps(tab, 64,
                                  ScanStrategy(kind=DFA_KIND)) == 64
            # NFA kinds keep their pass multiplier; the DFA does not.
            assert strategy_steps(tab, 64, ScanStrategy()) \
                == 64 * (1 + tab.extra_passes)


class TestCacheRoundTrip:
    def test_format_version_bumped(self):
        from pingoo_tpu.compiler.cache import FORMAT_VERSION

        # 13: default strategy selection dropped the unmeasured Pallas
        # kinds (ISSUE 21); 12 added the plan_proof block (ISSUE 18).
        assert FORMAT_VERSION == 13

    def test_dfa_tables_survive_cache(self, tmp_path, monkeypatch):
        from pingoo_tpu.compiler.cache import compile_ruleset_cached

        rules, lists = generate_ruleset(60, with_lists=True,
                                        list_sizes=(64, 16))
        cache = str(tmp_path / "cache")
        plan1 = compile_ruleset_cached(rules, lists, cache_dir=cache)
        plan2 = compile_ruleset_cached(rules, lists, cache_dir=cache)
        for key, e1 in plan1.scan_plans.items():
            e2 = plan2.scan_plans[key]
            assert e1.dfa_key == e2.dfa_key
            assert e1.dfa_auto == e2.dfa_auto
            if e1.dfa_key:
                t1 = plan1.np_tables[e1.dfa_key]
                t2 = plan2.np_tables[e2.dfa_key]
                assert t1.num_states == t2.num_states
                assert t1.exact == t2.exact
                np.testing.assert_array_equal(np.asarray(t1.trans_flat),
                                              np.asarray(t2.trans_flat))
        assert plan1.dfa_default_mode == plan2.dfa_default_mode
        reqs = generate_traffic(32, lists=lists, seed=9,
                                attack_fraction=0.4)
        batch = encode_requests(reqs)
        b2 = RequestBatch(size=batch.size,
                          arrays=bucket_arrays(batch.arrays))
        monkeypatch.setenv("PINGOO_DFA", "force")
        m1 = evaluate_batch(plan1, make_verdict_fn(plan1),
                            plan1.device_tables(), b2, lists)
        m2 = evaluate_batch(plan2, make_verdict_fn(plan2),
                            plan2.device_tables(), b2, lists)
        np.testing.assert_array_equal(m1, m2)

    def test_dfa_knobs_enter_fingerprint(self, monkeypatch):
        from pingoo_tpu.compiler.cache import ruleset_fingerprint

        rules = [RuleConfig(name="r0",
                            expression=compile_expression(
                                'http_request.path.contains("/etc")'),
                            actions=(Action.BLOCK,))]
        base = ruleset_fingerprint(rules, {})
        monkeypatch.setenv("PINGOO_DFA_STATES", "99")
        assert ruleset_fingerprint(rules, {}) != base
        monkeypatch.delenv("PINGOO_DFA_STATES")
        monkeypatch.setenv("PINGOO_DFA_LOWER", "0")
        assert ruleset_fingerprint(rules, {}) != base


class TestRegistrations:
    def test_lint_registries_cover_dfa(self):
        from tools.analyze import lint_config

        assert ("pingoo_tpu/ops/bitsplit_dfa.py::dfa_scan"
                in lint_config.TRACED_FUNCTIONS)
        assert ("pingoo_tpu/ops/bitsplit_dfa.py::_fused_dfa"
                in lint_config.TRACED_FUNCTIONS)
        assert ("pingoo_tpu/engine/service.py::"
                "VerdictService._observe_dfa"
                in lint_config.HOT_FUNCTIONS)

    def test_dfa_metrics_schemad_and_wired(self):
        from pingoo_tpu.obs import schema

        assert set(schema.DFA_METRICS) <= schema.all_metric_names()
        assert "pingoo_dfa_banks_total" in schema.DFA_METRICS
        assert "pingoo_dfa_recheck_total" in schema.DFA_METRICS
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        for rel in ("pingoo_tpu/engine/service.py",
                    "pingoo_tpu/native_ring.py",
                    "docs/OBSERVABILITY.md"):
            with open(os.path.join(repo, rel)) as f:
                src = f.read()
            for name in schema.DFA_METRICS:
                assert name in src, (rel, name)

    def test_service_stats_snapshot_has_dfa_keys(self):
        from pingoo_tpu.engine.service import ServiceStats

        snap = ServiceStats().snapshot()
        assert "dfa_banks" in snap
        assert "dfa_rechecks" in snap

    def test_dispatch_counts_host_static(self, crs_plan, monkeypatch):
        from pingoo_tpu.engine.verdict import dfa_dispatch_counts

        _, _, plan, _, _ = crs_plan
        monkeypatch.setenv("PINGOO_DFA", "off")
        assert dfa_dispatch_counts(plan) == ("off", 0, 0)
        monkeypatch.setenv("PINGOO_DFA", "force")
        mode, banks, rechecks = dfa_dispatch_counts(plan)
        assert mode == "force"
        assert banks == plan.stats["dfa_banks"]
        assert 0 <= rechecks <= banks
        # A pinned NFA strategy override disables auto for the NFA
        # banks (but not force, and not the window-bank DFAs — those
        # are independent of the NFA strategy pin and stay live under
        # auto on the CPU backend).
        monkeypatch.setenv("PINGOO_DFA", "auto")
        monkeypatch.setenv("PINGOO_SCAN_STRATEGY", "pair")
        import jax

        expect_win = (len(getattr(plan, "win_dfa", {}))
                      if jax.default_backend() == "cpu" else 0)
        assert dfa_dispatch_counts(plan)[1] == expect_win
