"""Fused Pallas NFA scan kernel: differential parity + strategy plumbing.

The kernel (ops/pallas_scan.py) must be BIT-IDENTICAL to the lax.scan
path (ops/nfa_scan.scan_chunk) — which the corpus parity suite already
pins to the interpreter oracle — under every structural variation:
single/pair stepping, cross-word carry + extra opt-propagation passes,
per-row offsets and negative-t warm-up (the halo split), odd chunk
lengths, and non-tile-multiple batches. Runs under interpret=True on
this chip-less host, i.e. the exact kernel program a TPU would execute.

Also covered here: the plan-time strategy selector (compiler/plan.py),
its round-trip through the ruleset artifact cache, the footprint-
extension pass (compiler/repat.extend_footprint), and the halo
partition (PINGOO_NFA_SPLIT).
"""

import random

import numpy as np
import pytest

from pingoo_tpu.compiler import compile_ruleset
from pingoo_tpu.compiler.nfa import build_bank, pattern_footprint, simulate
from pingoo_tpu.compiler.repat import (
    compile_regex,
    extend_footprint,
    has_unbounded_rep,
)
from pingoo_tpu.config.schema import Action, RuleConfig
from pingoo_tpu.engine import (
    RequestTuple,
    batch_to_contexts,
    encode_requests,
    evaluate_batch,
    make_verdict_fn,
)
from pingoo_tpu.expr import compile_expression
from pingoo_tpu.ops.nfa_scan import (
    bank_to_tables,
    halo_split_k,
    halo_split_scan,
    nfa_scan,
)

SEEDS = (7, 1234, 999983, 31337, 2026)


def _random_field_batch(rng, L, B, alphabet):
    data = np.zeros((B, L), dtype=np.uint8)
    lens = np.zeros(B, dtype=np.int32)
    for i in range(B):
        n = rng.randint(0, L)
        data[i, :n] = np.frombuffer(
            bytes(rng.choice(alphabet) for _ in range(n)), np.uint8)
        lens[i] = n
    return data, lens


class TestFusedKernelParity:
    def test_full_corpus_banks_all_seeds(self):
        """Pallas vs lax.scan on every NFA bank of CRS-style rulesets
        across the 5 differential seeds, with REAL traffic bytes —
        multi-word carry and extra-pass banks included (asserted)."""
        from pingoo_tpu.engine.batch import bucket_arrays
        from pingoo_tpu.utils.crs import generate_ruleset, generate_traffic

        saw_carry = saw_passes = False
        for seed in SEEDS:
            rules, lists = generate_ruleset(
                60, with_lists=True, list_sizes=(128, 32), seed=seed)
            plan = compile_ruleset(rules, lists)
            reqs = generate_traffic(96, lists=lists, seed=seed + 1,
                                    attack_fraction=0.4)
            arrays = bucket_arrays(encode_requests(reqs).arrays)
            for key, tables in plan.np_tables.items():
                if not key.startswith("nfa_") or "@" in key:
                    continue
                field = key[4:]
                data = arrays[f"{field}_bytes"]
                lens = arrays[f"{field}_len"]
                saw_carry |= tables.has_carry
                saw_passes |= tables.extra_passes > 0
                want = np.asarray(nfa_scan(tables, data, lens))
                for lookup in (None, "pair"):
                    got = np.asarray(nfa_scan(tables, data, lens,
                                              lookup=lookup,
                                              backend="pallas"))
                    np.testing.assert_array_equal(
                        got, want, err_msg=f"seed {seed} {key} {lookup}")
        assert saw_carry and saw_passes

    def test_halo_split_rows_on_pallas_backend(self):
        """The within-device halo split (stacked rows, per-row NEGATIVE
        t offsets) over the fused kernel — both steppings."""
        rng = random.Random(5)
        sources = [r"abc", "x" * 40, r"<svg[^>]{0,40}onload", r"\.php$",
                   "b" * 45 + "$", r"\babc\b", "e{0,60}f", r"qq"]
        patterns = []
        for src in sources:
            patterns.extend(compile_regex(src))
        tables = bank_to_tables(build_bank(patterns))
        assert tables.halo_ok
        L = 256
        data, lens = _random_field_batch(
            rng, L, 37, b"xab<svg>onload .phpeqcf")
        for i, p in enumerate([b"p" * 40 + b"x" * 40,
                               b"w" * 211 + b"b" * 45,
                               b"z" * 60 + b"<svg " + b"a" * 30 + b"onload",
                               b"q" * 250 + b"qq"]):
            data[i, :len(p)] = np.frombuffer(p, np.uint8)
            lens[i] = len(p)
        k = halo_split_k(tables, L)
        assert k > 1
        want = np.asarray(nfa_scan(tables, data, lens))
        for lookup in (None, "pair"):
            got = np.asarray(halo_split_scan(tables, data, lens, k,
                                             lookup=lookup,
                                             backend="pallas"))
            np.testing.assert_array_equal(got, want, err_msg=str(lookup))

    def test_odd_length_and_tiny_batch(self):
        """Odd Lc exercises the synthetic pad column's structural skip;
        B below one batch tile exercises row padding."""
        patterns = []
        for src in (r"ab", r"c$", r"^d", r"e+f"):
            patterns.extend(compile_regex(src))
        tables = bank_to_tables(build_bank(patterns))
        rng = random.Random(9)
        data, lens = _random_field_batch(rng, 7, 3, b"abcdef")
        data[0, :2] = np.frombuffer(b"ab", np.uint8)
        lens[0] = 7
        want = np.asarray(nfa_scan(tables, data, lens))
        got = np.asarray(nfa_scan(tables, data, lens, lookup="pair",
                                  backend="pallas"))
        np.testing.assert_array_equal(got, want)


class TestStrategySelection:
    RULES = [
        'http_request.url.matches("(?i)union\\s+select")',
        'http_request.path.contains("passwd")',
        'http_request.path.matches("^/(admin|wp-admin)")',
        'http_request.url.matches("%3[Cc]script")',
    ]

    def _plan(self):
        rules = [RuleConfig(name=f"r{i}", expression=compile_expression(s),
                            actions=(Action.BLOCK,))
                 for i, s in enumerate(self.RULES)]
        return rules, compile_ruleset(rules, {})

    def test_default_selection_recorded(self):
        _, plan = self._plan()
        assert plan.scan_plans, "nfa banks must carry scan plans"
        for key, entry in plan.scan_plans.items():
            assert entry.strategy.kind in ("scan", "pallas")
            assert entry.strategy.source == "default"

    def test_env_override_strategies_agree(self, monkeypatch):
        """The env override (scan/pair) and a MEASURED selection of the
        fused kernel (the only route to it since ISSUE 21 — interpret
        mode here) all serve the same verdicts."""
        import copy

        from pingoo_tpu.compiler.plan import reselect_scan_strategies

        rules, plan = self._plan()
        batch = encode_requests(
            [RequestTuple(path=p, url=u)
             for p, u in [("/admin", "/?q=union  select"),
                          ("/etc/passwd", "/x"), ("/ok", "/%3Cscript")]])
        results = {}
        for mode in ("", "scan", "pair"):
            monkeypatch.setenv("PINGOO_SCAN_STRATEGY", mode)
            verdict_fn = make_verdict_fn(plan)
            results[mode] = evaluate_batch(
                plan, verdict_fn, plan.device_tables(), batch, {})
        monkeypatch.delenv("PINGOO_SCAN_STRATEGY")
        monkeypatch.setenv("PINGOO_DFA", "off")
        for mode, costs in (("pallas", {"pallas": 0.01}),
                            ("pallas_pair", {"pallas_pair": 0.01})):
            tuned = copy.deepcopy(plan)
            reselect_scan_strategies(tuned, costs)
            assert all(e.strategy.kind == "pallas"
                       and e.strategy.pair == (mode == "pallas_pair")
                       for e in tuned.scan_plans.values())
            results[mode] = evaluate_batch(
                tuned, make_verdict_fn(tuned), tuned.device_tables(),
                batch, {})
        base = results[""]
        for mode, got in results.items():
            np.testing.assert_array_equal(got, base, err_msg=mode)
        assert base[0, 0] and base[0, 2] and base[1, 1] and base[2, 3]

    def test_cache_round_trip_preserves_selection(self, tmp_path):
        """VERDICT criterion: the strategy selection is persisted in the
        ruleset artifact cache — including a measured re-selection."""
        from pingoo_tpu.compiler.cache import (
            compile_ruleset_cached,
            update_cached_plan,
        )
        from pingoo_tpu.compiler.plan import reselect_scan_strategies

        rules, _ = self._plan()
        cache_dir = str(tmp_path)
        plan1 = compile_ruleset_cached(rules, {}, cache_dir=cache_dir)
        plan2 = compile_ruleset_cached(rules, {}, cache_dir=cache_dir)
        assert plan2.scan_plans == plan1.scan_plans
        assert all(e.strategy.source == "default"
                   for e in plan2.scan_plans.values())

        # Autotune path: measured costs flip the selection; the updated
        # artifact must serve the measured choice on the next load.
        reselect_scan_strategies(
            plan1, {"scan": 1.0, "pair": 5.0, "pallas": 5.0,
                    "pallas_pair": 5.0})
        assert all(e.strategy == e.strategy.__class__(
            kind="scan", pair=False, halo_k=e.strategy.halo_k,
            source="measured", cost=1.0)
            for e in plan1.scan_plans.values())
        update_cached_plan(rules, {}, plan1, cache_dir)
        plan3 = compile_ruleset_cached(rules, {}, cache_dir=cache_dir)
        assert plan3.scan_plans == plan1.scan_plans
        assert all(e.strategy.source == "measured"
                   for e in plan3.scan_plans.values())

    def test_autotune_hook_produces_costs(self):
        """bench.autotune_scan_strategies measures every strategy kind
        on the live (CPU) backend and returns scan-relative costs."""
        import os as _os
        import sys as _sys

        _sys.path.insert(0, _os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))))
        from bench import autotune_scan_strategies

        rules, plan = self._plan()
        from pingoo_tpu.engine.batch import bucket_arrays

        reqs = [RequestTuple(path="/admin", url="/?q=union select")] * 16
        arrays = bucket_arrays(encode_requests(reqs).arrays)
        costs = autotune_scan_strategies(
            plan, plan.device_tables(), arrays, iters=2)
        assert costs.get("scan") == 1.0
        assert {"pair", "pallas", "pallas_pair"} <= set(costs)


class TestFootprintExtension:
    SOURCES = [r"ab+c", r"x[0-9]*y", r"(?i)union\s+select", r"'\s*--",
               r"a+b+c", r"\bor\b\s+1=1", r"onload\s*=", r"x+$", r"^a+b",
               r"\bword\b", r"q+"]

    def test_extension_exact_over_truncated_view(self):
        rng = random.Random(7)
        maxl = 24
        alpha = b"abcxy0union select'-=wordq19\t"
        for src in self.SOURCES:
            for lp in compile_regex(src):
                ext = extend_footprint(lp, maxl)
                assert ext is not None, src
                assert not has_unbounded_rep(ext), src
                for _ in range(150):
                    n = rng.randint(0, maxl)
                    s = bytes(rng.choice(alpha) for _ in range(n))
                    assert simulate(lp, s) == simulate(ext, s), (src, s)
                # saturating runs at the cap — the boundary the bound
                # must be exact at
                for s in (b"ab" + b"b" * 21 + b"c", b"q" * maxl,
                          b"x" + b"5" * 22 + b"y", b"'" + b" " * 21 + b"--"):
                    s = s[:maxl]
                    assert simulate(lp, s) == simulate(ext, s), (src, s)

    def test_extended_bank_is_halo_ok(self):
        pats = []
        for src in (r"ab+c", r"x[0-9]*y", r"abc"):
            for lp in compile_regex(src):
                ext = extend_footprint(lp, 24)
                assert ext is not None
                pats.append(ext)
        tables = bank_to_tables(build_bank(pats))
        assert tables.halo_ok
        # positions bounded by the 24-byte cap + guard/sticky bits
        assert tables.max_footprint <= 24 + 3
        assert all(pattern_footprint(p) <= 24 + 3 for p in pats)

    def test_split_plan_end_to_end_parity(self, monkeypatch):
        """PINGOO_NFA_SPLIT=1: url/path banks partition into a
        halo-splittable @short sub-bank + @rest residual; the recombined
        verdict stays exact against the interpreter oracle."""
        from pingoo_tpu.engine import RequestTuple
        from pingoo_tpu.engine.verdict import interpret_rules_row
        from pingoo_tpu.utils.crs import generate_ruleset, generate_traffic

        monkeypatch.setenv("PINGOO_NFA_SPLIT", "1")
        rules, lists = generate_ruleset(
            80, with_lists=True, list_sizes=(128, 32), seed=31337)
        plan = compile_ruleset(rules, lists)
        split_entries = [e for e in plan.scan_plans.values()
                         if e.split is not None]
        assert split_entries, "corpus must produce a partitioned bank"
        for entry in split_entries:
            short = plan.np_tables[entry.split[0]]
            assert short.halo_ok
            assert entry.short_strategy.halo_k > 1
        reqs = generate_traffic(64, lists=lists, seed=4, attack_fraction=0.4)
        batch = encode_requests(reqs)
        matched = evaluate_batch(plan, make_verdict_fn(plan),
                                 plan.device_tables(), batch, lists)
        for i, ctx in enumerate(batch_to_contexts(batch, lists)):
            want = interpret_rules_row(plan, ctx)
            assert np.array_equal(matched[i], want), i
