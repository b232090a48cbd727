"""Literal-prefilter verdict cascade (ISSUE 4): soundness + parity.

The cascade's contract is structural: Stage A (compile-time factor
extraction + the packed shift-AND kernel) may only PRUNE work — the
candidate set must be a superset of the true match set for every
factor-gated pattern, and the end-to-end verdicts must be bit-identical
across PINGOO_PREFILTER=off|banks|compact and against the host
interpreter oracle. This file asserts all of that with randomized
rulesets/traffic, plus the satellite behaviors (batch dedup, metrics
schema coverage, the untouched ring ABI).
"""

import asyncio
import pickle
import random

import jax
import numpy as np
import pytest

from pingoo_tpu.compiler import compile_ruleset
from pingoo_tpu.compiler.lowering import BLeaf, nfa_leaf_patterns
from pingoo_tpu.compiler.nfa import simulate
from pingoo_tpu.compiler.repat import (Quant, compile_regex,
                                       factor_present, literal_pattern,
                                       necessary_factor)
from pingoo_tpu.config.schema import Action, RuleConfig
from pingoo_tpu.engine import (RequestTuple, encode_requests,
                               evaluate_batch, make_verdict_fn)
from pingoo_tpu.engine.batch import RequestBatch, bucket_arrays
from pingoo_tpu.expr import compile_expression
from pingoo_tpu.ops.prefilter import (bank_to_prefilter_tables,
                                      build_prefilter_bank,
                                      prefilter_scan, scan_numpy)
from pingoo_tpu.utils.crs import (LFI_RCE_CORES, SQLI_CORES, XSS_CORES,
                                  generate_ruleset, generate_traffic)

CORPUS_PATTERNS = SQLI_CORES + XSS_CORES + LFI_RCE_CORES


def _random_match(rng: random.Random, lp) -> bytes:
    """A byte string biased to match `lp`: walk the positions choosing
    class members, with random padding when unanchored."""
    out = bytearray()
    if not lp.anchor_start and rng.random() < 0.7:
        out += bytes(rng.randrange(32, 127)
                     for _ in range(rng.randrange(0, 8)))
    for pos in lp.positions:
        if pos.quant == Quant.ONE:
            reps = 1
        elif pos.quant == Quant.OPT:
            reps = rng.randrange(0, 2)
        elif pos.quant == Quant.PLUS:
            reps = rng.randrange(1, 4)
        else:
            reps = rng.randrange(0, 4)
        choices = sorted(pos.bytes)
        out += bytes(rng.choice(choices) for _ in range(reps))
    if not (lp.anchor_end or lp.anchor_end_abs) and rng.random() < 0.7:
        out += bytes(rng.randrange(32, 127)
                     for _ in range(rng.randrange(0, 8)))
    return bytes(out)


class TestFactorExtraction:
    def test_factor_is_necessary_on_corpus_patterns(self):
        """Property (randomized): whenever a pattern matches a string,
        its extracted factor appears in that string — the soundness
        theorem of the whole cascade."""
        rng = random.Random(20260804)
        matched_total = 0
        for pat in CORPUS_PATTERNS:
            try:
                alts = compile_regex(pat)
            except Exception:
                continue
            for lp in alts:
                fac = necessary_factor(lp)
                if fac is None:
                    continue
                assert 1 <= len(fac) <= 12
                for _ in range(24):
                    s = _random_match(rng, lp)
                    if simulate(lp, s):
                        matched_total += 1
                        assert factor_present(fac, s), (pat, fac, s)
        assert matched_total > 200  # the property was actually exercised

    def test_factor_respects_quantifier_structure(self):
        # Interior PLUS breaks a window: a(b+)c matches "abbc" which has
        # no consecutive "abc" — the factor must be a 2-window.
        (lp,) = compile_regex("ab+c")
        fac = necessary_factor(lp)
        assert fac is not None and len(fac) == 2
        for s in (b"abc", b"abbbbc", b"xxabcyy"):
            assert simulate(lp, s) and factor_present(fac, s)

    def test_no_factor_for_weak_or_empty_patterns(self):
        for pat in ("a*b?", "x", ".{3}", "[a-z]+"):
            for lp in compile_regex(pat):
                assert necessary_factor(lp) is None, pat

    def test_case_fold_classes_ride_the_factor(self):
        lp = literal_pattern(b"UnIoN", case_insensitive=True)
        fac = necessary_factor(lp)
        assert fac is not None
        assert factor_present(fac, b"xxunionyy")
        assert factor_present(fac, b"xxUNIONyy")
        assert not factor_present(fac, b"xxonionyy")


class TestPrefilterKernel:
    def _random_factors(self, rng, n=40):
        out = []
        for _ in range(n):
            m = rng.randrange(2, 13)
            fac = []
            for _ in range(m):
                b = rng.randrange(33, 127)
                cls = {b}
                if rng.random() < 0.3:
                    cls.add(rng.randrange(33, 127))
                fac.append(frozenset(cls))
            out.append(tuple(fac))
        # dedupe (build_prefilter_bank packs whatever it is given; the
        # plan layer dedupes, so mirror that here)
        seen, uniq = set(), []
        for f in out:
            if f not in seen:
                seen.add(f)
                uniq.append(f)
        return uniq

    def test_kernel_matches_numpy_and_naive_oracles(self):
        rng = random.Random(7)
        factors = self._random_factors(rng)
        bank = build_prefilter_bank(factors)
        tables = bank_to_prefilter_tables(bank)
        B, L = 48, 40
        data = np.zeros((B, L), dtype=np.uint8)
        lens = np.zeros(B, dtype=np.int32)
        for i in range(B):
            n = rng.randrange(0, L + 1)
            row = bytes(rng.randrange(33, 127) for _ in range(n))
            if n and rng.random() < 0.5:  # embed a factor occurrence
                fac = factors[rng.randrange(len(factors))]
                emb = bytes(rng.choice(sorted(c)) for c in fac)
                p = rng.randrange(0, max(n - len(emb), 0) + 1)
                row = row[:p] + emb + row[p + len(emb):]
                row = row[:L]
                n = len(row)
            data[i, :n] = np.frombuffer(row, dtype=np.uint8)
            lens[i] = n
        ref = scan_numpy(bank, data, lens)
        naive = np.zeros_like(ref)
        for i in range(B):
            s = bytes(data[i, :lens[i]])
            for j, fac in enumerate(factors):
                naive[i, j] = factor_present(fac, s)
        np.testing.assert_array_equal(ref, naive)
        got = np.asarray(prefilter_scan(tables, data, lens))
        np.testing.assert_array_equal(got, ref)
        got_pl = np.asarray(
            prefilter_scan(tables, data, lens, backend="pallas"))
        np.testing.assert_array_equal(got_pl, ref)

    def test_staged_width_length_patterns(self, live_lengths):
        """ISSUE 29: the scan stops at the batch's longest row; at the
        staged width of 2,048 it equals the oracle, which walks every
        column, on every edge of that bound (conftest's patterns)."""
        rng = random.Random(29)
        factors = self._random_factors(rng)
        bank = build_prefilter_bank(factors)
        tables = bank_to_prefilter_tables(bank)
        lens, stage = live_lengths
        L = 2048
        fill = np.zeros((len(lens), L), dtype=np.uint8)
        for i in range(len(lens)):
            row = bytearray()
            while len(row) < L:  # noise with a factor every few bytes
                row += bytes(rng.randrange(33, 127)
                             for _ in range(rng.randrange(0, 9)))
                fac = factors[rng.randrange(len(factors))]
                row += bytes(rng.choice(sorted(c)) for c in fac)
            fill[i] = np.frombuffer(bytes(row[:L]), dtype=np.uint8)
        data = stage(fill)
        ref = scan_numpy(bank, data, lens)
        got = np.asarray(jax.jit(prefilter_scan)(tables, data, lens))
        np.testing.assert_array_equal(got, ref)
        if lens.max() >= 16:
            assert ref.any() and not ref.all()

    def test_padding_never_arms_a_factor(self):
        # A factor containing NUL would match the zero padding were the
        # length gate wrong.
        bank = build_prefilter_bank([(frozenset([0]), frozenset([0]))])
        data = np.zeros((2, 8), dtype=np.uint8)
        lens = np.array([0, 3], dtype=np.int32)
        assert not scan_numpy(bank, data, lens)[0].any()
        assert scan_numpy(bank, data, lens)[1].all()


@pytest.fixture(scope="module")
def crs_plan():
    rules, lists = generate_ruleset(120, with_lists=True,
                                    list_sizes=(256, 64))
    plan = compile_ruleset(rules, lists)
    reqs = generate_traffic(160, lists=lists, seed=9, attack_fraction=0.3)
    batch = encode_requests(reqs)
    b2 = RequestBatch(size=batch.size, arrays=bucket_arrays(batch.arrays))
    return rules, lists, plan, b2


class TestCandidateSuperset:
    def test_candidates_cover_every_match(self, crs_plan, monkeypatch):
        """Property (1): for every factor-gated leaf, candidate set ⊇
        true match set — checked leaf-by-leaf against the device matched
        matrix of the unprefiltered path."""
        rules, lists, plan, batch = crs_plan
        monkeypatch.setenv("PINGOO_PREFILTER", "off")
        matched = evaluate_batch(plan, make_verdict_fn(plan),
                                 plan.device_tables(), batch, lists)
        checked = 0
        for rule in plan.rules:
            if not isinstance(rule.ir, BLeaf):
                continue
            leaf = plan.leaves[rule.ir.leaf_id]
            binding = plan.bindings.get(rule.ir.leaf_id)
            if binding is None or binding.kind not in ("nfa", "window"):
                continue
            alts = [lp for lp in nfa_leaf_patterns(leaf)
                    if not lp.never_match]
            facs = [necessary_factor(lp) for lp in alts]
            if not facs or any(f is None for f in facs):
                continue  # always-scan leaf: never gated
            field = binding.field
            data = batch.arrays[f"{field}_bytes"]
            lens = batch.arrays[f"{field}_len"]
            for i in range(batch.size):
                if not matched[i, rule.index]:
                    continue
                s = bytes(data[i, :int(lens[i])])
                assert any(factor_present(f, s) for f in facs), (
                    rule.name, leaf, s)
                checked += 1
        assert checked > 10  # the superset property was exercised

    def test_gating_metadata_shape(self, crs_plan):
        _, _, plan, _ = crs_plan
        pf = plan.prefilter
        assert pf is not None and pf.fields
        for key, mask in pf.bank_masks.items():
            field = pf.bank_field[key]
            assert mask.shape[0] == pf.fields[field].num_factors
            assert len(pf.slot_codes[key]) >= 1
        assert plan.stats["prefilter_gated_banks"] >= 1


class TestModeParity:
    def test_end_to_end_parity_across_modes(self, crs_plan, monkeypatch):
        """Property (2) + (3): matched bitmaps bit-identical between
        off and each on mode, and equal to the host interpreter."""
        from pingoo_tpu.engine.batch import batch_to_contexts
        from pingoo_tpu.engine.verdict import interpret_rules_row

        rules, lists, plan, batch = crs_plan
        tables = plan.device_tables()
        monkeypatch.setenv("PINGOO_PREFILTER_LEVELS", "2")
        outs = {}
        for mode in ("off", "banks", "compact"):
            monkeypatch.setenv("PINGOO_PREFILTER", mode)
            outs[mode] = evaluate_batch(plan, make_verdict_fn(plan),
                                        tables, batch, lists)
        np.testing.assert_array_equal(outs["off"], outs["banks"])
        np.testing.assert_array_equal(outs["off"], outs["compact"])
        assert outs["off"].any(), "corpus traffic must match something"
        contexts = batch_to_contexts(batch, lists)
        for i in (0, 7, 31, 63, 100, 159):
            want = interpret_rules_row(plan, contexts[i])
            np.testing.assert_array_equal(outs["off"][i], want)

    def test_parity_across_seeds_and_small_batches(self, monkeypatch):
        """Randomized (hypothesis-style) sweep: fresh rulesets + odd
        batch sizes so the compaction ladder hits its degenerate shapes
        (count == 0, count == B, B below the ladder floor)."""
        monkeypatch.setenv("PINGOO_PREFILTER_LEVELS", "3")
        for seed, nreq in ((101, 40), (2027, 33)):
            rules, lists = generate_ruleset(
                60, with_lists=True, list_sizes=(64, 16), seed=seed)
            plan = compile_ruleset(rules, lists)
            reqs = generate_traffic(nreq, lists=lists, seed=seed + 1,
                                    attack_fraction=0.5)
            # all-clean tail exercises the zero-candidate skip branch
            reqs += generate_traffic(7, lists=lists, seed=seed + 2,
                                     attack_fraction=0.0)
            batch = encode_requests(reqs)
            b2 = RequestBatch(size=batch.size,
                              arrays=bucket_arrays(batch.arrays))
            tables = plan.device_tables()
            outs = {}
            for mode in ("off", "banks", "compact"):
                monkeypatch.setenv("PINGOO_PREFILTER", mode)
                outs[mode] = evaluate_batch(
                    plan, make_verdict_fn(plan), tables, b2, lists)
            np.testing.assert_array_equal(outs["off"], outs["banks"])
            np.testing.assert_array_equal(outs["off"], outs["compact"])

    def test_prefilter_fn_feeds_verdict(self, crs_plan, monkeypatch):
        """The service path (Stage A as its own dispatch feeding
        pf_hits) must agree with the inline-traced path."""
        from pingoo_tpu.engine.verdict import make_prefilter_fn

        rules, lists, plan, batch = crs_plan
        tables = plan.device_tables()
        monkeypatch.setenv("PINGOO_PREFILTER", "banks")
        pf = make_prefilter_fn(plan)
        n_gated = len(pf.gated)
        assert n_gated >= 1
        hits, aux = pf.fn(tables, batch.arrays)
        aux = np.asarray(aux)
        assert 0 <= int(aux[1]) <= n_gated
        fn = make_verdict_fn(plan)
        got = evaluate_batch(plan, lambda t, a: fn(t, a, hits),
                             tables, batch, lists)
        monkeypatch.setenv("PINGOO_PREFILTER", "off")
        want = evaluate_batch(plan, make_verdict_fn(plan), tables,
                              batch, lists)
        np.testing.assert_array_equal(got, want)

    def test_plan_prefilter_survives_pickle(self, crs_plan, monkeypatch):
        """PrefilterPlan + pf_ tables ride the artifact cache pickle."""
        rules, lists, plan, batch = crs_plan
        clone = pickle.loads(pickle.dumps(plan))
        assert clone.prefilter is not None
        assert set(clone.prefilter.fields) == set(plan.prefilter.fields)
        monkeypatch.setenv("PINGOO_PREFILTER", "banks")
        got = evaluate_batch(clone, make_verdict_fn(clone),
                             clone.device_tables(), batch, lists)
        monkeypatch.setenv("PINGOO_PREFILTER", "off")
        want = evaluate_batch(plan, make_verdict_fn(plan),
                              plan.device_tables(), batch, lists)
        np.testing.assert_array_equal(got, want)

    def test_ungated_ruleset_degrades_to_off(self, monkeypatch):
        """A ruleset with no extractable factor must behave exactly like
        off mode (no prefilter plan at all)."""
        rules = [RuleConfig(name="r0",
                            expression=compile_expression(
                                'client.asn > 100'),
                            actions=(Action.BLOCK,))]
        plan = compile_ruleset(rules, {})
        assert plan.prefilter is None
        monkeypatch.setenv("PINGOO_PREFILTER", "compact")
        batch = encode_requests([RequestTuple(asn=200),
                                 RequestTuple(asn=5)])
        matched = evaluate_batch(plan, make_verdict_fn(plan),
                                 plan.device_tables(), batch, {})
        assert matched[:, 0].tolist() == [True, False]


class TestBatchDedup:
    def test_duplicates_evaluated_once_and_fanned_out(self):
        from pingoo_tpu.engine.service import VerdictService

        rules = [RuleConfig(
            name="env",
            expression=compile_expression(
                'http_request.path.starts_with("/.env")'),
            actions=(Action.BLOCK,))]
        plan = compile_ruleset(rules, {})
        svc = VerdictService(plan, {}, max_batch=64, max_wait_us=200_000,
                             use_device=False)

        async def go():
            await svc.start()
            reqs = [RequestTuple(path="/.env", trace_id="a"),
                    RequestTuple(path="/.env", trace_id="b"),
                    RequestTuple(path="/ok", trace_id="c"),
                    RequestTuple(path="/.env", trace_id="d")]
            verdicts = await asyncio.gather(
                *(svc.evaluate(r) for r in reqs))
            await svc.stop()
            return verdicts

        verdicts = asyncio.run(go())
        assert [v.action for v in verdicts] == [1, 1, 0, 1]
        assert [bool(v.matched[0]) for v in verdicts] == [
            True, True, False, True]
        # 4 requests, 2 distinct tuples (trace_id excluded from the key)
        assert svc.stats.dedup_hits == 2
        assert svc.stats.snapshot()["dedup_hits"] == 2


class TestObservabilitySchema:
    def test_prefilter_metrics_schemad_and_wired(self):
        import os

        from pingoo_tpu.obs import schema

        assert "prefilter" in schema.VERDICT_STAGES
        assert set(schema.PREFILTER_METRICS) <= schema.all_metric_names()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        for rel in ("pingoo_tpu/engine/service.py",
                    "pingoo_tpu/native_ring.py"):
            with open(os.path.join(repo, rel)) as f:
                src = f.read()
            for name in schema.PREFILTER_METRICS:
                assert name in src, (rel, name)

    def test_service_stats_snapshot_has_prefilter_keys(self):
        from pingoo_tpu.engine.service import ServiceStats

        snap = ServiceStats().snapshot()
        assert "prefilter_candidate_rate" in snap
        assert "scan_banks_skipped" in snap
        assert "prefilter" in snap["stages"]


class TestRingAbiUntouched:
    def test_ring_abi_matches_committed_golden(self):
        """ISSUE 4 satellite: the cascade never touches the shm ring —
        the committed ABI golden must still match the numpy mirror
        without regeneration."""
        from tools.analyze import abi

        golden = abi.load_golden()
        assert golden, "committed abi_golden.json must exist"
        py = abi.python_table()
        assert abi.diff_tables(py, golden, "python", "golden") == []
