"""Verdict provenance layer (ISSUE 5): per-rule attribution lanes, the
shadow-parity auditor, and the cross-plane flight recorder.

Acceptance properties pinned here:
  * per-rule hit counters agree with the host interpreter's per-rule
    trace on a randomized CRS-style ruleset (python fold AND the
    on-device lane-plane fold, batch padding masked);
  * a deliberate interpreter divergence (monkeypatched oracle) is
    reported by the auditor AND flight-recorded with provenance detail;
  * flight-recorder wrap-around keeps exactly the last N records, and
    the SIGTERM drain dump writes/returns the full payload;
  * /__pingoo/explain output matches the interpreter's rule trace;
  * a bare host sync inserted into the attribution fold / parity
    submit path fails the analyze lint (mutation proof);
  * bench trajectory: bench_regress flags a regression between the two
    latest comparable history entries and ignores incomparable ones.
"""

import asyncio
import json
import os
import queue

import numpy as np
import pytest

from pingoo_tpu.compiler import compile_ruleset
from pingoo_tpu.config.schema import Action, RuleConfig
from pingoo_tpu.engine import RequestTuple, encode_requests, evaluate_batch, \
    make_verdict_fn
from pingoo_tpu.engine.batch import (RequestBatch, bucket_arrays, pad_batch,
                                     tuple_to_context)
from pingoo_tpu.engine.service import VerdictService
from pingoo_tpu.engine.verdict import (interpret_rules_row, lane_rows,
                                       make_lane_fn, make_prefilter_fn,
                                       rule_hit_counts)
from pingoo_tpu.expr import compile_expression
from pingoo_tpu.obs import schema
from pingoo_tpu.obs.flightrecorder import (FlightRecorder, dump_all,
                                           dump_on_drain,
                                           register_recorder,
                                           tuple_digest,
                                           unregister_recorder)
from pingoo_tpu.obs.provenance import (ParityAuditor, RuleAttribution,
                                       OVERFLOW_LABEL)
from pingoo_tpu.obs.registry import MetricRegistry, lint_prometheus_text
from pingoo_tpu.utils.crs import generate_ruleset, generate_traffic


def _basic_rules():
    return [
        RuleConfig(name="waf", actions=(Action.BLOCK,),
                   expression=compile_expression(
                       'http_request.path.starts_with("/.env")')),
        RuleConfig(name="sqli", actions=(Action.BLOCK,),
                   expression=compile_expression(
                       'http_request.url.contains("union select")')),
    ]


@pytest.fixture(scope="module")
def crs_setup():
    rules, lists = generate_ruleset(80, with_lists=True,
                                    list_sizes=(128, 32))
    plan = compile_ruleset(rules, lists)
    reqs = generate_traffic(96, lists=lists, seed=5, attack_fraction=0.4)
    return rules, lists, plan, reqs


# -- schema ------------------------------------------------------------------


class TestSchema:
    def test_provenance_stage_and_metric_inventory(self):
        assert "provenance" in schema.VERDICT_STAGES
        names = schema.all_metric_names()
        for family in (schema.PROVENANCE_METRICS, schema.PARITY_METRICS):
            for name in family:
                assert name in names, name

    def test_server_drain_wires_flight_dump(self):
        # Source-text check (importing host.server needs 'cryptography',
        # absent on this image): the SIGTERM drain path must call the
        # flight-recorder auto-dump.
        path = os.path.join(os.path.dirname(__file__), "..",
                            "pingoo_tpu", "host", "server.py")
        with open(path) as f:
            src = f.read()
        assert "dump_on_drain" in src
        finally_block = src.split("finally:")[-1]
        assert 'dump_on_drain("sigterm")' in finally_block


# -- attribution -------------------------------------------------------------


class TestRuleAttribution:
    def test_topk_bounded_exposition_and_monotone_overflow(self):
        from pingoo_tpu.obs.provenance import RULE_SERIES_CAP

        reg = MetricRegistry()
        names = tuple(f"rule_{i:03d}" for i in range(100))
        attr = RuleAttribution(names, plane="t", registry=reg, top_k=5)
        rng = np.random.default_rng(3)
        # Stable distribution: exactly the top-K + "_overflow" export.
        stable = np.arange(100)[::-1]
        attr.fold_batch(stable)
        text = reg.prometheus_text()
        series = [ln for ln in text.splitlines()
                  if ln.startswith("pingoo_rule_hits_total{")]
        assert len(series) == 5 + 1
        prev: dict = {}
        for _ in range(6):
            # Churny distributions promote new entrants, but the total
            # labelled cardinality stays hard-bounded and every series
            # (overflow included) stays a monotone counter.
            counts = rng.integers(0, 50, size=100)
            attr.fold_batch(counts)
            text = reg.prometheus_text()
            assert lint_prometheus_text(text) == []
            series = [ln for ln in text.splitlines()
                      if ln.startswith("pingoo_rule_hits_total{")]
            assert 1 <= len(series) <= RULE_SERIES_CAP + 1
            vals = {}
            for ln in series:
                label, val = ln.rsplit(" ", 1)
                vals[label] = int(val)
                assert int(val) >= prev.get(label, 0), ln
            # conservation: labelled + overflow == total hits
            assert sum(vals.values()) == attr.total_hits
            prev = vals
        snap = attr.snapshot()
        assert snap["total"] == attr.total_hits
        assert len(snap["top"]) <= 5

    def test_fold_with_device_column_indices(self):
        reg = MetricRegistry()
        attr = RuleAttribution(("a", "b", "c"), plane="t", registry=reg)
        attr.fold_batch(np.array([7, 9]), indices=np.array([2, 0]))
        assert attr._counts.tolist() == [9, 0, 7]


class TestAttributionParityProperty:
    def test_hit_counters_agree_with_interpreter_trace(self, crs_setup):
        """ISSUE 5 acceptance: per-rule hit counters == the host
        interpreter's per-rule trace, randomized CRS ruleset."""
        rules, lists, plan, reqs = crs_setup
        batch = encode_requests(reqs)
        b2 = RequestBatch(size=batch.size,
                          arrays=bucket_arrays(batch.arrays))
        matched = evaluate_batch(plan, make_verdict_fn(plan),
                                 plan.device_tables(), b2, lists)
        want = np.stack([
            interpret_rules_row(plan, tuple_to_context(r, lists))
            for r in reqs])
        reg = MetricRegistry()
        attr = RuleAttribution(plan.rule_names, plane="t", registry=reg)
        attr.fold_batch(matched.sum(axis=0))
        np.testing.assert_array_equal(attr._counts, want.sum(axis=0))

    def test_on_device_lane_fold_masks_padding(self, crs_setup):
        """The sidecar's aux lane (folded ON DEVICE over a padded
        batch) must agree with the matrix fold over the REAL rows for
        every device-resident column."""
        rules, lists, plan, reqs = crs_setup
        n = len(reqs)
        batch = encode_requests(reqs)
        b2 = RequestBatch(size=batch.size,
                          arrays=bucket_arrays(batch.arrays))
        padded = pad_batch(b2, 128)
        tables = plan.device_tables()
        full = np.asarray(make_lane_fn(plan, with_rule_hits=True)(
            tables, padded.arrays, None, np.int32(n)))
        hits = rule_hit_counts(full, lane_rows(plan, 0, True))
        matched = evaluate_batch(plan, make_verdict_fn(plan), tables,
                                 b2, lists)
        dev_cols = plan.device_rule_indices
        np.testing.assert_array_equal(
            hits, matched[:, dev_cols].sum(axis=0))

    def test_prefilter_aux_per_bank_lanes(self, crs_setup):
        """Stage-A aux layout: the per-bank lanes sum to the aggregate
        lanes (banks-skipped attribution, obs/provenance)."""
        rules, lists, plan, reqs = crs_setup
        pf = make_prefilter_fn(plan)
        if pf is None:
            pytest.skip("ruleset extracted no factors")
        batch = encode_requests(reqs)
        arrays = bucket_arrays(batch.arrays)
        _, aux = pf.fn(plan.device_tables(), arrays)
        aux = np.asarray(aux)
        m = len(pf.masked)
        assert len(aux) == 2 + 2 * m
        assert int(aux[0]) == int(aux[2:2 + m].sum())
        never_only = len(pf.gated) - m
        assert int(aux[1]) == never_only + int(aux[2 + m:].sum())


# -- flight recorder ---------------------------------------------------------


class TestFlightRecorder:
    def test_wraparound_keeps_last_n(self):
        reg = MetricRegistry()
        rec = FlightRecorder("t", capacity=8, registry=reg)
        for i in range(20):
            rec.record(trace_id=f"t{i}", digest="d", stages={},
                       matched_rules=(), action=0)
        assert len(rec) == 8
        assert rec.recorded_total == 20
        ids = [e["trace_id"] for e in rec.snapshot()]
        assert ids == [f"t{i}" for i in range(12, 20)]  # oldest->newest
        assert reg.counter("pingoo_flightrecorder_records_total",
                           labels={"plane": "t"}).value == 20

    def test_mark_parity_and_rule_names(self):
        rec = FlightRecorder("t", capacity=4, registry=MetricRegistry(),
                             rule_names=("waf", "sqli"))
        rec.record(trace_id="x", digest="d", stages={"wait_ms": 1.0},
                   matched_rules=(1,), action=1)
        assert rec.mark_parity("x", "mismatch", {"rules": ["sqli"]})
        assert not rec.mark_parity("nope", "ok")
        (entry,) = rec.snapshot()
        assert entry["parity"] == "mismatch"
        assert entry["parity_detail"] == {"rules": ["sqli"]}
        assert entry["matched_rule_names"] == ["sqli"]

    def test_digest_stable_and_hex(self):
        a = tuple_digest("GET", "h", "/p", "/p?q", "ua", "1.2.3.4")
        b = tuple_digest("GET", "h", "/p", "/p?q", "ua", "1.2.3.4")
        c = tuple_digest("GET", "h", "/p2", "/p2", "ua", "1.2.3.4")
        assert a == b != c
        int(a, 16)

    def test_drain_dump_writes_file(self, tmp_path, monkeypatch):
        rec = FlightRecorder("t_drain", capacity=4,
                             registry=MetricRegistry())
        register_recorder(rec)
        try:
            rec.record(trace_id="x", digest="d", stages={},
                       matched_rules=(), action=0)
            monkeypatch.setenv("PINGOO_FLIGHT_DUMP_DIR", str(tmp_path))
            path = dump_on_drain("test")
            assert path is not None and os.path.exists(path)
            with open(path) as f:
                payload = json.load(f)
            assert payload["reason"] == "test"
            assert len(payload["planes"]["t_drain"]["entries"]) == 1
            assert "t_drain" in dump_all()["planes"]
        finally:
            unregister_recorder(rec)


# -- parity auditor ----------------------------------------------------------


def _auditor(plan, lists, recorder=None, sample=1.0, **kw):
    return ParityAuditor(plan, lists, plane="t_parity",
                         recorder=recorder, registry=MetricRegistry(),
                         sample=sample, **kw)


class TestParityAuditor:
    def test_clean_traffic_audits_without_mismatch(self):
        rules = _basic_rules()
        plan = compile_ruleset(rules, {})
        reqs = [RequestTuple(path="/.env", url="/.env", user_agent="x"),
                RequestTuple(path="/ok", url="/ok", user_agent="x")]
        batch = encode_requests(reqs)
        b2 = RequestBatch(size=batch.size,
                          arrays=bucket_arrays(batch.arrays))
        matched = evaluate_batch(plan, make_verdict_fn(plan),
                                 plan.device_tables(), b2, {})
        aud = _auditor(plan, {})
        assert aud.submit_matrix(reqs, matched)
        assert aud.flush(20)
        assert aud.checked_total.value == 2
        assert aud.mismatch_total.value == 0
        aud.stop()

    def test_sampling_fraction_of_batches(self):
        plan = compile_ruleset(_basic_rules(), {})
        aud = _auditor(plan, {}, sample=0.25)
        decisions = [aud._sampled() for _ in range(100)]
        assert sum(decisions) == 25
        aud.stop()

    def test_monkeypatched_interpreter_divergence_reported(
            self, monkeypatch):
        """ISSUE 5 acceptance: a deliberate oracle divergence shows up
        in the mismatch counters, the per-rule breakdown, AND the
        flight record's parity status + detail."""
        import pingoo_tpu.engine.verdict as verdict_mod

        plan = compile_ruleset(_basic_rules(), {})
        reqs = [RequestTuple(path="/ok", url="/ok", user_agent="x",
                             trace_id="trace-mm")]
        batch = encode_requests(reqs)
        b2 = RequestBatch(size=batch.size,
                          arrays=bucket_arrays(batch.arrays))
        matched = evaluate_batch(plan, make_verdict_fn(plan),
                                 plan.device_tables(), b2, {})
        real = verdict_mod.interpret_rules_row

        def broken(plan_, ctx):
            row = real(plan_, ctx)
            row[0] = not row[0]  # the injected engine bug
            return row

        monkeypatch.setattr(verdict_mod, "interpret_rules_row", broken)
        rec = FlightRecorder("t_parity", capacity=8,
                             registry=MetricRegistry(),
                             rule_names=plan.rule_names)
        rec.record(trace_id="trace-mm", digest="d", stages={},
                   matched_rules=(), action=0)
        aud = _auditor(plan, {}, recorder=rec)
        assert aud.submit_matrix(reqs, matched)
        assert aud.flush(20)
        assert aud.checked_total.value == 1
        assert aud.mismatch_total.value == 1
        assert aud._rule_series.get("waf") is not None
        assert aud._rule_series["waf"].value == 1
        (entry,) = rec.snapshot()
        assert entry["parity"] == "mismatch"
        assert entry["parity_detail"]["rules"] == ["waf"]
        assert entry["parity_detail"]["interpreter"] == [True]
        assert entry["parity_detail"]["device"] == [False]
        aud.stop()

    def test_fault_inject_knob_is_oracle_only(self, monkeypatch):
        monkeypatch.setenv("PINGOO_PARITY_FAULT_INJECT", "/faulty")
        plan = compile_ruleset(_basic_rules(), {})
        reqs = [RequestTuple(path="/faulty", url="/faulty",
                             user_agent="x")]
        batch = encode_requests(reqs)
        b2 = RequestBatch(size=batch.size,
                          arrays=bucket_arrays(batch.arrays))
        matched = evaluate_batch(plan, make_verdict_fn(plan),
                                 plan.device_tables(), b2, {})
        assert not matched[0, 0]  # the SERVED verdict is untouched
        aud = _auditor(plan, {})
        aud.submit_matrix(reqs, matched)
        assert aud.flush(20)
        assert aud.mismatch_total.value == 1
        aud.stop()

    def test_full_queue_drops_and_counts(self):
        plan = compile_ruleset(_basic_rules(), {})
        aud = _auditor(plan, {}, queue_max=1)
        aud._ensure_worker = lambda: None  # keep the queue full
        assert aud.submit_matrix((), np.zeros((0, 2), dtype=bool))
        assert not aud.submit_matrix((), np.zeros((0, 2), dtype=bool))
        assert aud.dropped_total.value == 1
        aud.stop()

    def test_lane_audit_skips_masked_rows(self):
        plan = compile_ruleset(_basic_rules(), {})
        reqs = [RequestTuple(path="/.env", url="/.env", user_agent="x"),
                RequestTuple(path="/ok", url="/ok", user_agent="x")]

        def builder():
            contexts = [tuple_to_context(r, {}) for r in reqs]
            return contexts, [r.path for r in reqs]

        aud = _auditor(plan, {})
        # Served lanes deliberately WRONG for row 0 — but row 0 is
        # skip-masked (a truncated/spilled slot), so no mismatch.
        aud.submit_lanes(builder, np.array([0, 0]),
                         np.array([False, False]),
                         skip_mask=np.array([True, False]))
        assert aud.flush(20)
        assert aud.checked_total.value == 1
        assert aud.mismatch_total.value == 0
        aud.stop()


# -- service integration (python plane) --------------------------------------


class TestServiceProvenance:
    @pytest.fixture()
    def svc(self, loop_runner, monkeypatch):
        monkeypatch.setenv("PINGOO_PARITY_SAMPLE", "1")
        plan = compile_ruleset(_basic_rules(), {})
        service = VerdictService(plan, {}, use_device=True)
        loop_runner.run(service.start())
        yield service
        loop_runner.run(service.stop())

    def test_live_requests_attributed_and_recorded(self, svc,
                                                   loop_runner):
        before = svc.flight_recorder.recorded_total
        checked0 = svc.parity.checked_total.value
        v = loop_runner.run(svc.evaluate(RequestTuple(
            path="/.env", url="/.env", user_agent="x",
            trace_id="t-live-1")))
        assert v.action == 1
        assert svc.flight_recorder.recorded_total == before + 1
        entry = next(e for e in svc.flight_recorder.snapshot()
                     if e["trace_id"] == "t-live-1")
        assert entry["matched_rule_names"] == ["waf"]
        assert entry["action"] == 1
        assert "wait_ms" in entry["stages_ms"]
        assert svc._attribution._counts[0] >= 1
        assert svc.parity.flush(30)
        assert svc.parity.checked_total.value > checked0

    def test_explain_matches_interpreter_trace(self, svc, loop_runner):
        """ISSUE 5 acceptance: explain output validated against the
        interpreter's rule trace."""
        tup = RequestTuple(path="/.env", url="/.env?union select",
                           user_agent="x", trace_id="t-explain")
        out = loop_runner.run(svc.explain(tup))
        want = interpret_rules_row(svc.plan, tuple_to_context(tup, {}))
        assert out["action"] == 1
        assert out["parity"]["consistent"] is True
        for rule_row in out["rules"]:
            assert rule_row["interpreter"] == bool(
                want[rule_row["index"]])
            assert rule_row["device"] == bool(want[rule_row["index"]])
        assert out["matched_rules"] == ["waf", "sqli"]
        assert out["stages_ms"] is not None
        assert out["digest"] == tuple_digest(
            tup.method, tup.host, tup.path, tup.url, tup.user_agent,
            tup.ip)

    def test_injected_divergence_via_service(self, svc, loop_runner,
                                             monkeypatch):
        import pingoo_tpu.engine.verdict as verdict_mod

        real = verdict_mod.interpret_rules_row

        def broken(plan_, ctx):
            row = real(plan_, ctx)
            row[1] = not row[1]
            return row

        mm0 = svc.parity.mismatch_total.value
        monkeypatch.setattr(verdict_mod, "interpret_rules_row", broken)
        loop_runner.run(svc.evaluate(RequestTuple(
            path="/x", url="/x", user_agent="x", trace_id="t-div")))
        assert svc.parity.flush(30)
        assert svc.parity.mismatch_total.value > mm0
        entry = next(e for e in svc.flight_recorder.snapshot()
                     if e["trace_id"] == "t-div")
        assert entry["parity"] == "mismatch"
        assert "sqli" in entry["parity_detail"]["rules"]

    def test_provenance_stage_observed(self, svc, loop_runner):
        loop_runner.run(svc.evaluate(RequestTuple(
            path="/s", url="/s", user_agent="x")))
        snap = svc.stats.snapshot()
        assert snap["stages"]["provenance"]["count"] >= 1

    def test_provenance_disable_knob(self, loop_runner, monkeypatch):
        monkeypatch.setenv("PINGOO_PROVENANCE", "0")
        plan = compile_ruleset(_basic_rules(), {})
        service = VerdictService(plan, {}, use_device=True)
        assert service.flight_recorder is None
        assert service._attribution is None
        assert service.parity is None
        loop_runner.run(service.start())
        v = loop_runner.run(service.evaluate(RequestTuple(
            path="/.env", url="/.env", user_agent="x")))
        assert v.action == 1  # verdicts unaffected
        loop_runner.run(service.stop())


# -- sidecar integration (native/lane plane) ---------------------------------


class TestSidecarProvenance:
    def test_ring_drain_attributes_records_and_audits(
            self, tmp_path, monkeypatch):
        """The lane plane end to end: shm ring -> sidecar -> on-device
        attribution fold + flight records (ticket trace ids) + parity
        audit of the served lanes."""
        import threading

        from pingoo_tpu import native_ring
        from pingoo_tpu.native_ring import Ring, RingSidecar

        if not native_ring.ensure_built():
            pytest.skip("native toolchain unavailable")
        monkeypatch.setenv("PINGOO_PARITY_SAMPLE", "1")
        plan = compile_ruleset(_basic_rules(), {})
        ring = Ring(str(tmp_path / "ring"), capacity=64, create=True)
        sidecar = RingSidecar(ring, plan, {}, max_batch=32)
        try:
            t = threading.Thread(target=sidecar.run,
                                 kwargs={"max_requests": 3},
                                 daemon=True)
            t.start()
            for path in (b"/.env", b"/ok", b"/.env/x"):
                assert ring.enqueue(path=path, url=path,
                                    user_agent=b"ua") is not None
            t.join(timeout=120)
            assert sidecar.processed == 3
            # on-device fold: the block rule hit twice
            assert sidecar._attribution._counts[0] == 2
            entries = sidecar.flight_recorder.snapshot()
            assert len(entries) == 3
            by_trace = {e["trace_id"]: e for e in entries}
            assert by_trace["t-0"]["matched_rule_names"] == ["waf"]
            assert by_trace["t-0"]["action"] == 1
            assert by_trace["t-1"]["matched_rules"] == []
            assert "enqueue_to_post_ms" in by_trace["t-0"]["stages_ms"]
            assert sidecar.parity.flush(60)
            assert sidecar.parity.checked_total.value >= 3
            assert sidecar.parity.mismatch_total.value == 0
            assert all(e["parity"] == "ok" for e in
                       sidecar.flight_recorder.snapshot())
        finally:
            sidecar.stop()
            ring.close()


# -- lint mutation proofs ----------------------------------------------------


class TestLintMutations:
    def _source(self):
        path = os.path.join(os.path.dirname(__file__), "..",
                            "pingoo_tpu", "obs", "provenance.py")
        with open(path) as f:
            return f.read()

    def test_bare_sync_in_attribution_fold_fails_lint(self):
        """The fold takes a HOST array (ISSUE 37: the attribution lane
        comes in the batch's one copy), so it carries no sanctioned
        sync any more; putting a materialization back fails the lint."""
        from tools.analyze import lint

        src = self._source()
        assert "allow(sync-asarray-hot)" not in src
        marker = "        if indices is not None:\n            np.add.at("
        assert src.count(marker) == 1
        mutated = src.replace(
            marker, "        hit_counts = np.asarray(hit_counts)\n" + marker)
        findings, _ = lint.lint_source(mutated,
                                       "pingoo_tpu/obs/provenance.py")
        assert any(f.rule == "sync-asarray-hot"
                   and "fold_batch" in f.message for f in findings)

    def test_sync_in_parity_submit_fails_lint(self):
        """The parity sampler's hot side must stay sync-free: inserting
        a materialization into submit_matrix fails the lint."""
        from tools.analyze import lint

        src = self._source()
        marker = "    def submit_matrix(self, reqs, matched, trace_ids=None)"
        assert marker in src
        mutated = src.replace(
            marker,
            "    def submit_matrix(self, reqs, matched, trace_ids=None,"
            " _x=None):\n"
            "        matched = np.asarray(matched)\n"
            "        return self._submit_matrix(reqs, matched, trace_ids)\n"
            "    def _submit_matrix(self, reqs, matched, trace_ids=None)")
        findings, _ = lint.lint_source(mutated,
                                       "pingoo_tpu/obs/provenance.py")
        assert any(f.rule == "sync-asarray-hot"
                   and "submit_matrix" in f.message for f in findings)

    def test_current_tree_clean_including_obs(self):
        from tools.analyze import lint
        from tools.analyze import lint_config as cfg

        assert "pingoo_tpu/obs" in cfg.LINT_DIRS
        assert ("pingoo_tpu/obs/provenance.py::RuleAttribution"
                ".fold_batch") in cfg.HOT_FUNCTIONS
        findings, warnings = lint.lint_paths()
        assert findings == [], "\n".join(str(f) for f in findings)
        assert warnings == [], "\n".join(warnings)


# -- bench trajectory --------------------------------------------------------


class TestBenchRegress:
    def _write_history(self, tmp_path, entries):
        path = tmp_path / "BENCH_history.jsonl"
        path.write_text("\n".join(json.dumps(e) for e in entries) + "\n")
        return str(path)

    def test_regression_detected(self, tmp_path, capsys):
        from tools import bench_regress

        path = self._write_history(tmp_path, [
            {"ts": 1, "backend": "tpu", "value": 1000.0,
             "p_batch_ms": 1.0},
            {"ts": 2, "backend": "tpu", "value": 800.0,
             "p_batch_ms": 1.05},
        ])
        assert bench_regress.main(["--file", path]) == 1
        out = capsys.readouterr()
        assert "REGRESSION" in out.out
        assert "value" in out.err

    def test_improvement_and_threshold_pass(self, tmp_path):
        from tools import bench_regress

        path = self._write_history(tmp_path, [
            {"ts": 1, "backend": "tpu", "value": 1000.0,
             "p_batch_ms": 1.0},
            {"ts": 2, "backend": "tpu", "value": 950.0,
             "p_batch_ms": 1.02},
        ])
        assert bench_regress.main(["--file", path]) == 0
        # tighter threshold flips the same delta into a failure
        assert bench_regress.main(
            ["--file", path, "--threshold", "0.02"]) == 1

    def test_incomparable_backends_skipped(self, tmp_path):
        from tools import bench_regress

        path = self._write_history(tmp_path, [
            {"ts": 1, "backend": "tpu", "value": 1000.0},
            {"ts": 2, "backend": "cpu", "value": 5.0},
        ])
        # latest is a cpu run; only a tpu prior exists
        assert bench_regress.main(["--file", path]) == 0

    def test_baseline_picks_same_backend(self, tmp_path, capsys):
        from tools import bench_regress

        path = self._write_history(tmp_path, [
            {"ts": 1, "backend": "tpu", "value": 1000.0},
            {"ts": 2, "backend": "cpu", "value": 5.0},
            {"ts": 3, "backend": "tpu", "value": 990.0},
        ])
        assert bench_regress.main(["--file", path]) == 0
        assert "ts=1" in capsys.readouterr().out

    def test_missing_or_short_history_is_not_failure(self, tmp_path):
        from tools import bench_regress

        assert bench_regress.main(
            ["--file", str(tmp_path / "nope.jsonl")]) == 0
        path = self._write_history(tmp_path, [
            {"ts": 1, "backend": "tpu", "value": 1.0}])
        assert bench_regress.main(["--file", path]) == 0

    def test_bench_emit_appends_history(self, tmp_path, monkeypatch):
        import bench

        hist = tmp_path / "h.jsonl"
        monkeypatch.setenv("BENCH_HISTORY", "1")
        monkeypatch.setenv("BENCH_HISTORY_FILE", str(hist))
        monkeypatch.setattr(bench, "_EMITTED", False)
        bench._emit_once(json.dumps({"metric": "m", "value": 1}))
        monkeypatch.setattr(bench, "_EMITTED", False)
        bench._emit_once(json.dumps({"metric": "m", "value": 2}))
        lines = [json.loads(ln) for ln in
                 hist.read_text().strip().splitlines()]
        assert [e["value"] for e in lines] == [1, 2]
        assert all("ts" in e for e in lines)
