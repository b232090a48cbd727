"""Perf ledger + cross-plane timeline (ISSUE 17): compile-event
tracking, the durable CostModel cost ledger, and the span timeline's
nesting + Chrome-trace export. Pure host-side — no jax program runs
here (the live-wiring half is tools/timeline_smoke.py)."""

import json
import os

import pytest

from pingoo_tpu.obs import perf, timeline
from pingoo_tpu.obs.registry import MetricRegistry, lint_prometheus_text
from pingoo_tpu.sched.scheduler import (
    CostModel,
    load_cost_ledger,
    save_cost_ledger,
)


def _seeded_cost() -> CostModel:
    """A CostModel with every EWMA family populated by observation."""
    cost = CostModel(max_batch=256, seed_ms=4.0)
    cost.observe(16, 3.25)
    cost.observe(64, 9.5)
    cost.observe_stage("encode", 16, 0.8)
    cost.observe_stage("dispatch", 16, 0.4)
    cost.observe_stage("compute", 64, 6.0)
    cost.observe_dispatch_bytes(48 * 1024, 0.9)
    return cost


class TestCostModelPersistence:
    def test_snapshot_restore_round_trip(self):
        cost = _seeded_cost()
        snap = json.loads(json.dumps(cost.snapshot()))  # JSON round trip
        fresh = CostModel(max_batch=256)
        assert fresh.restore(snap) is True
        assert fresh.snapshot() == cost.snapshot()
        # The reloaded model estimates from the restored EWMAs (no
        # BENCH_history re-seeding): stage estimates match.
        for stage in ("encode", "dispatch", "compute"):
            assert fresh.estimate_stage(stage, 16) == pytest.approx(
                cost.estimate_stage(stage, 16))

    def test_restore_rejects_garbage(self):
        fresh = CostModel()
        assert fresh.restore("not a dict") is False
        assert fresh.restore({}) is False
        # Unparseable keys are skipped, parseable ones restore.
        ok = fresh.restore({"ewma_ms": {"16": 2.0, "what": 1.0},
                            "stage_ewma_ms": {"bogus_stage": {"8": 1.0}},
                            "dispatch_bytes_ewma_ms": {"nonsense": 3.0}})
        assert ok is True
        assert fresh._ewma == {16: 2.0}
        assert fresh._stage_ewma == {}

    def test_ledger_save_load_round_trip(self, tmp_path):
        path = str(tmp_path / "COST_LEDGER.json")
        cost = _seeded_cost()
        reg = MetricRegistry()
        assert save_cost_ledger(cost, backend="cpu", fingerprint="fp01",
                                plane="python", path=path) is True
        fresh = CostModel(max_batch=256)
        result = load_cost_ledger(fresh, backend="cpu", fingerprint="fp01",
                                  plane="python", path=path, registry=reg)
        assert result == "ok"
        assert fresh.snapshot() == cost.snapshot()
        assert reg.counter(
            "pingoo_costmodel_reload_total",
            labels={"plane": "python", "result": "ok"}).value == 1

    def test_stale_fingerprint_discarded_and_counted(self, tmp_path):
        path = str(tmp_path / "COST_LEDGER.json")
        cost = _seeded_cost()
        save_cost_ledger(cost, backend="cpu", fingerprint="fp01",
                         plane="python", path=path)
        reg = MetricRegistry()
        fresh = CostModel(max_batch=256)
        result = load_cost_ledger(fresh, backend="cpu",
                                  fingerprint="OTHER", plane="python",
                                  path=path, registry=reg)
        assert result == "stale"
        # Discarded: nothing restored from the mismatched entry.
        assert fresh._ewma == {}
        assert reg.counter(
            "pingoo_costmodel_reload_total",
            labels={"plane": "python", "result": "stale"}).value == 1
        # All four result series exist at zero-or-counted from boot.
        for res in ("ok", "stale", "missing", "error"):
            assert reg.counter(
                "pingoo_costmodel_reload_total",
                labels={"plane": "python", "result": res}) is not None

    def test_missing_and_version_mismatch(self, tmp_path):
        path = str(tmp_path / "COST_LEDGER.json")
        reg = MetricRegistry()
        fresh = CostModel()
        assert load_cost_ledger(fresh, backend="cpu", fingerprint="fp",
                                plane="python", path=path,
                                registry=reg) == "missing"
        with open(path, "w") as f:
            json.dump({"version": 999, "entries": {}}, f)
        assert load_cost_ledger(fresh, backend="cpu", fingerprint="fp",
                                plane="python", path=path,
                                registry=reg) == "stale"
        with open(path, "w") as f:
            f.write("{broken json")
        assert load_cost_ledger(fresh, backend="cpu", fingerprint="fp",
                                plane="python", path=path,
                                registry=reg) == "error"

    def test_merge_preserves_other_plane_entries(self, tmp_path):
        path = str(tmp_path / "COST_LEDGER.json")
        save_cost_ledger(_seeded_cost(), backend="cpu", fingerprint="fp",
                         plane="python", path=path)
        save_cost_ledger(_seeded_cost(), backend="cpu", fingerprint="fp",
                         plane="sidecar", path=path)
        with open(path) as f:
            doc = json.load(f)
        assert set(doc["entries"]) == {"cpu|python", "cpu|sidecar"}


class _FakeJit:
    """A jit-shaped callable with a controllable executable cache."""

    def __init__(self):
        self.cache = 0
        self.calls = 0
        self.grow_on = set()

    def __call__(self, *args):
        self.calls += 1
        if self.calls in self.grow_on:
            self.cache += 1
        return self.calls

    def _cache_size(self):
        return self.cache


class TestCompileLedger:
    def _ledger(self, tmp_path):
        return perf.CompileLedger(
            path=str(tmp_path / "COMPILE_LEDGER.jsonl"),
            registry=MetricRegistry())

    def test_cold_then_warm_events(self, tmp_path):
        ledger = self._ledger(tmp_path)
        fake = _FakeJit()
        fake.grow_on = {1, 3}  # compile on calls 1 (cold) and 3 (warm)
        fn = perf.instrument_jit(fake, "verdict", plane="python",
                                 fingerprint="fp", ledger=ledger)
        assert fn is not fake  # enabled -> wrapped
        for _ in range(4):
            fn()
        snap = ledger.snapshot()
        assert snap["totals"] == {"python/verdict/cold": 1,
                                  "python/verdict/warm": 1}
        kinds = [e["kind"] for e in snap["events"]]
        assert kinds == ["cold", "warm"]
        # The JSONL file agrees line-for-line with the in-memory ring.
        with open(ledger.path) as f:
            lines = [json.loads(ln) for ln in f]
        assert len(lines) == snap["compiles_total"] == 2
        assert all(ln["fingerprint"] == "fp" for ln in lines)

    def test_disabled_returns_fn_unchanged(self):
        ledger = perf.CompileLedger(path=None, registry=MetricRegistry())
        fake = _FakeJit()
        assert perf.instrument_jit(fake, "verdict", plane="python",
                                   ledger=ledger) is fake
        assert perf.instrument_jit(None, "verdict", plane="python",
                                   ledger=ledger) is None

    def test_wrapper_delegates_attributes(self, tmp_path):
        ledger = self._ledger(tmp_path)
        fake = _FakeJit()
        fn = perf.instrument_jit(fake, "lanes", plane="sidecar",
                                 ledger=ledger)
        assert fn._cache_size() == 0  # __getattr__ delegation

    def test_shape_context(self):
        assert perf._shape_context(
            [(64, 128), (64, 16), (8, 64, 4)]) == 64
        assert perf._shape_context([]) is None

    def test_path_gate(self, monkeypatch):
        monkeypatch.delenv("PINGOO_PERF_LEDGER", raising=False)
        assert perf.perf_ledger_path() is None
        monkeypatch.setenv("PINGOO_PERF_LEDGER", "0")
        assert perf.perf_ledger_path() is None
        monkeypatch.setenv("PINGOO_PERF_LEDGER", "1")
        assert perf.perf_ledger_path() == perf.DEFAULT_LEDGER_FILE
        # PERF_LEDGER.jsonl is the DRIVER's on-chip record (ISSUE 21):
        # the program neither writes that name nor hides it from git.
        assert perf.DEFAULT_LEDGER_FILE == "COMPILE_LEDGER.jsonl"
        import os

        ignored = [ln.strip() for ln in open(os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".gitignore")) if not ln.startswith("#")]
        assert "PERF_LEDGER.jsonl" not in ignored
        assert "COMPILE_LEDGER.jsonl" in ignored
        monkeypatch.setenv("PINGOO_PERF_LEDGER", "/tmp/x.jsonl")
        assert perf.perf_ledger_path() == "/tmp/x.jsonl"


class TestTimeline:
    def _timeline(self):
        return timeline.Timeline(rate=1.0, registry=MetricRegistry())

    def test_stride_sampler(self):
        tl = timeline.Timeline(rate=0.25, registry=MetricRegistry())
        hits = sum(tl.sample() for _ in range(100))
        assert hits == 25  # deterministic, no RNG
        off = timeline.Timeline(rate=0.0, registry=MetricRegistry())
        assert not any(off.sample() for _ in range(100))
        assert off.enabled is False

    def test_batch_python_spans_nest(self):
        tl = self._timeline()
        tl.batch_python(
            stages_ms={"encode_ms": 1.0, "prefilter_ms": 0.5,
                       "device_dispatch_ms": 0.5,
                       "device_compute_ms": 2.0},
            t_launch=10.0, t_resolve=10.005, t_end=10.006,
            rows=[("trace01", 9.998, 9.999)])
        spans = list(tl.spans)
        batch = [s for s in spans if s[2] == "batch"]
        assert len(batch) == 1
        b0, b1 = batch[0][3], batch[0][3] + batch[0][4]
        children = [s for s in spans
                    if s[1] == "python/batch" and s[2] != "batch"]
        assert children
        for s in children:
            assert s[3] >= b0 - 1.0
            assert s[3] + s[4] <= b1 + 1.0
        # The request lane covers enqueue -> batch end.
        req = [s for s in spans if s[2] == "request"]
        assert req and req[0][3] == pytest.approx(9.998e6)

    def test_batch_sidecar_cross_plane_join(self):
        tl = self._timeline()
        tl.batch_sidecar(
            points={"encode": (20.0, 20.001),
                    "prefilter": (20.001, 20.0015),
                    "dispatch": (20.0015, 20.002),
                    "device_wait": (20.003, 20.004),
                    "resolve": (20.004, 20.005)},
            rows=[("t-7", 19990.0)])  # enq_ms = 19.99 s
        spans = list(tl.spans)
        # device_compute runs from the dispatch's end, not the wait's
        # start: the window other batches hide behind.
        compute = [s for s in spans if s[2] == "device_compute"][0]
        assert compute[3] == pytest.approx(20.002e6)
        assert compute[4] == pytest.approx(2_000.0)
        join = [s for s in spans if s[0] == "native"
                and s[2] == "ring_wait"]
        assert len(join) == 1
        # enq at 19.99 s, sidecar pickup at 20.0 s -> 10 ms wait.
        assert join[0][4] == pytest.approx(10_000.0)

    def test_chrome_trace_export(self):
        tl = self._timeline()
        tl.batch_python(stages_ms={"encode_ms": 1.0}, t_launch=1.0,
                        t_resolve=1.002, t_end=1.003)
        doc = json.loads(tl.chrome_trace_json())
        assert doc["clock"]["unit"] == "monotonic_us"
        names = {e["name"] for e in doc["traceEvents"]
                 if e["ph"] == "M"}
        assert "process_name" in names and "thread_name" in names
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert xs and all(e["dur"] >= 0 for e in xs)
        assert doc["otherData"]["spans"] == len(tl.spans)

    def test_bounded_retention(self):
        tl = self._timeline()
        for i in range(tl.spans.maxlen + 100):
            tl.add_span("python", "t", "s", float(i), 1.0)
        assert len(tl.spans) == tl.spans.maxlen

    def test_sample_rate_env(self, monkeypatch):
        monkeypatch.delenv("PINGOO_TIMELINE_SAMPLE", raising=False)
        assert timeline.timeline_sample_rate() == 0.0
        monkeypatch.setenv("PINGOO_TIMELINE_SAMPLE", "0.1")
        assert timeline.timeline_sample_rate() == pytest.approx(0.1)
        monkeypatch.setenv("PINGOO_TIMELINE_SAMPLE", "7")
        assert timeline.timeline_sample_rate() == 1.0
        monkeypatch.setenv("PINGOO_TIMELINE_SAMPLE", "junk")
        assert timeline.timeline_sample_rate() == 0.0


class TestExposition:
    def test_perf_series_lint_clean(self):
        reg = MetricRegistry()
        ledger = perf.CompileLedger(path=None, registry=reg)
        ledger.ensure_instruments("python")
        ledger.ensure_instruments("sidecar")
        tl = timeline.Timeline(rate=0.0, registry=reg)
        tl.ensure_instruments("python")
        tl.ensure_instruments("sidecar")
        for res in ("ok", "stale", "missing", "error"):
            load_cost_ledger(CostModel(), backend="cpu", fingerprint="",
                             plane="python", path=os.devnull,
                             registry=reg)
            break  # one call creates all four series eagerly
        text = reg.prometheus_text()
        assert lint_prometheus_text(text) == []
        for name in ("pingoo_compile_total", "pingoo_compile_ms",
                     "pingoo_timeline_spans_total",
                     "pingoo_costmodel_reload_total"):
            assert name in text


class TestBenchRegressRefusal:
    def _run(self, tmp_path, entries):
        import tools.bench_regress as br

        path = str(tmp_path / "hist.jsonl")
        with open(path, "w") as f:
            for e in entries:
                f.write(json.dumps(e) + "\n")
        return br.main(["--file", path])

    def test_cross_backend_refused(self, tmp_path, capsys):
        rc = self._run(tmp_path, [
            {"ts": 1, "backend": "tpu", "value": 100},
            {"ts": 2, "backend": "cpu", "value": 5},
        ])
        assert rc == 0
        out = capsys.readouterr().out
        assert "REFUSED" in out
        assert "'cpu'" in out and "'tpu'" in out

    def test_unstamped_latest_is_an_error(self, tmp_path, capsys):
        rc = self._run(tmp_path, [
            {"ts": 1, "backend": "tpu", "value": 100},
            {"ts": 2, "value": 90},
        ])
        assert rc == 2
        assert "no 'backend' stamp" in capsys.readouterr().err

    def test_same_backend_still_compares(self, tmp_path, capsys):
        rc = self._run(tmp_path, [
            {"ts": 1, "backend": "tpu", "value": 100},
            {"ts": 2, "backend": "tpu", "value": 101},
        ])
        assert rc == 0
        assert "bench-regress: OK" in capsys.readouterr().out
