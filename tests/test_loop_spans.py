"""The sidecar drain loop's one span source (obs/pipeline.py) and the
device-trace scopes (engine/verdict.py), docs/OBSERVABILITY.md "Spans
and scopes":

  * under a profiler trace every batch leaves one `sidecar/encode`,
    `dispatch`, `device_wait`, `resolve` event with the same `batch`
    stat, the jitted call's event nests inside the dispatch span, and
    the drain thread's phase spans never overlap;
  * the phases of `pingoo_sidecar_loop_ms_total` partition the loop's
    wall time, and the `pingoo_verdict_stage_ms` sums equal the phases
    they mirror;
  * the lowered text of `lanes_packed` / `stage_a_packed` carries every
    scope of the vocabulary, and the scopes add no compile;
  * a phase held past the threshold bumps `pingoo_sidecar_stall_total`
    once and logs once.
"""

from __future__ import annotations

import glob
import logging
import re
import threading
import time

import pytest

from pingoo_tpu import native_ring
from pingoo_tpu.obs import REGISTRY
from pingoo_tpu.obs.pipeline import LOOP_PHASES, PHASE_STAGE, STALL_MS

needs_native = pytest.mark.skipif(
    not native_ring.ensure_built(), reason="native ring library unavailable")

RULES = (
    ("sqli", 'http_request.url.matches("(?i)union\\\\s+select")'),
    ("trav", 'http_request.path.matches("(?i)etc/+passwd[0-9]*")'),
    ("env", 'http_request.path.starts_with("/.env")'),
    ("bot", 'http_request.user_agent.contains("sqlmap")'),
    ("ips", 'lists["bad_ips"].contains(client.ip)'),
    ("asn", 'lists["bad_asns"].contains(client.asn)'),
)
LISTS = {"bad_ips": ["10.9.0.0/16", "192.0.2.7"], "bad_asns": [64500, 64501]}


def _plan():
    from pingoo_tpu.compiler import compile_ruleset
    from pingoo_tpu.config.schema import Action, RuleConfig
    from pingoo_tpu.expr import compile_expression

    return compile_ruleset(
        [RuleConfig(name=name, actions=(Action.BLOCK,),
                    expression=compile_expression(src))
         for name, src in RULES], LISTS)


class _Served:
    """A sidecar draining one ring on a thread of its own; `wave(n)`
    enqueues n requests and waits for their verdicts."""

    def __init__(self, tmp_path, max_requests=None):
        self.ring = native_ring.Ring(str(tmp_path / "ring"), capacity=256,
                                     create=True)
        self.sidecar = native_ring.RingSidecar(self.ring, _plan(), LISTS,
                                               max_batch=16)
        self.thread = threading.Thread(
            target=self.sidecar.run, daemon=True,
            kwargs={"max_requests": max_requests})
        self.sent = 0

    def enqueue(self, n):
        for _ in range(n):
            path = b"/.env" if self.sent % 3 == 0 else b"/ok/%d" % self.sent
            assert self.ring.enqueue(
                method=b"GET", host=b"h.test", path=path,
                url=path + b"?q=1+union+select", user_agent=b"Mozilla/5.0",
                ip=b"\x00" * 10 + b"\xff\xff" + bytes([172, 16, 0, 9]),
                port=4000 + self.sent, asn=64496, country=b"FR") is not None
            self.sent += 1

    def wave(self, n, timeout=120.0):
        self.enqueue(n)
        got, deadline = 0, time.monotonic() + timeout
        while got < n and time.monotonic() < deadline:
            if self.ring.poll_verdict() is None:
                time.sleep(0.001)
            else:
                got += 1
        assert got == n

    def close(self):
        self.sidecar.stop()
        assert not self.thread.is_alive()
        self.ring.close()


def _counter(name, **labels):
    return REGISTRY.counter(name, labels={"plane": "sidecar", **labels}).value


def _stage_sum(stage):
    return REGISTRY.histogram(
        "pingoo_verdict_stage_ms",
        labels={"plane": "sidecar", "stage": stage}).sum


@needs_native
def test_trace_holds_one_span_per_phase_and_batch(tmp_path):
    import jax
    from jax.profiler import ProfileData

    served = _Served(tmp_path)
    served.thread.start()
    try:
        served.wave(5)  # compiles: outside the trace
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path / "trace"),
                                 profiler_options=options)
        try:
            for n in (3, 7, 4):
                served.wave(n)
                time.sleep(0.02)  # an idle stretch between the batches
        finally:
            jax.profiler.stop_trace()
    finally:
        served.close()
    path = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                     recursive=True)[0]
    lines = [line for plane in ProfileData.from_file(path).planes
             for line in plane.lines
             if any(ev.name.startswith("sidecar/") for ev in line.events)]
    assert len(lines) == 1  # the drain thread, and no other
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
               dict(ev.stats)) for ev in lines[0].events]
    spans = sorted((e for e in events if e[0].startswith("sidecar/")),
                   key=lambda e: e[1])
    assert {name.split("/", 1)[1] for name, *_ in spans} <= set(LOOP_PHASES)
    for (_, _, end, _), (name, start, _, _) in zip(spans, spans[1:]):
        assert start >= end, f"{name} overlaps its predecessor"
    by_batch: dict = {}
    for name, start, end, stats in spans:
        if "batch" in stats:
            by_batch.setdefault(stats["batch"], []).append((name, start, end))
    assert len(by_batch) >= 3
    rows = 0
    for batch, its in by_batch.items():
        names = [name for name, _, _ in its]
        for phase in ("encode", "dispatch", "device_wait", "resolve"):
            assert names.count(f"sidecar/{phase}") == 1, (batch, names)
        d0, d1 = next((s, e) for name, s, e in its
                      if name == "sidecar/dispatch")
        assert any(name.startswith("PjitFunction(") and d0 <= s and e <= d1
                   for name, s, e, _ in events), f"batch {batch}"
        rows += next(st["rows"] for name, _, _, st in spans
                     if st.get("batch") == batch)
    assert rows == 3 + 7 + 4
    assert any(name == "sidecar/idle" for name, *_ in spans)


@needs_native
def test_phases_partition_the_loop_and_mirror_the_stage_sums(tmp_path):
    phases0 = {p: _counter("pingoo_sidecar_loop_ms_total", phase=p)
               for p in LOOP_PHASES}
    stages0 = {s: _stage_sum(s) for s in PHASE_STAGE.values()}
    served = _Served(tmp_path, max_requests=16)
    served.thread.start()
    # Idle past the once-a-second flush. No wall-clock figure: under
    # `-n 6` the thread may start late, so wait for the flush itself
    # (the first folds everything since loop_start: a second at least).
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        idle_mid = _counter("pingoo_sidecar_loop_ms_total", phase="idle")
        if idle_mid > phases0["idle"]:
            break
        time.sleep(0.05)
    assert idle_mid - phases0["idle"] > 900.0  # flushed while idle
    for n in (5, 9, 2):
        served.wave(n)
    served.thread.join(30)  # the loop ends itself at max_requests
    served.close()
    phases = {p: _counter("pingoo_sidecar_loop_ms_total", phase=p)
              - phases0[p] for p in LOOP_PHASES}
    # a partition of the loop's OWN time, first stamp to last: every
    # instant is in one phase, so the sum differs by rounding alone
    first, last = served.sidecar._pipe.loop_stamps
    assert sum(phases.values()) == pytest.approx((last - first) * 1e3,
                                                 abs=1e-3), phases
    for phase in ("poll", "encode", "dispatch", "device_wait", "resolve",
                  "idle"):
        assert phases[phase] > 0.0, phase
    assert phases["bodies"] == phases["swap"] == 0.0
    for phase, stage in PHASE_STAGE.items():
        assert _stage_sum(stage) - stages0[stage] == \
            pytest.approx(phases[phase], abs=1e-6), phase
    stats = served.sidecar.stats()
    assert stats["pipeline"]["loop_ms"]["idle"] == pytest.approx(
        _counter("pingoo_sidecar_loop_ms_total", phase="idle"), abs=1e-3)
    assert stats["device_wait_ms_per_batch"] == pytest.approx(
        phases["device_wait"] / stats["batches"], abs=1e-3)


@needs_native
def test_a_held_phase_counts_one_stall(tmp_path, monkeypatch, caplog):
    monkeypatch.setenv("PINGOO_CHAOS", f"stall:encode:{int(STALL_MS) + 60}")
    stalls0 = _counter("pingoo_sidecar_stall_total", phase="encode")
    served = _Served(tmp_path)
    served.enqueue(4)  # before the loop starts: one pass, one batch
    with caplog.at_level(logging.WARNING, logger="pingoo_tpu.obs.pipeline"):
        served.sidecar.run(max_requests=4)
    served.close()
    assert served.sidecar.batches == 1
    assert _counter("pingoo_sidecar_stall_total", phase="encode") \
        - stalls0 == 1
    lines = [r for r in caplog.records
             if r.getMessage() == "drain loop stalled"
             and r.fields["phase"] == "encode"]
    assert len(lines) == 1
    assert lines[0].fields["ms"] > STALL_MS and lines[0].fields["batch"] >= 1
    assert "ring_depth" in lines[0].fields


def _bare_loop(registry):
    """A PipelineStats wired as the drain loop wires it, with sinks that
    keep nothing."""
    from pingoo_tpu.obs import pipeline

    class _Sink:
        def observe(self, _ms):
            pass

    ps = pipeline.PipelineStats("sidecar", 2, registry=registry)
    ps.attach_loop({s: _Sink() for s in PHASE_STAGE.values()},
                   lambda *_: None, 16)
    return ps


@pytest.mark.parametrize("schedstat", ["readable", "missing"])
def test_the_drain_thread_counts_its_run_queue_wait(monkeypatch, tmp_path,
                                                     schedstat):
    """pingoo_sidecar_runqueue_us_total{plane} is the drain thread's own
    schedstat run-queue wait, read at a flush at most once a second; where
    the file is missing the series does not exist (not a 0)."""
    import os

    from pingoo_tpu.obs import pipeline
    from pingoo_tpu.obs.registry import MetricRegistry

    if schedstat == "missing":
        monkeypatch.setattr(pipeline, "SCHEDSTAT", str(tmp_path / "none"))
    elif not os.path.exists(pipeline.SCHEDSTAT):
        pytest.skip("no /proc/thread-self/schedstat here")
    monkeypatch.setattr(pipeline, "_RUNQUEUE_READ_S", 0.0)
    reg = MetricRegistry()
    ps = _bare_loop(reg)
    ps.loop_start()
    with ps.stage("encode"):
        sum(range(200_000))
    ps.loop_stop()   # its flush reads the wait
    text = reg.prometheus_text()
    if schedstat == "missing":
        assert "pingoo_sidecar_runqueue_us_total" not in text
        return
    assert 'pingoo_sidecar_runqueue_us_total{plane="sidecar"}' in text
    assert reg.counter("pingoo_sidecar_runqueue_us_total",
                       labels={"plane": "sidecar"}).value >= 0.0
    # the fds the loop opened are closed with it
    assert ps._sched_fd is None and ps._stat_fd is None


def test_a_stall_line_says_what_the_thread_waited_for(caplog):
    """The wedge's witness: a stalled phase's log line carries the run
    queue wait over it and the blkio ticks, None where unreadable."""
    import os

    from pingoo_tpu.obs.registry import MetricRegistry

    ps = _bare_loop(MetricRegistry())
    ps.loop_start()
    with caplog.at_level(logging.WARNING, logger="pingoo_tpu.obs.pipeline"):
        with ps.stage("encode"):
            time.sleep(STALL_MS / 1e3 + 0.05)
    ps.loop_stop()
    lines = [r for r in caplog.records
             if r.getMessage() == "drain loop stalled"]
    assert len(lines) == 1
    fields = lines[0].fields
    assert fields["phase"] == "encode" and fields["ms"] > STALL_MS
    if os.path.exists("/proc/thread-self/schedstat"):
        # asleep, not runnable: the thread waited on no run queue
        assert 0.0 <= fields["runqueue_ms"] < STALL_MS
    if os.path.exists("/proc/thread-self/stat"):
        assert fields["blkio_ticks"] >= 0


def _scopes_of(lowered) -> set:
    """Every vocabulary scope in a lowered program's locations."""
    from pingoo_tpu.engine.verdict import SCOPE_KINDS

    found = set()
    for loc in re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)):
        parts = loc.split("/")
        for i, part in enumerate(parts):
            if part in ("unpack", "num", "bool", "act"):
                found.add(part)
            elif part in SCOPE_KINDS and i + 1 < len(parts):
                found.add(f"{part}/{parts[i + 1]}")
    return found


@pytest.mark.parametrize("dfa", ["off", "force"])
def test_lowered_programs_carry_the_scope_vocabulary(monkeypatch, dfa):
    import jax

    from pingoo_tpu.engine.batch import (StagingEncoder, resolve_stage_caps,
                                         stage_overflow_thresholds)
    from pingoo_tpu.engine.verdict import (make_packed_lane_fn,
                                           make_packed_prefilter_fn)
    from test_parity import random_requests
    import random

    monkeypatch.setenv("PINGOO_STAGING", "compact")
    monkeypatch.setenv("PINGOO_DFA", dfa)
    monkeypatch.setenv("PINGOO_PREFILTER", "banks")
    plan = _plan()
    caps = resolve_stage_caps(plan)
    batch = StagingEncoder(
        64, plan.field_specs, stage_caps=caps,
        overflow_thresholds=stage_overflow_thresholds(plan, caps)
    ).encode_requests(random_requests(random.Random(5), 9), pad_to=16)
    tables = jax.device_put(plan.device_tables())
    packed = jax.device_put(batch.packed)
    pf = make_packed_prefilter_fn(plan)
    lanes = make_packed_lane_fn(plan, service_groups=[["pong"]])
    pf_hits, _ = pf.fn(tables, packed, batch.layout)

    stage_a = _scopes_of(pf.fn.lower(tables, packed, batch.layout))
    assert {"unpack", "pf/url", "pf/path", "pf/user_agent"} <= stage_a
    got = _scopes_of(lanes.lower(tables, packed, batch.layout, pf_hits))
    bank = "dfa" if dfa == "force" else "nfa"
    want = {"unpack", "bool", "act", "num", f"{bank}/url", f"{bank}/path",
            "win/user_agent", "grp/prefix_path", "list/intlist_5"}
    assert want <= got, (sorted(want - got), sorted(got))
    assert any(s.startswith("list/iplist_") for s in got)
    # inline Stage A when no hits are handed in
    assert "pf/url" in _scopes_of(
        lanes.lower(tables, packed, batch.layout, None))
    # scopes are metadata: one program per jitted function, as before
    for _ in range(2):
        lanes(tables, packed, batch.layout, pf_hits)
        pf.fn(tables, packed, batch.layout)
    assert lanes._cache_size() == 1 and pf.fn._cache_size() == 1


_KEY_PROBE = '''
import os, sys, textwrap
from pingoo_tpu.backend import place_compile_cache
cache = place_compile_cache()
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

def program(scope, pad):
    src = "\\n" * pad + textwrap.dedent("""
        import jax, jax.numpy as jnp
        @jax.jit
        def f(x):
            with jax.named_scope(SCOPE):
                return jnp.cumsum(jnp.sin(x) * 2, axis=1)
        """).replace("SCOPE", repr(scope))
    ns = {}
    exec(compile(src, "probe_%s_%d.py" % (scope.replace("/", "_"), pad),
                 "exec"), ns)
    return ns["f"]

def entries():
    return len([n for n in os.listdir(cache) if not n.endswith("-atime")])

x = jnp.ones((8, 128))
counts = []
for scope, pad in (("nfa/url", 0), ("nfa/url", 9), ("dfa/url", 0)):
    program(scope, pad)(x).block_until_ready()
    counts.append(entries())
print("ENTRIES", *counts)
'''


def test_the_compile_cache_key_covers_scope_names_not_source_lines(tmp_path):
    """A cache filled before a scope was named must not serve a program
    without it (backend.place_compile_cache); moving code must still
    hit."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    proc = subprocess.run([sys.executable, "-c", _KEY_PROBE], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    first, moved, renamed = map(int, next(
        line for line in proc.stdout.splitlines()
        if line.startswith("ENTRIES")).split()[1:])
    assert first >= 1
    assert moved == first      # the same names on other lines: a hit
    assert renamed > moved     # another scope: another program
