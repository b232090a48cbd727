"""Sidecar supervision (ISSUE 10, docs/RESILIENCE.md): liveness
protocol primitives, crash-reattach reconciliation, degradation
ladder, chaos injector, and the stop()/SIGTERM drain contract.

The subprocess end of these scenarios — a real SIGKILLed consumer,
bounded p99 across the outage — lives in tools/chaos_smoke.py
(`make chaos-smoke`); here the same protocol is driven in-process so
tier-1 stays fast and deterministic. A "dead epoch" is simulated by
dequeuing tickets from a ring without ever posting their verdicts:
exactly the shm state a SIGKILL between dequeue and post leaves
behind, minus the process teardown.
"""

import threading
import time

import numpy as np
import pytest

from pingoo_tpu import native_ring
from pingoo_tpu.native_ring import Ring, RingSidecar

pytestmark = pytest.mark.skipif(
    not native_ring.ensure_built(), reason="native toolchain unavailable")


def _has_jax():
    try:
        import jax  # noqa: F401
        return True
    except Exception:
        return False


needs_jax = pytest.mark.skipif(not _has_jax(), reason="jax unavailable")


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    """Supervision knobs the sidecar reads at construction time; a
    leaked PINGOO_CHAOS would fault-inject every test below."""
    for var in ("PINGOO_CHAOS", "PINGOO_DFA", "PINGOO_MESH",
                "PINGOO_SCHED_MODE", "PINGOO_PARITY_SAMPLE",
                "PINGOO_PIPELINE", "PINGOO_PIPELINE_DEPTH"):
        monkeypatch.delenv(var, raising=False)


def _make_plan():
    from pingoo_tpu.compiler import compile_ruleset
    from pingoo_tpu.config.schema import Action, RuleConfig
    from pingoo_tpu.expr import compile_expression

    rules = [
        RuleConfig(name="waf", actions=(Action.BLOCK,),
                   expression=compile_expression(
                       'http_request.path.starts_with("/evil")')),
        RuleConfig(name="bot", actions=(Action.BLOCK,),
                   expression=compile_expression(
                       'http_request.user_agent.contains("chaosbot")')),
    ]
    return compile_ruleset(rules, {})


@pytest.fixture(scope="module")
def plan():
    return _make_plan()


def _enq(ring, i):
    path = b"/evil/%d" % i if i % 3 == 0 else b"/ok/%d" % i
    ua = b"chaosbot/1.0" if i % 7 == 0 else b"Mozilla/5.0"
    return ring.enqueue(method=b"GET", host=b"r.test", path=path,
                        url=path, user_agent=ua)


def _want(i):
    return 1 if (i % 3 == 0 or i % 7 == 0) else 0


def _poll_all(ring, need, timeout=120.0):
    """ticket -> [actions] until `need` verdicts arrive, plus a short
    grace window so a double-post would be caught, not raced past."""
    got: dict = {}
    count = 0
    deadline = time.monotonic() + timeout
    while count < need and time.monotonic() < deadline:
        v = ring.poll_verdict()
        if v is None:
            time.sleep(0.002)
            continue
        t, a, _ = v
        got.setdefault(t, []).append(a)
        count += 1
    grace = time.monotonic() + 0.2
    while time.monotonic() < grace:
        v = ring.poll_verdict()
        if v is None:
            time.sleep(0.01)
            continue
        t, a, _ = v
        got.setdefault(t, []).append(a)
    return got


class TestLivenessProtocol:
    """Ring v5 header primitives — pure shm, no verdict engine."""

    def test_attach_bumps_epoch_and_stamps_heartbeat(self, tmp_path):
        ring = Ring(str(tmp_path / "ring"), capacity=64, create=True)
        try:
            lv = ring.liveness()
            # heartbeat_ms == 0 is the bootstrap sentinel the native
            # detector keys on: no sidecar has EVER attached, so the
            # data plane must not flip degraded (httpd.cc).
            assert lv["epoch"] == 0 and lv["heartbeat_ms"] == 0
            assert ring.sidecar_attach() == 1
            lv = ring.liveness()
            assert lv["epoch"] == 1
            assert 0 < lv["heartbeat_ms"] <= lv["now_ms"]
            # One consumer generation = one epoch.
            assert ring.sidecar_attach() == 2
        finally:
            ring.close()

    def test_heartbeat_advances_on_ring_clock(self, tmp_path):
        ring = Ring(str(tmp_path / "ring"), capacity=64, create=True)
        try:
            ring.sidecar_attach()
            hb0 = ring.liveness()["heartbeat_ms"]
            time.sleep(0.02)
            ring.heartbeat()
            lv = ring.liveness()
            assert lv["heartbeat_ms"] > hb0
            assert lv["heartbeat_ms"] <= lv["now_ms"]
        finally:
            ring.close()

    def test_posted_floor_is_monotonic_max(self, tmp_path):
        ring = Ring(str(tmp_path / "ring"), capacity=64, create=True)
        try:
            ring.set_posted_floor(5)
            assert ring.liveness()["posted_floor"] == 5
            ring.set_posted_floor(3)  # stale writer loses the CAS race
            assert ring.liveness()["posted_floor"] == 5
            ring.set_posted_floor(9)
            assert ring.liveness()["posted_floor"] == 9
        finally:
            ring.close()

    def test_reclaim_consumed_slot_returns_intact_bytes(self, tmp_path):
        ring = Ring(str(tmp_path / "ring"), capacity=64, create=True)
        try:
            _enq(ring, 0)
            _enq(ring, 1)
            assert len(ring.dequeue_batch()) == 2  # consumed, unposted
            s = ring.reclaim(0)
            assert s is not None
            assert bytes(s[0]["path"][:int(s[0]["path_len"])]) == b"/evil/0"
            s = ring.reclaim(1)
            assert s is not None
            assert bytes(s[0]["path"][:int(s[0]["path_len"])]) == b"/ok/1"
        finally:
            ring.close()

    def test_reclaim_recycled_slot_returns_none(self, tmp_path):
        ring = Ring(str(tmp_path / "ring"), capacity=8, create=True)
        try:
            for i in range(8):
                assert _enq(ring, i) is not None
            assert len(ring.dequeue_batch()) == 8
            for i in range(8, 16):  # wrap: every slot overwritten
                assert _enq(ring, i) is not None
            for ticket in range(8):
                assert ring.reclaim(ticket) is None  # -> fail-open
            # ... and the reclaim probes did not disturb the live
            # generation occupying those slots.
            slots = ring.dequeue_batch()
            assert slots["ticket"].tolist() == list(range(8, 16))
        finally:
            ring.close()


@needs_jax
class TestReattachReconciliation:
    def test_orphans_reevaluated_exactly_once(self, tmp_path, plan,
                                              monkeypatch):
        monkeypatch.setenv("PINGOO_PARITY_SAMPLE", "1")
        ring = Ring(str(tmp_path / "ring"), capacity=256, create=True)
        sidecar = None
        try:
            ring.sidecar_attach()  # epoch 1: the consumer that "dies"
            n = 24
            for i in range(n):
                assert _enq(ring, i) is not None
            # Crash window: dequeued, never posted, floor never moved.
            assert len(ring.dequeue_batch(10)) == 10
            lv = ring.liveness()
            assert lv["req_tail"] == 10 and lv["posted_floor"] == 0

            sidecar = RingSidecar(ring, plan, {}, max_batch=16)
            assert sidecar.epoch == 2
            # All 10 orphan slots survived intact -> re-evaluated, not
            # failed open; floor advanced so a THIRD attach rescans
            # nothing.
            assert sidecar.reconciled == {"reeval": 10, "failopen": 0}
            assert ring.liveness()["posted_floor"] == 10
            assert sidecar.stats()["supervision"] == {
                "epoch": 2, "reconciled": {"reeval": 10, "failopen": 0}}

            t = threading.Thread(target=sidecar.run,
                                 kwargs={"max_requests": n - 10},
                                 daemon=True)
            t.start()
            got = _poll_all(ring, n)
            t.join(60)
            assert not t.is_alive()
            assert sorted(got) == list(range(n))           # zero lost
            assert all(len(a) == 1 for a in got.values())  # exactly once
            for i in range(n):  # reconciled verdicts bit-exact too
                assert got[i][0] & 3 == _want(i), i
            assert sidecar.parity is not None
            assert sidecar.parity.flush(30)
            assert sidecar.parity.mismatch_total.value == 0
        finally:
            if sidecar is not None:
                sidecar.stop()
            ring.close()

    def test_recycled_orphans_fail_open(self, tmp_path, plan):
        ring = Ring(str(tmp_path / "ring"), capacity=8, create=True)
        sidecar = None
        try:
            ring.sidecar_attach()
            for i in range(8):
                assert _enq(ring, i) is not None
            assert len(ring.dequeue_batch()) == 8  # dead epoch's batch
            for i in range(8, 16):  # producers lapped the dead consumer
                assert _enq(ring, i) is not None

            sidecar = RingSidecar(ring, plan, {}, max_batch=16)
            assert sidecar.reconciled == {"reeval": 0, "failopen": 8}
            # Fail-open is ALLOW even for tickets whose (overwritten)
            # request would have matched a block rule.
            t = threading.Thread(target=sidecar.run,
                                 kwargs={"max_requests": 8}, daemon=True)
            t.start()
            got = _poll_all(ring, 16)
            t.join(60)
            assert not t.is_alive()
            assert sorted(got) == list(range(16))
            assert all(len(a) == 1 for a in got.values())
            for ticket in range(8):
                assert got[ticket][0] & 3 == 0, ticket
            for i in range(8, 16):  # the live generation: full verdicts
                assert got[i][0] & 3 == _want(i), i
        finally:
            if sidecar is not None:
                sidecar.stop()
            ring.close()


@needs_jax
class TestHeartbeatFreezeDetection:
    def test_frozen_heartbeat_goes_stale_while_serving(self, tmp_path,
                                                       plan, monkeypatch):
        monkeypatch.setenv("PINGOO_CHAOS", "heartbeat_freeze")
        ring = Ring(str(tmp_path / "ring"), capacity=64, create=True)
        sidecar = RingSidecar(ring, plan, {}, max_batch=16)
        monkeypatch.delenv("PINGOO_CHAOS")
        try:
            assert sidecar.chaos.freeze_heartbeat
            hb0 = ring.liveness()["heartbeat_ms"]  # the attach stamp
            assert hb0 > 0
            for i in range(8):
                assert _enq(ring, i) is not None
            t = threading.Thread(target=sidecar.run,
                                 kwargs={"max_requests": 8}, daemon=True)
            t.start()
            got = _poll_all(ring, 8)
            t.join(60)
            assert not t.is_alive()
            # Verdicts flowed the whole time ...
            assert sorted(got) == list(range(8))
            for i in range(8):
                assert got[i][0] & 3 == _want(i), i
            time.sleep(0.25)
            lv = ring.liveness()
            # ... yet the heartbeat never re-stamped, so its age is
            # exactly what a PINGOO_SIDECAR_TIMEOUT_MS detector sees:
            # well past the 500 ms default by now (serving took >250 ms
            # of XLA compile alone).
            assert lv["heartbeat_ms"] == hb0
            assert lv["now_ms"] - lv["heartbeat_ms"] >= 200
        finally:
            sidecar.stop()
            ring.close()


class TestOverdueSyncProbe:
    """A device->host sync that outlasts its mark is probed from a
    thread beside the watchdog (ISSUE 30): is what the loop waits for
    ready on the device, and does the runtime answer a round trip of
    the probe's own while the loop stays blocked."""

    class _NeverReady:  # a device array whose program never finishes
        def is_ready(self):
            return False

    @pytest.mark.parametrize("replicas, chip", [(1, 0), (2, 1)])
    def test_overdue_sync_is_probed_and_other_windows_are_not(
            self, tmp_path, plan, caplog, replicas, chip):
        """Counted by difference (the counter is the process's), and
        with the sync window held open until the probe has started, so
        a loaded machine neither misses the mark nor the probe the
        window's close. The probe names the chip it probed and makes
        its round trip there."""
        import jax

        from pingoo_tpu.obs import REGISTRY

        def overdue():
            return REGISTRY.counter(
                "pingoo_sidecar_sync_overdue_total", "",
                labels={"plane": "sidecar", "ready": "false"}).value

        ring = Ring(str(tmp_path / "ring"), capacity=64, create=True)
        sidecar = RingSidecar(ring, plan, {}, max_batch=16,
                              replicas=replicas)
        sidecar._SYNC_PROBE_AT_S = (0.05,)
        before = overdue()
        try:
            hb0 = ring.liveness()["heartbeat_ms"]
            # a compile window names no arrays: stamped, never probed
            with sidecar._hb_busy():
                time.sleep(0.3)
            assert not sidecar._sync_probes
            assert ring.liveness()["heartbeat_ms"] > hb0
            with caplog.at_level("WARNING", logger="pingoo_tpu.native_ring"):
                with sidecar._hb_busy(sync=(self._NeverReady(),),
                                      device=chip):
                    deadline = time.monotonic() + 10
                    while not any(t.name == "pingoo-sync-probe"
                                  for t in threading.enumerate()) \
                            and not sidecar._sync_probes:
                        assert time.monotonic() < deadline
                        time.sleep(0.01)
                    time.sleep(0.05)
                deadline = time.monotonic() + 10
                while not sidecar._sync_probes \
                        and time.monotonic() < deadline:
                    time.sleep(0.01)
            (rec,) = sidecar._sync_probes
            assert rec["probe"] == 0 and rec["overdue_ms"] >= 50
            assert rec["device"] == chip
            assert rec["ready"] == [False] == rec["ready_after"]
            # the runtime answered the probe while the loop was blocked,
            # and the loop's own sync came back (the window closed)
            assert rec["up_ms"] >= 0 and rec["back_ms"] >= 0
            assert rec["loop_back_ms"] is not None
            assert "device sync overdue" in caplog.text
            assert overdue() - before == 1
            # ... whose round trip went where the chip's batches go
            assert sidecar._replica_devices == (
                [None] if replicas == 1 else jax.local_devices()[:2])
        finally:
            sidecar.stop()
            ring.close()


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class TestDegradationLadder:
    """Ladder state machine with an injected clock — no sleeping."""

    def _ladder(self, clk, **kw):
        from pingoo_tpu.engine.ladder import DegradationLadder

        return DegradationLadder("test", clock=clk, **kw)

    def test_demote_probe_repromote(self):
        clk = _FakeClock()
        lad = self._ladder(clk, base_backoff_s=1.0)
        assert lad.try_rung("device")
        lad.note_failure("device", RuntimeError("boom"))
        assert not lad.healthy("device")
        assert lad.demoted() == ["device"]
        assert not lad.try_rung("device")   # backoff window closed
        clk.t = 1.0
        assert lad.try_rung("device")       # the probe
        assert not lad.try_rung("device")   # one probe per window
        lad.note_success("device")
        assert lad.healthy("device")
        assert lad.try_rung("device") and lad.try_rung("device")
        assert lad.demoted() == []

    def test_backoff_doubles_and_caps(self):
        clk = _FakeClock()
        lad = self._ladder(clk, base_backoff_s=1.0, max_backoff_s=4.0)
        lad.note_failure("dfa", RuntimeError("1"))
        assert lad.snapshot()["dfa"]["backoff_s"] == 1.0
        lad.note_failure("dfa", RuntimeError("2"))
        assert lad.snapshot()["dfa"]["backoff_s"] == 2.0
        lad.note_failure("dfa", RuntimeError("3"))
        lad.note_failure("dfa", RuntimeError("4"))
        assert lad.snapshot()["dfa"]["backoff_s"] == 4.0  # capped
        # Re-promotion resets to base for the next incident.
        lad.note_success("dfa")
        assert lad.snapshot()["dfa"]["backoff_s"] == 1.0

    def test_snapshot_counts_errors_and_demotions(self):
        clk = _FakeClock()
        lad = self._ladder(clk)
        lad.note_success("mesh")  # no-op while healthy
        snap0 = lad.snapshot()["mesh"]
        assert snap0["healthy"] and snap0["errors"] == 0 \
            and snap0["demotions"] == 0
        lad.note_failure("mesh", ValueError("shard"))
        lad.note_failure("mesh", ValueError("shard again"))
        clk.t = 100.0
        assert lad.try_rung("mesh")
        lad.note_success("mesh")
        lad.note_failure("mesh", ValueError("relapse"))
        snap = lad.snapshot()["mesh"]
        assert snap["errors"] == 3
        assert snap["demotions"] == 2  # healthy->demoted transitions
        assert snap["fallback"] == "single-device"
        assert "relapse" in snap["last_error"]


class TestChaosInjector:
    def test_spec_parses_every_fault(self):
        from pingoo_tpu.obs.chaos import ChaosInjector

        c = ChaosInjector("kill,pause:50:2,heartbeat_freeze,"
                          "stall:encode:5,xla_error:3,verdict_full:2")
        assert c.active
        assert c.kill_after == 1       # default N
        assert c.pause_ms == 50 and c.pause_after == 2
        assert c.freeze_heartbeat
        assert c.stalls == {"encode": 5.0}
        assert c.xla_error_at == 3
        assert c.verdict_full_budget == 2

    def test_malformed_spec_raises(self):
        from pingoo_tpu.obs.chaos import ChaosInjector

        for bad in ("bogus", "pause", "stall:encode", "kill:x"):
            with pytest.raises(ValueError):
                ChaosInjector(bad)

    def test_dormant_without_env(self, monkeypatch):
        from pingoo_tpu.obs.chaos import ChaosInjector

        monkeypatch.delenv("PINGOO_CHAOS", raising=False)
        c = ChaosInjector.from_env()
        assert not c.active
        c.on_batch_done(100)           # would SIGKILL if armed
        c.maybe_xla_error(100)
        c.stage("encode")
        assert not c.verdict_full()
        assert not c.heartbeat_frozen()

    def test_verdict_full_budget_decrements(self):
        from pingoo_tpu.obs.chaos import ChaosInjector

        c = ChaosInjector("verdict_full:2")
        assert c.verdict_full() and c.verdict_full()
        assert not c.verdict_full()


@needs_jax
class TestLadderRoundTrip:
    def test_device_fault_demotes_then_repromotes_bit_identical(
            self, tmp_path, monkeypatch):
        # Private plan: dfa demotion mutates plan.dfa_default_mode.
        plan = _make_plan()
        monkeypatch.setenv("PINGOO_CHAOS", "xla_error:1")
        ring = Ring(str(tmp_path / "ring"), capacity=256, create=True)
        sidecar = RingSidecar(ring, plan, {}, max_batch=16)
        monkeypatch.delenv("PINGOO_CHAOS")
        try:
            n1 = 16
            for i in range(n1):
                assert _enq(ring, i) is not None
            t = threading.Thread(target=sidecar.run,
                                 kwargs={"max_requests": n1}, daemon=True)
            t.start()
            got = _poll_all(ring, n1)
            t.join(60)
            assert not t.is_alive()
            # The injected device fault fired and demoted a rung ...
            assert "xla" in sidecar.chaos._fired
            assert sidecar.ladder.demoted()
            snap = sidecar.ladder.snapshot()
            assert sum(r["errors"] for r in snap.values()) >= 1
            # ... and the fallback rung served bit-identical verdicts.
            assert sorted(got) == list(range(n1))
            for i in range(n1):
                assert got[i][0] & 3 == _want(i), i

            # Past the base backoff window the next dispatch probes the
            # demoted rung; the fault was one-shot, so the probe
            # succeeds and re-promotes.
            time.sleep(1.1)
            for i in range(n1, 2 * n1):
                assert _enq(ring, i) is not None
            t = threading.Thread(target=sidecar.run,
                                 kwargs={"max_requests": 2 * n1},
                                 daemon=True)
            t.start()
            got2 = _poll_all(ring, n1)
            t.join(60)
            assert not t.is_alive()
            assert sidecar.ladder.demoted() == []
            assert sorted(got2) == list(range(n1, 2 * n1))
            assert all(len(a) == 1 for a in got2.values())
            for i in range(n1, 2 * n1):
                assert got2[i][0] & 3 == _want(i), i
        finally:
            sidecar.stop()
            ring.close()


@needs_jax
class TestSigtermDrain:
    def test_stop_drains_inflight_and_pending(self, tmp_path, plan):
        """stop() is the SIGTERM drain path (host/server.py installs
        the handler): every ticket dequeued before the stop must still
        get a verdict — pending accumulation AND in-flight pipeline
        batches flush — and the posted floor must catch the dequeue
        cursor so the next epoch reconciles nothing."""
        ring = Ring(str(tmp_path / "ring"), capacity=256, create=True)
        sidecar = RingSidecar(ring, plan, {}, max_batch=8,
                              pipeline_depth=3)
        try:
            n = 64
            for i in range(n):
                assert _enq(ring, i) is not None
            t = threading.Thread(target=sidecar.run, daemon=True)
            t.start()
            deadline = time.monotonic() + 120
            while ring.liveness()["req_tail"] == 0:
                assert time.monotonic() < deadline, "nothing dequeued"
                time.sleep(0.001)
            sidecar.stop(join_timeout_s=120)
            t.join(10)
            assert not t.is_alive()
            lv = ring.liveness()
            served = lv["req_tail"]
            assert served >= 1
            assert lv["posted_floor"] == served  # zero orphans left
            got = _poll_all(ring, served)
            assert sorted(got) == list(range(served))
            assert all(len(a) == 1 for a in got.values())
            for i in range(served):
                assert got[i][0] & 3 == _want(i), i
        finally:
            sidecar.stop()
            ring.close()
