"""The httpd workers' own clock as the benchmark reads it
(lib/httpd_clock.py): the readers of the loop account and the chain,
and the join of the workers' pass rings with the
device's idle gaps, on hand-made snapshots and a hand-made trace."""

import os

import numpy as np
import pytest

from lib import harness, httpd_clock, metrics

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")
NEW = ("httpd_busy_share", "httpd_upstream_ms_mean", "outside_httpd_ms_mean")


def _native(loop, chain, n, n_up, runqueue=None, workers=2):
    doc = {"requests": n, "workers": workers,
           "loop_us": dict(loop), "passes": 10 * n, "empty_passes": 5 * n,
           "chain_us": dict(chain), "chain_requests": n,
           "chain_upstream_requests": n_up}
    if runqueue is not None:
        doc["runqueue_us"] = runqueue
    return doc


def _obs(runqueue=True, sidecar_rq=True):
    phases = ("wait", "verdicts", "supervise", "accept", "client",
              "upstream", "tls", "close", "sweep")
    before = _native(dict.fromkeys(phases, 0),
                     dict.fromkeys(httpd_clock.SEGMENTS, 0), 0, 0,
                     0 if runqueue else None)
    # two workers over 2 s: 4 s of loop time, 3 s of it waiting
    loop = dict.fromkeys(phases, 0)
    loop.update(wait=3_000_000, client=600_000, upstream=300_000,
                verdicts=100_000)
    # 1,000 requests, 800 proxied: 2 ms read, 10 verdict, 5 upstream
    # (over the 800), 1 reply
    chain = {"read": 2_000_000, "verdict": 10_000_000,
             "upstream": 4_000_000, "reply": 1_000_000}
    after = _native(loop, chain, 1000, 800, 80_000 if runqueue else None)
    reg0 = [("pingoo_sidecar_runqueue_us_total", {"plane": "sidecar"}, 0.0)]
    reg1 = [("pingoo_sidecar_runqueue_us_total", {"plane": "sidecar"},
             20_000.0)]
    rec = np.zeros(3, dtype=harness.RECORD)
    # sent 1.5, 2.0 and 3.9 s after the generator's t0; the snapshots
    # are 1.0 and 3.0 s after it: the first two count, 50 and 30 ms
    rec["sent_ns"] = [1_500_000_000, 2_000_000_000, 3_900_000_000]
    rec["done_ns"] = rec["sent_ns"] + [50_000_000, 30_000_000, 900_000_000]
    return {"before": {"at_mono": 101.0, "native": before,
                       "registry": reg0 if sidecar_rq else []},
            "after": {"at_mono": 103.0, "native": after,
                      "registry": reg1 if sidecar_rq else []},
            "gen_t0_mono": 100.0, "all_records": rec}


def _read(name, obs):
    return metrics.load_reader(METRICS, name + ".pooled")(obs)


def test_the_readers_read_the_clock():
    obs = _obs()
    assert _read("httpd_busy_share", obs) == pytest.approx(25.0)
    assert _read("httpd_upstream_ms_mean", obs) == pytest.approx(5.0)
    # the client's 40 ms, less httpd's 17 (2 + 10 + 4000/1000 + 1)
    assert _read("outside_httpd_ms_mean", obs) == pytest.approx(23.0)
    assert metrics.load_reader(METRICS, "httpd_busy_share.steady")(obs) \
        == pytest.approx(25.0)


@pytest.mark.parametrize("name", NEW)
def test_each_reader_reads_none_without_its_counter(name):
    """The parent's snapshots: no loop account, no chain, no run-queue
    readings."""
    obs = _obs()
    for side in ("before", "after"):
        for key in ("loop_us", "passes", "empty_passes", "chain_us",
                    "chain_requests", "chain_upstream_requests",
                    "runqueue_us"):
            obs[side]["native"].pop(key, None)
        obs[side]["registry"] = []
    assert _read(name, obs) is None
    # and nothing at all: a run whose scrapes failed
    assert _read(name, {"all_records": obs["all_records"]}) is None


def test_the_readers_need_no_run_queue_reading():
    """A machine without /proc/thread-self/schedstat has no run-queue
    counters; the clock's readers read all the same."""
    obs = _obs(runqueue=False, sidecar_rq=False)
    assert _read("httpd_busy_share", obs) == pytest.approx(25.0)
    assert _read("httpd_upstream_ms_mean", obs) == pytest.approx(5.0)
    assert _read("outside_httpd_ms_mean", obs) == pytest.approx(23.0)


def _timeline(workers):
    """A timeline as httpd renders it: {worker: [(start µs, phase,
    dur µs)]}."""
    events = [{"ph": "M", "name": "process_name", "pid": 3, "tid": 0}]
    for w, spans in workers.items():
        for start, phase, dur in spans:
            events.append({"ph": "X", "pid": 10 + w, "tid": 1,
                           "name": f"httpd/{phase}", "ts": start,
                           "dur": dur})
    events.append({"ph": "X", "pid": 3, "tid": 1, "name": "request",
                   "ts": 1000, "dur": 5})
    return {"traceEvents": events}


def test_a_known_idle_gap_lands_under_its_httpd_phase():
    """A synthetic trace: the device busy 0-1000 ns and 5000-6000 ns, so
    idle 1000-5000 under sidecar/idle. The profiler's first instant
    (0 ns) is at monotonic 10.000000 s; worker 0 spends 11-13 µs after
    it in `client` and the rest in `wait`, worker 1 is all `upstream`."""
    trace = {"devices": [{"name": "/device:TPU:0", "lanes_calls": 1,
                          "ops": [("act", 0, 1000), ("act", 5000, 1000)]}],
             "spans": [("dispatch", 0, 1000, 1), ("idle", 1000, 4000, None),
                       ("resolve", 5000, 1000, 1)]}
    mono_us = 10_000_000
    timeline = _timeline({
        0: [(mono_us + 0, "wait", 1), (mono_us + 1, "client", 2),
            (mono_us + 3, "wait", 10)],
        1: [(mono_us + 0, "upstream", 20)]})
    out = httpd_clock.join(trace, timeline, anchor_mono=10.0, start_s=0.002)
    table = out["idle_by_sidecar_httpd"]
    assert set(table) == {"idle"}
    # idle 4000 ns: worker 0 client 1000-3000, wait 3000-5000, each half
    assert table["idle"]["client"] == pytest.approx(1000e-9)
    assert table["idle"]["wait"] == pytest.approx(1000e-9)
    assert table["idle"]["upstream"] == pytest.approx(2000e-9)
    assert sum(out["idle_by_httpd_phase"].values()) == pytest.approx(4000e-9)
    assert out["alignment_error_s"] == 0.002
    # a ring that ended before the gap: all of it uncovered
    late = httpd_clock.join(trace, _timeline({0: [(mono_us, "wait", 1)]}),
                            anchor_mono=10.0)
    assert late["idle_by_httpd_phase"] == {
        httpd_clock.UNCOVERED: pytest.approx(4000e-9)}


def test_the_join_cuts_a_gap_at_every_boundary():
    sidecar = [(0, 50, "poll"), (50, 100, "device_wait")]
    workers = {0: [(0, 30, "wait"), (30, 80, "verdicts")],
               1: [(10, 100, "client")]}
    table = httpd_clock.join_gaps([(20, 90)], sidecar, workers)
    assert table["poll"] == {"wait": 5.0, "verdicts": 10.0, "client": 15.0}
    assert table["device_wait"] == {"verdicts": 15.0,
                                    httpd_clock.UNCOVERED: 5.0,
                                    "client": 20.0}
    total = sum(v for row in table.values() for v in row.values())
    assert total == pytest.approx(70.0)


def test_pass_spans_reads_every_workers_ring():
    spans = httpd_clock.pass_spans(_timeline({
        0: [(5, "client", 2), (1, "wait", 4)], 2: [(3, "sweep", 1)]}))
    assert spans == {0: [(1, 5, "wait"), (5, 7, "client")],
                     2: [(3, 4, "sweep")]}
    assert httpd_clock.pass_spans(None) == {}
