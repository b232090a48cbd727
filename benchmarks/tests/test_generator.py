"""native/httpgen against stub servers: the schedule is kept,
each request is timed from when it was due, and nothing a server does
ends the run - it is an outcome in the request's record."""

import json
import os
import subprocess
import threading
import time

import numpy as np
import pytest

from conftest import BENCH
from lib import harness, reduce
from stubs import BLOCK, OK, StubServer, stall_between


@pytest.fixture(scope="module")
def binary(tmp_path_factory):
    out = tmp_path_factory.mktemp("bin") / "httpgen"
    subprocess.run(["g++", "-O2", "-std=c++17", "-o", str(out),
                    os.path.join(BENCH, "native", "httpgen.cc")],
                   check=True)
    return str(out)


def drive(binary, tmp_path, port, due_s, tmpl, templates, connections=4,
          window_s=2.0, drain_s=2.0, on_start=None, addresses=None,
          loop="open"):
    tpath, spath, rpath = (str(tmp_path / n) for n in
                           ("t.bin", "s.bin", "r.bin"))
    harness.write_templates(tpath, templates)
    harness.write_schedule(spath, (np.asarray(due_s) * 1e9).astype(np.int64),
                           np.asarray(tmpl, dtype=np.uint32))
    argv = [binary, str(port), str(connections), tpath, spath, rpath,
            str(int(window_s * 1e9)), str(int(drain_s * 1e9))]
    if addresses is not None:
        apath = str(tmp_path / "a.bin")
        np.asarray(addresses, dtype="<u4").tofile(apath)
        argv += [apath, loop]
    proc = subprocess.Popen(argv, stdout=subprocess.PIPE)
    started = json.loads(proc.stdout.readline())
    if on_start:
        on_start(started)
    out, _ = proc.communicate(timeout=60)
    assert proc.returncode == 0
    summary = json.loads(out.strip().splitlines()[-1])
    return np.fromfile(rpath, dtype=harness.RECORD), started, summary


GET = {"method": "GET", "host": "h", "url": "/ok", "user_agent": "ua"}
BAD = {"method": "GET", "host": "h", "url": "/.env", "user_agent": "ua"}
HEAD = {"method": "HEAD", "host": "h", "url": "/ok", "user_agent": "ua"}


def test_schedule_is_kept_and_statuses_recorded(binary, tmp_path):
    server = StubServer(lambda head, n: BLOCK if b"/.env" in head else OK)
    due = np.arange(200) * 0.005
    tmpl = np.arange(200) % 3
    rec, started, summary = drive(binary, tmp_path, server.port, due, tmpl,
                                  [GET, BAD, HEAD])
    server.close()
    assert started["connections_at_start"] == 4
    assert (rec["outcome"] == reduce.ANSWERED).all()
    assert (rec["status"][tmpl == 1] == 403).all()
    assert (rec["status"][tmpl != 1] == 200).all()
    late = rec["sent_ns"] - rec["due_ns"]
    assert (late >= 0).all() and np.percentile(late, 90) < 20e6
    assert summary["reconnects"] >= 130    # every 403 and HEAD reconnects
    want = np.array([200, 403, 200], np.uint16)[rec["tmpl"]]
    cmp = reduce.compare(rec, want, 0)
    assert reduce.decide(cmp)[0] and cmp["right"] == 200
    # the first request on a connection says so: at the start, and
    # after every reconnect
    assert rec["fresh"][0] == 1 and rec["fresh"].sum() >= 130
    assert set(rec["conn"].tolist()) <= {0, 1, 2, 3}


def test_closed_loop_sends_the_sequence_as_fast_as_replies_come(binary,
                                                                tmp_path):
    """`closed`: no due times; each of the connections sends the next
    template of the sequence when its reply is in (after a 403 or a
    HEAD, once it has reconnected from its own address), until the
    window closes; what is left of the sequence stays unsent."""
    def behave(head, _n):
        time.sleep(0.004)
        return BLOCK if b"/.env" in head else OK
    server = StubServer(behave)
    base = (127 << 24) + (9 << 16)
    n = 20000
    rec, _, summary = drive(binary, tmp_path, server.port, np.zeros(n),
                            np.arange(n) % 8 == 7, [GET, BAD], connections=4,
                            window_s=1.5, drain_s=1.0,
                            addresses=[base + i for i in range(4)],
                            loop="closed")
    server.close()
    sent = rec[rec["sent_ns"] >= 0]
    assert 200 < len(sent) < 4 * 1.5 / 0.004 + 8    # 4 at a time, 4 ms each
    assert (sent["outcome"] == reduce.ANSWERED).all()
    assert (sent["due_ns"] == sent["sent_ns"]).all()  # due when it is sent
    assert (rec["outcome"][len(sent):] == reduce.UNSENT).all()   # in order
    assert (sent["status"] == np.where(sent["tmpl"] == 1, 403, 200)).all()
    assert set(server.peers) == {f"127.9.0.{i}" for i in range(4)}
    assert summary["reconnects"] >= (sent["tmpl"] == 1).sum()
    # never two in flight on one connection
    for conn in range(4):
        mine = sent[sent["conn"] == conn]
        assert (mine["sent_ns"][1:] >= mine["done_ns"][:-1]).all()


def test_each_connection_comes_from_its_own_address(binary, tmp_path):
    """The server sees clients across 127.0.0.0/8, a slot keeps its
    address over reconnects, and every connection carries its share in
    turn."""
    base = (127 << 24) + (7 << 16)
    addresses = [base + 10 + i for i in range(8)]
    due = np.arange(240) * 0.005
    server = StubServer(lambda head, n: BLOCK if b"/.env" in head else OK)
    rec, _, summary = drive(binary, tmp_path, server.port, due,
                            np.arange(240) % 2, [GET, BAD],
                            connections=8, addresses=addresses)
    server.close()
    assert (rec["outcome"] == reduce.ANSWERED).all()
    assert set(server.peers) == {f"127.7.0.{10 + i}" for i in range(8)}
    assert summary["reconnects"] >= 100 and len(server.peers) > 100
    # 240 over 8, about evenly
    assert np.bincount(rec["conn"], minlength=8).min() >= 20


def test_a_stall_is_charged_from_the_due_time(binary, tmp_path):
    t0 = [time.monotonic()]
    server = StubServer(stall_between(0.5, 1.0, t0))
    due = np.arange(300) * 0.005            # 1.5 s at 200/s over 2 connections
    rec, _, _ = drive(binary, tmp_path, server.port, due, np.zeros(300),
                      [GET], connections=2,
                      on_start=lambda s: t0.__setitem__(0, time.monotonic()))
    server.close()
    assert (rec["outcome"] == reduce.ANSWERED).all()
    lat = (rec["done_ns"] - rec["due_ns"]) / 1e9
    late = (rec["sent_ns"] - rec["due_ns"]) / 1e9
    stalled = (due >= 0.55) & (due < 0.95)
    # both connections are held by the stall: requests due in it wait
    # for a connection, and that wait is in their latency
    assert late[stalled].min() > 0.02
    assert np.allclose(lat[stalled], 1.0 - due[stalled], atol=0.08)
    assert lat[due < 0.4].max() < 0.05
    assert lat[due > 1.3].max() < 0.05      # and the backlog drains


def test_resets_and_silence_are_outcomes_not_errors(binary, tmp_path):
    def behave(_head, n):
        if n % 5 == 0:
            return "reset"
        if n % 7 == 0:
            return None                      # never answered
        return OK
    server = StubServer(behave)
    due = np.arange(150) * 0.01
    rec, _, summary = drive(binary, tmp_path, server.port, due,
                            np.zeros(150), [GET], connections=16,
                            window_s=1.6, drain_s=1.0)
    server.close()
    counts = np.bincount(rec["outcome"], minlength=4)
    assert counts[reduce.CONN_LOST] >= 25
    assert counts[reduce.NO_ANSWER] >= 10
    assert counts[reduce.ANSWERED] >= 90
    assert counts.sum() == 150
    e2e = reduce.end_to_end(rec, np.full(150, 200, np.uint16),
                            (0, int(1.6e9)), 1.6, 0, 9999.0)
    assert e2e["latency_p90_ms"] == 9999.0   # the lost ones are in the tail
    assert e2e["inspected_rps"] == pytest.approx(
        counts[reduce.ANSWERED] / 1.6, rel=0.05)


def test_a_server_killed_mid_window_ends_nothing(binary, tmp_path):
    server = StubServer(lambda head, n: OK)
    due = np.arange(400) * 0.005
    threading.Timer(1.0, server.close).start()
    rec, _, summary = drive(binary, tmp_path, server.port, due,
                            np.zeros(400), [GET], connections=8,
                            window_s=2.0, drain_s=1.0)
    counts = np.bincount(rec["outcome"], minlength=4)
    assert counts[reduce.ANSWERED] > 100
    # after the kill a request finds no connection, or loses the one
    # it went out on, or is never answered: each an outcome, none fatal
    assert counts[reduce.ANSWERED] + counts[reduce.UNSENT] \
        + counts[reduce.CONN_LOST] + counts[reduce.NO_ANSWER] == 400
    assert counts[reduce.ANSWERED] < 300
    assert summary["connect_failures"] + summary["reconnects"] > 0
    assert (rec["outcome"][due < 0.8] == reduce.ANSWERED).all()
