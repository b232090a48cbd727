"""`--native-workers N` (ISSUE 31): N httpd processes on one
SO_REUSEPORT port, a verdict ring each, ONE sidecar draining all the
rings in a merged pass, and one counter surface for the listener.

  * every verdict returns on the ring that enqueued its request and
    equals the interpreter's first-match action, whatever share of the
    traffic a ring carries;
  * a ring kept deeper than a whole batch starves no sibling: their
    rows are in the first or second batch launched (the rotating start);
  * whichever worker answers `/__pingoo/metrics`, the totals are the
    listener's: they never go down, they equal the sums (for high-water
    marks the maxima) of `per_worker`, and a release on one worker is
    counted in a scrape answered by another;
  * at one worker the JSON's keys are what they were, plus `workers`,
    `answered_by` and `per_worker`.
"""

from __future__ import annotations

import ipaddress
import json
import os
import random
import socket
import threading
import time

import pytest

from pingoo_tpu import native_ring
from pingoo_tpu.engine.batch import tuple_to_context
from pingoo_tpu.engine.verdict import action_lanes, interpret_rules_row
from pingoo_tpu.obs import REGISTRY
from pingoo_tpu.obs.registry import lint_prometheus_text
from test_native_httpd import _raw_get
from test_native_plane import NativeStack, recv_one_response

pytestmark = pytest.mark.skipif(
    not native_ring.ensure_built(), reason="native toolchain unavailable")

SIZES = dict(num_rules=40, seed=20260728, list_sizes=(64, 16))
MAX_BATCH = 16


@pytest.fixture(scope="module")
def ruleset():
    from pingoo_tpu.compiler import compile_ruleset
    from pingoo_tpu.utils.crs import generate_ruleset

    rules, lists = generate_ruleset(**SIZES)
    return rules, lists, compile_ruleset(rules, lists)


def _want_action(plan, lists, tup) -> int:
    """The interpreter's first-match action: 1 block, 0 pass."""
    row = interpret_rules_row(plan, tuple_to_context(tup, lists))
    return int(action_lanes(plan, row[None, :])[0][0])


def _enqueue(ring, tup):
    ip = b"\x00" * 10 + b"\xff\xff" + ipaddress.ip_address(tup.ip).packed
    ticket = ring.enqueue(
        method=tup.method.encode(), host=tup.host.encode(),
        path=tup.path.encode(), url=tup.url.encode(),
        user_agent=tup.user_agent.encode(), ip=ip, port=tup.remote_port,
        asn=tup.asn, country=tup.country.encode())
    assert ticket is not None
    return ticket


def _rings(tmp_path, n, capacity=1024):
    return [native_ring.Ring(str(tmp_path / f"ring_t_{w}"),
                             capacity=capacity, create=True)
            for w in range(n)]


def _verdicts(ring, want: int, timeout=120.0) -> dict:
    """ticket -> unverified action (verdict byte bits 0-1), for `want`
    verdicts off this ring's own verdict queue."""
    got, deadline = {}, time.monotonic() + timeout
    while len(got) < want and time.monotonic() < deadline:
        v = ring.poll_verdict()
        if v is None:
            time.sleep(0.001)
        else:
            got[v[0]] = v[1] & 3
    return got


@pytest.mark.parametrize("pipeline", ["on", "off"])
def test_every_verdict_returns_on_its_own_ring(tmp_path, monkeypatch,
                                               ruleset, pipeline):
    from pingoo_tpu.utils.crs import generate_traffic

    monkeypatch.setenv("PINGOO_PIPELINE", pipeline)
    _, lists, plan = ruleset
    reqs = generate_traffic(600, attack_fraction=0.2, seed=31, lists=lists)
    rng = random.Random(31)
    shares = rng.choices((0, 1, 2), weights=(80, 15, 5), k=len(reqs))
    rings = _rings(tmp_path, 3)
    sidecar = native_ring.RingSidecar(rings, plan, lists,
                                      max_batch=MAX_BATCH)
    # the registry outlives a sidecar: counters are read as differences
    rows0 = [c.value for c in sidecar._ring_rows]
    batch_rings0 = sidecar._batch_rings.value
    drain = threading.Thread(target=sidecar.run, daemon=True,
                             kwargs={"max_requests": len(reqs)})
    sent: list = [{} for _ in rings]      # ring -> ticket -> request

    def enqueuer(w):
        for tup, share in zip(reqs, shares):
            if share == w:
                sent[w][_enqueue(rings[w], tup)] = tup

    feeders = [threading.Thread(target=enqueuer, args=(w,))
               for w in range(3)]
    drain.start()
    for t in feeders:
        t.start()
    try:
        for t in feeders:
            t.join(60)
        got = [_verdicts(ring, len(sent[w]))
               for w, ring in enumerate(rings)]
        drain.join(60)
    finally:
        sidecar.stop()
    assert sum(len(s) for s in sent) == 600 and all(sent)
    blocked = 0
    for w in range(3):
        assert set(got[w]) == set(sent[w]), f"ring {w}"
        for ticket, tup in sent[w].items():
            want = _want_action(plan, lists, tup)
            assert got[w][ticket] == want, (w, ticket, tup)
            blocked += want == 1
        assert rings[w].poll_verdict() is None    # and none besides
    assert 0 < blocked < 600
    stats = sidecar.stats()
    assert [stats["ring_rows"][name] - r0 for name, r0 in
            zip(sidecar.ring_names, rows0)] == [len(s) for s in sent]
    assert stats["rings"] == 3 and set(stats["ring_depth"]) == \
        set(sidecar.ring_names) == {"ring_t_0", "ring_t_1", "ring_t_2"}
    # every batch drew on 1..3 rings; the counter is their sum
    assert stats["batches"] <= stats["batch_rings"] - batch_rings0 \
        <= 3 * stats["batches"]
    for ring in rings:
        ring.close()


@pytest.mark.parametrize("deep", [0, 1, 2])
def test_a_saturated_ring_starves_no_sibling(tmp_path, ruleset, deep):
    from pingoo_tpu.utils.crs import generate_traffic

    _, lists, plan = ruleset
    rings = _rings(tmp_path, 3)
    sidecar = native_ring.RingSidecar(rings, plan, lists,
                                      max_batch=MAX_BATCH)
    reqs = generate_traffic(3 * MAX_BATCH + 2, seed=32 + deep, lists=lists)
    for tup in reqs[2:]:              # three whole batches deep
        _enqueue(rings[deep], tup)
    lone = [w for w in range(3) if w != deep]
    for w, tup in zip(lone, reqs[:2]):
        _enqueue(rings[w], tup)
    launched: list = []               # a batch: {ring index: rows}
    dispatch = sidecar._dispatch

    def recording(parts, n, *args, **kw):
        batch: dict = {}
        for ring, slots in parts:
            w = rings.index(ring)
            batch[w] = batch.get(w, 0) + len(slots)
        launched.append(batch)
        return dispatch(parts, n, *args, **kw)

    sidecar._dispatch = recording
    try:
        assert sidecar.run(max_requests=len(reqs)) == len(reqs)
    finally:
        sidecar.stop()
    assert sum(sum(b.values()) for b in launched) == len(reqs)
    for w in lone:
        first = next(i for i, b in enumerate(launched) if w in b)
        assert first <= 1, (w, launched)
    assert all(sum(b.values()) <= MAX_BATCH for b in launched)
    for w, ring in enumerate(rings):
        want = len(reqs) - 2 if w == deep else 1
        assert len(_verdicts(ring, want, timeout=5.0)) == want
        ring.close()


# -- the counter surface, over real sockets -------------------------------------


def _scrape(port) -> dict:
    body = _raw_get(port, "/__pingoo/metrics",
                    extra="accept: application/json\r\n"
                    ).partition(b"\r\n\r\n")[2]
    return json.loads(body)


SUMMED = ("requests", "blocked", "verdicts", "fail_open")


def _check_totals(m: dict) -> None:
    per = m["per_worker"]
    assert len(per) == m["workers"] and \
        [p["worker"] for p in per] == list(range(m["workers"]))
    for key in SUMMED:
        assert m[key] == sum(p[key] for p in per), key
    for key in ("awaiting", "connections"):
        assert m[key] == sum(p[key] for p in per), key
    ring = m["ring"]
    assert ring["enqueued"] == sum(p["ring"]["enqueued"] for p in per)
    assert ring["wait_sum_ms"] == sum(p["ring"]["wait_sum_ms"] for p in per)
    assert ring["depth"] == sum(p["ring"]["depth"] for p in per)
    assert ring["depth_hwm"] == max(p["ring"]["depth_hwm"] for p in per)
    assert m["release"]["loop_gap_max_ms"] == \
        max(p["loop_gap_max_ms"] for p in per)
    assert sum(m["release"][f"tickets_{c}"] for c in
               ("deadline", "degraded", "bypass", "ring_full")) \
        == m["fail_open"]
    # the worker's own clock: the listener's account is the workers' sum
    for key in ("passes", "empty_passes", "chain_requests",
                "chain_upstream_requests", "io_calls", "epoll_ctl_calls",
                "upstream_direct_sends"):
        assert m[key] == sum(p[key] for p in per), key
    for key in ("loop_us", "chain_us"):
        assert m[key] == {k: sum(p[key][k] for p in per)
                          for k in m[key]}, key
    if "runqueue_us" in m:
        assert m["runqueue_us"] == sum(p.get("runqueue_us", 0) for p in per)


def _exchange(port, paths) -> list:
    """Requests one after another on ONE keep-alive connection ->
    their statuses."""
    c = socket.create_connection(("127.0.0.1", port), timeout=120)
    statuses = []
    for path, ua in paths:
        c.sendall(f"GET {path} HTTP/1.1\r\nhost: w.test\r\n"
                  f"user-agent: {ua}\r\n\r\n".encode())
        statuses.append(int(recv_one_response(c).split(b" ", 2)[1]))
        if statuses[-1] != 200:
            break                      # a 403 closes the connection
    c.close()
    return statuses


def test_three_workers_answer_as_one_listener(tmp_path, monkeypatch,
                                              ruleset):
    from pingoo_tpu.engine.batch import RequestTuple
    from pingoo_tpu.utils.crs import generate_traffic

    rules, lists, plan = ruleset
    # The benchmark's staging (one program pair), and a verdict deadline
    # and a liveness window that no CPU compile on a loaded machine
    # outlasts: nothing here may be released uninspected.
    monkeypatch.setenv("PINGOO_STAGING", "compact")
    stack = NativeStack(tmp_path, rules, lists, workers=3,
                        max_batch=MAX_BATCH,
                        env={"PINGOO_VERDICT_TIMEOUT_MS": "120000",
                             "PINGOO_SIDECAR_TIMEOUT_MS": "120000"})
    try:
        reqs = [r for r in generate_traffic(192, attack_fraction=0.2,
                                            seed=33)
                if " " not in r.url and r.user_agent]
        conns = [reqs[i::64] for i in range(64)]
        results: list = [None] * 64

        def client(i):
            results[i] = _exchange(
                stack.port, [(r.url, r.user_agent) for r in conns[i]])

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(64)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        sent = blocked = 0
        for i, statuses in enumerate(results):
            assert statuses, i
            for r, status in zip(conns[i], statuses):
                # the request as the plane sees it on loopback
                seen = RequestTuple(
                    host="w.test", url=r.url, path=r.url.split("?")[0],
                    method="GET", user_agent=r.user_agent, ip="127.0.0.1",
                    remote_port=0, asn=0, country="XX")
                want = 403 if _want_action(plan, lists, seen) == 1 else 200
                assert status == want, (i, r.url, r.user_agent)
                sent += 1
                blocked += want == 403
        assert 0 < blocked < sent

        scrapes = [_scrape(stack.port) for _ in range(12)]
        for before, after in zip(scrapes, scrapes[1:]):
            for key in SUMMED:
                assert after[key] >= before[key], key
        for m in scrapes:
            _check_totals(m)
            assert m["workers"] == 3 and m["requests"] >= sent
            assert m["verdicts"] >= sent and m["blocked"] == blocked
            assert m["fail_open"] == 0
        assert len({m["answered_by"] for m in scrapes}) > 1
        # the work was spread: the kernel hashes 64 connections over 3
        assert sum(p["requests"] > 0
                   for p in scrapes[-1]["per_worker"]) >= 2
        text = _raw_get(stack.port, "/__pingoo/metrics").partition(
            b"\r\n\r\n")[2].decode()
        assert lint_prometheus_text(text) == []
        assert 'pingoo_native_workers{plane="native"} 3' in text
        assert f'pingoo_blocked_total{{plane="native"}} {blocked}' in text
        last = scrapes[-1]["per_worker"]
        for w in range(3):
            assert (f'pingoo_worker_requests_total{{plane="native",'
                    f'worker="{w}"}} {last[w]["requests"]}') in text
        for path in ("/__pingoo/flightrecorder", "/__pingoo/timeline"):
            doc = json.loads(_raw_get(stack.port, path).partition(
                b"\r\n\r\n")[2])
            assert doc["answered_by"] in (0, 1, 2)
        # the sidecar named its rings, and every worker's rows came by
        rows = {name: REGISTRY.counter(
            "pingoo_ring_rows_total",
            labels={"plane": "sidecar", "ring": name}).value
            for name in stack.sidecar.ring_names}
        assert sorted(rows) == ["ring", "ring_1", "ring_2"]
        assert [rows[n] > 0 for n in ("ring", "ring_1", "ring_2")] == \
            [p["requests"] > 0 for p in last]
    finally:
        stack.stop()


def test_a_release_on_one_worker_shows_in_a_scrape_by_another(tmp_path):
    """TestReleaseWitness's pattern on two workers: rings nobody drains
    and a 150 ms verdict deadline, so every request is released by the
    worker that took it."""
    import http.server
    import os
    import subprocess

    from test_native_httpd import HTTPD, _free_port

    class Upstream(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            self.send_response(200)
            self.send_header("content-length", "2")
            self.end_headers()
            self.wfile.write(b"up")

        def log_message(self, *a):
            pass

    upstream = http.server.HTTPServer(("127.0.0.1", 0), Upstream)
    threading.Thread(target=upstream.serve_forever, daemon=True).start()
    rings = _rings(tmp_path, 2, capacity=64)
    port = _free_port()
    stats_fd = os.memfd_create("workers")
    procs, errs = [], []
    try:
        for w in range(2):
            errs.append(open(tmp_path / f"httpd_{w}.err", "wb"))
            procs.append(subprocess.Popen(
                [HTTPD, str(port), str(tmp_path / f"ring_t_{w}"),
                 "127.0.0.1", str(upstream.server_address[1]),
                 "--worker-stats-fd", str(stats_fd),
                 "--workers", "2", "--worker", str(w)],
                stdout=subprocess.PIPE, stderr=errs[-1],
                pass_fds=(stats_fd,),
                env=dict(os.environ, PINGOO_VERDICT_TIMEOUT_MS="150")))
            assert b"listening" in procs[-1].stdout.readline()
        # requests until ONE worker has released something and the other
        # nothing yet; then a scrape the OTHER answers must hold it
        releaser = None
        for i in range(200):
            assert b" 200" in _raw_get(port, f"/r{i}").split(b"\r\n", 1)[0]
            m = _scrape(port)
            busy = [p["worker"] for p in m["per_worker"] if p["fail_open"]]
            if len(busy) == 1:
                releaser = busy[0]
                break
            assert len(busy) == 0, "both released before one was seen alone"
        assert releaser is not None
        for _ in range(200):
            m = _scrape(port)
            if m["answered_by"] != releaser:
                break
        assert m["answered_by"] == 1 - releaser
        _check_totals(m)
        assert m["fail_open"] == m["per_worker"][releaser]["fail_open"] >= 1
        rel = m["release"]
        assert rel["tickets_deadline"] == rel["events_deadline"] \
            == m["fail_open"]
        assert rel["last_cause"] == "deadline" and rel["worker"] == releaser
        assert 150 < rel["oldest_age_max_ms"] < 1000
    finally:
        for proc in procs:
            proc.terminate()
            proc.wait(timeout=5)
        for err in errs:
            err.close()
        upstream.shutdown()
        for ring in rings:
            ring.close()
        os.close(stats_fd)
    log = (tmp_path / f"httpd_{releaser}.err").read_text()
    assert f"worker {releaser} of 2)" in log
    assert "RELEASED" not in (
        tmp_path / f"httpd_{1 - releaser}.err").read_text().split(
            "release summary")[0]


def test_the_timeline_renders_every_workers_ring_from_any_worker(tmp_path):
    """Two workers, a ring of passes each in the shared block: whichever
    answers /__pingoo/timeline renders both as `httpd/<phase>` spans
    (pid 10 + worker), laid end to end, beside its own requests' chains
    (`request` with `read`, `verdict_wait`, `upstream`, `reply` nested
    in it)."""
    from test_native_httpd import ClockStack, exchange

    stack = ClockStack(tmp_path, workers=2)
    try:
        threads = [threading.Thread(target=exchange, args=(
            stack.port, [f"/t{i}/{j}" for j in range(8)]))
            for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        docs = {}
        for _ in range(200):
            doc = stack.json("/__pingoo/timeline")
            docs.setdefault(doc["answered_by"], doc)
            if len(docs) == 2:
                break
        m = stack.json("/__pingoo/metrics")
    finally:
        stack.stop()
    assert set(docs) == {0, 1}
    assert all(p["requests"] > 0 for p in m["per_worker"]), \
        "the kernel gave one worker every connection"
    _check_totals(m)
    for answered_by, doc in docs.items():
        assert doc["workers"] == 2
        spans = {}
        for ev in doc["traceEvents"]:
            if ev["ph"] == "X" and ev["name"].startswith("httpd/"):
                spans.setdefault(ev["pid"], []).append(ev)
        assert set(spans) == {10, 11}, answered_by
        for pid, evs in spans.items():
            phases = {ev["name"][len("httpd/"):] for ev in evs}
            assert {"wait", "client", "verdicts"} <= phases, (pid, phases)
            evs.sort(key=lambda ev: ev["ts"])
            for a, b in zip(evs, evs[1:]):   # end to end, no overlap
                assert a["ts"] + a["dur"] <= b["ts"], pid
        requests = [ev for ev in doc["traceEvents"]
                    if ev["ph"] == "X" and ev["name"] == "request"]
        assert requests and all(ev["pid"] == 3 for ev in requests)
        assert len({ev["tid"] for ev in requests}) == len(requests)
        for req in requests:   # its row holds its segments, end to end
            inner = [ev for ev in doc["traceEvents"]
                     if ev["ph"] == "X" and ev["pid"] == 3
                     and ev["tid"] == req["tid"] and ev is not req]
            assert {"verdict_wait", "upstream"} <= \
                {ev["name"] for ev in inner}
            assert all(req["ts"] <= ev["ts"] and ev["ts"] + ev["dur"]
                       <= req["ts"] + req["dur"] for ev in inner)
            assert sum(ev["dur"] for ev in inner) == req["dur"]


def test_runqueue_us_is_published_only_where_it_was_read(tmp_path):
    """A block of two slots with only worker 0 running: its slot read
    /proc/thread-self/schedstat and carries `runqueue_us`, the slot that
    never ran has no such key (not a 0), and the listener has it."""
    from test_native_httpd import ClockStack, exchange

    if not os.path.exists("/proc/thread-self/schedstat"):
        pytest.skip("no /proc/thread-self/schedstat here")
    stack = ClockStack(tmp_path, workers=2, started=[0])
    try:
        exchange(stack.port, ["/a"])
        time.sleep(1.1)   # the once-a-second reading
        m = stack.json("/__pingoo/metrics")
    finally:
        stack.stop()
    assert "runqueue_us" in m["per_worker"][0]
    assert "runqueue_us" not in m["per_worker"][1]
    assert m["runqueue_us"] == m["per_worker"][0]["runqueue_us"] >= 0
    _check_totals(m)


# what /__pingoo/metrics (JSON) held before ISSUE 31, top level
KEYS_BEFORE = {
    "requests", "blocked", "captcha", "ua_rejected", "fail_open",
    "no_service", "upstream_fail", "upstream_tls_fail", "verdicts",
    "verdict_wait_ms_hist", "ring_pending", "awaiting", "connections",
    "pooled_upstreams", "degraded", "degraded_entered", "sidecar_up",
    "sidecar_epoch", "body", "release", "ring"}


# the worker's own clock (the loop account, the request chain, the
# calls it makes, the run-queue wait where /proc/thread-self/schedstat
# exists)
CLOCK_KEYS = {"loop_us", "passes", "empty_passes", "chain_us",
              "chain_requests", "chain_upstream_requests", "io_calls",
              "epoll_ctl_calls", "upstream_direct_sends"}
RUNQUEUE_KEY = {"runqueue_us"} if os.path.exists(
    "/proc/thread-self/schedstat") else set()


@pytest.mark.parametrize("flags", ["no worker flags", "one of one"])
def test_one_worker_keeps_its_keys(tmp_path, flags):
    import subprocess

    from test_native_plane import HTTPD, _block_rules

    stack = NativeStack(tmp_path, _block_rules())
    if flags == "one of one":   # as host/native_plane.py starts it
        stack.proc.kill()
        stack.proc.wait()
        stack.proc = subprocess.Popen(
            [HTTPD, str(stack.port), stack.ring_path, "127.0.0.1",
             str(stack.upstream.server_address[1]),
             "--worker-stats-fd", str(stack.stats_fd),
             "--workers", "1", "--worker", "0"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            pass_fds=(stack.stats_fd,))
        assert b"listening" in stack.proc.stdout.readline()
    try:
        m0 = _scrape(stack.port)  # holds NativeStack's warm-up row
        assert _exchange(stack.port, [("/ok", "ua"), ("/evil", "ua")]) \
            == [200, 403]
        m = _scrape(stack.port)
    finally:
        stack.stop()
    assert set(m) == KEYS_BEFORE | {"workers", "answered_by", "per_worker",
                                    "accepted", "closed_after_block"} \
        | CLOCK_KEYS | RUNQUEUE_KEY
    assert (m["workers"], m["answered_by"]) == (1, 0)
    # two scrapes and the exchange's one connection, which the 403 closed
    assert (m["accepted"], m["closed_after_block"]) == (3, 1)
    assert (m["requests"], m["verdicts"], m["blocked"], m["fail_open"]) \
        == (2, 2, 1, 0)
    assert [m["ring"][key] - m0["ring"][key]
            for key in ("enqueued", "verdicts_posted")] == [2, 2]
    assert m["release"]["worker"] == 0
    _check_totals(m)
