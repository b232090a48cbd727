"""Native data plane end-to-end: C++ epoll listener -> verdict ring ->
TPU sidecar -> 403/proxy, driven over real sockets."""

import http.server
import json
import os
import socket
import subprocess
import threading
import time

import pytest

from pingoo_tpu import native_ring
from pingoo_tpu.native_ring import Ring, RingSidecar

pytestmark = pytest.mark.skipif(
    not native_ring.ensure_built(), reason="native toolchain unavailable")

HTTPD = os.path.join(native_ring.NATIVE_DIR, "httpd")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _raw_get(port, path, ua="Mozilla/5.0", timeout=10, extra=""):
    s = socket.create_connection(("127.0.0.1", port), timeout=timeout)
    ua_line = f"user-agent: {ua}\r\n" if ua is not None else ""
    s.sendall(f"GET {path} HTTP/1.1\r\nhost: n.test\r\n{ua_line}{extra}"
              f"connection: close\r\n\r\n".encode())
    data = b""
    s.settimeout(timeout)
    try:
        while True:
            chunk = s.recv(65536)
            if not chunk:
                break
            data += chunk
    except socket.timeout:
        pass
    s.close()
    return data


@pytest.fixture(scope="module")
def native_stack(tmp_path_factory):
    if not os.path.exists(HTTPD):
        subprocess.run(["make", "-C", native_ring.NATIVE_DIR, "httpd"],
                       check=True, capture_output=True)
    tmp = tmp_path_factory.mktemp("native_httpd")

    class Upstream(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            body = f"upstream:{self.path}".encode()
            self.send_response(200)
            self.send_header("content-length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    upstream = http.server.HTTPServer(("127.0.0.1", 0), Upstream)
    up_port = upstream.server_address[1]
    threading.Thread(target=upstream.serve_forever, daemon=True).start()

    from pingoo_tpu.compiler import compile_ruleset
    from pingoo_tpu.config.schema import Action, RuleConfig
    from pingoo_tpu.expr import compile_expression

    rules = [
        RuleConfig(name="waf", actions=(Action.BLOCK,),
                   expression=compile_expression(
                       'http_request.path.starts_with("/.env")')),
        RuleConfig(name="bot", actions=(Action.CAPTCHA,),
                   expression=compile_expression(
                       'http_request.user_agent.contains("sqlmap")')),
    ]
    plan = compile_ruleset(rules, {})
    ring_path = str(tmp / "ring")
    ring = Ring(ring_path, capacity=1024, create=True)
    sidecar = RingSidecar(ring, plan, {}, max_batch=128)
    worker = threading.Thread(target=sidecar.run, daemon=True)
    worker.start()

    port = _free_port()
    proc = subprocess.Popen([HTTPD, str(port), ring_path, "127.0.0.1",
                             str(up_port)], stdout=subprocess.PIPE)
    line = proc.stdout.readline()
    assert b"listening" in line
    time.sleep(0.2)
    yield port
    proc.terminate()
    sidecar.stop()
    upstream.shutdown()
    ring.close()


class TestNativeHttpd:
    def test_allowed_request_proxied(self, native_stack):
        data = _raw_get(native_stack, "/hello")
        assert b"200" in data.split(b"\r\n", 1)[0]
        assert b"upstream:/hello" in data

    def test_waf_block(self, native_stack):
        data = _raw_get(native_stack, "/.env")
        assert data.startswith(b"HTTP/1.1 403")
        assert b"server: pingoo" in data

    def test_captcha_redirect(self, native_stack):
        data = _raw_get(native_stack, "/", ua="sqlmap/1.8")
        assert data.startswith(b"HTTP/1.1 302")
        assert b"/__pingoo/captcha" in data

    def test_empty_ua_blocked_without_ring(self, native_stack):
        data = _raw_get(native_stack, "/", ua="")
        assert data.startswith(b"HTTP/1.1 403")

    def test_malformed_request(self, native_stack):
        s = socket.create_connection(("127.0.0.1", native_stack), timeout=5)
        s.sendall(b"NONSENSE\r\n\r\n")
        data = s.recv(4096)
        s.close()
        assert data.startswith(b"HTTP/1.1 400")

    def test_metrics_json_complete(self, native_stack):
        """The truncation assertion for the metrics body: the old fixed
        1024-byte snprintf buffer could silently cut the JSON mid-field
        (invalid on the wire); the std::string builder must always emit
        a complete, parseable document with every schema field."""
        import json

        _raw_get(native_stack, "/warm")  # ensure counters are non-zero
        data = _raw_get(native_stack, "/__pingoo/metrics",
                        extra="accept: application/json\r\n")
        head, _, body = data.partition(b"\r\n\r\n")
        assert head.startswith(b"HTTP/1.1 200")
        assert b"application/json" in head
        clen = int([line for line in head.split(b"\r\n")
                    if line.lower().startswith(b"content-length")][0]
                   .split(b":")[1])
        assert len(body) == clen  # body not truncated mid-flight
        m = json.loads(body)  # complete + valid (the assertion proper)
        from pingoo_tpu.obs import schema

        for key in schema.NATIVE_JSON_KEYS:
            assert key in m, key
        assert set(m["ring"]) >= {"enqueued", "dequeued", "depth",
                                  "depth_hwm", "enqueue_full",
                                  "verdicts_posted", "verdict_post_full"}
        assert m["ring"]["enqueued"] >= 1

    def test_metrics_prometheus_default(self, native_stack):
        from pingoo_tpu.obs import schema
        from pingoo_tpu.obs.registry import lint_prometheus_text

        data = _raw_get(native_stack, "/__pingoo/metrics")
        head, _, body = data.partition(b"\r\n\r\n")
        assert head.startswith(b"HTTP/1.1 200")
        assert b"text/plain" in head
        text = body.decode()
        assert lint_prometheus_text(text) == []
        for name in schema.SHARED_METRICS:
            assert f'{name}{{plane="native"}}' in text, name
        assert 'pingoo_verdict_wait_ms_bucket{plane="native",le="+Inf"}' \
            in text
        assert 'pingoo_ring_depth{plane="native"}' in text

    def test_many_concurrent(self, native_stack):
        results = []

        def one(i):
            path = "/.env" if i % 3 == 0 else f"/ok{i}"
            results.append((i % 3 == 0, _raw_get(native_stack, path)))

        threads = [threading.Thread(target=one, args=(i,)) for i in range(30)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert len(results) == 30
        for blocked, data in results:
            if blocked:
                assert data.startswith(b"HTTP/1.1 403")
            else:
                assert b"upstream:/ok" in data


class TestReleaseWitness:
    """Every request the native plane lets through uninspected names its
    cause in the stats' `release` block and on stderr (ISSUE 30): a
    ring nobody drains, a 150 ms verdict deadline, a 100 ms liveness
    window."""

    def test_each_cause_is_counted_and_logged(self, tmp_path):
        import json

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
        ring_path = str(tmp_path / "ring")
        ring = Ring(ring_path, capacity=64, create=True)
        port = _free_port()
        err = open(tmp_path / "httpd.err", "wb")
        proc = subprocess.Popen(
            [HTTPD, str(port), ring_path, "127.0.0.1",
             str(upstream.server_address[1])],
            stdout=subprocess.PIPE, stderr=err,
            env=dict(os.environ, PINGOO_VERDICT_TIMEOUT_MS="150",
                     PINGOO_SIDECAR_TIMEOUT_MS="100"))
        try:
            assert b"listening" in proc.stdout.readline()
            # no sidecar ever attached: the per-ticket deadline governs
            assert b" 200" in _raw_get(port, "/a").split(b"\r\n", 1)[0]
            # a heartbeat lands and goes stale under an awaiting ticket:
            # degraded entry releases it, the next request bypasses
            ring.sidecar_attach()
            assert b" 200" in _raw_get(port, "/b").split(b"\r\n", 1)[0]
            assert b" 200" in _raw_get(port, "/c").split(b"\r\n", 1)[0]
            body = _raw_get(port, "/__pingoo/metrics",
                            extra="accept: application/json\r\n"
                            ).partition(b"\r\n\r\n")[2]
            prom = _raw_get(port, "/__pingoo/metrics").decode()
        finally:
            proc.terminate()
            proc.wait(timeout=5)
            err.close()
            upstream.shutdown()
            ring.close()
        m = json.loads(body)
        rel = m["release"]
        assert [rel[f"tickets_{c}"] for c in
                ("deadline", "degraded", "bypass", "ring_full")] \
            == [1, 1, 1, 0]
        assert [rel[f"events_{c}"] for c in
                ("deadline", "degraded", "bypass", "ring_full")] \
            == [1, 1, 1, 0]
        assert m["fail_open"] == 3 and m["degraded_entered"] == 1
        assert rel["last_cause"] == "bypass"
        assert 150 < rel["oldest_age_max_ms"] < 1000
        assert rel["heartbeat_age_max_ms"] > 100
        assert rel["heartbeat_late"] == 1
        for cause, n in (("deadline", 1), ("degraded", 1), ("bypass", 1),
                         ("ring_full", 0)):
            assert (f'pingoo_released_total{{plane="native",'
                    f'cause="{cause}"}} {n}') in prom
        log = (tmp_path / "httpd.err").read_text()
        for cause in ("deadline", "degraded", "bypass"):
            assert f"RELEASED 1 ticket(s) uninspected (cause {cause}," in log
        # which tickets, against how far the sidecar had got: nobody
        # drained this ring, so both were at or past its dequeue mark
        assert "tickets 0..0, sidecar posted below 0 and dequeued below 0" \
            in log
        assert "tickets 1..1, sidecar posted below 0 and dequeued below 0" \
            in log
        # and the whole block once more as the drained plane's last
        # line, where the end of a log still holds it
        last = [ln for ln in log.splitlines() if "release summary" in ln]
        summary = json.loads(last[-1].split("release summary ", 1)[1])
        moving = {"heartbeat_age_max_ms", "loop_gap_max_ms"}  # still stale
        assert {k: v for k, v in summary.items() if k not in moving} \
            == {k: v for k, v in rel.items() if k not in moving}


# -- the worker's own clock ------------------------------------------------------


class FakeSidecar:
    """A ring drainer without the engine: every request `delay_s` after
    it is dequeued gets block (1) when its path starts with /evil, else
    proxy (0). Heartbeats like the sidecar, so httpd supervises it."""

    def __init__(self, ring, delay_s=0.0):
        self.ring, self.delay_s = ring, delay_s
        self.stop = threading.Event()
        ring.sidecar_attach()
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def _run(self):
        while not self.stop.is_set():
            self.ring.heartbeat()
            rows = self.ring.dequeue_batch(64)
            if not len(rows):
                time.sleep(0.0005)
                continue
            if self.delay_s:
                time.sleep(self.delay_s)
            for row in rows:
                path = bytes(row["path"][:row["path_len"]])
                self.ring.post_verdict(int(row["ticket"]),
                                       1 if path.startswith(b"/evil") else 0)

    def close(self):
        self.stop.set()
        self.thread.join(5)


class SlowUpstream(http.server.BaseHTTPRequestHandler):
    """`/slow...` answers after UPSTREAM_S, everything else at once."""

    UPSTREAM_S = 0.06

    def do_GET(self):
        if self.path.startswith("/slow"):
            time.sleep(self.UPSTREAM_S)
        self.send_response(200)
        self.send_header("content-length", "2")
        self.end_headers()
        self.wfile.write(b"up")

    def log_message(self, *a):
        pass


class ClockStack:
    """One httpd (or `workers` of them on one port and one counter
    block, a ring and a FakeSidecar each), a slow-on-demand upstream."""

    def __init__(self, tmp_path, workers=1, started=None, delay_s=0.02):
        self.upstream = http.server.ThreadingHTTPServer(
            ("127.0.0.1", 0), SlowUpstream)
        threading.Thread(target=self.upstream.serve_forever,
                         daemon=True).start()
        self.port = _free_port()
        self.stats_fd = os.memfd_create("workers")
        self.rings, self.sidecars, self.procs, self.errs = [], [], [], []
        for w in (range(workers) if started is None else started):
            ring = Ring(str(tmp_path / f"ring_{w}"), capacity=1024,
                        create=True)
            self.rings.append(ring)
            self.sidecars.append(FakeSidecar(ring, delay_s))
            self.errs.append(open(tmp_path / f"httpd_{w}.err", "wb"))
            self.procs.append(subprocess.Popen(
                [HTTPD, str(self.port), str(tmp_path / f"ring_{w}"),
                 "127.0.0.1", str(self.upstream.server_address[1]),
                 "--worker-stats-fd", str(self.stats_fd),
                 "--workers", str(workers), "--worker", str(w)],
                stdout=subprocess.PIPE, stderr=self.errs[-1],
                pass_fds=(self.stats_fd,)))
            assert b"listening" in self.procs[-1].stdout.readline()

    def json(self, path):
        extra = "accept: application/json\r\n" if path.endswith("metrics") \
            else ""
        return json.loads(_raw_get(self.port, path, extra=extra).partition(
            b"\r\n\r\n")[2])

    def stop(self):
        for proc in self.procs:
            proc.terminate()
            proc.wait(timeout=10)
        for sidecar in self.sidecars:
            sidecar.close()
        for err in self.errs:
            err.close()
        for ring in self.rings:
            ring.close()
        self.upstream.shutdown()
        os.close(self.stats_fd)


def exchange(port, paths):
    """Requests one after another on one keep-alive connection ->
    [(status, client round trip µs)]; a 403 ends the connection."""
    from test_native_plane import recv_one_response

    c = socket.create_connection(("127.0.0.1", port), timeout=30)
    out = []
    for path in paths:
        t0 = time.monotonic()
        c.sendall(f"GET {path} HTTP/1.1\r\nhost: c.test\r\n"
                  f"user-agent: ua\r\n\r\n".encode())
        status = int(recv_one_response(c).split(b" ", 2)[1])
        out.append((status, (time.monotonic() - t0) * 1e6))
        if status != 200:
            break
    c.close()
    return out


PHASES = ("wait", "verdicts", "supervise", "accept", "client", "upstream",
          "tls", "close", "sweep")
SEGMENTS = ("read", "verdict", "upstream", "reply")


class TestWorkerClock:
    def test_the_phases_partition_a_workers_wall_time(self, tmp_path):
        stack = ClockStack(tmp_path)
        try:
            exchange(stack.port, ["/a", "/b", "/evil"])
            t1 = time.monotonic()
            m1 = stack.json("/__pingoo/metrics")
            load = threading.Thread(target=exchange, args=(
                stack.port, [f"/n{i}" for i in range(20)]))
            load.start()
            time.sleep(1.0)
            load.join(30)
            t2 = time.monotonic()
            m2 = stack.json("/__pingoo/metrics")
        finally:
            stack.stop()
        w1, w2 = m1["per_worker"][0], m2["per_worker"][0]
        assert set(w2["loop_us"]) == set(PHASES)
        spent = sum(w2["loop_us"][p] - w1["loop_us"][p] for p in PHASES)
        # the answering worker: the phases add up to its clock exactly,
        # and its clock to the client's within 1 %
        assert spent == w2["loop_at_us"] - w1["loop_at_us"]
        assert spent == pytest.approx((t2 - t1) * 1e6, rel=0.01)
        for p in ("wait", "verdicts", "client", "upstream"):
            assert w2["loop_us"][p] > w1["loop_us"][p], p
        assert m2["passes"] > m2["empty_passes"] > 0
        assert m2["loop_us"] == w2["loop_us"]  # one worker: its own

    def test_a_requests_segments_add_up_to_its_residence(self, tmp_path):
        stack = ClockStack(tmp_path, delay_s=0.02)
        try:
            rtts = dict(zip(("/slow", "/fast", "/evil"), (
                rtt for _, rtt in exchange(stack.port,
                                           ["/slow", "/fast", "/evil"]))))
            fr = stack.json("/__pingoo/flightrecorder")
        finally:
            stack.stop()
        entries = {e["path"]: e for e in fr["entries"] if e["end_us"]}
        assert set(entries) == {"/slow", "/fast", "/evil"}
        for path, e in entries.items():
            seg = e["seg_us"]
            assert set(seg) == set(SEGMENTS)
            assert sum(seg.values()) == e["end_us"] - e["first_us"], path
            # the residence is inside the client's round trip
            assert e["end_us"] - e["first_us"] <= rtts[path] + 1000, path
            # the sidecar held every verdict 20 ms
            assert seg["verdict"] >= 20_000, path
            assert e["wait_ms"] == seg["verdict"] // 1000
            assert e["ticket"] is not None
        assert entries["/slow"]["seg_us"]["upstream"] >= \
            SlowUpstream.UPSTREAM_S * 1e6
        assert entries["/fast"]["seg_us"]["upstream"] > 0
        assert entries["/evil"]["seg_us"]["upstream"] == 0  # a 403
        assert entries["/evil"]["decided"] == 1

    def test_chain_requests_equal_the_requests_answered(self, tmp_path):
        stack = ClockStack(tmp_path, delay_s=0.005)
        try:
            m1 = stack.json("/__pingoo/metrics")
            answered = []
            for i in range(4):
                answered += exchange(stack.port, [f"/ok{i}/{j}"
                                                  for j in range(10)]
                                     + [f"/evil{i}"])
            m2 = stack.json("/__pingoo/metrics")
        finally:
            stack.stop()
        proxied = sum(status == 200 for status, _ in answered)
        assert (proxied, len(answered)) == (40, 44)
        assert m2["chain_requests"] - m1["chain_requests"] == 44
        assert m2["requests"] - m1["requests"] == 44
        assert m2["chain_upstream_requests"] \
            - m1["chain_upstream_requests"] == 40
        verdict_ms = (m2["chain_us"]["verdict"] - m1["chain_us"]["verdict"]) \
            / 44 / 1e3
        assert verdict_ms >= 5.0

    def test_a_long_pass_gap_leaves_the_wedges_witness(self, tmp_path):
        import signal

        stack = ClockStack(tmp_path)
        try:
            exchange(stack.port, ["/a"])
            stack.procs[0].send_signal(signal.SIGSTOP)
            time.sleep(0.4)
            stack.procs[0].send_signal(signal.SIGCONT)
            m = stack.json("/__pingoo/metrics")
        finally:
            stack.stop()
        rel = m["release"]
        assert rel["wedges"] >= 1 and rel["wedge_gap_ms"] >= 350
        assert rel["loop_gap_max_ms"] >= rel["wedge_gap_ms"]
        # stopped, not runnable: it waited on no run queue meanwhile
        assert rel["wedge_runqueue_us"] < 250_000
        log = (tmp_path / "httpd_0.err").read_text()
        assert "WEDGE pass gap" in log and "worker 0 of 1)" in log
