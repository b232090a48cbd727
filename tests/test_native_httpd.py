"""Native data plane end-to-end: C++ epoll listener -> verdict ring ->
TPU sidecar -> 403/proxy, driven over real sockets."""

import http.server
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
