"""Unit tests: JWT/JOSE, TLS manager, captcha manager, discovery,
verdict service fallback."""

import asyncio
import json
import os
import ssl
import time

import pytest

from pingoo_tpu.host import jwt as jose
from pingoo_tpu.host.captcha import CaptchaManager, generate_captcha_client_id
from pingoo_tpu.host.tlsmgr import TlsManager, cert_sans, generate_self_signed


class TestJose:
    @pytest.mark.parametrize("alg", [jose.ALG_HS512, jose.ALG_EDDSA,
                                     jose.ALG_ES256, jose.ALG_ES512])
    def test_sign_verify_roundtrip(self, alg):
        key = jose.Key.generate(alg, kid="k1")
        now = int(time.time())
        token = jose.sign(key, {"sub": "x", "exp": now + 60, "iss": "pingoo"})
        claims = jose.parse_and_verify(token, key, issuer="pingoo")
        assert claims["sub"] == "x"

    def test_tampered_signature_rejected(self):
        key = jose.Key.generate(jose.ALG_EDDSA)
        token = jose.sign(key, {"sub": "x"})
        head, payload, sig = token.split(".")
        bad = head + "." + payload + "." + sig[:-4] + "AAAA"
        with pytest.raises(jose.JwtError, match="signature"):
            jose.parse_and_verify(bad, key)

    def test_tampered_claims_rejected(self):
        key = jose.Key.generate(jose.ALG_EDDSA)
        token = jose.sign(key, {"admin": False})
        head, _, sig = token.split(".")
        forged_claims = jose.b64url_encode(json.dumps({"admin": True}).encode())
        with pytest.raises(jose.JwtError):
            jose.parse_and_verify(head + "." + forged_claims + "." + sig, key)

    def test_expiry_and_nbf(self):
        key = jose.Key.generate(jose.ALG_HS512)
        now = time.time()
        token = jose.sign(key, {"exp": int(now - 3600)})
        with pytest.raises(jose.JwtError, match="expired"):
            jose.parse_and_verify(token, key)
        # within drift tolerance -> accepted (jwt.rs drift checks)
        token = jose.sign(key, {"exp": int(now - 10)})
        jose.parse_and_verify(token, key, drift_tolerance_s=60)
        token = jose.sign(key, {"nbf": int(now + 3600)})
        with pytest.raises(jose.JwtError, match="not yet valid"):
            jose.parse_and_verify(token, key)

    def test_audience_issuer(self):
        key = jose.Key.generate(jose.ALG_HS512)
        token = jose.sign(key, {"aud": ["a", "b"], "iss": "me"})
        jose.parse_and_verify(token, key, audience="a", issuer="me")
        with pytest.raises(jose.JwtError, match="audience"):
            jose.parse_and_verify(token, key, audience="c")
        with pytest.raises(jose.JwtError, match="issuer"):
            jose.parse_and_verify(token, key, issuer="you")

    def test_alg_confusion_rejected(self):
        """Token signed HS512 must not verify against an Ed25519 key."""
        hs = jose.Key.generate(jose.ALG_HS512)
        ed = jose.Key.generate(jose.ALG_EDDSA)
        token = jose.sign(hs, {"sub": "x"})
        with pytest.raises(jose.JwtError, match="algorithm mismatch"):
            jose.parse_and_verify(token, ed)

    @pytest.mark.parametrize("alg", [jose.ALG_EDDSA, jose.ALG_ES256,
                                     jose.ALG_ES512, jose.ALG_HS512])
    def test_jwk_roundtrip(self, alg):
        key = jose.Key.generate(alg, kid="kid9")
        jwks_json = jose.Jwks(keys=[key]).to_json(include_private=True)
        restored = jose.Jwks.from_json(jwks_json).find("kid9")
        token = jose.sign(key, {"sub": "x"})
        assert jose.parse_and_verify(token, restored)["sub"] == "x"
        # public-only JWKS still verifies (asymmetric algs)
        if alg != jose.ALG_HS512:
            pub = jose.Jwks.from_json(
                jose.Jwks(keys=[key]).to_json()).find("kid9")
            assert jose.parse_and_verify(token, pub)["sub"] == "x"


class TestTlsManager:
    def test_self_signed_and_sni(self, tmp_path):
        mgr = TlsManager(str(tmp_path / "tls"))
        # Default '*' cert generated on first boot (tls_manager.rs:193-231).
        assert (tmp_path / "tls" / "default.pingoo.pem").exists()
        assert mgr.resolve("anything.example") is not None

        cert, key = generate_self_signed(["example.com", "*.api.example.com"])
        (tmp_path / "tls" / "example.pem").write_bytes(cert)
        (tmp_path / "tls" / "example.key").write_bytes(key)
        mgr2 = TlsManager(str(tmp_path / "tls"))
        exact = mgr2.resolve("example.com")
        wild = mgr2.resolve("v1.api.example.com")
        default = mgr2.resolve("other.test")
        assert exact is not None and wild is not None and default is not None
        assert exact is not default and wild is not default

    def test_cert_sans(self):
        cert, _ = generate_self_signed(["a.test", "*.b.test"])
        assert set(cert_sans(cert)) == {"a.test", "*.b.test"}

    def test_tls13_only(self, tmp_path):
        mgr = TlsManager(str(tmp_path / "tls"))
        ctx = mgr.server_context()
        assert ctx.minimum_version == ssl.TLSVersion.TLSv1_3


class TestCaptchaManager:
    def test_pow_flow(self, tmp_path):
        mgr = CaptchaManager(str(tmp_path / "jwks.json"))
        client_id = generate_captcha_client_id("1.2.3.4", "UA", "host")
        body, cookie = mgr.init_challenge(client_id)
        token = cookie.split("=", 1)[1].split(";")[0]
        import hashlib

        nonce = 0
        while True:
            digest = hashlib.sha256(
                (body["challenge"] + str(nonce)).encode()).hexdigest()
            if digest.startswith("0" * body["difficulty"]):
                break
            nonce += 1
        ok, verified_cookie = mgr.verify_challenge(
            {"nonce": str(nonce), "hash": digest}, token, client_id)
        assert ok and verified_cookie
        verified_token = verified_cookie.split("=", 1)[1].split(";")[0]
        assert mgr.is_verified(verified_token, client_id)
        # A different client id must not validate (constant-time compare).
        other = generate_captcha_client_id("5.6.7.8", "UA", "host")
        assert not mgr.is_verified(verified_token, other)

    def test_wrong_pow_rejected(self, tmp_path):
        mgr = CaptchaManager(str(tmp_path / "jwks.json"))
        client_id = generate_captcha_client_id("1.2.3.4", "UA", "host")
        _, cookie = mgr.init_challenge(client_id)
        token = cookie.split("=", 1)[1].split(";")[0]
        ok, _ = mgr.verify_challenge(
            {"nonce": "1", "hash": "f" * 64}, token, client_id)
        assert not ok

    def test_key_persistence(self, tmp_path):
        path = str(tmp_path / "jwks.json")
        mgr1 = CaptchaManager(path)
        client_id = generate_captcha_client_id("1.2.3.4", "UA", "host")
        _, cookie = mgr1.init_challenge(client_id)
        # A new manager instance reuses the persisted key (captcha.rs:78-123).
        mgr2 = CaptchaManager(path)
        token = cookie.split("=", 1)[1].split(";")[0]
        from pingoo_tpu.host import jwt as j

        claims = j.parse_and_verify(token, mgr2.key, issuer="pingoo",
                                    drift_tolerance_s=5)
        assert claims["client_id"] == client_id


class TestDiscovery:
    def test_static_and_dns(self, loop_runner):
        from pingoo_tpu.config import parse_config
        from pingoo_tpu.host.discovery import ServiceRegistry

        config = parse_config({
            "listeners": {"l": {"address": "http://0.0.0.0:8080"}},
            "services": {
                "s": {"http_proxy": ["http://127.0.0.1:9000",
                                      "http://localhost:9001"]},
            },
        })
        registry = ServiceRegistry(config.services, enable_docker=False,
                                   enable_dns=True)
        loop_runner.run(registry.discover())
        ups = registry.get_upstreams("s")
        assert {(u.ip, u.port) for u in ups} >= {("127.0.0.1", 9000),
                                                ("127.0.0.1", 9001)}
        assert registry.get_upstreams("unknown") == []


class TestHostParsing:
    def test_ipv6_host_header(self):
        from pingoo_tpu.host.httpd import Request, get_host

        req = Request(method="GET", target="/", path="/",
                      headers=[("host", "[::1]:8080")])
        assert get_host(req) == "[::1]"
        req = Request(method="GET", target="/", path="/",
                      headers=[("host", "example.com:443")])
        assert get_host(req) == "example.com"
        req = Request(method="GET", target="http://[2001:db8::1]:80/x",
                      path="/x", headers=[])
        assert get_host(req) == "[2001:db8::1]"

    def test_overlong_host_becomes_empty(self):
        """Reference get_host: heapless from_str overflow -> EMPTY, not
        truncated (http_listener.rs:287,292)."""
        from pingoo_tpu.host.httpd import Request, get_host

        long_host = "a" * 300 + ".example.com"
        req = Request(method="GET", target="/", path="/",
                      headers=[("host", long_host)])
        assert get_host(req) == ""
        ok = "b" * 256  # exactly at the cap still fits
        req = Request(method="GET", target="/", path="/",
                      headers=[("host", ok)])
        assert get_host(req) == ok


class TestRingCapacityValidation:
    def test_non_pow2_rejected(self, tmp_path):
        from pingoo_tpu import native_ring

        if not native_ring.ensure_built():
            pytest.skip("no native toolchain")
        with pytest.raises(ValueError, match="power of two"):
            native_ring.Ring(str(tmp_path / "r"), capacity=1000, create=True)


class TestBackendSelection:
    """JAX decides the backend, once, in the process that serves
    (ISSUE 21): no probe child, no silent CPU pin. A platform that
    cannot initialise fails the boot with JAX's own error; --no-device
    pins the CPU explicitly, before first use."""

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def _config(self, tmp_path):
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        cfg = tmp_path / "pingoo.yml"
        cfg.write_text(
            "listeners:\n  http:\n"
            f"    address: http://127.0.0.1:{port}\n"
            "services:\n  app:\n    http_proxy: [http://127.0.0.1:9]\n"
            "rules:\n  env:\n"
            "    expression: http_request.path.starts_with(\"/.env\")\n"
            "    actions: [{action: block}]\n")
        return str(cfg), port

    def _argv(self, tmp_path, *extra):
        import sys

        cfg, port = self._config(tmp_path)
        return [sys.executable, "-m", "pingoo_tpu", "--config", cfg,
                "--no-docker", "--captcha-jwks",
                str(tmp_path / "jwks.json"), *extra], port

    def test_bogus_platform_fails_the_boot_with_the_jax_error(
            self, tmp_path):
        import subprocess

        env = dict(os.environ, JAX_PLATFORMS="bogus",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
        argv, _ = self._argv(tmp_path)
        proc = subprocess.run(argv, cwd=self.REPO, env=env, timeout=120,
                              capture_output=True, text=True)
        assert proc.returncode != 0
        assert "bogus" in proc.stderr  # JAX's own message, verbatim
        assert "starting pingoo-tpu" not in proc.stderr

    def test_a_required_capability_the_build_lacks_refuses_the_start(
            self, capsys):
        """`--require CAPABILITY` (ISSUE 31): a deployment's file names
        what it needs of the build; one without it refuses the command
        line (exit 2, no boot line) instead of serving without it, as a
        build older than the flag refuses the flag."""
        from pingoo_tpu.__main__ import CAPABILITIES, main

        assert "listener-metrics" in CAPABILITIES
        with pytest.raises(SystemExit) as exc:
            main(["--require", "no-such-capability"])
        assert exc.value.code == 2
        assert "no-such-capability" in capsys.readouterr().err

    def test_no_device_pins_cpu_and_still_boots(self, tmp_path):
        """--no-device beats even an unusable ambient platform, states
        what it serves on in the boot line, and drains to exit 0."""
        import signal
        import socket
        import subprocess

        env = dict(os.environ, JAX_PLATFORMS="bogus",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
        argv, port = self._argv(tmp_path, "--no-device",
                                "--require", "listener-metrics")
        proc = subprocess.Popen(argv, cwd=self.REPO, env=env,
                                stderr=subprocess.PIPE, text=True)
        try:
            boot = None
            for line in proc.stderr:
                rec = json.loads(line) if line.startswith("{") else {}
                if rec.get("message") == "starting pingoo-tpu":
                    boot = rec
                    break
            assert boot is not None, "no boot line"
            assert boot["platform"] == "cpu" and boot["device"] is False
            assert boot["device_count"] >= 1 and boot["device_kind"]
            assert boot["compile_cache"] == str(tmp_path / "cache")
            assert "listener-metrics" in boot["capabilities"]
            deadline = time.monotonic() + 60
            while True:  # listening == the signal handlers are in place
                try:
                    socket.create_connection(("127.0.0.1", port), 1).close()
                    break
                except OSError:
                    assert time.monotonic() < deadline, "never listened"
                    time.sleep(0.1)
            time.sleep(0.5)  # run() installs them right after the bind
            proc.send_signal(signal.SIGTERM)
            assert proc.wait(timeout=60) == 0
        finally:
            if proc.poll() is None:
                proc.kill()


class TestProfilerHook:
    def test_profile_dir_captures_trace(self, loop_runner, tmp_path,
                                        monkeypatch):
        """PINGOO_PROFILE_DIR wraps the serving window in a
        jax.profiler trace (SURVEY §5 tracing/profiling)."""
        from pingoo_tpu.compiler import compile_ruleset
        from pingoo_tpu.config.schema import Action, RuleConfig
        from pingoo_tpu.engine.batch import RequestTuple
        from pingoo_tpu.engine.service import VerdictService
        from pingoo_tpu.expr import compile_expression

        monkeypatch.setenv("PINGOO_PROFILE_DIR", str(tmp_path))
        rules = [RuleConfig(
            name="r", actions=(Action.BLOCK,),
            expression=compile_expression('http_request.path == "/x"'))]
        plan = compile_ruleset(rules, {})
        svc = VerdictService(plan, {}, use_device=True, max_wait_us=100)

        async def flow():
            await svc.start()
            try:
                return await svc.evaluate(RequestTuple(path="/x"))
            finally:
                await svc.stop()

        v = loop_runner.run(flow())
        assert v.block
        # jax writes plugins/profile/<ts>/*.xplane.pb under the dir
        produced = list(tmp_path.rglob("*"))
        assert any(p.is_file() for p in produced), produced


class TestVerdictServiceFallback:
    def test_host_fallback_on_device_error(self, loop_runner):
        from pingoo_tpu.compiler import compile_ruleset
        from pingoo_tpu.config.schema import Action, RuleConfig
        from pingoo_tpu.engine.batch import RequestTuple
        from pingoo_tpu.engine.service import VerdictService
        from pingoo_tpu.expr import compile_expression

        rules = [RuleConfig(
            name="r", actions=(Action.BLOCK,),
            expression=compile_expression('http_request.path == "/x"'))]
        plan = compile_ruleset(rules, {})
        svc = VerdictService(plan, {}, use_device=True, max_wait_us=100)
        svc._verdict_fn = None  # simulate a dead device path

        async def flow():
            await svc.start()
            try:
                v1 = await svc.evaluate(RequestTuple(path="/x"))
                v2 = await svc.evaluate(RequestTuple(path="/y"))
                return v1, v2
            finally:
                await svc.stop()

        v1, v2 = loop_runner.run(flow())
        assert v1.block and not v2.block
        assert svc.stats.device_errors >= 1
        assert svc.stats.host_fallback_batches >= 1

    def test_collector_survives_total_failure(self, loop_runner):
        """Even if BOTH device and host paths explode, requests must
        resolve fail-open instead of hanging forever."""
        from pingoo_tpu.compiler import compile_ruleset
        from pingoo_tpu.config.schema import Action, RuleConfig
        from pingoo_tpu.engine.batch import RequestTuple
        from pingoo_tpu.engine.service import VerdictService
        from pingoo_tpu.expr import compile_expression

        rules = [RuleConfig(
            name="r", actions=(Action.BLOCK,),
            expression=compile_expression("true"))]
        plan = compile_ruleset(rules, {})
        svc = VerdictService(plan, {}, use_device=False, max_wait_us=100)
        svc._evaluate_host = lambda batch: (_ for _ in ()).throw(
            RuntimeError("boom"))

        async def flow():
            await svc.start()
            try:
                import asyncio

                return await asyncio.wait_for(
                    svc.evaluate(RequestTuple(path="/x")), timeout=5), \
                    await asyncio.wait_for(
                        svc.evaluate(RequestTuple(path="/y")), timeout=5)
            finally:
                await svc.stop()

        v1, v2 = loop_runner.run(flow())
        assert v1.action == 0 and v2.action == 0  # fail-open, not hung


class TestOverflowRouting:
    """Fields past device capacity -> host interpreter over the FULL
    strings (reference matches full path/url; padding must not bypass)."""

    def _service(self, expr, use_device):
        from pingoo_tpu.compiler import compile_ruleset
        from pingoo_tpu.config.schema import Action, RuleConfig
        from pingoo_tpu.engine.service import VerdictService
        from pingoo_tpu.expr import compile_expression

        rules = [RuleConfig(name="r", actions=(Action.BLOCK,),
                            expression=compile_expression(expr))]
        plan = compile_ruleset(rules, {})
        return plan, VerdictService(plan, {}, use_device=use_device,
                                    max_wait_us=100)

    @pytest.mark.parametrize("use_device", [True, False])
    def test_padded_url_cannot_bypass_contains(self, use_device):
        from pingoo_tpu.engine.batch import RequestTuple

        plan, svc = self._service(
            'http_request.url.contains("attackmarker")', use_device)
        cap = plan.field_specs["url"]
        long_url = "/" + "A" * (cap + 100) + "attackmarker"
        matched = svc._evaluate_sync([
            RequestTuple(url=long_url, path="/x"),
            RequestTuple(url="/clean", path="/x"),
            RequestTuple(url="/attackmarker", path="/x"),
        ])
        assert matched[0, 0], "marker past device cap must still match"
        assert not matched[1, 0]
        assert matched[2, 0]

    def test_overflow_length_uses_full_string(self):
        from pingoo_tpu.engine.batch import RequestTuple

        plan, svc = self._service("length(http_request.path) > 3000", False)
        cap = plan.field_specs["path"]
        matched = svc._evaluate_sync([
            RequestTuple(path="/" + "p" * 3200),
            RequestTuple(path="/" + "p" * (cap - 10)),
        ])
        assert matched[0, 0]
        assert not matched[1, 0]

    def test_encode_marks_overflow_rows(self):
        from pingoo_tpu.engine.batch import RequestTuple, encode_requests

        batch = encode_requests([
            RequestTuple(url="/" + "x" * 5000),
            RequestTuple(url="/short"),
        ])
        assert batch.overflow.tolist() == [True, False]
        assert "overflow" not in batch.arrays  # never rides the pytree


class TestDiscoveryTtlAndWarnOnce:
    def _registry_with_dns_target(self):
        from pingoo_tpu.config.schema import ServiceConfig, Upstream
        from pingoo_tpu.host.discovery import ServiceRegistry

        svc = ServiceConfig(
            name="s", route=None,
            http_proxy=(Upstream(hostname="backend.test", port=9000,
                                 tls=False, ip=None),))
        return ServiceRegistry([svc], enable_docker=False, enable_dns=True)

    def test_dns_positive_min_ttl_suppresses_reresolve(self, loop_runner):
        """dns.rs positive_min_ttl=60s equivalent: a fresh answer is not
        re-resolved on every 2s tick."""
        reg = self._registry_with_dns_target()
        calls = {"n": 0}

        async def stub(hostname, port):
            calls["n"] += 1
            return [(2, 1, 6, "", ("10.0.0.5", port))]

        reg._getaddrinfo = stub
        for _ in range(5):
            loop_runner.run(reg.discover())
        assert calls["n"] == 1  # floor: one resolution within the window
        assert [u.ip for u in reg.get_upstreams("s")] == ["10.0.0.5"]

    def test_dns_failure_serves_last_known_within_negative_ttl(
            self, loop_runner):
        reg = self._registry_with_dns_target()
        state = {"fail": False}

        async def stub(hostname, port):
            if state["fail"]:
                raise OSError("resolver down")
            return [(2, 1, 6, "", ("10.0.0.7", port))]

        reg._getaddrinfo = stub
        loop_runner.run(reg.discover())
        # Age the cache past the positive floor, then fail the resolver.
        key = ("backend.test", 9000)
        ups, ts = reg._dns_cache[key]
        reg._dns_cache[key] = (ups, ts - 120)
        state["fail"] = True
        loop_runner.run(reg.discover())
        assert [u.ip for u in reg.get_upstreams("s")] == ["10.0.0.7"]
        # Past the negative cap the stale answer drops.
        reg._dns_cache[key] = (ups, ts - 4000)
        loop_runner.run(reg.discover())
        assert reg.get_upstreams("s") == []

    def test_docker_problem_container_warned_once(self, caplog):
        import logging

        from pingoo_tpu.host.discovery import ServiceRegistry

        reg = ServiceRegistry([], enable_docker=True, enable_dns=False)
        with caplog.at_level(logging.WARNING):
            for _ in range(3):
                reg._warn_container("abc123def456", "no usable port")
        warnings = [r for r in caplog.records
                    if "abc123def456"[:12] in r.getMessage()]
        assert len(warnings) == 1  # once per idle window, not per tick


class TestDockerDiscoveryEndToEnd:
    """Full Docker-discovery drive against a MOCK daemon on a real unix
    socket (reference docker/src/client.rs:41-145 + service_registry
    docker merge): labeled containers become upstreams; chunked
    transfer-encoding is de-framed; hot-swap applies on the next tick."""

    def _mock_daemon(self, tmp_path, payload_json):
        import socket as socketmod

        path = str(tmp_path / "docker.sock")
        srv = socketmod.socket(socketmod.AF_UNIX, socketmod.SOCK_STREAM)
        srv.bind(path)
        srv.listen(4)
        state = {"payload": payload_json}

        def serve():
            import threading as th

            while True:
                try:
                    conn, _ = srv.accept()
                except OSError:
                    return

                def handle(conn=conn):
                    req = b""
                    while b"\r\n\r\n" not in req:
                        ch = conn.recv(65536)
                        if not ch:
                            break
                        req += ch
                    if b"GET /v1.43/containers/json" not in req:
                        # surface protocol mismatches in the TEST, not
                        # as a swallowed OSError in the daemon thread
                        state["bad_request"] = bytes(req[:200])
                        conn.sendall(b"HTTP/1.1 400 Bad Request\r\n"
                                     b"content-length: 0\r\n\r\n")
                        conn.close()
                        return
                    body = state["payload"].encode()
                    # chunked framing: exercises the client's de-chunker
                    half = len(body) // 2
                    chunks = b""
                    for part in (body[:half], body[half:]):
                        chunks += (f"{len(part):x}\r\n".encode()
                                   + part + b"\r\n")
                    chunks += b"0\r\n\r\n"
                    conn.sendall(
                        b"HTTP/1.1 200 OK\r\n"
                        b"content-type: application/json\r\n"
                        b"transfer-encoding: chunked\r\n"
                        b"connection: close\r\n\r\n" + chunks)
                    conn.close()

                th.Thread(target=handle, daemon=True).start()

        import threading as th

        th.Thread(target=serve, daemon=True).start()
        return path, srv, state

    def test_labeled_containers_become_upstreams(self, tmp_path,
                                                 loop_runner):
        import json as jsonmod

        from pingoo_tpu.host.discovery import ServiceRegistry
        from pingoo_tpu.config.schema import ServiceConfig

        containers = [
            {   # labeled with explicit port
                "Id": "aaa111",
                "Labels": {"pingoo.service": "api", "pingoo.port": "8080"},
                "NetworkSettings": {"Networks": {
                    "bridge": {"IPAddress": "172.17.0.2"}}},
            },
            {   # single private port: inferred
                "Id": "bbb222",
                "Labels": {"pingoo.service": "api"},
                "Ports": [{"PrivatePort": 9000}],
                "NetworkSettings": {"Networks": {
                    "bridge": {"IPAddress": "172.17.0.3"}}},
            },
            {   # unlabeled: ignored
                "Id": "ccc333",
                "Labels": {},
                "NetworkSettings": {"Networks": {
                    "bridge": {"IPAddress": "172.17.0.4"}}},
            },
        ]
        path, srv, state = self._mock_daemon(
            tmp_path, jsonmod.dumps(containers))
        try:
            svc = ServiceConfig(name="api", http_proxy=())
            reg = ServiceRegistry([svc], enable_docker=True,
                                  enable_dns=False, docker_socket=path)
            loop_runner.run(reg.discover())
            ups = reg.get_upstreams("api")
            got = sorted((u.ip, u.port) for u in ups)
            assert "bad_request" not in state, state["bad_request"]
            assert got == [("172.17.0.2", 8080), ("172.17.0.3", 9000)], got
            # hot-swap: a container goes away -> next tick drops it
            state["payload"] = jsonmod.dumps(containers[:1])
            loop_runner.run(reg.discover())
            ups = reg.get_upstreams("api")
            assert [(u.ip, u.port) for u in ups] == [("172.17.0.2", 8080)]
        finally:
            srv.close()
