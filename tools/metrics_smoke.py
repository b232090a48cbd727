#!/usr/bin/env python
"""Live metrics smoke (make metrics-smoke; ISSUE 2 satellite).

Boots the real serving pieces on loopback — native C++ httpd + shm ring
+ Python ring sidecar, plus an in-process Python HttpListener — drives
a few requests through both planes, scrapes BOTH /__pingoo/metrics
endpoints in BOTH formats, and validates:

  * Prometheus text passes the exposition lint on both planes;
  * every shared metric name (obs/schema.py) appears on both planes;
  * JSON (Accept: application/json) parses and keeps the legacy keys;
  * the native JSON carries the shm ring telemetry block;
  * a normal response carries x-pingoo-trace-id.

ISSUE 5 additions (verdict provenance): the shadow-parity auditor runs
against the live traffic on BOTH engine planes (PINGOO_PARITY_SAMPLE=1
below), a fault-injected path proves an oracle divergence is observable
via the mismatch counters AND the flight-recorder dump, the
/__pingoo/flightrecorder endpoints answer on both the Python listener
and the native httpd, and /__pingoo/explain returns per-rule provenance
that agrees with the interpreter.

Runs on the CPU backend (JAX_PLATFORMS=cpu) in ~a minute; exits 0/1.
"""

import asyncio
import http.server
import json
import os
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Provenance live checks: audit every batch, and let the chaos knob
# inject an ORACLE-side divergence for this one path (the served
# verdicts stay correct — that is the point of the auditor).
os.environ.setdefault("PINGOO_PARITY_SAMPLE", "1")
FAULT_PATH = "/__parity-fault"
os.environ.setdefault("PINGOO_PARITY_FAULT_INJECT", FAULT_PATH)
# Perf ledger + timeline live checks (ISSUE 17 satellite): sample every
# batch and append compile events to a throwaway JSONL so the smoke can
# assert the /__pingoo/compileledger + /__pingoo/timeline endpoints see
# real traffic. Must be set before the pingoo imports (the singletons
# read the env once at construction).
_PERF_TMP = tempfile.mkdtemp(prefix="pingoo-perf-smoke-")
os.environ.setdefault("PINGOO_TIMELINE_SAMPLE", "1")
os.environ.setdefault("PINGOO_PERF_LEDGER",
                      os.path.join(_PERF_TMP, "COMPILE_LEDGER.jsonl"))
os.environ.setdefault("PINGOO_COST_LEDGER",
                      os.path.join(_PERF_TMP, "COST_LEDGER.json"))
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

FAILURES: list = []


def check(ok, what):
    print(("  ok  " if ok else "  FAIL") + f" {what}")
    if not ok:
        FAILURES.append(what)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _get(port, path, accept=None, ua="smoke/1.0", timeout=10):
    headers = {"user-agent": ua}
    if accept:
        headers["accept"] = accept
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 headers=headers)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return (r.status, {k.lower(): v for k, v in r.headers.items()},
                r.read())


def validate_plane(label, port, shared_names, lint):
    status, headers, body = _get(port, "/__pingoo/metrics")
    check(status == 200, f"{label}: scrape status 200")
    check("text/plain" in headers.get("content-type", ""),
          f"{label}: default exposition is Prometheus text")
    text = body.decode()
    problems = lint(text)
    check(not problems, f"{label}: prometheus lint clean {problems[:3]}")
    for name in sorted(shared_names):
        check(name in text, f"{label}: exposes {name}")
    status, headers, body = _get(port, "/__pingoo/metrics",
                                 accept="application/json")
    check("application/json" in headers.get("content-type", ""),
          f"{label}: JSON under Accept: application/json")
    payload = json.loads(body)
    return text, payload


def main() -> int:
    from pingoo_tpu import native_ring
    from pingoo_tpu.compiler import compile_ruleset
    from pingoo_tpu.config.schema import Action, RuleConfig
    from pingoo_tpu.engine.service import VerdictService
    from pingoo_tpu.expr import compile_expression
    from pingoo_tpu.host.httpd import HttpListener
    from pingoo_tpu.native_ring import Ring, RingSidecar
    from pingoo_tpu.obs import schema
    from pingoo_tpu.obs.registry import lint_prometheus_text
    from pingoo_tpu.obs.trace import TRACE_HEADER

    if not native_ring.ensure_built():
        print("native toolchain unavailable; smoke needs g++")
        return 1
    subprocess.run(["make", "-C", native_ring.NATIVE_DIR, "httpd"],
                   check=True, capture_output=True)

    import tempfile

    tmp = tempfile.mkdtemp(prefix="pingoo-metrics-smoke-")

    class Upstream(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            body = b"up"
            self.send_response(200)
            self.send_header("content-length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    upstream = http.server.HTTPServer(("127.0.0.1", 0), Upstream)
    threading.Thread(target=upstream.serve_forever, daemon=True).start()

    rules = [RuleConfig(name="waf", actions=(Action.BLOCK,),
                        expression=compile_expression(
                            'http_request.path.starts_with("/.env")'))]
    plan = compile_ruleset(rules, {})

    ring_path = os.path.join(tmp, "ring")
    ring = Ring(ring_path, capacity=1024, create=True)
    sidecar = RingSidecar(ring, plan, {}, max_batch=128)
    threading.Thread(target=sidecar.run, daemon=True).start()

    nport = _free_port()
    httpd = subprocess.Popen(
        [os.path.join(native_ring.NATIVE_DIR, "httpd"), str(nport),
         ring_path, "127.0.0.1", str(upstream.server_address[1])],
        stdout=subprocess.PIPE)
    assert b"listening" in httpd.stdout.readline()
    time.sleep(0.3)

    shared = set(schema.SHARED_METRICS) | {schema.SHARED_WAIT_HISTOGRAM}

    class _NoCaptcha:
        # The smoke drives no captcha flow; a stub avoids requiring the
        # 'cryptography' package (CaptchaManager generates an Ed25519
        # key at construction).
        def serve(self, *a):
            return 404, [], b""

        def is_verified(self, *a):
            return False

    async def python_plane():
        svc = VerdictService(plan, {}, use_device=True)
        await svc.start()
        listener = HttpListener(
            name="smoke", host="127.0.0.1", port=0, services=[],
            verdict=svc, lists={}, rules_meta=plan.rules,
            captcha=_NoCaptcha())
        await listener.bind()
        port = listener.bound_port
        serve = asyncio.create_task(listener.serve_forever())

        def drive():
            try:
                _get(port, "/hello")
                check(False, "python: plain request served (404, no svc)")
            except urllib.error.HTTPError as e:
                check(e.code == 404,
                      "python: plain request served (404, no svc)")
                check(e.headers.get(TRACE_HEADER) is not None,
                      "python: response carries x-pingoo-trace-id")
            try:
                _get(port, "/.env")
                check(False, "python: /.env blocked")
            except urllib.error.HTTPError as e:
                check(e.code == 403, "python: /.env blocked 403")
            # Parity fault path: the ORACLE diverges here, the served
            # verdict stays correct (404: no service routes it).
            try:
                _get(port, FAULT_PATH)
            except urllib.error.HTTPError:
                pass
            # Both engine planes' auditors run off the hot path; drain
            # them so the counters below are deterministic.
            check(svc.parity is not None and svc.parity.flush(30),
                  "python: parity auditor drained")
            check(sidecar.parity is not None
                  and sidecar.parity.flush(30),
                  "sidecar: parity auditor drained")
            text, payload = validate_plane(
                "python", port, shared, lint_prometheus_text)
            for key in schema.PYTHON_JSON_KEYS:
                check(key in payload, f"python JSON: legacy key {key}")
            check("stages" in payload.get("verdict", {}),
                  "python JSON: per-stage verdict breakdown")
            check("provenance" in payload["verdict"]["stages"],
                  "python JSON: provenance stage instrumented")
            check("pingoo_ring_depth" in text,
                  "python scrape carries shm ring telemetry (sidecar)")
            # ISSUE 5 acceptance: with PINGOO_PARITY_SAMPLE>0 the
            # auditor ran against the live traffic and the mismatch
            # counters exist on BOTH planes under identical names.
            for plane in ("python", "sidecar"):
                check(f'pingoo_parity_checked_total{{plane="{plane}"}}'
                      in text, f"{plane}: parity checked counter")
                check(f'pingoo_parity_mismatch_total{{plane="{plane}"}}'
                      in text, f"{plane}: parity mismatch counter")
            check(svc.parity.checked_total.value > 0,
                  "python: auditor audited live traffic")
            check(sidecar.parity.checked_total.value > 0,
                  "sidecar: auditor audited live traffic")
            check(svc.parity.mismatch_total.value > 0,
                  "python: injected divergence observable via metrics")
            check("pingoo_rule_hits_total" in text,
                  "scrape carries per-rule attribution series")
            # ISSUE 6: the continuous-batching scheduler + mesh gauge
            # export on BOTH engine planes under identical names.
            for plane in ("python", "sidecar"):
                for name in ("pingoo_sched_queue_depth",
                             "pingoo_sched_deadline_miss_total",
                             "pingoo_sched_failopen_total",
                             "pingoo_mesh_devices"):
                    check(f'{name}{{plane="{plane}"}}' in text,
                          f"{plane}: sched metric {name}")
                check(f'pingoo_sched_batch_size_bucket{{le="1",'
                      f'plane="{plane}"}}' in text,
                      f"{plane}: sched batch-size histogram")
            check(svc.sched.launches > 0,
                  "python: scheduler drove live launches")
            check(sidecar.sched.launches > 0,
                  "sidecar: scheduler drove live launches")
            check(svc.sched.metrics.mesh_devices.value == 1
                  and sidecar.sched.metrics.mesh_devices.value == 1,
                  "mesh gauge reports single-device serving (no "
                  "PINGOO_MESH)")
            check("sched" in payload["verdict"]["stages"],
                  "python JSON: sched stage instrumented")
            # Flight recorder: the listener dumps every co-resident
            # plane; the injected divergence must appear in it with
            # full provenance.
            status, _hdrs, body = _get(port, "/__pingoo/flightrecorder")
            check(status == 200, "python: flightrecorder endpoint 200")
            fr = json.loads(body)
            check({"python", "sidecar"} <= set(fr.get("planes", {})),
                  "flightrecorder dump covers python + sidecar planes")
            mismatches = [
                e for e in fr["planes"]["python"]["entries"]
                if e["parity"] == "mismatch"]
            check(bool(mismatches),
                  "injected divergence observable in flightrecorder dump")
            check(mismatches and "parity_detail" in mismatches[0],
                  "flightrecorder mismatch carries provenance detail")
            # Explain endpoint: per-rule provenance for one request.
            status, _hdrs, body = _get(
                port, "/__pingoo/explain?path=/.env")
            check(status == 200, "python: explain endpoint 200")
            ex = json.loads(body)
            check(ex.get("action") == 1 and "waf" in ex.get(
                "matched_rules", []),
                "explain: device verdict + matched rule names")
            check(ex.get("parity", {}).get("consistent") is True,
                  "explain: interpreter agrees with device path")
            # Perf metric series (ISSUE 17): present on BOTH planes at
            # boot (ensure_instruments), moving where traffic ran.
            for plane in ("python", "sidecar"):
                for name in ("pingoo_compile_total",
                             "pingoo_timeline_spans_total",
                             "pingoo_costmodel_reload_total"):
                    check(f'plane="{plane}"' in "".join(
                        ln for ln in text.splitlines()
                        if ln.startswith(name)),
                        f"{plane}: perf metric {name}")
            # Compile ledger endpoint: the warm-up compile of the
            # verdict fn must be on it (PINGOO_PERF_LEDGER set above).
            status, _hdrs, body = _get(port, "/__pingoo/compileledger")
            check(status == 200, "python: compileledger endpoint 200")
            ledger = json.loads(body)
            check(ledger.get("enabled") is True
                  and ledger.get("compiles_total", 0) >= 1,
                  "compileledger: warm-up compile recorded")
            check(any(e.get("fn") == "verdict"
                      for e in ledger.get("events", [])),
                  "compileledger: verdict fn compile event present")
            # Timeline endpoint: Chrome-trace JSON with real spans
            # (PINGOO_TIMELINE_SAMPLE=1 above samples every batch).
            status, _hdrs, body = _get(port, "/__pingoo/timeline")
            check(status == 200, "python: timeline endpoint 200")
            trace = json.loads(body)
            spans = [e for e in trace.get("traceEvents", [])
                     if e.get("ph") == "X"]
            check(bool(spans), "timeline: sampled batch spans exported")
            check("clock" in trace and "monotonic_now_us"
                  in trace["clock"], "timeline: clock pin block present")
            # On-demand profiler window (ISSUE 17 satellite): a bounded
            # capture starts, reports its trace dir, and refuses a
            # second concurrent window with 409.
            # First-ever start_trace pays a multi-second one-time
            # profiler init; give it headroom.
            status, _hdrs, body = _get(port,
                                       "/__pingoo/profile?seconds=1",
                                       timeout=90)
            check(status == 200, "python: profile endpoint 200")
            prof = json.loads(body)
            check(prof.get("profiling") is True and prof.get("dir"),
                  "profile: bounded window started with trace dir")
            try:
                _get(port, "/__pingoo/profile?seconds=1")
                check(False, "profile: concurrent window refused 409")
            except urllib.error.HTTPError as e:
                check(e.code == 409,
                      "profile: concurrent window refused 409")
            # SIGTERM drain path: ensure_trace_stopped flushes the live
            # window synchronously and is idempotent (host/server.py
            # calls it unconditionally from the drain finally block).
            svc.ensure_trace_stopped()
            svc.ensure_trace_stopped()
            check(not getattr(svc, "_tracing", True),
                  "profile: ensure_trace_stopped idempotent + flushed")
            check(os.path.isdir(prof["dir"])
                  and bool(os.listdir(prof["dir"])),
                  "profile: flushed trace dir is non-empty")

        await asyncio.get_running_loop().run_in_executor(None, drive)
        serve.cancel()
        await listener.close()
        await svc.stop()

    try:
        # Drive the native plane first so counters are non-zero (the
        # parity fault path rides along: its oracle-side divergence
        # lands on the SIDECAR plane's auditor).
        for path in ("/ok", "/.env", "/ok2", FAULT_PATH):
            try:
                _get(nport, path)
            except urllib.error.HTTPError:
                pass
        text, payload = validate_plane(
            "native", nport, shared, lint_prometheus_text)
        for key in schema.NATIVE_JSON_KEYS:
            check(key in payload, f"native JSON: legacy key {key}")
        check("ring" in payload and "depth_hwm" in payload["ring"],
              "native JSON: shm ring telemetry block")
        check(payload["ring"]["enqueued"] >= 2,
              "native JSON: ring enqueued counter moved")
        check(text.rstrip().endswith(tuple("0123456789")),
              "native prometheus body complete (no truncation)")
        # Native-plane flight recorder: its own C++ ring at the same
        # endpoint path both Python planes use.
        status, _hdrs, body = _get(nport, "/__pingoo/flightrecorder")
        check(status == 200, "native: flightrecorder endpoint 200")
        nfr = json.loads(body)
        check(nfr.get("plane") == "native" and nfr.get("entries"),
              "native: flightrecorder carries verdict records")
        check(any(e.get("decided") == 1 for e in nfr.get("entries", [])),
              "native: flightrecorder recorded the /.env block")
        # Native-plane timeline (ISSUE 17): Chrome-trace JSON from the
        # same flight stamps, mergeable with the python dump.
        status, _hdrs, body = _get(nport, "/__pingoo/timeline")
        check(status == 200, "native: timeline endpoint 200")
        ntl = json.loads(body)
        nxs = [e for e in ntl.get("traceEvents", [])
               if e.get("ph") == "X"]
        check(bool(nxs) and all(e["name"] == "verdict_wait"
                                for e in nxs),
              f"native: timeline carries verdict_wait spans ({len(nxs)})")
        check(ntl.get("clock", {}).get("unit") == "monotonic_us",
              "native: timeline clock pin block present")

        asyncio.run(python_plane())
        check(sidecar.parity is not None
              and sidecar.parity.mismatch_total.value > 0,
              "sidecar: injected divergence observable via metrics")
    finally:
        httpd.terminate()
        sidecar.stop()
        upstream.shutdown()
        ring.close()

    if FAILURES:
        print(f"\nmetrics smoke FAILED ({len(FAILURES)} problems)")
        return 1
    print("\nmetrics smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
