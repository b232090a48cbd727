#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served WAF path starts,
answers and answers RIGHT on the accelerator.

Drives BASELINE config 2 (500-rule CRS-style ruleset, 131,072-entry
IP/CIDR list + 4,096-entry ASN set, max_batch=1024) once through the
entry point a user calls:

    client -> native/httpd -> shm ring -> RingSidecar -> device
           -> verdict -> 403 / proxy -> pong

via `python -m pingoo_tpu --config ... --native-plane --state-dir ...`,
then the Python plane (`python -m pingoo_tpu --config ...`), then the
jitted lane program on one 1024-row batch in a child of its own. Every
response status is compared with the `expr` interpreter's verdict for
that request (403 on a matched Block rule, pong's 200 otherwise).

Phases, each of which fails the run with a non-zero exit: build the
native plane from what git would commit; write the deployment; serve
(the boot line must say platform "tpu"); warm, then a checked window
plus a loadgen burst; SIGTERM (exit 0) and a second boot on the warm
compile cache; the Python plane; the engine child.

One process holds the chip at a time: THIS process never initialises a
JAX backend (importing pingoo_tpu pulls `jax` in; no device call is
made here) and every JAX user is a child, run one after the other.

The last line of stdout is exactly
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`,
the device as the server's boot line reported it (jax.devices() in the
process that serves). The line before it is the run's detail record
(versions, per-phase wall seconds, compile seconds, requests checked),
also written to chiprun_out/chip_smoke/summary.json; wall times in it
are a smoke's, not benchmark results. On any failure nothing is printed
to stdout and the exit code is non-zero — including on a machine where
JAX finds no accelerator.
"""

from __future__ import annotations

import argparse
import collections
import importlib.metadata
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import Callable, Optional

REPO = os.path.dirname(os.path.abspath(__file__))
NATIVE_DIR = os.path.join(REPO, "pingoo_tpu", "native")
RUN_DIR = os.path.join(REPO, ".smoke_run")  # state, rings, configs
LOG_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")  # child logs

NUM_RULES = 500
LIST_SIZES = (131072, 4096)  # BASELINE config 2
ATTACK_FRACTION = 0.05
WINDOW = 300            # one-by-one checked requests
PYTHON_PLANE_WINDOW = 24
SECOND_BOOT_WINDOW = 100  # the warm-cache boot replays the window's head
BURST_REQUESTS = 20000
BURST_CONCURRENCY = 1024
BURST_ATTACK_PERMILLE = 50
WARM_BURST_REQUESTS = 8192
ENGINE_SHAPES = 4        # staging shapes the engine child re-checks
DEADLINE_S = 1150.0     # the contract allows 1200 s, compiles included

# native/loadgen_http.cc's fixed request mix (kCleanPaths/kAttackPaths,
# host and user-agent), mirrored so the burst's blocked count can be
# held to the interpreter's verdicts and its shapes warmed beforehand.
LOADGEN_CLEAN = ("/api/v1/users?page=2", "/index.html",
                 "/static/app.9f3c2.js", "/blog/2026/07/scaling-wafs",
                 "/products/widget-2000?sort=price")
LOADGEN_ATTACK = ("/page?x=<script>alert(1)</script>",
                  "/?b=eval(atob('x'))")
LOADGEN_HOST = "bench.test"
LOADGEN_UA = "pingoo-bench/1.0"


class SmokeFailure(Exception):
    """A phase failed; the message says which and why."""


def log(msg: str) -> None:
    print(f"[chip_smoke {time.monotonic() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.monotonic()


def check_deadline() -> None:
    if time.monotonic() - _T0 > DEADLINE_S:
        raise SmokeFailure(f"run exceeded its {DEADLINE_S:.0f}s deadline")


# -- phase: build --------------------------------------------------------------


def build_native() -> None:
    """`make clean all` in pingoo_tpu/native: the tool copies the tree
    as it stands, ignored binaries included, so what serves must be
    built here from the sources git would commit."""
    if not os.path.isfile(os.path.join(NATIVE_DIR, "Makefile")):
        raise SmokeFailure(
            f"{NATIVE_DIR}/Makefile not found: chip_smoke.py runs from "
            f"the root of a pingoo-tpu checkout")
    jobs = str(min(8, os.cpu_count() or 1))
    for target in ("clean", "all"):
        proc = subprocess.run(
            ["make", "-C", NATIVE_DIR, "-j", jobs, target],
            capture_output=True, text=True, timeout=600)
        if proc.returncode != 0:
            raise SmokeFailure(
                f"make {target} failed rc={proc.returncode}: "
                f"{proc.stderr[-2000:]}")


# -- phase: write the deployment ------------------------------------------------


def write_deployment(run_dir: str, listen_port: int, upstream_port: int,
                     sources: list, lists: dict) -> str:
    """A config-2 `pingoo.yml` + its two list files in `run_dir`, from
    the (name, expression) pairs of utils/crs.generate_rule_sources:
    one http listener, one service -> pong, every rule a Block."""
    import yaml

    os.makedirs(run_dir, exist_ok=True)
    list_cfg = {}
    for name, items in lists.items():
        path = os.path.join(run_dir, f"{name}.csv")
        with open(path, "w", encoding="utf-8") as f:
            f.write("\n".join(str(item) for item in items) + "\n")
        kind = "Int" if items and isinstance(items[0], int) else "Ip"
        list_cfg[name] = {"type": kind, "file": path}
    doc = {
        "listeners": {"http": {"address": f"http://127.0.0.1:{listen_port}"}},
        "services": {"pong": {
            "http_proxy": [f"http://127.0.0.1:{upstream_port}"]}},
        "rules": {name: {"expression": src,
                         "actions": [{"action": "block"}]}
                  for name, src in sources},
        "lists": list_cfg,
    }
    path = os.path.join(run_dir, "pingoo.yml")
    with open(path, "w", encoding="utf-8") as f:
        yaml.safe_dump(doc, f, sort_keys=False, width=4096)
    return path


# -- the status oracle ----------------------------------------------------------


def wire_request(req) -> bytes:
    """The HTTP/1.1 bytes for one generated RequestTuple. The target
    goes out raw (the native parser splits the request line on its
    first and last space, so an attack URL with spaces stays one
    target)."""
    head = (f"{req.method} {req.url} HTTP/1.1\r\nhost: {req.host}\r\n"
            f"user-agent: {req.user_agent}\r\n")
    if req.method == "POST":
        head += "content-length: 0\r\n"
    return (head + "\r\n").encode("latin-1")


def served_tuple(req, client_port: int):
    """What the server sees of `req` on a loopback connection: the
    generated ip/asn/country never reach the wire — the peer address
    is 127.0.0.1:<client_port>, and with no GeoIP database the asn and
    country are the unknown markers (0, "XX")."""
    import dataclasses

    return dataclasses.replace(
        req, ip="127.0.0.1", remote_port=client_port, asn=0, country="XX",
        path=req.url.split("?", 1)[0])


def make_oracle(sources: list, lists: dict) -> Callable:
    """expected_status(tuple) -> 403 | 200 straight from the `expr`
    interpreter over the rule SOURCES (no plan, no device): the first
    matched rule with an action decides, every action here is Block,
    and a rule whose evaluation raises is a no-match (fail-open)."""
    from pingoo_tpu.engine.batch import tuple_to_context
    from pingoo_tpu.expr import compile_expression, execute_as_bool

    programs = [compile_expression(src) for _, src in sources]
    memo: dict = {}

    def expected_status(tup) -> int:
        key = (tup.method, tup.host, tup.url, tup.user_agent, tup.ip,
               tup.remote_port, tup.asn, tup.country)
        if key not in memo:
            ctx = tuple_to_context(tup, lists)
            memo[key] = 200
            for program in programs:
                try:
                    if execute_as_bool(program, ctx):
                        memo[key] = 403
                        break
                except Exception:
                    continue  # a rule that errors is a no-match
        return memo[key]

    return expected_status


def shape_key(req) -> tuple:
    """The (path, url, user_agent) pow2 column buckets a one-request
    batch of `req` stages — one compiled program per distinct key."""
    from pingoo_tpu.engine.batch import bucket_len

    return (bucket_len(len(req.url.split("?", 1)[0]), 2048),
            bucket_len(len(req.url), 2048),
            bucket_len(len(req.user_agent), 256))


def loadgen_requests() -> list:
    from pingoo_tpu.engine.batch import RequestTuple

    return [RequestTuple(host=LOADGEN_HOST, url=url, path=url.split("?")[0],
                         method="GET", user_agent=LOADGEN_UA)
            for url in LOADGEN_CLEAN + LOADGEN_ATTACK]


def loadgen_expected_blocked(n: int, permille: int,
                             expected_status: Callable) -> int:
    """How many of loadgen_http's n requests the interpreter blocks
    (the generator's sequence is a pure function of the request
    index; the client port cannot matter: ephemeral ports are >=1024)."""
    verdict = {r.url: expected_status(served_tuple(r, 40000))
               for r in loadgen_requests()}
    blocked = 0
    for seq in range(n):
        attack = (seq % 1000) < permille
        url = (LOADGEN_ATTACK[seq % 2] if attack
               else LOADGEN_CLEAN[seq % 5])
        blocked += verdict[url] == 403
    return blocked


# -- plain HTTP client ------------------------------------------------------------


class Client:
    """One keep-alive connection, one request at a time."""

    def __init__(self, port: int, timeout: float = 30.0):
        self.port = port
        self.timeout = timeout
        self.sock: Optional[socket.socket] = None

    def close(self) -> None:
        if self.sock is not None:
            self.sock.close()
            self.sock = None

    def send(self, raw: bytes, head_only: bool = False) -> tuple:
        """-> (status, body, client_port). A kept-alive connection the
        server closed while idle is retried once on a fresh one."""
        reused = self.sock is not None
        try:
            return self._exchange(raw, head_only)
        except ConnectionError:
            self.close()
            if not reused:
                raise
        return self._exchange(raw, head_only)

    def _exchange(self, raw: bytes, head_only: bool) -> tuple:
        if self.sock is None:
            self.sock = socket.create_connection(
                ("127.0.0.1", self.port), timeout=self.timeout)
            self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        port = self.sock.getsockname()[1]
        self.sock.sendall(raw)
        buf = b""
        while b"\r\n\r\n" not in buf:
            chunk = self.sock.recv(65536)
            if not chunk:
                raise ConnectionResetError("closed before the response head")
            buf += chunk
        head, _, body = buf.partition(b"\r\n\r\n")
        lines = head.decode("latin-1").split("\r\n")
        status = int(lines[0].split()[1])
        headers = {k.strip().lower(): v.strip() for k, _, v in
                   (ln.partition(":") for ln in lines[1:])}
        if head_only:  # a HEAD response carries no body to sync on
            self.close()
            return status, b"", port
        want = int(headers.get("content-length", "0"))
        while len(body) < want:
            chunk = self.sock.recv(65536)
            if not chunk:
                raise ConnectionResetError("closed inside the body")
            body += chunk
        if headers.get("connection", "").lower() == "close":
            self.close()
        return status, body, port

    def send_tuple(self, req) -> tuple:
        status, _, port = self.send(wire_request(req),
                                    head_only=req.method == "HEAD")
        return status, port

    def get(self, path: str, accept: str = "*/*") -> bytes:
        raw = (f"GET {path} HTTP/1.1\r\nhost: smoke\r\naccept: {accept}\r\n"
               f"user-agent: chip-smoke\r\nconnection: close\r\n\r\n")
        status, body, _ = self.send(raw.encode())
        self.close()
        if status != 200:
            raise SmokeFailure(f"GET {path} -> {status}")
        return body


_SAMPLE = re.compile(r'^([a-zA-Z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$')
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse_prometheus(text: str) -> list:
    """-> [(name, {label: value}, float)] for every sample line."""
    out = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line)
        if m:
            out.append((m.group(1), dict(_LABEL.findall(m.group(2) or "")),
                        float(m.group(3))))
    return out


def metric_sum(samples: list, name: str, **labels) -> float:
    return sum(v for n, ls, v in samples if n == name
               and all(ls.get(k) == want for k, want in labels.items()))


# -- child processes ------------------------------------------------------------


def _child_env(run_dir: str) -> dict:
    """The environment every JAX child runs in: the ambient platform
    (never pinned here), the compile ledger + surface check + parity
    auditor switched on through their existing knobs, and no Pallas
    interpret mode."""
    env = dict(os.environ)
    env.pop("PINGOO_PALLAS_INTERPRET", None)
    env["PINGOO_PERF_LEDGER"] = os.path.join(run_dir, "compile_ledger.jsonl")
    env["PINGOO_COMPILE_SURFACE"] = os.path.join(REPO, "COMPILE_SURFACE.json")
    env["PINGOO_COST_LEDGER"] = os.path.join(run_dir, "cost_ledger.json")
    # One batch in 50 re-interpreted off the hot path: enough to audit
    # the one-by-one windows; at 1 the auditor's 1024-row audits hold
    # the GIL against the drain loop and slow it ~10x (PERF.md).
    env["PINGOO_PARITY_SAMPLE"] = "0.02"
    return env


_PROCS: list = []  # every process started here, for the final sweep


def _spawn(argv: list, **kw) -> subprocess.Popen:
    proc = subprocess.Popen(argv, start_new_session=True, **kw)
    _PROCS.append(proc)
    return proc


def _kill_group(proc: subprocess.Popen) -> None:
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        pass


def stop_everything() -> None:
    for proc in _PROCS:
        _kill_group(proc)


def start_pong() -> tuple:
    proc = _spawn([os.path.join(NATIVE_DIR, "pong"), "0"],
                  stdout=subprocess.PIPE)
    port = json.loads(proc.stdout.readline())["listening"]
    return proc, port


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """`python -m pingoo_tpu ...` as a child; its JSON log lines are
    read off stderr by a pump thread (and kept in LOG_DIR)."""

    def __init__(self, name: str, config: str, run_dir: str,
                 native_plane: bool):
        self.name = name
        argv = [sys.executable, "-m", "pingoo_tpu", "--config", config,
                "--no-docker", "--captcha-jwks",
                os.path.join(run_dir, "captcha_jwks.json")]
        if native_plane:
            argv += ["--native-plane", "--state-dir",
                     os.path.join(run_dir, f"state_{name}")]
        self.records: list = []
        self._cond = threading.Condition()
        self.t_spawn = time.monotonic()
        self.proc = _spawn(argv, cwd=REPO, env=_child_env(run_dir),
                           stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)
        os.makedirs(LOG_DIR, exist_ok=True)
        self._log = open(os.path.join(LOG_DIR, f"{name}.log"), "wb")
        self._pump = threading.Thread(target=self._pump_stderr, daemon=True)
        self._pump.start()

    def _pump_stderr(self) -> None:
        for raw in self.proc.stderr:
            self._log.write(raw)
            self._log.flush()
            try:
                rec = json.loads(raw)
            except ValueError:
                continue
            if isinstance(rec, dict):
                with self._cond:
                    self.records.append(rec)
                    self._cond.notify_all()
        with self._cond:
            self._cond.notify_all()

    def wait_log(self, message: str, timeout: float) -> dict:
        """Block until a log record with this `message` arrives."""
        deadline = time.monotonic() + timeout
        seen = 0
        with self._cond:
            while True:
                for rec in self.records[seen:]:
                    if rec.get("message") == message:
                        return rec
                seen = len(self.records)
                if self.proc.poll() is not None and not self._pump.is_alive():
                    raise SmokeFailure(
                        f"{self.name}: server exited rc={self.proc.returncode}"
                        f" before logging {message!r}: {self.tail()}")
                left = deadline - time.monotonic()
                if left <= 0:
                    raise SmokeFailure(
                        f"{self.name}: no {message!r} log line within "
                        f"{timeout:.0f}s: {self.tail()}")
                self._cond.wait(min(left, 1.0))

    def tail(self, n: int = 6) -> str:
        return " | ".join(json.dumps(r)[:400] for r in self.records[-n:])

    def stop(self) -> int:
        """SIGTERM -> the graceful drain; returns the exit code."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=90)
        except subprocess.TimeoutExpired:
            rc = None
        _kill_group(self.proc)  # httpd workers that outlived the drain
        self._pump.join(timeout=5)
        self._log.close()
        if rc is None:
            raise SmokeFailure(f"{self.name}: no exit within 90s of SIGTERM")
        return rc


def require_accelerator(server: Server) -> dict:
    """Read the device from the boot line; anything but a TPU fails."""
    boot = server.wait_log("starting pingoo-tpu", 180)
    device = {"platform": boot.get("platform"),
              "kind": boot.get("device_kind"),
              "count": boot.get("device_count")}
    log(f"{server.name}: boot line says {device}, compile cache at "
        f"{boot.get('compile_cache')}")
    if device["platform"] != "tpu":
        raise SmokeFailure(
            f"the server booted on platform {device['platform']!r} "
            f"({device['kind']!r} x{device['count']}), not on a TPU: "
            f"chip_smoke needs the accelerator")
    return {"device": device, "compile_cache": boot["compile_cache"]}


def cache_entries(path: str) -> int:
    try:
        return sum(1 for name in os.listdir(path)
                   if not name.endswith("-atime"))
    except OSError:
        return 0


# -- phase: the native plane -----------------------------------------------------


class NativeRun:
    """One boot of the native plane and the requests driven at it."""

    def __init__(self, name: str, config: str, run_dir: str, port: int,
                 window: list, expected_status: Callable,
                 with_burst: bool):
        self.name = name
        self.port = port
        self.window = window
        self.expected_status = expected_status
        self.with_burst = with_burst
        self.client = Client(port)
        self.server = Server(name, config, run_dir, native_plane=True)
        self.boot = require_accelerator(self.server)
        up = self.server.wait_log("native listener up", 600)
        self.registry_port = int(up["fail_open"].rsplit(":", 1)[1])
        self.boot_s = time.monotonic() - self.server.t_spawn
        log(f"{name}: native listener up after {self.boot_s:.1f}s")

    # -- scrapes --

    def native(self) -> dict:
        return json.loads(Client(self.port).get(
            "/__pingoo/metrics", accept="application/json"))

    def registry(self) -> list:
        return parse_prometheus(Client(self.registry_port).get(
            "/__pingoo/metrics").decode())

    def python_json(self) -> dict:
        return json.loads(Client(self.registry_port).get(
            "/__pingoo/metrics", accept="application/json"))

    def compile_ledger(self) -> dict:
        return json.loads(Client(self.registry_port).get(
            "/__pingoo/compileledger"))

    def sidecar_compiles(self) -> float:
        return metric_sum(self.registry(), "pingoo_compile_total",
                          plane="sidecar")

    # -- traffic --

    def first_device_verdict(self) -> float:
        """Seconds from spawn to the first request a DEVICE verdict
        decided: `/.env` is blocked by rule, and only a verdict blocks
        (a request released by the 3 s fail-open deadline is proxied)."""
        from pingoo_tpu.engine.batch import RequestTuple
        from pingoo_tpu.utils.crs import NORMAL_UAS

        probe = RequestTuple(host="www.example.com", url="/.env",
                             path="/.env", method="GET",
                             user_agent=NORMAL_UAS[0])
        while True:
            check_deadline()
            status, port = self.client.send_tuple(probe)
            if status == 403:
                if self.expected_status(served_tuple(probe, port)) != 403:
                    raise SmokeFailure("oracle disagrees on the probe")
                return time.monotonic() - self.server.t_spawn
            if time.monotonic() - self.server.t_spawn > 600:
                raise SmokeFailure(
                    f"{self.name}: no device verdict within 600s of spawn")
            time.sleep(0.05)

    def _served_by_verdict(self, req) -> bool:
        """Send one request; True when no fail-open released it."""
        before = self.native()["fail_open"]
        status, port = self.client.send_tuple(req)
        if self.native()["fail_open"] != before:
            return False
        want = self.expected_status(served_tuple(req, port))
        if status != want:
            raise SmokeFailure(
                f"{self.name} warm-up: {req.method} {req.url!r} -> "
                f"{status}, interpreter says {want}")
        return True

    def warm(self) -> dict:
        """Compile, off the checked window, every program it will use:
        one request per distinct staging shape of the window and of the
        loadgen mix (re-sent until a verdict, not the fail-open
        deadline, answers it), then unchecked rehearsals — a burst and
        the window — until a whole round compiles nothing and nothing
        fails open."""
        t0 = time.monotonic()
        reps: dict = {}
        for req in self.window + (loadgen_requests() if self.with_burst
                                  else []):
            reps.setdefault(shape_key(req), req)
        for req in reps.values():
            # While its program compiles a request is released by the
            # 3 s verdict deadline — or at once, when the compile's
            # stalls let the heartbeat go stale and the plane degrades.
            t_shape = time.monotonic()
            while not self._served_by_verdict(req):
                check_deadline()
                if time.monotonic() - t_shape > 240:
                    raise SmokeFailure(
                        f"{self.name}: {req.url!r} still fails open 240s "
                        f"after it was first sent")
                time.sleep(0.25)
        log(f"{self.name}: {len(reps)} staging shapes warmed in "
            f"{time.monotonic() - t0:.1f}s")
        rounds = 0
        while True:
            check_deadline()
            rounds += 1
            compiles, fo = self.sidecar_compiles(), self.native()["fail_open"]
            if self.with_burst:
                self.burst(WARM_BURST_REQUESTS)
            for req in self.window:
                self.client.send_tuple(req)
            compiled = self.sidecar_compiles() - compiles
            failed_open = self.native()["fail_open"] - fo
            log(f"{self.name}: rehearsal {rounds}: {compiled:.0f} compiles, "
                f"{failed_open} fail-opens")
            if not compiled and not failed_open:
                break
            if rounds >= 6:
                raise SmokeFailure(
                    f"{self.name}: a rehearsal still compiles or fails "
                    f"open after {rounds} rounds")
        ledger = self.compile_ledger()
        compile_s = sum(e["wall_ms"] for e in ledger["events"]) / 1e3
        return {"shapes": len(reps), "rehearsals": rounds,
                "compiles": ledger["compiles_total"],
                "compile_s": round(compile_s, 1),
                "wall_s": round(time.monotonic() - t0, 1)}

    def burst(self, n: int) -> dict:
        proc = subprocess.run(
            [os.path.join(NATIVE_DIR, "loadgen_http"), str(self.port),
             str(n), str(BURST_CONCURRENCY), str(BURST_ATTACK_PERMILLE)],
            capture_output=True, text=True, timeout=300)
        if proc.returncode != 0 or not proc.stdout.strip():
            raise SmokeFailure(
                f"{self.name}: loadgen_http rc={proc.returncode} "
                f"{proc.stderr[-300:]}")
        return json.loads(proc.stdout.strip().splitlines()[-1])

    def checked(self) -> dict:
        """The checked window: every status against the interpreter,
        then (first boot) the loadgen burst against the interpreter's
        blocked count — and the counters that must not have moved."""
        t0 = time.monotonic()
        nat0 = self.native()
        compiles0 = self.sidecar_compiles()
        blocked = 0
        for i, req in enumerate(self.window):
            status, port = self.client.send_tuple(req)
            want = self.expected_status(served_tuple(req, port))
            if status != want:
                raise SmokeFailure(
                    f"{self.name} checked window: request {i} "
                    f"{req.method} {req.url!r} ua={req.user_agent!r} -> "
                    f"{status}, interpreter says {want}")
            blocked += status == 403
        checked = len(self.window)
        result = {"requests": checked, "blocked": blocked}
        problems = []
        if self.with_burst:
            res = self.burst(BURST_REQUESTS)
            want_blocked = loadgen_expected_blocked(
                BURST_REQUESTS, BURST_ATTACK_PERMILLE, self.expected_status)
            if (res["errors"] or res["completed"] != BURST_REQUESTS
                    or res["blocked"] != want_blocked):
                problems.append(
                    f"burst {res}: the interpreter blocks {want_blocked} "
                    f"of {BURST_REQUESTS}, with 0 errors")
            result["burst"] = {"requests": res["completed"],
                               "blocked": res["blocked"],
                               "errors": res["errors"]}
            checked += res["completed"]
        nat1 = self.native()
        reg = self.registry()
        for key in ("fail_open", "degraded_entered", "upstream_fail"):
            if nat1[key] != nat0[key]:
                problems.append(f"native {key} moved {nat0[key]}->{nat1[key]}")
        pre_ring = nat1["ua_rejected"] - nat0["ua_rejected"]
        if nat1["verdicts"] - nat0["verdicts"] < checked - pre_ring:
            problems.append(
                f"only {nat1['verdicts'] - nat0['verdicts']} verdicts "
                f"applied for {checked} requests ({pre_ring} answered "
                f"before the ring)")
        for name in ("pingoo_degrade_total",
                     "pingoo_compile_unexpected_total",
                     "pingoo_parity_mismatch_total"):
            if metric_sum(reg, name):
                problems.append(f"{name} = {metric_sum(reg, name)}: " + str(
                    [(ls, v) for n, ls, v in reg if n == name and v]))
        if self.sidecar_compiles() != compiles0:
            problems.append("the sidecar compiled inside the checked window")
        audited = metric_sum(reg, "pingoo_parity_checked_total",
                             plane="sidecar")
        if not audited:
            problems.append("the parity auditor checked nothing")
        if problems:
            raise SmokeFailure(f"{self.name} checked window: "
                               + "; ".join(problems))
        result.update(checked=checked, parity_audited=int(audited),
                      wall_s=round(time.monotonic() - t0, 1))
        return result

    def dump(self) -> None:
        """Post-mortem material into LOG_DIR (best effort)."""
        try:
            with open(os.path.join(LOG_DIR, f"{self.name}.metrics.json"),
                      "w", encoding="utf-8") as f:
                json.dump({"native": self.native(),
                           "python": self.python_json(),
                           "compile_ledger": self.compile_ledger()}, f)
        except (OSError, ValueError, SmokeFailure):
            pass

    def stop(self) -> None:
        self.client.close()
        backend = self.python_json().get("backend") or {}
        if backend.get("platform") != self.boot["device"]["platform"]:
            raise SmokeFailure(
                f"{self.name}: metrics JSON says backend {backend}, boot "
                f"line said {self.boot['device']}")
        rc = self.server.stop()
        if rc != 0:
            raise SmokeFailure(
                f"{self.name}: exit code {rc} after SIGTERM: "
                f"{self.server.tail()}")


def native_phase(config: str, run_dir: str, port: int, window: list,
                 expected_status: Callable) -> dict:
    """Boot, warm, check, SIGTERM — twice. The second boot finds the
    compile cache the first one filled; it replays the head of the
    window (a subset of the first boot's shapes) and no burst."""
    out: dict = {}
    for boot, name in enumerate(("native1", "native2")):
        t0 = time.monotonic()
        run = NativeRun(name, config, run_dir, port,
                        window if boot == 0 else window[:SECOND_BOOT_WINDOW],
                        expected_status, with_burst=boot == 0)
        try:
            cache_dir = run.boot["compile_cache"]
            entries0 = cache_entries(cache_dir)
            first = run.first_device_verdict()
            log(f"{name}: first device verdict {first:.1f}s after spawn")
            warm = run.warm()
            log(f"{name}: warm {warm}")
            checked = run.checked()
            log(f"{name}: checked {checked}")
        finally:
            run.dump()
        run.stop()
        entries1 = cache_entries(cache_dir)
        shutil.rmtree(os.path.join(run_dir, f"state_{name}"),
                      ignore_errors=True)  # the rings are ~80 MB
        out[name] = {"boot_s": round(run.boot_s, 1),
                     "first_device_verdict_s": round(first, 1),
                     "warm": warm, "checked": checked,
                     "cache_entries": [entries0, entries1],
                     "wall_s": round(time.monotonic() - t0, 1)}
        out.setdefault("device", run.boot["device"])
        out.setdefault("compile_cache", cache_dir)
        if boot == 0 and not entries1:
            raise SmokeFailure(
                f"compile cache {cache_dir} is empty after the first boot")
        # "Almost nothing": JAX caches only compiles of >= 1 s, so a
        # program hovering at that threshold may be stored by either
        # boot; a cache that did not hit would re-add every one.
        added = [b[1] - b[0] for b in (out["native1"]["cache_entries"],
                                       out[name]["cache_entries"])]
        if boot == 1 and added[1] > max(4, added[0] // 4):
            raise SmokeFailure(
                f"the second boot added {added[1]} entries to {cache_dir} "
                f"(the first added {added[0]}): its compiles were not "
                f"found in the cache")
    return out


# -- phase: the Python plane ------------------------------------------------------


def python_plane_phase(config: str, run_dir: str, port: int, window: list,
                       expected_status: Callable) -> dict:
    """README's first entry point (also the fail-open target and the
    fuzzer's oracle): VerdictService's verdict/prefilter programs
    compile and serve on the chip once. h11 refuses a request target
    with a raw space, so those requests are left to the native phase."""
    t0 = time.monotonic()
    server = Server("python", config, run_dir, native_plane=False)
    boot = require_accelerator(server)
    client = Client(port, timeout=180)  # a request may wait on a compile
    while True:
        check_deadline()
        try:
            client.get("/__pingoo/metrics")
            break
        except (OSError, SmokeFailure):
            if server.proc.poll() is not None:
                raise SmokeFailure(
                    f"python plane exited rc={server.proc.returncode}: "
                    f"{server.tail()}")
            time.sleep(0.5)
    reqs = [r for r in window if " " not in r.url][:PYTHON_PLANE_WINDOW]
    blocked = 0
    for i, req in enumerate(reqs):
        status, cport = client.send_tuple(req)
        want = expected_status(served_tuple(req, cport))
        if status != want:
            raise SmokeFailure(
                f"python plane: request {i} {req.method} {req.url!r} -> "
                f"{status}, interpreter says {want}")
        blocked += status == 403
    doc = json.loads(client.get("/__pingoo/metrics",
                                accept="application/json"))
    reg = parse_prometheus(client.get("/__pingoo/metrics").decode())
    client.close()
    problems = []
    if (doc.get("backend") or {}).get("platform") != \
            boot["device"]["platform"]:
        problems.append(f"metrics JSON backend {doc.get('backend')}")
    verdict = doc["verdict"]
    for key in ("device_errors", "host_fallback_batches"):
        if verdict[key]:
            problems.append(f"{key} = {verdict[key]}")
    if doc["fail_open"]:
        problems.append(f"fail_open = {doc['fail_open']}")
    for name in ("pingoo_degrade_total", "pingoo_compile_unexpected_total",
                 "pingoo_parity_mismatch_total"):
        if metric_sum(reg, name):
            problems.append(f"{name} = {metric_sum(reg, name)}")
    compiles = metric_sum(reg, "pingoo_compile_total", plane="python")
    if not compiles:
        problems.append("no verdict program compiled on the python plane")
    if problems:
        raise SmokeFailure("python plane: " + "; ".join(problems))
    rc = server.stop()
    if rc != 0:
        raise SmokeFailure(f"python plane: exit code {rc} after SIGTERM")
    return {"requests": len(reqs), "blocked": blocked,
            "compiles": int(compiles),
            "wall_s": round(time.monotonic() - t0, 1)}


# -- phase: the engine child --------------------------------------------------------


def engine_phase(run_dir: str, seed: int, shapes: list) -> dict:
    """After the servers have exited: tools/engine_check.py in a child
    of its own (the jitted lane program over one 1024-row batch against
    the interpreter, and the fused-kernel status) — see its docstring."""
    t0 = time.monotonic()
    with open(os.path.join(LOG_DIR, "engine.log"), "wb") as errlog:
        proc = _spawn(
            [sys.executable, "-m", "tools.engine_check", "--seed",
             str(seed), "--rules", str(NUM_RULES), "--ip-list",
             str(LIST_SIZES[0]), "--asn-list", str(LIST_SIZES[1]),
             "--shapes", json.dumps(shapes)],
            cwd=REPO, env=_child_env(run_dir), stdout=subprocess.PIPE,
            stderr=errlog)
        try:
            out, _ = proc.communicate(timeout=max(
                60.0, DEADLINE_S - (time.monotonic() - _T0)))
        except subprocess.TimeoutExpired:
            _kill_group(proc)
            raise SmokeFailure("engine child ran out of time")
    lines = out.decode().strip().splitlines()
    if proc.returncode != 0 or not lines:
        raise SmokeFailure(
            f"engine child rc={proc.returncode}; see {LOG_DIR}/engine.log")
    res = json.loads(lines[-1])
    if not res.get("ok"):
        raise SmokeFailure(f"engine child: {res}")
    res["wall_s"] = round(time.monotonic() - t0, 1)
    return res


# -- main -------------------------------------------------------------------------


def _version(dist: str) -> Optional[str]:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return None


def run(seed: int) -> dict:
    phases: dict = {}

    def timed(name: str, fn: Callable, *args):
        t0 = time.monotonic()
        log(f"phase {name} ...")
        res = fn(*args)
        phases[name] = round(time.monotonic() - t0, 1)
        check_deadline()
        return res

    timed("build", build_native)
    shutil.rmtree(RUN_DIR, ignore_errors=True)
    os.makedirs(RUN_DIR)
    os.makedirs(LOG_DIR, exist_ok=True)

    from pingoo_tpu.utils.crs import generate_rule_sources, generate_traffic

    sources, lists = generate_rule_sources(
        NUM_RULES, seed=seed, list_sizes=LIST_SIZES)
    _, pong_port = start_pong()
    port = free_port()
    config = timed("deployment", write_deployment, RUN_DIR, port,
                   pong_port, sources, lists)
    expected_status = make_oracle(sources, lists)
    window = generate_traffic(WINDOW, attack_fraction=ATTACK_FRACTION,
                              seed=seed + 1, lists=lists)

    native = timed("native_plane", native_phase, config, RUN_DIR, port,
                   window, expected_status)
    python = timed("python_plane", python_plane_phase, config, RUN_DIR,
                   port, window, expected_status)
    # The engine child re-checks the window's commonest staging shapes
    # (the served phases above already drove every one of them).
    counts = collections.Counter(shape_key(r) for r in window)
    shapes = [list(key) for key, _ in counts.most_common(ENGINE_SHAPES)]
    engine = timed("engine", engine_phase, RUN_DIR, seed, shapes)

    n1, n2 = native["native1"], native["native2"]
    return {
        "ok": True,
        "device": native["device"],
        "jax": _version("jax"),
        "libtpu": _version("libtpu"),
        "config": {"rules": NUM_RULES, "ip_list": LIST_SIZES[0],
                   "asn_list": LIST_SIZES[1], "max_batch": 1024,
                   "seed": seed},
        "note": "wall seconds are a smoke's, not benchmark results",
        "phase_wall_s": phases,
        "requests_checked": (n1["checked"]["checked"]
                             + n2["checked"]["checked"]
                             + python["requests"] + engine["rows_checked"]),
        "first_device_verdict_s": {
            "cold_boot": n1["first_device_verdict_s"],
            "warm_cache_boot": n2["first_device_verdict_s"]},
        # Trace + compile (or cache load) wall per jitted program, from
        # the servers' own compile ledgers; the second boot replays a
        # subset of the first boot's shapes, hence the per-program mean.
        "compile_s": {
            "cold_boot": n1["warm"]["compile_s"],
            "cold_boot_programs": n1["warm"]["compiles"],
            "warm_cache_boot": n2["warm"]["compile_s"],
            "warm_cache_boot_programs": n2["warm"]["compiles"],
            "mean_per_program": {
                "cold_boot": round(n1["warm"]["compile_s"]
                                   / max(1, n1["warm"]["compiles"]), 2),
                "warm_cache_boot": round(n2["warm"]["compile_s"]
                                         / max(1, n2["warm"]["compiles"]),
                                         2)}},
        "compile_cache": {"dir": native["compile_cache"],
                          "entries_boot1": n1["cache_entries"],
                          "entries_boot2": n2["cache_entries"]},
        "native_plane": native,
        "python_plane": python,
        "engine": engine,
    }


def result_line(device: dict) -> dict:
    """The contract's last stdout line: these keys and no others."""
    return {"ok": True,
            "device": {"platform": str(device["platform"]),
                       "kind": str(device["kind"]),
                       "count": int(device["count"])}}


def main(argv: Optional[list] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seed", type=int, default=20260728,
                        help="ruleset, lists and traffic are made from it")
    args = parser.parse_args(argv)
    try:
        summary = run(args.seed)
    except SmokeFailure as exc:
        log(f"FAILED: {exc}")
        return 1
    finally:
        stop_everything()
        shutil.rmtree(RUN_DIR, ignore_errors=True)
    log(f"ok in {time.monotonic() - _T0:.1f}s")
    detail = json.dumps(summary)
    with open(os.path.join(LOG_DIR, "summary.json"), "w") as fh:
        fh.write(detail + "\n")
    print(detail)
    print(json.dumps(result_line(summary["device"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
