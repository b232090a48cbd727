"""Everything between a configuration file and a served deployment:
build, write `pingoo.yml`, start and stop processes, scrape counters.

Started from chip_smoke.py's build_native / write_deployment / Server /
scrapes (chip-proven in PR 21) and kept here so that the program's copy
may change. Nothing in this module raises once a window has started:
scrapes return None when the server does not answer.
"""

from __future__ import annotations

import glob
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
from typing import Optional

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
WORK = os.path.join(ROOT, ".bench")            # everything a run writes
BIN_DIR = os.path.join(WORK, "bin")
RUNS_DIR = os.path.join(WORK, "runs")
NATIVE_SRC = os.path.join(BENCH_DIR, "native")
PROGRAM_NATIVE = os.path.join(ROOT, "pingoo_tpu", "native")


class SetupFailure(Exception):
    """Set-up could not produce a window. `phase` says where."""

    def __init__(self, phase: str, reason: str, log_tail: str = ""):
        super().__init__(f"{phase}: {reason}")
        self.phase, self.reason, self.log_tail = phase, reason, log_tail


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - _T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


_T0 = time.monotonic()


# -- leftovers of earlier runs ---------------------------------------------------


def _kill_group(pgid: int) -> None:
    for sig in (signal.SIGTERM, signal.SIGKILL):
        try:
            os.killpg(pgid, sig)
        except (ProcessLookupError, PermissionError):
            return
        deadline = time.monotonic() + (3.0 if sig == signal.SIGTERM else 5.0)
        while time.monotonic() < deadline:
            try:
                os.killpg(pgid, 0)
            except (ProcessLookupError, PermissionError):
                return
            time.sleep(0.05)


def sweep_leftovers() -> None:
    """Kill the process groups an earlier run of this checkout recorded
    and did not stop, and remove its run directories (rings, state)."""
    for pidfile in glob.glob(os.path.join(RUNS_DIR, "*", "pgids")):
        try:
            with open(pidfile, encoding="utf-8") as f:
                pgids = [int(x) for x in f.read().split()]
        except (OSError, ValueError):
            pgids = []
        for pgid in pgids:
            if pgid != os.getpgid(0):
                _kill_group(pgid)
    shutil.rmtree(RUNS_DIR, ignore_errors=True)


# -- build ---------------------------------------------------------------------


def _stale(target: str, sources: list) -> bool:
    if not os.path.isfile(target):
        return True
    t = os.path.getmtime(target)
    return any(os.path.getmtime(s) > t for s in sources)


def build_native() -> None:
    """The program's native plane by its own Makefile (a no-op when the
    binaries are newer than the sources), and the benchmark's generator
    and upstream into .bench/bin."""
    if not os.path.isfile(os.path.join(PROGRAM_NATIVE, "Makefile")):
        raise SetupFailure("build", f"{PROGRAM_NATIVE}/Makefile not found: "
                           "the benchmark runs from the root of a checkout "
                           "that holds the program")
    jobs = str(min(8, os.cpu_count() or 1))
    try:
        proc = subprocess.run(
            ["make", "-C", PROGRAM_NATIVE, "-j", jobs, "libpingoo_ring.so",
             "httpd"], capture_output=True, text=True, timeout=600)
    except (OSError, subprocess.TimeoutExpired) as exc:
        raise SetupFailure("build", f"make did not finish: {exc!r}")
    if proc.returncode != 0:
        raise SetupFailure("build", f"make rc={proc.returncode}",
                           proc.stderr[-4000:])
    os.makedirs(BIN_DIR, exist_ok=True)
    for name in ("httpgen", "pong"):
        src = os.path.join(NATIVE_SRC, f"{name}.cc")
        out = os.path.join(BIN_DIR, name)
        if not _stale(out, [src]):
            continue
        tmp = f"{out}.{os.getpid()}.tmp"
        try:
            proc = subprocess.run(
                ["g++", "-O2", "-std=c++17", "-o", tmp, src],
                capture_output=True, text=True, timeout=300)
        except (OSError, subprocess.TimeoutExpired) as exc:
            raise SetupFailure("build", f"g++ {name}: {exc!r}")
        if proc.returncode != 0:
            raise SetupFailure("build", f"g++ {name} rc={proc.returncode}",
                               proc.stderr[-4000:])
        os.replace(tmp, out)


# -- the deployment's files ------------------------------------------------------


def write_deployment(run_dir: str, listen_port: int, upstream_port: int,
                     sources: list, lists: dict) -> str:
    """`pingoo.yml` and the list files: one http listener, one service
    -> the upstream, every rule a Block."""
    import yaml

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
        "rules": {name: {"expression": src, "actions": [{"action": "block"}]}
                  for name, src in sources},
    }
    if list_cfg:
        doc["lists"] = list_cfg
    path = os.path.join(run_dir, "pingoo.yml")
    with open(path, "w", encoding="utf-8") as f:
        yaml.safe_dump(doc, f, sort_keys=False, width=4096)
    return path


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# -- processes -------------------------------------------------------------------


class Procs:
    """Every process a run starts, each in a group of its own, recorded
    on disk so that the next run of this checkout can sweep them."""

    def __init__(self, run_dir: str):
        self.run_dir = run_dir
        self.procs: list = []

    def spawn(self, argv: list, **kw) -> subprocess.Popen:
        proc = subprocess.Popen(argv, start_new_session=True, **kw)
        self.procs.append(proc)
        with open(os.path.join(self.run_dir, "pgids"), "a",
                  encoding="utf-8") as f:
            f.write(f"{proc.pid}\n")
        return proc

    def stop_all(self) -> None:
        """SIGTERM, then kill each group; waits for every child. Never
        raises: teardown does not change a run's exit code."""
        for proc in self.procs:
            try:
                if proc.poll() is None:
                    proc.send_signal(signal.SIGTERM)
            except OSError:
                pass
        deadline = time.monotonic() + 20
        for proc in self.procs:
            try:
                proc.wait(timeout=max(0.1, deadline - time.monotonic()))
            except (subprocess.TimeoutExpired, OSError):
                pass
        for proc in self.procs:
            _kill_group(proc.pid)
            try:
                proc.wait(timeout=10)
            except (subprocess.TimeoutExpired, OSError):
                pass


def start_pong(procs: Procs) -> int:
    proc = procs.spawn([os.path.join(BIN_DIR, "pong"), "0"],
                       stdout=subprocess.PIPE)
    line = _readline_with_deadline(proc.stdout, 10.0)
    try:
        return int(json.loads(line)["listening"])
    except (ValueError, KeyError, TypeError):
        raise SetupFailure("upstream", f"pong said {line!r} instead of "
                           "its port within 10 s")


def _readline_with_deadline(stream, seconds: float) -> bytes:
    box: list = []
    t = threading.Thread(target=lambda: box.append(stream.readline()),
                         daemon=True)
    t.start()
    t.join(seconds)
    return box[0] if box else b""


class Server:
    """The served program as a child; its JSON log lines are read off
    stderr by a pump thread and kept in the run directory."""

    def __init__(self, procs: Procs, config_path: str, run_dir: str,
                 env: dict, extra_args: list):
        self.run_dir = run_dir
        self.memstats_path = os.path.join(run_dir, "memstats.json")
        argv = [sys.executable, os.path.join(HERE, "serve.py"),
                self.memstats_path,
                "--config", config_path, "--no-docker", "--native-plane",
                "--captcha-jwks", os.path.join(run_dir, "captcha_jwks.json"),
                "--state-dir", os.path.join(run_dir, "state")] + extra_args
        self.records: list = []
        self._cond = threading.Condition()
        self.log_path = os.path.join(run_dir, "server.log")
        self._log = open(self.log_path, "wb")
        self.t_spawn = time.monotonic()
        self.proc = procs.spawn(argv, cwd=run_dir, env=env,
                                stdout=subprocess.DEVNULL,
                                stderr=subprocess.PIPE)
        self._pump = threading.Thread(target=self._pump_stderr, daemon=True)
        self._pump.start()

    def _pump_stderr(self) -> None:
        try:
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
        except (OSError, ValueError):
            pass
        with self._cond:
            self._cond.notify_all()

    def alive(self) -> bool:
        return self.proc.poll() is None

    def wait_log(self, message: str, timeout: float, phase: str) -> dict:
        """Block until a log record with this `message` arrives."""
        deadline = time.monotonic() + timeout
        seen = 0
        with self._cond:
            while True:
                for rec in self.records[seen:]:
                    if rec.get("message") == message:
                        return rec
                seen = len(self.records)
                if not self.alive() and not self._pump.is_alive():
                    raise SetupFailure(
                        phase, f"the server exited rc={self.proc.returncode} "
                        f"before logging {message!r}", self.tail())
                left = deadline - time.monotonic()
                if left <= 0:
                    raise SetupFailure(
                        phase, f"waited {timeout:.0f}s for the server's "
                        f"{message!r} log line", self.tail())
                self._cond.wait(min(left, 1.0))

    def tail(self, n: int = 4000) -> str:
        try:
            self._log.flush()
            with open(self.log_path, "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - n))
                return f.read().decode("utf-8", "replace")
        except OSError:
            return ""

    def request_trace(self, seconds: float) -> dict:
        """Ask the wrapper (lib/serve.py) for a bounded profiler window
        from now; the trace goes under the run's tmp directory."""
        request = {"dir": os.path.join(self.run_dir, "tmp", "trace"),
                   "seconds": seconds}
        path = self.memstats_path + ".trace"
        with open(path + ".tmp", "w", encoding="utf-8") as f:
            json.dump(request, f)
        os.replace(path + ".tmp", path)
        return request

    def trace_done(self) -> Optional[dict]:
        try:
            with open(self.memstats_path + ".trace.done",
                      encoding="utf-8") as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def memstats(self) -> Optional[dict]:
        try:
            with open(self.memstats_path, encoding="utf-8") as f:
                return json.load(f)
        except (OSError, ValueError):
            return None


def server_env(run_dir: str, compile_cache: str, stated: dict) -> dict:
    """The child's environment: the ambient one (the platform is never
    pinned) without any of the program's own knobs, then what the
    configuration states and nothing else. Nothing a run learns
    reaches the next one: the cost ledger, the compile ledger and the
    scheduler's bench history are files of the run's own directory;
    only JAX's compile cache outlasts the run, where the machine says
    or else at a fixed path of the checkout."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PINGOO_") and k != "BENCH_RUN"}
    env.setdefault("JAX_COMPILATION_CACHE_DIR", compile_cache)
    tmp = os.path.join(run_dir, "tmp")
    os.makedirs(tmp, exist_ok=True)
    env["TMPDIR"] = tmp
    env["PINGOO_COST_LEDGER"] = os.path.join(run_dir, "cost_ledger.json")
    # on, because the compile ledger is what counts compilations
    env["PINGOO_PERF_LEDGER"] = os.path.join(run_dir, "compile_ledger.jsonl")
    env["BENCH_HISTORY_FILE"] = os.path.join(run_dir, "bench_history.jsonl")
    for key, value in stated.items():
        env[key] = str(value)
    return env


# -- scrapes ---------------------------------------------------------------------


def http_get(port: int, path: str, accept: str = "*/*",
             timeout: float = 5.0) -> Optional[bytes]:
    """One GET on a fresh connection; None on any failure."""
    raw = (f"GET {path} HTTP/1.1\r\nhost: bench\r\naccept: {accept}\r\n"
           f"user-agent: bench-scrape\r\nconnection: close\r\n\r\n").encode()
    try:
        with socket.create_connection(("127.0.0.1", port),
                                      timeout=timeout) as s:
            s.sendall(raw)
            buf = b""
            while True:
                chunk = s.recv(65536)
                if not chunk:
                    break
                buf += chunk
                head, sep, body = buf.partition(b"\r\n\r\n")
                if sep:
                    m = re.search(rb"(?i)content-length:\s*(\d+)", head)
                    if m and len(body) >= int(m.group(1)):
                        break
    except OSError:
        return None
    head, sep, body = buf.partition(b"\r\n\r\n")
    if not sep or not head.startswith(b"HTTP/1.1 200"):
        return None
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
            try:
                value = float(m.group(3))
            except ValueError:
                continue
            out.append((m.group(1), dict(_LABEL.findall(m.group(2) or "")),
                        value))
    return out


class Scraper:
    """Snapshots of the program's counters: the native plane's JSON and
    the control-plane listener's Prometheus text."""

    def __init__(self, listen_port: int, registry_port: int):
        self.listen_port = listen_port
        self.registry_port = registry_port

    def native(self) -> Optional[dict]:
        body = http_get(self.listen_port, "/__pingoo/metrics",
                        accept="application/json")
        try:
            return json.loads(body) if body else None
        except ValueError:
            return None

    def registry(self) -> Optional[list]:
        body = http_get(self.registry_port, "/__pingoo/metrics")
        return parse_prometheus(body.decode("utf-8", "replace")) \
            if body else None

    def snapshot(self, tries: int = 1) -> dict:
        """Both scrapes; with `tries` > 1 a scrape that got no answer
        (the listener busy) is made again."""
        at = time.monotonic()
        native = registry = None
        for _ in range(tries):
            native = native or self.native()
            registry = registry or self.registry()
            if native and registry:
                break
        return {"at_mono": at, "native": native, "registry": registry}
