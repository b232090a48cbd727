"""One run of one cell: set-up, the measured window, teardown, the
reduction to metrics and the comparison that decides `correct`.

The run's exit contract. Set-up may fail the run (SetupFailure: non-zero
exit, no result line, `failure.json` and the reason as the last line of
stderr) only when no window can be measured. Once the window has
started nothing raises: what goes wrong is counted, the line is
printed, the exit code is 0.

Nothing here names a cell, a configuration, a mix or a metric: they are
files, found by the names in BENCHMARK.json.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
import traceback
from typing import Optional

import numpy as np

from . import deploy, metrics as metrics_mod, reduce as reduce_mod
from .deploy import SetupFailure, log
from .reference import Reference
from .rules import rule_sources
from .traffic import Mix, wire_request

T_PROCESS_START = time.monotonic()

RECORD = np.dtype([("due_ns", "<i8"), ("sent_ns", "<i8"), ("done_ns", "<i8"),
                   ("tmpl", "<u4"), ("status", "<u2"), ("outcome", "u1"),
                   ("fresh", "u1"), ("conn", "<u4")])

LEAD_IN_S = 2.0       # unmeasured traffic before the window, same rate
LEAD_OUT_S = 1.0      # and after it, so the window's ends are steady state
DRAIN_S = 10.0        # how long answers still in flight are waited for
WARM_ROUND_S = 4.0    # one rehearsal round
WARM_MAX_ROUNDS = 10
# The traced part of a --trace 1 window, unless the cell's file gives
# its own `trace_seconds`: a busy device needs less (the profiler takes
# about 80 s to write out each second of some 570,000 operations).
TRACE_S = 1.5
RUN_LIMIT_S = 330.0   # a whole run, the trace's reading with it: under 360 s
COMPILES = {"registry": "pingoo_compile_total", "labels": {"plane": "sidecar"}}


# -- what a cell is ---------------------------------------------------------------


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


class Cell:
    """A workload of BENCHMARK.json with its data files: the
    configuration, the traffic (how it is offered) with the request mix
    it names, and the cell's own file."""

    def __init__(self, workload: str, root: str = deploy.ROOT,
                 bench_dir: str = deploy.BENCH_DIR):
        bench = load_json(os.path.join(root, "BENCHMARK.json"))
        entry = next((w for w in bench["workloads"]
                      if w["name"] == workload), None)
        if entry is None:
            raise SetupFailure("arguments", f"BENCHMARK.json has no workload "
                               f"{workload!r}")
        cfg_entry = next(c for c in bench["configs"]
                         if c["name"] == entry["config"])
        self.bench = bench
        self.bench_dir = bench_dir
        self.name = workload
        self.chips = entry["chips"]
        self.config = load_json(os.path.join(root, cfg_entry["file"]))
        if "status" not in self.config.get("probe", {}):
            raise SetupFailure("arguments", f"{cfg_entry['file']} names no "
                               "`probe`: a request and the `status` that "
                               "only a verdict gives it")
        self.traffic = load_json(os.path.join(
            bench_dir, "traffic", f"{entry['traffic']}.json"))
        self.requests = load_json(os.path.join(
            bench_dir, "traffic", "mixes",
            f"{self.traffic['requests']}.json"))
        self.cell = load_json(os.path.join(
            bench_dir, "cells", f"{workload}.json"))
        self.closed = self.traffic["loop"] == "closed"
        offered = (self.traffic.get("arrival", {}).get("process"),
                   self.traffic.get("connection_use"))
        if not self.closed and offered != ("poisson", "in_turn"):
            raise SetupFailure("arguments", f"traffic {entry['traffic']!r}: "
                               "an open loop is Poisson arrivals on "
                               "connections taken in_turn, the one way the "
                               f"generator offers it, not {offered}")

    def metric_names(self, group: str) -> list:
        """The metrics of `end_to_end` or `per_layer` this cell reports."""
        return [m["name"] for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]


# -- the generator ----------------------------------------------------------------


def write_templates(path: str, templates: list) -> None:
    with open(path, "wb") as f:
        f.write(np.uint32(len(templates)).tobytes())
        for t in templates:
            raw = wire_request(t)
            f.write(np.uint32(len(raw)).tobytes())
            f.write(b"\x01" if t["method"] == "HEAD" else b"\x00")
            f.write(raw)


def write_schedule(path: str, due_ns: np.ndarray, tmpl: np.ndarray) -> None:
    rows = np.empty(len(due_ns), dtype=[("due", "<i8"), ("tmpl", "<u4")])
    rows["due"], rows["tmpl"] = due_ns, tmpl
    rows.tofile(path)


class Generator:
    """One run of native/httpgen over a schedule (or, with `loop`
    "closed", over a sequence)."""

    def __init__(self, procs: deploy.Procs, port: int, connections: int,
                 templates_path: str, run_dir: str, tag: str,
                 due_ns: np.ndarray, tmpl: np.ndarray, span_s: float,
                 addresses_path: str, loop: str):
        self.records_path = os.path.join(run_dir, f"{tag}.records.bin")
        sched = os.path.join(run_dir, f"{tag}.schedule.bin")
        write_schedule(sched, due_ns, tmpl)
        self.n = len(due_ns)
        self.due_ns = due_ns
        self.proc = procs.spawn(
            [os.path.join(deploy.BIN_DIR, "httpgen"), str(port),
             str(connections), templates_path, sched, self.records_path,
             str(int(span_s * 1e9)), str(int(DRAIN_S * 1e9)),
             addresses_path, loop],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL)
        self.span_s = span_s
        self.summary: Optional[dict] = None

    def wait_started(self, timeout: float = 30.0) -> Optional[float]:
        """-> time.monotonic() at the generator's t0 (its clock is
        CLOCK_MONOTONIC too), or None when it never said."""
        line = deploy._readline_with_deadline(self.proc.stdout, timeout)
        try:
            return json.loads(line)["t0_mono_ns"] / 1e9
        except (ValueError, KeyError, TypeError):
            return None

    def finish(self) -> np.ndarray:
        """Wait for the generator and read its records; an empty array
        when it died or wrote none (the requests then count as lost)."""
        try:
            out, _ = self.proc.communicate(
                timeout=self.span_s + DRAIN_S + 60)
            self.summary = json.loads(out.strip().splitlines()[-1])
        except (subprocess.TimeoutExpired, ValueError, IndexError, OSError):
            try:
                self.proc.kill()
            except OSError:
                pass
        try:
            rec = np.fromfile(self.records_path, dtype=RECORD)
        except (OSError, ValueError):
            rec = np.empty(0, dtype=RECORD)
        try:
            os.remove(self.records_path)
        except OSError:
            pass
        return rec


# -- one run ----------------------------------------------------------------------


class Run:
    def __init__(self, workload: str, seed: int, seconds: float, trace: bool,
                 rehearsal: Optional[dict] = None):
        self.cell = Cell(workload)
        self.seed, self.seconds, self.trace = seed, seconds, trace
        # A rehearsal (tests only) overrides sizes (`rate_rps`,
        # `connections`, `templates`) and lifts the accelerator
        # requirement; it can never print a metric.
        self.rehearsal = rehearsal
        tag = f"{workload}-{seed}-t{int(trace)}"
        self.out_dir = os.path.join(deploy.WORK, "out", tag)
        self.run_dir = os.path.join(
            deploy.RUNS_DIR, f"{tag}-{os.getpid()}-{time.time_ns()}")
        self.procs: Optional[deploy.Procs] = None
        self.server: Optional[deploy.Server] = None
        self.scraper: Optional[deploy.Scraper] = None
        self.reference: Optional[Reference] = None
        self.device: dict = {}
        self.notes: dict = {}

    # -- sizes --

    def _size(self, key: str):
        """The traffic file's number, unless a rehearsal gives its own."""
        if self.rehearsal and key in self.rehearsal:
            return self.rehearsal[key]
        if key == "rate_rps":
            return self.cell.traffic["arrival"]["rate_rps"]
        return self.cell.traffic[key]

    # -- set-up --

    def setup(self) -> None:
        deploy.sweep_leftovers()
        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        os.makedirs(self.run_dir, exist_ok=True)
        self.procs = deploy.Procs(self.run_dir)
        deploy.build_native()
        log("native plane and generator built")

        cfg = self.cell.config
        self.sources, self.lists = rule_sources(cfg["rules"])
        upstream_port = deploy.start_pong(self.procs)
        env = deploy.server_env(self.run_dir,
                                os.path.join(deploy.WORK, "jax_cache"),
                                cfg.get("env", {}))
        extra = ["--cache-dir", os.path.join(self.run_dir, "plan_cache")]
        extra += [str(a) for a in cfg.get("server_args", [])]

        # The templates and addresses are made while the server boots.
        traffic = threading.Thread(target=self._prepare_traffic, daemon=True)
        traffic.start()

        boot = None
        for attempt in range(3):   # a lost race for the port: a new one
            self.port = deploy.free_port()
            config_path = deploy.write_deployment(
                self.run_dir, self.port, upstream_port, self.sources,
                self.lists)
            self.server = deploy.Server(self.procs, config_path,
                                        self.run_dir, env, extra)
            try:
                boot = self.server.wait_log("starting pingoo-tpu", 300,
                                            "boot")
                up = self.server.wait_log("native listener up", 900, "boot")
                break
            except SetupFailure as exc:
                if attempt == 2 or "bind" not in (exc.log_tail + exc.reason):
                    raise
                log(f"boot attempt {attempt + 1} lost its port; again")
        self.device = {"platform": boot.get("platform"),
                       "kind": boot.get("device_kind"),
                       "count": boot.get("device_count")}
        log(f"boot line: {self.device}, compile cache "
            f"{boot.get('compile_cache')}")
        if not self.rehearsal:
            if self.device["platform"] != "tpu":
                raise SetupFailure(
                    "boot", f"the server booted on platform "
                    f"{self.device['platform']!r}, not on a TPU: the "
                    f"benchmark needs the accelerator")
            if (self.device["count"] or 0) < self.cell.chips:
                raise SetupFailure(
                    "boot", f"{self.device['count']} chip(s) found, the "
                    f"cell asks for {self.cell.chips}")
        registry_port = int(up["fail_open"].rsplit(":", 1)[1])
        self.scraper = deploy.Scraper(self.port, registry_port)
        self.notes["boot_s"] = time.monotonic() - self.server.t_spawn

        self._first_verdict()
        traffic.join(timeout=600)
        if traffic.is_alive() or not getattr(self, "traffic_ready", False):
            raise SetupFailure("traffic", "the templates were not ready "
                               "within 600 s: "
                               + self.notes.get("traffic_error", ""))
        self._warm()

    def _prepare_traffic(self) -> None:
        try:
            spec = dict(self.cell.requests)
            if self.rehearsal and "templates" in self.rehearsal:
                spec["pool"] = dict(spec["pool"],
                                    templates=self.rehearsal["templates"])
            self.mix = Mix(spec)
            self.templates = self.mix.templates(self.seed)
            # who connects: one source address per connection slot, some
            # of them on the deployment's IP lists where it has any
            listed = [item for items in self.lists.values() for item in items
                      if isinstance(item, str)]
            self.addresses = self.mix.addresses(
                int(self._size("connections")), listed)
            self.addresses_path = os.path.join(self.run_dir, "addresses.bin")
            self.addresses.astype("<u4").tofile(self.addresses_path)
            self.templates_path = os.path.join(self.run_dir, "templates.bin")
            write_templates(self.templates_path, self.templates)
            self.traffic_ready = True
        except Exception:
            self.notes["traffic_error"] = traceback.format_exc()[-2000:]

    def want(self, records: np.ndarray) -> np.ndarray:
        """The plain reference's status for each record: its template
        sent by the client at its connection's source address. The
        reference is built at the first call, which comes once the
        window has closed: it is no part of set-up."""
        if self.reference is None:
            t0 = time.monotonic()
            self.reference = Reference(self.sources, self.lists)
            self.notes["reference_load_s"] = time.monotonic() - t0
        return self.reference.statuses(
            self.templates, records["tmpl"],
            self.addresses[records["conn"] % len(self.addresses)])

    def _exchange(self, template: dict, timeout: float = 10.0) -> int:
        """One request on a fresh connection -> status, 0 on failure."""
        import socket
        try:
            with socket.create_connection(("127.0.0.1", self.port),
                                          timeout=timeout) as s:
                s.sendall(wire_request(dict(template)))
                buf = b""
                while b"\r\n\r\n" not in buf:
                    chunk = s.recv(65536)
                    if not chunk:
                        return 0
                    buf += chunk
                return int(buf.split(b" ", 2)[1])
        except (OSError, ValueError, IndexError):
            return 0

    def _first_verdict(self) -> None:
        """Wait for the first request that a VERDICT decided: the
        configuration's own `probe`, a request that one of its rules
        answers with `status`, which only a verdict does (the fail-open
        deadline proxies it to a 200)."""
        probe = self.cell.config["probe"]
        deadline = time.monotonic() + 900
        while True:
            if self._exchange(probe) == int(probe["status"]):
                break
            if not self.server.alive():
                raise SetupFailure("first verdict", "the server exited "
                                   f"rc={self.server.proc.returncode}",
                                   self.server.tail())
            if time.monotonic() > deadline:
                raise SetupFailure("first verdict", "waited 900 s for the "
                                   "probe to be blocked by a verdict",
                                   self.server.tail())
            time.sleep(0.1)
        self.notes["first_verdict_s"] = (time.monotonic()
                                         - self.server.t_spawn)
        log(f"first verdict {self.notes['first_verdict_s']:.1f}s after spawn")

    def _counters(self) -> tuple:
        snap = self.scraper.snapshot()
        native = snap["native"] or {}
        compiles = metrics_mod.term_value(snap, COMPILES)
        return snap, native.get("fail_open"), compiles

    def _generate(self, tag: str, parts: list) -> Generator:
        """The generator over `parts`, [(salt, seconds)] laid end to
        end. Open loop: each part is a schedule of its own at the
        traffic's rate, so that the measured part holds the same
        requests (rate x seconds of them) whatever the seed. Closed
        loop: one sequence of the mix's templates, as many as
        `sequence_rps` could send in all the seconds."""
        total = sum(seconds for _, seconds in parts)
        if self.cell.closed:
            n = int(float(self._size("sequence_rps")) * total)
            tmpl = self.mix.sequence(self.seed, n, salt=parts[0][0])
            due, loop = np.zeros(n, np.int64), "closed"
        else:
            rate = float(self._size("rate_rps"))
            dues, tmpls, start = [], [], 0.0
            for salt, seconds in parts:
                d, t = self.mix.schedule(self.seed, rate, seconds, salt=salt)
                dues.append(d + int(start * 1e9))
                tmpls.append(t)
                start += seconds
            due, tmpl = np.concatenate(dues), np.concatenate(tmpls)
            loop = "open"
        return Generator(self.procs, self.port, int(self._size("connections")),
                         self.templates_path, self.run_dir, tag, due, tmpl,
                         total, self.addresses_path, loop)

    def _warm(self) -> None:
        """Rehearse the cell's own traffic, offered the cell's own way,
        unmeasured, until a whole round compiles nothing and nothing
        fails open. If the bound on rounds is hit the run still
        measures, and compiles_in_window tells."""
        t0 = time.monotonic()
        rounds, clean = 0, False
        while rounds < WARM_MAX_ROUNDS and not clean:
            rounds += 1
            _, fo0, c0 = self._counters()
            gen = self._generate(f"warm{rounds}", [(rounds, WARM_ROUND_S)])
            gen.wait_started()
            rec = gen.finish()
            _, fo1, c1 = self._counters()
            answered = int((rec["outcome"] == 0).sum())
            log(f"rehearsal {rounds}: {answered} answered "
                f"(outcomes {np.bincount(rec['outcome'], minlength=4).tolist()}"
                f"), compiles {c0}->{c1}, fail_open {fo0}->{fo1}")
            if not self.server.alive():
                raise SetupFailure("warm-up", "the server exited "
                                   f"rc={self.server.proc.returncode}",
                                   self.server.tail())
            clean = (None not in (fo0, fo1, c0, c1) and c1 == c0
                     and fo1 == fo0 and answered > 0)
        self.notes.update(warm_rounds=rounds, warm_clean=clean,
                          warm_s=time.monotonic() - t0)
        if rounds and not clean:
            log("warm-up hit its bound on rounds; measuring all the same")

    # -- the window --

    def window(self) -> dict:
        """Drive the generator: lead-in, the measured window, lead-out.
        Returns the observations; never raises."""
        obs: dict = {"seconds": self.seconds, "trace": None}
        gen = self._generate("window", [(101, LEAD_IN_S), (0, self.seconds),
                                        (102, LEAD_OUT_S)])
        t_gen0 = gen.wait_started()
        if t_gen0 is None:
            t_gen0 = time.monotonic()
        obs["gen_t0_mono"] = t_gen0   # the zero of the records' times
        t_open = t_gen0 + LEAD_IN_S
        t_close = t_open + self.seconds
        obs["setup_s"] = t_open - T_PROCESS_START
        _sleep_until(t_open)
        obs["before"] = self.scraper.snapshot(tries=2)
        if self.trace:
            # The traced part is the window's end, so that the counters
            # are read over seconds the profiler did not slow:
            # obs["after"] is taken just before it starts.
            span = min(float(self.cell.cell.get("trace_seconds", TRACE_S)),
                       self.seconds / 2)
            t_trace = t_close - span - 0.5
            _sleep_until(t_trace)
            obs["after"] = self.scraper.snapshot(tries=2)
            obs["counters_until_ns"] = int(
                (LEAD_IN_S + (t_trace - t_open)) * 1e9)
            try:
                request = self.server.request_trace(span)
            except OSError as exc:
                log(f"trace: could not ask for it: {exc!r}")
                request = None
            obs["trace"] = {"seconds": span, "request": request}
        _sleep_until(t_close)
        if not self.trace:
            obs["after"] = self.scraper.snapshot(tries=2)
        rec = gen.finish()
        # a request due in the window may be released by the fail-open
        # deadline seconds after it: count those until the drain ends
        obs["after_drain"] = self.scraper.snapshot(tries=2)
        obs["generator"] = gen.summary
        lo, hi = int(LEAD_IN_S * 1e9), int((LEAD_IN_S + self.seconds) * 1e9)
        rec = rec[rec["sent_ns"] >= 0] if self.cell.closed else rec
        in_window = (rec["due_ns"] >= lo) & (rec["due_ns"] < hi)
        # every request due (open loop) or sent (closed loop: a
        # request is due when it is sent) in the window
        obs["attempted"] = int(in_window.sum()) if self.cell.closed else int(
            ((gen.due_ns >= lo) & (gen.due_ns < hi)).sum())
        obs["sequence_exhausted"] = bool(self.cell.closed
                                         and len(rec) >= gen.n)
        obs["all_records"] = rec     # lead-in and lead-out included
        obs["records"] = rec[in_window]
        obs["window_ns"] = (lo, hi)
        obs["memstats"] = self._memstats_after(t_close)
        return obs

    def _memstats_after(self, t: float) -> Optional[dict]:
        deadline = time.monotonic() + 5
        stats = self.server.memstats()
        while time.monotonic() < deadline and self.server.alive() and \
                (stats is None or stats.get("at_mono", 0) < t):
            time.sleep(0.2)
            stats = self.server.memstats()
        return stats

    def find_trace(self, obs: dict) -> None:
        """Wait for the profiler's file of the traced part (the program
        writes it when its bounded window ends)."""
        self.trace_file = None
        request = (obs.get("trace") or {}).get("request")
        if not request:
            return
        wait_s = self.seconds_left() - 60    # the reading needs its share
        deadline = time.monotonic() + wait_s
        while time.monotonic() < deadline and self.server.alive():
            done = self.server.trace_done()
            if done is not None:
                obs["trace"]["done"] = done
                log(f"trace: {done}")
                for base, _, names in os.walk(request["dir"]):
                    for name in names:
                        if name.endswith(".xplane.pb"):
                            self.trace_file = os.path.join(base, name)
                if self.trace_file is None:
                    log(f"trace: no .xplane.pb under {request['dir']}: "
                        f"{done!r}")
                return
            time.sleep(0.25)
        log(f"trace: the profiler's window did not end within {wait_s:.0f} s")

    def seconds_left(self) -> float:
        """What the run may still take of its limit (30 s at the least)."""
        return max(30.0, RUN_LIMIT_S - (time.monotonic() - T_PROCESS_START))

    # -- teardown --

    def teardown(self) -> None:
        try:
            if self.procs is not None:
                self.procs.stop_all()
        except Exception:
            log("teardown: " + traceback.format_exc()[-500:])
        try:
            if self.server is not None:
                tail = self.server.tail(20000)
                with open(os.path.join(self.out_dir, "server.log.tail"), "w",
                          encoding="utf-8") as f:
                    f.write(tail)
            # the rings and lists go now; the trace (under tmp/) stays
            # until it has been reduced
            for name in os.listdir(self.run_dir):
                path = os.path.join(self.run_dir, name)
                if name == "tmp":
                    continue
                if os.path.isdir(path):
                    shutil.rmtree(path, ignore_errors=True)
                else:
                    os.remove(path)
        except OSError:
            pass


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.5))


def write_failure(out_dir: str, exc: SetupFailure) -> None:
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "failure.json"), "w",
              encoding="utf-8") as f:
        json.dump({"phase": exc.phase, "reason": exc.reason,
                   "log_tail": exc.log_tail[-4000:]}, f, indent=1)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearsal: Optional[dict] = None, out=sys.stdout) -> int:
    """The whole run -> the process's exit code."""
    run = None

    def on_signal(signum, _frame):   # the driver's time limit, ^C
        raise SetupFailure("signal", f"signal {signum} ended the run")

    for signum in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(signum, on_signal)
        except ValueError:
            pass   # not the main thread (a test's)
    try:
        run = Run(workload, seed, seconds, trace, rehearsal)
        run.setup()
    except SetupFailure as exc:
        out_dir = run.out_dir if run else os.path.join(deploy.WORK, "out")
        if run:
            run.teardown()
        write_failure(out_dir, exc)
        print(f"benchmark set-up failed in {exc.phase}: {exc.reason} "
              f"(see {out_dir}/failure.json)", file=sys.stderr, flush=True)
        return 1
    except Exception:   # a fault of the harness's own set-up
        reason = traceback.format_exc()
        if run:
            run.teardown()
            write_failure(run.out_dir,
                          SetupFailure("set-up", reason[-3000:]))
        print(f"benchmark set-up failed: {reason.strip().splitlines()[-1]}",
              file=sys.stderr, flush=True)
        return 1

    # From here on the run measured a window: it prints its line and
    # exits 0 whatever happened in it.
    try:
        obs = run.window()
        run.find_trace(obs)
    except (Exception, SetupFailure):
        log("window: " + traceback.format_exc()[-2000:])
        obs = {"seconds": seconds, "records": np.empty(0, dtype=RECORD),
               "error": traceback.format_exc()[-500:]}
    run.teardown()
    try:
        line = reduce_mod.result_line(run, obs)
    except Exception:
        log("reduction: " + traceback.format_exc()[-2000:])
        line = reduce_mod.empty_line(run)
    try:
        with open(os.path.join(run.out_dir, "result.json"), "w",
                  encoding="utf-8") as f:
            json.dump({"line": line, "notes": run.notes,
                       "latency_percentiles": reduce_mod.latency_percentiles(
                           obs.get("records")),
                       "latency_by_5s": reduce_mod.latency_slices(
                           obs.get("records"), obs.get("window_ns")),
                       "answered_per_s_by_5s": reduce_mod.answered_slices(
                           obs.get("all_records"), obs.get("window_ns")),
                       "generator": obs.get("generator"),
                       "before": obs.get("before"), "after": obs.get("after")},
                      f)
    except (OSError, TypeError, ValueError):
        pass
    shutil.rmtree(run.run_dir, ignore_errors=True)
    for name, pair in line["compared"].items():
        print(f"compared {name}: {pair['value']} (limit {pair['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), file=out, flush=True)
    return 0
