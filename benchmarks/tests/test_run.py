"""A whole run of the harness on the CPU, at a tiny size, with the real
served program underneath (a rehearsal: the look for a chip is skipped
and no metric is printed), and the exit contract: a run that measured a
window exits 0 and prints its line whatever happened in it; only a
set-up that cannot produce a window exits non-zero, with no line."""

import io
import json
import os
import shutil
import signal
import threading

import pytest

from conftest import BENCH, ROOT
from lib import deploy, harness
from stubs import hold_up

TINY = {"rate_rps": 300, "connections": 16, "templates": 64,
        "sequence_rps": 40000}
CELL = "prefix10.web_steady"     # a rehearsal's own cell: see `tree`
POOLED = "prefix10.web_pooled"
NO_ENV = "no_env.web_steady"


@pytest.fixture(autouse=True)
def on_the_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")


@pytest.fixture(autouse=True)
def tree(monkeypatch, tmp_path):
    """The benchmark's data with two cells added as new files only, as
    a later PR would: the small configuration under the open loop (the
    quickest to boot on a CPU), and a configuration none of whose rules
    blocks `/.env`, with a `probe` of its own."""
    bench_dir = tmp_path / "benchmarks"
    for sub in ("configs", "traffic", "cells", "metrics"):
        shutil.copytree(os.path.join(BENCH, sub), bench_dir / sub)
    (bench_dir / "configs" / "no_env.json").write_text(json.dumps({
        "name": "no_env", "rules": {"literal": [
            ["git", 'http_request.path.starts_with("/.git")'],
            ["nikto", 'http_request.user_agent.starts_with("Nikto")']]},
        "probe": {"method": "GET", "host": "www.example.com",
                  "url": "/.git/config", "user_agent": "probe/1",
                  "status": 403},
        "max_batch": 1024, "fail_open_deadline_ms": 3000, "reduced": [],
        "env": {"PINGOO_STAGING": "compact"}}), encoding="utf-8")
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "no_env", "source": "a test's own", "reduced": [],
        "file": "benchmarks/configs/no_env.json", "why": "a test's"})
    for name in (CELL, NO_ENV):
        config, traffic = name.split(".")
        (bench_dir / "cells" / f"{name}.json").write_text(json.dumps({
            "config": config, "traffic": traffic, "chips": 1}),
            encoding="utf-8")
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": traffic, "chips": 1,
                                   "why": "a test's"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench),
                                             encoding="utf-8")
    cell = harness.Cell
    monkeypatch.setattr(harness, "Cell", lambda workload: cell(
        workload, str(tmp_path), str(bench_dir)))


def run(seed, trace=False, rehearsal=TINY, seconds=3.0, cell=CELL):
    out = io.StringIO()
    rc = harness.run_cell(cell, seed, seconds, trace, rehearsal=rehearsal,
                          out=out)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)


def test_rehearsal_is_correct_and_prints_no_device_metric():
    rc, line = run(11)
    assert rc == 0
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 900               # rate x seconds, whatever the seed
    assert line["metrics"] == {}                  # no number from a CPU run
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "compared"           # comes last in the line
    assert all(v["value"] <= v["limit"] for v in line["compared"].values())
    assert not os.listdir(deploy.RUNS_DIR)        # nothing left behind


def test_pooled_rehearsal_counts_what_the_closed_loop_sent():
    rc, line = run(21, cell=POOLED)
    assert rc == 0
    assert line["correct"] is True and line["failed"] == 0
    # 16 connections, each its next request when the reply is in: as
    # many as were sent in the window, not a number fixed beforehand
    assert 100 < line["attempted"] < 3 * 40000
    assert line["counts"]["sequence_exhausted"] is False
    assert line["metrics"] == {}
    assert line["counts"]["compared"] == line["attempted"]


def test_traced_rehearsal_reads_no_device_trace():
    rc, line = run(12, trace=True)
    assert rc == 0 and line["correct"] is True
    assert line["metrics"] == {} and "busy_s" not in line["device"]
    assert "breakdown" not in line


def test_no_accelerator_no_line(capsys):
    rc, line = run(13, rehearsal=None, seconds=2.0)
    assert rc != 0 and line is None
    tag = f"{CELL}-13-t0"
    with open(os.path.join(deploy.WORK, "out", tag, "failure.json"),
              encoding="utf-8") as f:
        failure = json.load(f)
    assert failure["phase"] == "boot" and "not on a TPU" in failure["reason"]
    assert "not on a TPU" in capsys.readouterr().err.strip().splitlines()[-1]


def test_server_killed_mid_window_still_exits_0(monkeypatch):
    window = harness.Run.window

    def killed(self):
        threading.Timer(
            harness.LEAD_IN_S + 1.0,
            lambda: os.killpg(self.server.proc.pid, signal.SIGKILL)).start()
        return window(self)

    monkeypatch.setattr(harness.Run, "window", killed)
    rc, line = run(14)
    assert rc == 0
    assert line["attempted"] == 900               # rate x seconds, whatever the seed
    assert 300 < line["failed"] < line["attempted"]             # the losses are counted
    counts = line["counts"]
    assert counts["unsent"] + counts["lost"] + counts["no_answer"] > 300


@pytest.mark.parametrize("cell", [CELL, POOLED])
def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch,
                                                               cell):
    """The timed path broken underneath: the served deployment lacks
    the rules the reference holds it to, so it proxies what it should
    block."""
    write = deploy.write_deployment

    def fewer_rules(run_dir, listen_port, upstream_port, sources, lists):
        kept = [s for s in sources if s[0] in ("env", "git")]
        return write(run_dir, listen_port, upstream_port, kept, lists)

    monkeypatch.setattr(deploy, "write_deployment", fewer_rules)
    rc, line = run(15, cell=cell)
    assert rc == 0
    assert line["correct"] is False
    assert line["compared"]["missed_blocks"]["value"] > 0
    assert line["compared"]["false_blocks"]["value"] == 0


def test_a_run_held_up_is_late_not_wrong(monkeypatch):
    """The sidecar stopped for 1.5 s of the window: the native plane
    lets requests through uninspected (its heartbeat rule), attacks
    among them. They are counted as failed, and no verdict was wrong."""
    hold_up(monkeypatch, at_s=0.5, hold_s=1.5)
    rc, line = run(16)
    assert rc == 0
    counts = line["counts"]
    assert counts["fail_open"] > 0 and line["failed"] >= counts["fail_open"]
    assert counts["missed_released"] > 0          # attacks went through,
    assert counts["missed_released"] <= counts["fail_open"]
    assert line["compared"]["missed_blocks"]["value"] == 0   # none by a verdict
    assert "fail_open" not in line["compared"]
    assert line["correct"] is True


def test_a_configuration_boots_on_its_own_probe():
    """No rule of this configuration blocks `/.env`: set-up waits for
    the verdict on the probe its own file names, and the run comes out
    correct."""
    rc, line = run(17, cell=NO_ENV)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 900
    assert line["counts"]["right"] == 900
