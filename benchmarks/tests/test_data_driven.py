"""The harness is driven by data: BENCHMARK.json keeps to its contract's
limits and names only files that are there, and a configuration, a
request mix, a traffic, a cell and a per-layer metric added as NEW files
(none edited) are found by name."""

import json
import os
import re
import shutil

import numpy as np
import pytest

from conftest import BENCH, ROOT
from lib import harness, metrics
from lib.reference import Reference
from lib.rules import rule_sources
from lib.traffic import Mix

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def test_benchmark_json_keeps_to_the_contract():
    bench = _bench()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert 1 <= bench["run_seconds"] <= 51
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    assert len(configs) == len(bench["configs"]) <= 24
    assert len(cells) == len(bench["workloads"]) <= 24
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmarks/")
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        with open(os.path.join(ROOT, c["file"]), encoding="utf-8") as f:
            assert json.load(f)["reduced"] == c["reduced"]
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        pairs.add((w["config"], w["traffic"]))
        harness.Cell(w["name"])          # its three files are there
    assert len(pairs) == len(cells)
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and 0 < e2e["setup_s"]["bound"] <= 0.25
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES
        layers.add(m["layer"])
        metrics.load_reader(os.path.join(BENCH, "metrics"), m["name"])
        # each of its cells reports the end-to-end metric it moves
        moved = e2e[m["moves"]].get("workloads", list(cells))
        assert set(m.get("workloads", cells)) <= set(moved), m["name"]
        if m["name"].split(".")[0].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(cells)
    for name in cells:      # setup_s, one more end-to-end, one per-layer
        cell = harness.Cell(name)
        assert "setup_s" in cell.metric_names("end_to_end")
        assert len(cell.metric_names("end_to_end")) >= 2
        assert cell.metric_names("per_layer")
    assert sum(w["chips"] == 4 for w in bench["workloads"]) \
        <= max(1, len(cells) // 2)


def test_no_harness_code_names_a_cell_a_configuration_or_a_mix():
    bench = _bench()
    words = [c["name"] for c in bench["configs"]] \
        + sorted({w["traffic"] for w in bench["workloads"]})
    for name in ["run.py"] + [os.path.join("lib", n)
                              for n in os.listdir(os.path.join(BENCH, "lib"))
                              if n.endswith(".py")]:
        with open(os.path.join(BENCH, name), encoding="utf-8") as f:
            text = f.read()
        for word in words:
            assert word not in text, (name, word)


def test_new_files_only_are_found(tmp_path):
    """A later PR's whole change: five new files and entries in
    BENCHMARK.json. Nothing that was there is edited."""
    bench_dir = tmp_path / "benchmarks"
    for sub in ("configs", "traffic", "cells", "metrics"):
        shutil.copytree(os.path.join(BENCH, sub), bench_dir / sub)
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}

    def write(rel, doc):
        (bench_dir / rel).write_text(json.dumps(doc), encoding="utf-8")

    write("configs/two_rules.json", {
        "name": "two_rules", "rules": {"literal": [
            ["backup", 'http_request.path.starts_with("/backup")'],
            ["bot", 'http_request.user_agent.contains("evilbot")']]},
        "probe": {"method": "GET", "host": "a.example", "url": "/backup/db",
                  "user_agent": "ua/1", "status": 403},
        "max_batch": 1024, "reduced": [], "env": {}})
    write("traffic/mixes/tiny.json", {
        "name": "tiny", "shape_seed": 5, "pool": {"templates": 32, "zipf_s": 0.8},
        "methods": {"GET": 1.0}, "hosts": ["a.example"],
        "path": {"median": 12, "p95": 30, "cap": 64},
        "query": {"share": 0.5, "median": 10, "p95": 40, "url_cap": 128},
        "user_agents": {"browsers": [[1.0, "ua/1"]], "scanner_share": 0.1,
                        "scanners": ["evilbot/2"]},
        "payloads": {"share": 0.1, "query": ["x=1"], "path_head": ["/backup"],
                     "path_tail": ["/x.bak"]}})
    write("traffic/tiny_poisson.json", {
        "name": "tiny_poisson", "requests": "tiny", "loop": "open",
        "arrival": {"process": "poisson", "rate_rps": 50},
        "connections": 4, "connection_use": "in_turn"})
    write("cells/two_rules.tiny_poisson.json", {
        "config": "two_rules", "traffic": "tiny_poisson", "chips": 1})
    write("metrics/verdicts_per_s.json", {
        "what": "verdicts applied per second of the window's counters",
        "reader": "ratio", "num": {"native": "verdicts"},
        "den": {"native": "uptime_s"}})
    bench = _bench()
    bench["configs"].append({
        "name": "two_rules", "source": "a test's own", "reduced": [],
        "file": "benchmarks/configs/two_rules.json", "why": "a test's"})
    bench["workloads"].append({
        "name": "two_rules.tiny_poisson", "config": "two_rules",
        "traffic": "tiny_poisson", "chips": 1, "why": "a test's"})
    bench["per_layer"].append({
        "name": "verdicts_per_s.tiny", "unit": "1/s", "better": "higher",
        "source": "program_counter", "layer": "native httpd",
        "moves": "latency_p50_ms", "workloads": ["two_rules.tiny_poisson"]})
    for m in bench["end_to_end"]:   # the end-to-end metrics it reports
        if m["name"] in ("latency_p50_ms", "inspected_share"):
            m["workloads"].append("two_rules.tiny_poisson")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench),
                                             encoding="utf-8")

    cell = harness.Cell("two_rules.tiny_poisson", str(tmp_path),
                        str(bench_dir))
    assert not cell.closed and cell.requests["name"] == "tiny"
    assert "verdicts_per_s.tiny" in cell.metric_names("per_layer")
    assert "latency_p99_ms.steady" not in cell.metric_names("per_layer")
    assert cell.metric_names("end_to_end") == [
        "inspected_share", "latency_p50_ms", "setup_s"]
    assert cell.config["probe"]["url"] == "/backup/db"
    mix = Mix(cell.requests)
    pool = mix.templates(2 ** 31 + 5)
    due, tmpl = mix.schedule(9, cell.traffic["arrival"]["rate_rps"], 2.0)
    assert len(due) == 100 and 0 <= due.min() and due.max() < 2e9
    sources, lists = rule_sources(cell.config["rules"])
    statuses = Reference(sources, lists).statuses(
        pool, tmpl, mix.addresses(4)[np.arange(100) % 4])
    assert set(statuses.tolist()) == {200, 403}
    # an open loop is offered the one way the generator offers it
    write("traffic/tiny_evenly.json", {
        "name": "tiny_evenly", "requests": "tiny", "loop": "open",
        "arrival": {"process": "uniform", "rate_rps": 50},
        "connections": 4, "connection_use": "in_turn"})
    write("cells/two_rules.tiny_evenly.json", {
        "config": "two_rules", "traffic": "tiny_evenly", "chips": 1})
    bench["workloads"].append({
        "name": "two_rules.tiny_evenly", "config": "two_rules",
        "traffic": "tiny_evenly", "chips": 1, "why": "a test's"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench),
                                             encoding="utf-8")
    with pytest.raises(harness.SetupFailure, match="Poisson"):
        harness.Cell("two_rules.tiny_evenly", str(tmp_path), str(bench_dir))
    reader = metrics.load_reader(str(bench_dir / "metrics"),
                                 "verdicts_per_s.tiny")
    assert reader({"before": {"native": {"verdicts": 10, "uptime_s": 1}},
                   "after": {"native": {"verdicts": 110, "uptime_s": 3}}}) == 50
    assert reader({"before": None, "after": None}) is None
    # every file that was there is as it was
    assert all(p.read_bytes() == data for p, data in before.items())
