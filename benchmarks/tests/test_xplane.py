"""The reduction from a profiler trace to numbers, on a hand-made trace
and on a small one recorded on the chip (tests/data/)."""

import json
import os

import pytest

from lib import metrics, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _trace():
    ops = [("fusion.1", 1000, 300), ("fusion.2", 1200, 300),   # overlap
           ("copy.3", 2000, 100), ("fusion.1", 5000, 500)]
    modules = [("jit_lanes(123)", 1000, 1100), ("jit_prefilter(9)", 5000, 500)]
    host = [("sidecar.encode", 2200, 2500), ("outer", 0, 6000)]
    return {"devices": [{"name": "/device:TPU:0", "lines": [
        {"name": xplane.OPS_LINE, "events": ops},
        {"name": xplane.MODULES_LINE, "events": modules}]}],
        "host": [{"name": "/host:CPU", "lines": [
            {"name": "python", "events": host}]}]}


def test_busy_is_the_union_and_gaps_are_named_by_the_host():
    out = xplane.reduce_events(_trace())
    assert out["busy_s"] == pytest.approx((500 + 100 + 500) / 1e9)
    assert out["window_s"] == pytest.approx(6000 / 1e9)   # host span is wider
    assert out["modules"]["jit_lanes(123)"] == {
        "seconds": pytest.approx(1100 / 1e9), "calls": 1}
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(800 / 1e9)
    gaps = dict(out["breakdown"]["idle_gaps"])
    # the 2100-5000 gap falls under sidecar.encode, the 1500-2000 one
    # only under the outer span
    assert gaps["sidecar.encode"] == pytest.approx(2900 / 1e9)
    assert gaps["outer"] == pytest.approx(500 / 1e9)


def test_no_device_operation_is_nothing_to_read():
    trace = _trace()
    trace["devices"] = []
    assert xplane.reduce_events(trace) is None
    trace["devices"] = [{"name": "/device:TPU:0", "lines": [
        {"name": xplane.OPS_LINE, "events": []}]}]
    assert xplane.reduce_events(trace) is None


def test_the_device_time_reader_divides_by_the_traced_batches():
    reader = metrics.load_reader(
        os.path.join(os.path.dirname(DATA), "..", "metrics"),
        "lanes_device_ms_per_batch.steady")
    reduced = xplane.reduce_events(_trace())
    reduced["modules"]["jit_lanes(123)"]["calls"] = 4
    obs = {"trace": {"reduced": reduced}}
    assert reader(obs) == pytest.approx(1600 / 1e6 / 4)   # lanes + prefilter
    obs["trace"]["reduced"] = None
    assert reader(obs) is None          # nothing to read: left out, never 0


def test_counter_readers():
    d = os.path.join(os.path.dirname(DATA), "..", "metrics")
    before = {"native": {"verdicts": 100, "fail_open": 0,
                         "ring": {"wait_sum_ms": 1000, "depth_hwm": 7}},
              "registry": [("pingoo_compile_total",
                            {"plane": "sidecar", "fn": "lanes"}, 5.0)]}
    after = {"native": {"verdicts": 300, "fail_open": 0,
                        "ring": {"wait_sum_ms": 4000, "depth_hwm": 9}},
             "registry": [("pingoo_compile_total",
                           {"plane": "sidecar", "fn": "lanes"}, 6.0),
                          ("pingoo_compile_total",
                           {"plane": "python", "fn": "verdict"}, 3.0)]}
    obs = {"before": before, "after": after}
    assert metrics.load_reader(d, "verdict_wait_mean_ms.steady")(obs) == 15.0
    assert metrics.load_reader(d, "ring_depth_hwm.steady")(obs) == 9
    assert metrics.load_reader(d, "compiles_in_window.steady")(obs) == 1.0
    obs["after"] = {"native": None, "registry": None}   # the server was gone
    assert metrics.load_reader(d, "verdict_wait_mean_ms.steady")(obs) is None
    assert metrics.load_reader(d, "compiles_in_window.steady")(obs) is None


@pytest.mark.parametrize("config", ["prefix10", "crs500"])
def test_recorded_trace(config):
    """The head of a trace recorded on the chip (PR 27, both
    configurations): busy time against a count made another way (a
    timeline of nanoseconds), and the programs found by name."""
    import numpy as np

    with open(os.path.join(DATA, f"trace_v5e_{config}.json"),
              encoding="utf-8") as f:
        trace = json.load(f)["trace"]
    out = xplane.reduce_events(trace)
    ops = next(ln["events"] for ln in trace["devices"][0]["lines"]
               if ln["name"] == xplane.OPS_LINE)
    first = min(s for _, s, _ in ops)
    line = np.zeros(max(s + d for _, s, d in ops) - first, bool)
    for _, start, dur in ops:
        line[start - first:start - first + dur] = True
    assert out["busy_s"] == pytest.approx(line.sum() / 1e9, rel=1e-9)
    assert 0 < out["busy_s"] <= out["window_s"]
    lanes = [m for name, m in out["modules"].items() if "lanes" in name]
    assert len(lanes) == 1 and lanes[0]["calls"] >= 7
    assert len(out["breakdown"]["device_ops"]) <= 10
    assert all(sec > 0 for _, sec in out["breakdown"]["device_ops"])
    reader = metrics.load_reader(os.path.join(os.path.dirname(DATA), "..",
                                              "metrics"),
                                 "lanes_device_ms_per_batch.pooled")
    per_batch = reader({"trace": {"reduced": out}})
    assert per_batch == pytest.approx(
        sum(m["seconds"] for m in out["modules"].values()) * 1e3
        / lanes[0]["calls"])


@pytest.mark.parametrize("config", ["prefix10", "crs500"])
def test_the_roofline_counts_the_requests_not_the_padding(config):
    """`lanes_roofline` on both configurations: the least time comes
    from the templates' own lengths and the rule sources, for the
    requests the generator saw answered inside the traced interval,
    over the `lanes` device time of the same trace; the program's
    counters, its batches, the staged widths and the padded rows are no
    part of it."""
    import numpy as np

    from conftest import BENCH
    from lib import geometry
    from lib.harness import RECORD
    from lib.rules import rule_sources
    from lib.traffic import Mix

    def load(*parts):
        with open(os.path.join(BENCH, *parts), encoding="utf-8") as f:
            return json.load(f)

    cfg = load("configs", f"{config}.json")
    sources, _ = rule_sources(cfg["rules"])
    spec = load("traffic", "mixes", "web.json")
    spec["pool"] = dict(spec["pool"], templates=128)
    mix = Mix(spec)
    pool = mix.templates(7)
    rec = np.zeros(4000, RECORD)
    rec["tmpl"] = mix.sequence(7, 4000)
    rec["done_ns"] = np.arange(4000) * 1_000_000      # one a millisecond
    rec["outcome"][::50] = 2                          # no answer: no work
    with open(os.path.join(DATA, f"trace_v5e_{config}.json"),
              encoding="utf-8") as f:
        reduced = xplane.reduce_events(json.load(f)["trace"])
    # the generator's clock starts at 100.0 s of the host's; the profiler
    # traces from 101.25 s for half a second: records 1250..1749
    obs = {"records": rec[:10], "all_records": rec, "gen_t0_mono": 100.0,
           "templates": pool, "sources": sources,
           "config": cfg, "device": {"kind": "TPU v5 lite"},
           "trace": {"reduced": reduced,
                     "done": {"started_mono": 101.0, "start_s": 0.25,
                              "traced_s": 0.5}},
           "before": {"native": {"verdicts": 0}, "registry": [
               ("pingoo_pipeline_batches_total", {"plane": "sidecar"}, 0.0)]},
           "after": {"native": {"verdicts": 5.0}, "registry": [
               ("pingoo_pipeline_batches_total", {"plane": "sidecar"}, 1.0),
               ("pingoo_staging_field_cap",
                {"plane": "sidecar", "field": "url"}, 2048.0)]}}
    reader = metrics.load_reader(os.path.join(BENCH, "metrics"),
                                 "lanes_roofline.pooled")
    share = reader(obs)
    per_field = geometry.rules_per_field(sources)
    mine = [t for i, t in enumerate(rec["tmpl"][1250:1750], 1250) if i % 50]
    assert len(mine) == 490
    n_bytes = sum(geometry.request_work(pool[t], per_field)[0] for t in mine)
    lanes = next(m for n, m in reduced["modules"].items() if "lanes" in n)
    by_hand = 100.0 * (n_bytes / 819e9) / lanes["seconds"]
    assert 0 < share < 100
    if config == "prefix10":            # ten rules: the bytes bound it
        assert share == pytest.approx(by_hand, rel=1e-9)
    else:                               # 500 rules: never under the bytes' time
        assert share >= by_hand * (1 - 1e-9)
    # neither the program's counters nor the staged widths are part of it
    obs["after"]["native"]["verdicts"] = 5000.0
    obs["after"]["registry"][1] = ("pingoo_staging_field_cap",
                                   {"plane": "sidecar", "field": "url"}, 64.0)
    assert reader(obs) == share
    # twice the requests in the same device time: twice the share
    obs["trace"]["done"]["traced_s"] = 1.0
    assert reader(obs) == pytest.approx(2 * share, rel=0.05)
    obs["trace"]["done"]["traced_s"] = 0.5
    for missing in ("reduced", "done"):   # nothing to read: left out, never 0
        kept, obs["trace"][missing] = obs["trace"][missing], None
        assert reader(obs) is None
        obs["trace"][missing] = kept
    obs["device"] = {"kind": "TPU v9"}
    with pytest.raises(KeyError):
        reader(obs)


def test_the_scans_least_work_and_an_unknown_device():
    from lib import geometry

    sources = [("a", 'http_request.url.matches("x")'),
               ("b", 'http_request.url.contains("y") && '
                     'http_request.path.starts_with("/z")'),
               ("c", 'client.asn == 3')]
    assert geometry.rules_per_field(sources)["url"] == 2
    per_field = geometry.rules_per_field(sources)
    # a request's own bytes, whatever they are padded to
    req = {"method": "GET", "host": "h.example", "url": "/abc?q=12345",
           "user_agent": "ua/1"}
    assert geometry.request_work(req, per_field) == (
        12 + 4 + 4 + 9 + 3, 2 * 12 + 1 * 4)
    # and the padded batch: every row at the staged widths
    n_bytes, ops = geometry.padded_work(1024, {"url": 2048, "path": 64},
                                        per_field)
    assert (n_bytes, ops) == (1024 * 2112, 1024 * (2 * 2048 + 64))
    least = geometry.least_seconds(819e9 * 1e-3, ops, "TPU v5 lite")
    assert least["bound"] == "hbm" and least["seconds"] == pytest.approx(1e-3)
    assert geometry.least_seconds(1.0, 393e12, "TPU v5 lite")["bound"] == "int8"
    with pytest.raises(KeyError):
        geometry.peaks_for("TPU v9")
