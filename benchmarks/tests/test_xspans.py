"""The second reading of the profiler's trace (lib/xspans.py): scopes
from op names, self time of nested operations, idle under the program's
own spans, and the readers on top, on a small hand-made trace."""

import os

import pytest

from lib import metrics, xspans

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def test_the_scope_is_the_deepest_component_of_the_vocabulary():
    assert xspans.scope_of(
        "jit(lanes_packed)/jit(main)/act/bool/dfa/url/while/body/gather") \
        == "dfa/url"
    assert xspans.scope_of("jit(lanes_packed)/act/bool/nfa/url@rest/cond") \
        == "nfa/url@rest"
    assert xspans.scope_of("jit(stage_a_packed)/unpack/slice") == "unpack"
    assert xspans.scope_of("jit(lanes_packed)/act/bool/stack") == "bool"
    assert xspans.scope_of("jit(lanes_packed)/act/argmin") == "act"
    assert xspans.scope_of("jit(lanes_packed)/copy") == xspans.UNSCOPED
    assert xspans.scope_of("") == xspans.UNSCOPED
    name = ('%while.60 = (u32[1024,8]{1,0}) while(%tuple.1), condition=%c, '
            'body=%b, metadata={op_name="jit(lanes_packed)/act/bool/nfa/'
            'path/while" source_file="verdict.py" source_line=339}')
    op_name, where = xspans._event_op_name(name, {}, {})
    assert where == "hlo_line" and xspans.scope_of(op_name) == "nfa/path"
    op_name, where = xspans._event_op_name(
        "%fusion.7", {"tf_op": "jit(lanes_packed)/act/bool/pf/url/and"}, {})
    assert where == "stat:tf_op" and xspans.scope_of(op_name) == "pf/url"
    # this libtpu: the HLO line without metadata, the module's HloProto
    hlo = {"fusion.7": "jit(lanes_packed)/act/bool/win/url/dot_general"}
    op_name, where = xspans._event_op_name(
        "%fusion.7 = s32[8]{0:T(128)S(1)} fusion(s32[256,8] %x), kind=kLoop",
        {"device_offset_ps": 5}, hlo)
    assert where == "hlo_proto" and xspans.scope_of(op_name) == "win/url"
    assert xspans._event_op_name("%fusion.8 = x", {"flops": 3}, hlo) == \
        ("", "none")
    assert xspans._module_ops({"jit_lanes_packed(7)": hlo},
                              "jit_lanes_packed(7)") is hlo
    assert xspans._module_ops({"jit_lanes_packed(7)": hlo},
                              "jit_lanes_packed(99)") is hlo
    assert xspans._module_ops({"jit_lanes_packed(7)": hlo},
                              "jit_stage_a_packed(7)") == {}


def _trace():
    # one device: a while (nfa/url) 1000-2000 holding two body ops
    # (300 ns, one of them under dfa/url), then an unscoped copy, then a
    # gap 2100-5000 under sidecar/device_wait and sidecar/idle, then a
    # pf op; the trace starts at 0 and ends at 6000 by the host's spans
    ops = [("nfa/url", 1000, 1000), ("nfa/url", 1100, 100),
           ("dfa/url", 1500, 200), (xspans.UNSCOPED, 2000, 100),
           ("pf/url", 5000, 500)]
    spans = [("poll", 0, 900, None), ("dispatch", 900, 200, 7),
             ("device_wait", 2050, 1950, 7), ("idle", 4000, 900, None),
             ("resolve", 5600, 400, 8)]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "lanes_calls": 2}],
            "spans": spans, "op_name_found_in": {"hlo_line": 5}}


def test_self_time_goes_to_the_innermost_operation():
    by_scope, merged = xspans.self_times(_trace()["devices"][0]["ops"])
    assert by_scope == {"nfa/url": 800, "dfa/url": 200,
                        xspans.UNSCOPED: 100, "pf/url": 500}
    assert merged == [[1000, 2100], [5000, 5500]]
    # a child that outlasts its parent counts only what lies inside it
    by_scope, merged = xspans.self_times([("a", 0, 100), ("b", 50, 100)])
    assert by_scope == {"a": 50, "b": 50} and merged == [[0, 100]]
    # an operation with no name of its own is its enclosing event's
    by_scope, _ = xspans.self_times([("dfa/url", 0, 100),
                                     (xspans.UNSCOPED, 10, 30),
                                     (xspans.UNSCOPED, 200, 5)])
    assert by_scope == {"dfa/url": 100, xspans.UNSCOPED: 5}


def test_the_tables_add_up():
    out = xspans.reduce_spans(_trace())
    assert out["interval_s"] == pytest.approx(6000 / 1e9)
    assert out["busy_s"] == pytest.approx(1600 / 1e9)
    assert sum(out["by_scope"].values()) == pytest.approx(out["busy_s"])
    assert out["by_kind"]["nfa"] == pytest.approx(800 / 1e9)
    assert out["idle_s"] == pytest.approx((6000 - 1600) / 1e9)
    idle = out["idle_by_phase"]
    # 0-1000: poll 900 + dispatch 100; 2100-5000: device_wait 1900,
    # idle 900, 100 under no span; 5500-6000: resolve 400, 100 bare
    assert idle["poll"] == pytest.approx(900 / 1e9)
    assert idle["dispatch"] == pytest.approx(100 / 1e9)
    assert idle["device_wait"] == pytest.approx(1900 / 1e9)
    assert idle["idle"] == pytest.approx(900 / 1e9)
    assert idle["resolve"] == pytest.approx(400 / 1e9)
    assert idle[xspans.UNCOVERED] == pytest.approx(200 / 1e9)
    longest = out["longest_gaps"][0]
    assert longest["seconds"] == pytest.approx(2900 / 1e9)
    assert (longest["phase"], longest["batch"]) == ("device_wait", 7)
    assert out["batches_spanned"] == 2
    assert xspans.reduce_spans({"devices": [], "spans": []}) is None


def _reader(name):
    return metrics.load_reader(METRICS, name)


def test_the_trace_readers_divide_by_the_lanes_calls():
    obs = {"_xspans": xspans.reduce_spans(_trace())}
    assert _reader("scan_device_ms_per_batch.pooled")(obs) == \
        pytest.approx(1000 / 1e6 / 2)
    assert _reader("prefilter_device_ms_per_batch.steady")(obs) == \
        pytest.approx(500 / 1e6 / 2)
    assert _reader("lanes_unscoped_share.pooled")(obs) == \
        pytest.approx(100 * 100 / 1600)
    assert _reader("device_idle_ms_per_batch.pooled")(obs) == \
        pytest.approx(4400 / 1e6 / 2)


def test_a_program_without_the_names_reads_as_nothing():
    trace = _trace()
    trace["devices"][0]["ops"] = [(xspans.UNSCOPED, s, d)
                                  for _, s, d in trace["devices"][0]["ops"]]
    trace["spans"] = []
    out = xspans.reduce_spans(trace)
    assert out["idle_by_phase"] == {xspans.UNCOVERED: pytest.approx(
        (5500 - 1000 - 1600) / 1e9)}
    obs = {"_xspans": out}
    for name in ("scan_device_ms_per_batch.pooled",
                 "prefilter_device_ms_per_batch.pooled",
                 "lanes_unscoped_share.pooled"):
        assert _reader(name)(obs) is None
    assert _reader("device_idle_ms_per_batch.pooled")(obs) is not None
    # no trace at all, and no counter of the loop's phases
    assert _reader("lanes_unscoped_share.steady")({"_xspans": None}) is None
    snap = {"registry": [("pingoo_pipeline_batches_total",
                          {"plane": "sidecar", "mode": "on"}, 9.0)]}
    assert _reader("sidecar_busy_ms_per_batch.pooled")(
        {"before": snap, "after": snap}) is None


def test_busy_wait_and_idle_add_up_to_the_batch_period():
    def snap(scale):
        loop = [("pingoo_sidecar_loop_ms_total",
                 {"plane": "sidecar", "phase": phase}, ms * scale)
                for phase, ms in (("poll", 10.0), ("encode", 20.0),
                                  ("prefilter", 1.0), ("dispatch", 9.0),
                                  ("host_rules", 2.0), ("device_wait", 500.0),
                                  ("resolve", 30.0), ("provenance", 1.0),
                                  ("bodies", 0.0), ("swap", 0.0),
                                  ("idle", 427.0))]
        return {"registry": loop + [
            ("pingoo_pipeline_batches_total",
             {"plane": "sidecar", "mode": "on"}, 10.0 * scale),
            ("pingoo_sidecar_loop_ms_total",
             {"plane": "python", "phase": "poll"}, 99.0 * scale)]}

    obs = {"before": snap(1), "after": snap(3), "seconds": 2.0}
    busy = _reader("sidecar_busy_ms_per_batch.pooled")(obs)
    wait = _reader("sidecar_device_wait_ms_per_batch.steady")(obs)
    idle = _reader("sidecar_idle_ms_per_batch.pooled")(obs)
    assert (busy, wait, idle) == (pytest.approx(7.3), pytest.approx(50.0),
                                  pytest.approx(42.7))
    # 20 batches in 2.0 s: a batch every 100 ms
    assert busy + wait + idle == pytest.approx(obs["seconds"] * 1e3 / 20)


def _msg(*fields) -> bytes:
    """A protobuf message of (number, bytes | int) fields."""
    def varint(n):
        more = []
        while n > 0x7F:
            more.append(n & 0x7F | 0x80)
            n >>= 7
        return bytes(more + [n])

    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += bytes([number << 3]) + varint(value)
        else:
            out += bytes([number << 3 | 2]) + varint(len(value)) + value
    return out


def test_op_names_come_off_the_wire_of_the_metadata_plane(tmp_path):
    def instruction(name, op_name=None):
        meta = [(7, _msg((2, op_name.encode())))] if op_name else []
        return _msg((1, name.encode()), (2, b"fusion"), *meta)

    hlo = _msg((1, _msg((1, b"jit_lanes"), (3, _msg(
        (1, b"main"),
        (2, instruction("fusion.7", "jit(lanes)/act/bool/dfa/url/gather")),
        (2, instruction("copy.1")))))))
    event = _msg((1, 5), (2, b"jit_lanes(7)"),
                 (5, _msg((1, 3), (6, hlo))))
    device = _msg((2, b"/device:TPU:0"), (4, _msg((1, 9), (2, _msg(
        (1, 9), (2, b"%fusion.7 = ..."))))))
    metadata = _msg((1, 2), (2, b"/host:metadata"),
                    (4, _msg((1, 5), (2, event))))
    space = _msg((1, device), (1, metadata))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    assert xspans.hlo_op_names(str(path)) == {"jit_lanes(7)": {
        "fusion.7": "jit(lanes)/act/bool/dfa/url/gather", "copy.1": ""}}
