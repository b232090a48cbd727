"""Streaming body-inspection tests (ISSUE 13).

The core property is split-anywhere parity: a payload split at EVERY
byte boundary (and across ring-window boundaries) must produce verdict
bits identical to the contiguous scan and to the `re` interpreter
oracle, across NFA / DFA / prefilter-lazy modes and odd batch tails —
WAFFLED's split-payload discrepancy class, pinned as a test. Also
covers the chunk-carry kernel primitives directly (dfa_scan_chunk /
prefilter_scan_chunk vs their whole-field scans), lane composition
(merge_actions), flow-table admission/eviction degrades, and the
PINGOO_BODY_INSPECT=off bit-exactness gate.
"""

import os
import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from pingoo_tpu.compiler import repat  # noqa: E402
from pingoo_tpu.compiler.nfa import build_bank, lower_bank_to_dfa  # noqa: E402
from pingoo_tpu.engine import bodyscan  # noqa: E402
from pingoo_tpu.engine.bodyscan import (  # noqa: E402
    BodyRule,
    BodyScanner,
    BodyWindow,
    body_lanes_oracle,
    compile_body_plan,
    merge_actions,
    split_payload,
)
from pingoo_tpu.ops.bitsplit_dfa import (  # noqa: E402
    dfa_finalize,
    dfa_init_state,
    dfa_scan,
    dfa_scan_chunk,
    dfa_to_tables,
)
from pingoo_tpu.ops.nfa_scan import (  # noqa: E402
    bank_to_tables,
    extract_slots,
    init_scan_state,
    nfa_scan,
    scan_chunk,
)
from pingoo_tpu.ops.prefilter import (  # noqa: E402
    bank_to_prefilter_tables,
    build_prefilter_bank,
    prefilter_extract,
    prefilter_init_state,
    prefilter_scan,
    prefilter_scan_chunk,
)

RULES = bodyscan.DEFAULT_BODY_RULES

PAYLOADS = [
    b"",
    b"a",
    b"hello world, nothing to see",
    b"id=1+UNION SELECT password from users--",
    b"union selec",  # near miss
    b"x" * 37 + b"<ScRiPt>alert(1)</script>" + b"y" * 11,
    b"../../" + b"../../etc/shadow",
    b"path=....//....//etc/passwd\x00",
    b"e" * 64 + b"eval(base64_decode('aGk='))",  # captcha rule
    b"union" + b" " * 30 + b"select",  # no match: literal needs one space
    b"UNION SELECT",  # exact boundary match at both ends
    b"<scrip" + b"t src=x>",  # literal straddle bait
    b"' or '1'='1",
    bytes(random.Random(7).randrange(256) for _ in range(301)),
]


def _split_points(n: int):
    """Every byte boundary for short payloads, a dense sample for long."""
    if n <= 64:
        return range(n + 1)
    pts = set(range(0, 17))
    pts |= {n - i for i in range(17) if n - i >= 0}
    pts |= set(random.Random(n).sample(range(n + 1), 24))
    return sorted(pts)


def _feed(scanner, payload, cuts, flow_id=1):
    """Drive a payload through the scanner split at `cuts` offsets."""
    bounds = [0] + list(cuts) + [len(payload)]
    outs = []
    seq = 0
    # slice into (possibly empty) windows between consecutive bounds
    pieces = [payload[a:b] for a, b in zip(bounds, bounds[1:])]
    if not pieces:
        pieces = [b""]
    for i, piece in enumerate(pieces):
        outs = scanner.scan_windows([BodyWindow(
            flow_id=flow_id, win_seq=seq, data=piece,
            final=(i == len(pieces) - 1))])
        seq += 1
    assert outs, "final window must yield a verdict"
    return outs[0]


@pytest.fixture(scope="module")
def plan():
    return compile_body_plan(RULES, window=64)


def test_plan_shape(plan):
    assert plan.slot_rule.shape[0] >= len(RULES)
    assert plan.dfa_tables is not None and plan.dfa_tables.exact
    assert plan.pf_tables is not None
    assert plan.lazy_ok, "seed literal rules must enable the lazy cascade"


# -- kernel chunk-carry primitives -------------------------------------------


def test_dfa_chunk_matches_whole_scan(plan):
    rng = random.Random(3)
    tables = plan.dfa_tables
    B, L = 5, 96
    data = np.zeros((B, L), dtype=np.uint8)
    rows = [b"union select now", b"<script>x", b"no match here at all",
            b"", b"ev" + b"al(" + bytes(rng.randrange(256)
                                        for _ in range(40))]
    lens = np.array([len(r) for r in rows], dtype=np.int32)
    for i, r in enumerate(rows):
        data[i, :len(r)] = np.frombuffer(r, np.uint8)
    whole = np.asarray(dfa_scan(tables, jnp.asarray(data),
                                jnp.asarray(lens)))
    for cut in (0, 1, 7, 48, 95, 96):
        st, H = dfa_init_state(B, tables.num_words)
        st, H = dfa_scan_chunk(tables, jnp.asarray(data[:, :cut]),
                               jnp.asarray(lens), st, H, 0)
        st, H = dfa_scan_chunk(tables, jnp.asarray(data[:, cut:]),
                               jnp.asarray(lens), st, H, cut)
        got = np.asarray(dfa_finalize(tables, st, H, jnp.asarray(lens)))
        np.testing.assert_array_equal(got, whole)


def test_prefilter_chunk_matches_whole_scan(plan):
    tables = plan.pf_tables
    B, L = 4, 80
    rows = [b"xxunion selectyy", b"union sele", b"ct from t",
            b"eval(') /etc/passwd"]
    data = np.zeros((B, L), dtype=np.uint8)
    lens = np.array([len(r) for r in rows], dtype=np.int32)
    for i, r in enumerate(rows):
        data[i, :len(r)] = np.frombuffer(r, np.uint8)
    whole = np.asarray(prefilter_scan(tables, jnp.asarray(data),
                                      jnp.asarray(lens)))
    for cut in (0, 3, 9, 40, 80):
        S, H = prefilter_init_state(B, tables.init.shape[0])
        S, H = prefilter_scan_chunk(tables, jnp.asarray(data[:, :cut]),
                                    jnp.asarray(lens), S, H, 0)
        S, H = prefilter_scan_chunk(tables, jnp.asarray(data[:, cut:]),
                                    jnp.asarray(lens), S, H, cut)
        got = np.asarray(prefilter_extract(tables, H))
        np.testing.assert_array_equal(got, whole)


def test_prefilter_literal_straddle(plan):
    """A factor split across the chunk boundary completes on the S
    carry — the straddle case the overlap-tail-free design rests on."""
    tables = plan.pf_tables
    payload = b"zzzunion selectzzz"
    mid = payload.index(b"n sel")  # cut inside the literal
    data = np.frombuffer(payload, np.uint8)[None, :]
    lens = np.array([len(payload)], dtype=np.int32)
    whole = np.asarray(prefilter_scan(tables, jnp.asarray(data),
                                      jnp.asarray(lens)))
    S, H = prefilter_init_state(1, tables.init.shape[0])
    S, H = prefilter_scan_chunk(tables, jnp.asarray(data[:, :mid]),
                                jnp.asarray(lens), S, H, 0)
    S, H = prefilter_scan_chunk(tables, jnp.asarray(data[:, mid:]),
                                jnp.asarray(lens), S, H, mid)
    np.testing.assert_array_equal(
        np.asarray(prefilter_extract(tables, H)), whole)
    assert whole.any(), "the union-select factor must be present"


def test_chunks_past_every_row_are_identity(plan):
    """ISSUE 29: the chunk loops run ceil(max(lens - t_offset) / 8)
    blocks. Rows resume at their OWN offset (per-row t_offset, as ring
    windows do), the ring keeps handing out windows after the longest
    row has ended, and a window wholly past every row must run zero
    blocks: the carry comes back as it went in, whatever bytes the
    window holds, and the final hits equal the contiguous scan."""
    rows = [b"x" * 5 + b"union select 1", b"<script>alert(1)</script>",
            b"", b"q=" + b"../" * 9 + b"etc/passwd", b"eval(abc)"]
    B, W = len(rows), 16
    lens = np.array([len(r) for r in rows], dtype=np.int32)
    start = np.array([0, 3, 0, 7, 1], dtype=np.int32)  # already consumed
    n_win = 6  # the longest row ends in window 3; 4 and 5 are past all
    assert (start + n_win * W > lens.max() + 2 * W).all()
    whole = np.zeros((B, int(lens.max())), dtype=np.uint8)
    for i, r in enumerate(rows):
        whole[i, :len(r)] = np.frombuffer(r, np.uint8)
    jl = jnp.asarray(lens)
    dt, pt = plan.dfa_tables, plan.pf_tables
    dfa_chunk, pf_chunk = jax.jit(dfa_scan_chunk), jax.jit(prefilter_scan_chunk)
    ref_dfa = np.asarray(dfa_scan(dt, jnp.asarray(whole), jl))
    ref_pf = np.asarray(prefilter_scan(pt, jnp.asarray(whole), jl))

    # The bytes before each row's start, as one first chunk at offset 0.
    head = np.zeros((B, int(start.max())), dtype=np.uint8)
    for i, r in enumerate(rows):
        head[i, :start[i]] = np.frombuffer(r[:start[i]], np.uint8)
    st, Hd = dfa_chunk(dt, jnp.asarray(head),
                       jnp.minimum(jl, jnp.asarray(start)),
                       *dfa_init_state(B, dt.num_words), 0)
    S, Hp = pf_chunk(pt, jnp.asarray(head),
                     jnp.minimum(jl, jnp.asarray(start)),
                     *prefilter_init_state(B, pt.init.shape[0]), 0)
    past_all = 0
    for w in range(n_win):
        t_off = start + w * W
        win = np.full((B, W), 0x27, dtype=np.uint8)  # garbage past rows
        for i, r in enumerate(rows):
            piece = r[t_off[i]:t_off[i] + W]
            win[i, :len(piece)] = np.frombuffer(piece, np.uint8)
        before = [np.asarray(a) for a in (st, Hd, S, Hp)]
        st, Hd = dfa_chunk(dt, jnp.asarray(win), jl, st, Hd,
                           jnp.asarray(t_off))
        S, Hp = pf_chunk(pt, jnp.asarray(win), jl, S, Hp,
                         jnp.asarray(t_off))
        if (t_off >= lens).all():
            past_all += 1
            for a, b in zip(before, (st, Hd, S, Hp)):
                np.testing.assert_array_equal(np.asarray(b), a)
    assert past_all >= 2
    np.testing.assert_array_equal(
        np.asarray(dfa_finalize(dt, st, Hd, jl)), ref_dfa)
    np.testing.assert_array_equal(
        np.asarray(prefilter_extract(pt, Hp)), ref_pf)
    assert ref_dfa.any() and ref_pf.any()


# -- split-anywhere property --------------------------------------------------


def _contiguous_lanes(plan, payload, mode):
    scanner = BodyScanner(plan, mode=mode)
    v = scanner.scan_buffered(payload)
    return v.unverified, v.verified_block, v.matched


@pytest.mark.parametrize("mode", ["nfa", "dfa"])
def test_split_anywhere_parity(plan, mode):
    for payload in PAYLOADS:
        oracle = body_lanes_oracle(plan, payload)
        contiguous = _contiguous_lanes(plan, payload, mode)
        assert contiguous[:2] == oracle[:2], (payload, mode)
        assert set(contiguous[2]) == set(oracle[2]), (payload, mode)
        for cut in _split_points(len(payload)):
            scanner = BodyScanner(plan, mode=mode)
            got = _feed(scanner, payload, [cut])
            assert (got.unverified, got.verified_block) == oracle[:2], (
                payload, mode, cut)
            assert set(got.matched) == set(oracle[2]), (payload, mode, cut)


@pytest.mark.parametrize("lazy", ["auto", "off"])
def test_split_anywhere_lazy_modes(plan, lazy, monkeypatch):
    monkeypatch.setenv("PINGOO_BODY_LAZY", lazy)
    for payload in PAYLOADS:
        oracle = body_lanes_oracle(plan, payload)
        for cut in _split_points(len(payload))[::3]:
            scanner = BodyScanner(plan, mode="nfa")
            assert scanner.lazy == (lazy == "auto")
            got = _feed(scanner, payload, [cut])
            assert (got.unverified, got.verified_block) == oracle[:2], (
                payload, lazy, cut)


def test_multiwindow_three_way_splits(plan):
    """Windows smaller than the ring cap: three-way and many-way splits,
    batched across interleaved flows (odd batch tails)."""
    rng = random.Random(11)
    payloads = [p for p in PAYLOADS if p]
    oracles = {i: body_lanes_oracle(plan, p) for i, p in
               enumerate(payloads)}
    for mode in ("nfa", "dfa"):
        scanner = BodyScanner(plan, mode=mode)
        # interleave windows of all flows in one scan_windows call
        windows = []
        for i, p in enumerate(payloads):
            cuts = sorted(rng.sample(range(len(p) + 1),
                                     min(3, len(p))))
            bounds = [0] + cuts + [len(p)]
            pieces = [p[a:b] for a, b in zip(bounds, bounds[1:])]
            for j, piece in enumerate(pieces):
                windows.append(BodyWindow(i, j, piece,
                                          final=(j == len(pieces) - 1)))
        verdicts = scanner.scan_windows(windows)
        assert len(verdicts) == len(payloads)
        for v in verdicts:
            assert (v.unverified, v.verified_block) == oracles[v.flow_id][:2]


def test_regex_rules_split_parity():
    """Regex body rules (rep loops, classes) through the same property;
    unbounded footprint disables lazy but carry must stay exact."""
    rules = (
        BodyRule("rx-sel-from", r"select[ ]+[a-z*]+[ ]+from", "regex", True,
                 ("block",)),
        BodyRule("rx-digits", r"id=[0-9]+--", "regex", False, ("captcha",)),
    )
    plan = compile_body_plan(rules, window=32)
    payloads = [
        b"SELECT * FROM users",
        b"x" * 30 + b"select  password   from creds" + b"y" * 9,
        b"id=12345--",
        b"id=--",
        b"select from",
    ]
    for mode in ["nfa"] + (["dfa"] if plan.dfa_tables is not None else []):
        for p in payloads:
            oracle = body_lanes_oracle(plan, p)
            for cut in _split_points(len(p)):
                scanner = BodyScanner(plan, mode=mode)
                got = _feed(scanner, p, [cut])
                assert (got.unverified, got.verified_block) == oracle[:2], (
                    p, mode, cut)


def test_ring_window_sized_splits(plan):
    """Payloads longer than the scan window arrive as multiple ring
    windows regardless of transport chunking — exercise window-cap
    slicing plus an extra transport split."""
    p = (b"A" * 100 + b"union sel" + b"B" * 60 + b"ect nope"
         + b"C" * 50 + b"UNION SELECT" + b"D" * 40)
    oracle = body_lanes_oracle(plan, p)
    for mode in ("nfa", "dfa"):
        for w in (16, 64, 4096):
            scanner = BodyScanner(plan, mode=mode)
            pieces = split_payload(p, w)
            outs = []
            for i, piece in enumerate(pieces):
                outs = scanner.scan_windows([BodyWindow(
                    9, i, piece, final=(i == len(pieces) - 1))])
            got = outs[0]
            assert (got.unverified, got.verified_block) == oracle[:2], (
                mode, w)


# -- lanes + composition ------------------------------------------------------


def test_lane_semantics(plan):
    # captcha rule only
    v = BodyScanner(plan).scan_buffered(b"eval('x')")
    assert v.unverified == bodyscan.ACTION_CAPTCHA
    assert not v.verified_block
    # block rule wins the first-action race when it comes first
    v = BodyScanner(plan).scan_buffered(b"<script>eval('x')")
    assert v.unverified == bodyscan.ACTION_BLOCK
    assert v.verified_block


def test_merge_actions():
    CAPTCHA, BLOCK, VB = 2, 1, 0x4
    route = 0x5 << 3
    # metadata first-action wins
    assert merge_actions(route | CAPTCHA, BLOCK, True) == (
        route | VB | CAPTCHA)
    # body supplies the action when metadata had none
    assert merge_actions(route, CAPTCHA, False) == route | CAPTCHA
    assert merge_actions(0, BLOCK, True) == VB | BLOCK
    # verified-block ORs across both verdicts
    assert merge_actions(VB, 0, False) == VB
    assert merge_actions(0, 0, True) == VB
    # no body match leaves the metadata byte untouched
    for meta in (0, BLOCK, CAPTCHA, VB | BLOCK, route | CAPTCHA):
        assert merge_actions(meta, 0, False) == meta


def test_merge_actions_matches_native_twin():
    # httpd.cc merge_body_action is the C twin of merge_actions; pin
    # them byte-for-byte over the whole domain (meta byte x body
    # verdict byte, where the body byte is BodyVerdict.action_byte():
    # unverified in bits 0-1, verified-block in bit 2).
    def c_twin(meta, body):
        unverified = (meta & 3) if (meta & 3) else (body & 3)
        return (meta & 0xF8) | ((meta | body) & 4) | unverified

    for meta in range(256):
        for unverified in range(4):
            for verified in (False, True):
                body = unverified | (0x4 if verified else 0)
                assert merge_actions(meta, unverified, verified) == \
                    c_twin(meta, body), (meta, unverified, verified)


# -- flow table ---------------------------------------------------------------


def test_flow_eviction_degrades(plan):
    scanner = BodyScanner(plan, mode="nfa", max_flows=2)
    scanner.scan_windows([BodyWindow(1, 0, b"union sel"),
                          BodyWindow(2, 0, b"<scr")])
    assert scanner.flows_active == 2
    # third flow evicts the stalest; evicted flow finishes degraded
    scanner.scan_windows([BodyWindow(3, 0, b"x")])
    assert scanner.flows_active == 2
    assert scanner.stats.degrade_total == 1
    out = scanner.scan_windows([BodyWindow(1, 1, b"ect", final=True)])
    assert out and out[0].degraded and out[0].unverified == 0


def test_flow_ttl_eviction(plan):
    clock = [0]
    scanner = BodyScanner(plan, mode="nfa", flow_ttl_ms=100,
                          now_ms=lambda: clock[0])
    scanner.scan_windows([BodyWindow(5, 0, b"union")])
    clock[0] = 500
    assert scanner.evict_stale() == 1
    assert scanner.flows_active == 0
    assert scanner.stats.degrade_total == 1


def test_window_gap_degrades(plan):
    scanner = BodyScanner(plan, mode="nfa")
    scanner.scan_windows([BodyWindow(7, 0, b"union select")])
    out = scanner.scan_windows([BodyWindow(7, 2, b"x", final=True)])
    assert out[0].degraded


def test_lazy_skips_clean_traffic(plan):
    """Bodies with no factor hit must never run the NFA at all."""
    scanner = BodyScanner(plan, mode="nfa")
    assert scanner.lazy
    v = scanner.scan_buffered(b"perfectly ordinary form data " * 20)
    assert v.unverified == 0 and not v.verified_block
    assert scanner.stats.lazy_skips > 0


# -- stats / gate -------------------------------------------------------------


def test_stats_accumulate(plan):
    scanner = BodyScanner(plan, mode="nfa")
    scanner.scan_buffered(b"union select " * 40)
    st = scanner.stats
    assert st.windows_total >= 1
    assert st.bytes_total == len(b"union select " * 40)
    assert st.flows_started == st.flows_finished == 1
    assert st.carry_depth >= 1


def test_inspect_gate_default_off(monkeypatch):
    monkeypatch.delenv("PINGOO_BODY_INSPECT", raising=False)
    assert not bodyscan.body_inspect_enabled()
    monkeypatch.setenv("PINGOO_BODY_INSPECT", "on")
    assert bodyscan.body_inspect_enabled()


def test_custom_rules_file(tmp_path, monkeypatch):
    import json

    path = tmp_path / "body_rules.json"
    path.write_text(json.dumps([
        {"name": "r1", "pattern": "abc", "kind": "literal",
         "actions": ["block"]},
    ]))
    monkeypatch.setenv("PINGOO_BODY_RULES", str(path))
    rules = bodyscan.load_body_rules()
    assert rules == (BodyRule("r1", "abc", "literal", False, ("block",)),)
