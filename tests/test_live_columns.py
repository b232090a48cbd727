"""The byte loops' shared driver (ops/live_columns.py, ISSUE 29 and 32):
the trip count follows the batch's longest live row, not the staged
width, and the rows walked follow its last live row, not the padded
batch.

  * the block count on every edge of the bound (conftest's length
    patterns at the staged width of 2,048), and that the loop really
    takes that many trips;
  * structure: the jaxprs of `dfa_scan` and `prefilter_scan` at width
    2,048 hold no loop with a static trip count (2,048 columns, or 256
    blocks of 8) — one dynamic `while` each;
  * the planes' `pingoo_scan_columns_total{kind="walked"}` is the
    device's own count, on a batch the staging encoder made;
  * the row bound: both kernels' carries bit-identical to a fixed walk
    of every column and row, for the live rows on every edge of a row
    tile, for batches below, at and off a multiple of ROW_TILE, for
    live rows that are not packed, and for bodyscan's per-row offsets;
    the rows the device walks are the host's `walked_rows`; a batch
    sharded over a mesh takes one walk of its rows; and
    `pingoo_scan_rows_total` counts all of that on both planes.

Value parity with the numpy oracles lives with each kernel's tests
(test_bitsplit_dfa.py, test_prefilter.py, test_bodyscan.py).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from pingoo_tpu.compiler import compile_ruleset  # noqa: E402
from pingoo_tpu.compiler.nfa import lower_bank_to_dfa  # noqa: E402
from pingoo_tpu.compiler.repat import literal_pattern  # noqa: E402
from pingoo_tpu.config.schema import Action, RuleConfig  # noqa: E402
from pingoo_tpu.engine.batch import (  # noqa: E402
    RequestTuple,
    ScanColumnCounters,
    StagingEncoder,
    scan_columns,
    scan_rows,
)
from pingoo_tpu.expr import compile_expression  # noqa: E402
from pingoo_tpu.obs import REGISTRY  # noqa: E402
from pingoo_tpu.ops import bitsplit_dfa, prefilter  # noqa: E402
from pingoo_tpu.ops.bitsplit_dfa import dfa_scan, dfa_to_tables  # noqa: E402
from pingoo_tpu.ops.live_columns import (  # noqa: E402
    BLOCK,
    ROW_TILE,
    live_blocks,
    live_tiles,
    rows_sharded,
    scan_live_columns,
    walked_columns,
    walked_rows,
)
from pingoo_tpu.ops.prefilter import (  # noqa: E402
    bank_to_prefilter_tables,
    build_prefilter_bank,
    prefilter_scan,
)

WIDTH = 2048  # conftest.STAGED_WIDTH


def _expected_blocks(lens, width=WIDTH):
    return -(-min(int(lens.max()), width) // BLOCK)


def test_block_count_on_every_edge(live_lengths):
    lens, _ = live_lengths
    want = _expected_blocks(lens)
    assert int(live_blocks(jnp.asarray(lens), WIDTH)) == want
    assert walked_columns(lens, WIDTH) == BLOCK * want

    # ...and the loop takes exactly that many trips of BLOCK steps: a
    # step that counts itself, live or not.
    def step(count, col, live):
        return count + 1

    steps = jax.jit(lambda d, l: scan_live_columns(
        step, jnp.int32(0), d, l, 0))(
            jnp.zeros((len(lens), WIDTH), dtype=jnp.uint8),
            jnp.asarray(lens))
    assert int(steps) == BLOCK * want


@pytest.mark.parametrize("lens, t_off, want", [
    ([20, 7, 33], 0, 5), ([20, 7, 33], [0, 0, 0], 5),
    ([20, 7, 33], [16, 0, 30], 1), ([20, 7, 33], 40, 0),
    ([20, 7, 33], [40, 7, 33], 0), ([20, 7, 333], [0, 0, 80], 5)])
def test_block_count_follows_the_offset(lens, t_off, want):
    """A 40-column chunk: the bound is the longest REMAINDER, clipped to
    [0, width]; a chunk past every row runs no block (bodyscan's
    per-row offsets)."""
    rem = jnp.asarray(np.asarray(lens) - np.asarray(t_off), dtype=jnp.int32)
    assert int(live_blocks(rem, 40)) == want


def _loops(jaxpr, depth=0):
    """(primitive name, static trip count or None, loops around it) of
    every loop in a jaxpr, nested jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        inner = depth
        if eqn.primitive.name == "scan":
            out.append(("scan", int(eqn.params["length"]), depth))
            inner += 1
        elif eqn.primitive.name == "while":
            out.append(("while", None, depth))
            inner += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_loops(sub, inner))
    return out


@pytest.fixture(scope="module")
def small_tables():
    pats = [literal_pattern(b"union select", case_insensitive=True),
            literal_pattern(b"<script", case_insensitive=True)]
    dfa = lower_bank_to_dfa(pats, state_budget=4096, merge_depths=())
    pf = build_prefilter_bank([tuple(frozenset([b]) for b in b"union"),
                               tuple(frozenset([b]) for b in b"<scr")])
    return dfa_to_tables(dfa), bank_to_prefilter_tables(pf)


@pytest.mark.parametrize("rows", [16, 4 * ROW_TILE, 4 * ROW_TILE - 24])
@pytest.mark.parametrize("kernel", ["dfa_scan", "prefilter_scan"])
def test_no_loop_with_a_static_trip_count(small_tables, kernel, rows):
    """Neither axis is walked by a loop whose trip count is the shape's:
    not the 2,048 columns (256 blocks of 8), and above one row tile not
    the batch's tiles either: the row loop is a dynamic `while` around
    the column loop's, and there is no second walk beside them."""
    dt, pt = small_tables
    fn, tables = {"dfa_scan": (dfa_scan, dt),
                  "prefilter_scan": (prefilter_scan, pt)}[kernel]
    data = jnp.zeros((rows, WIDTH), dtype=jnp.uint8)
    lens = jnp.zeros((rows,), dtype=jnp.int32)
    loops = _loops(jax.make_jaxpr(fn)(tables, data, lens).jaxpr)
    whiles = sorted(depth for kind, _, depth in loops if kind == "while")
    assert whiles == ([0] if rows <= ROW_TILE else [0, 1]), loops
    static = [n for kind, n, _ in loops if kind == "scan"]
    assert not [n for n in static
                if n >= WIDTH // BLOCK or n == -(-rows // ROW_TILE)], loops


# -- the row bound (ISSUE 32) -------------------------------------------------

CHUNK = 24  # columns of the row-bound cases: three blocks


def _fixed_walk(step, carry, data, lengths, t_offset, prepare=None):
    """`scan_live_columns`'s contract with neither bound: every column of
    every row, in order, masked by the same `live`."""
    remaining = jnp.clip(
        lengths.astype(jnp.int32) - jnp.asarray(t_offset, dtype=jnp.int32),
        0, data.shape[1])
    cols = data.T
    if prepare is not None:
        cols = prepare(cols)
    return jax.lax.fori_loop(
        0, data.shape[1],
        lambda i, carry: step(carry, cols[i], i < remaining), carry)


_CHUNK_FNS: dict = {}


def _chunk_fn(kernel, fixed):
    """The jitted chunk kernel over this module's driver, or over the
    fixed walk; one trace per batch size (`t_offset` is always [B])."""
    if (kernel, fixed) not in _CHUNK_FNS:
        module, name = {"dfa": (bitsplit_dfa, "dfa_scan_chunk"),
                        "pf": (prefilter, "prefilter_scan_chunk")}[kernel]
        chunk = getattr(module, name)

        def run(tables, data, lengths, a, b, t_offset):
            driver = module.scan_live_columns
            module.scan_live_columns = (
                _fixed_walk if fixed else scan_live_columns)
            try:
                return chunk(tables, data, lengths, a, b, t_offset)
            finally:
                module.scan_live_columns = driver

        _CHUNK_FNS[kernel, fixed] = jax.jit(run)
    return _CHUNK_FNS[kernel, fixed]


def _fresh(kernel, tables, B):
    if kernel == "dfa":
        return bitsplit_dfa.dfa_init_state(B, tables.num_words)
    return prefilter.prefilter_init_state(B, tables.init.shape[0])


def _bytes(B, width, seed):
    """Bytes that keep both kernels' carries moving: the patterns'
    alphabet, so prefixes of them turn up in every row."""
    rng = np.random.default_rng(seed)
    return rng.choice(np.frombuffer(b"union select<scr", dtype=np.uint8),
                      size=(B, width))


def _assert_same_carry(kernel, tables, data, lens, t_offset=None,
                       carry=None):
    B = data.shape[0]
    if t_offset is None:
        t_offset = np.zeros((B,), dtype=np.int32)
    carry = carry or _fresh(kernel, tables, B)
    args = (tables, jnp.asarray(data), jnp.asarray(lens), *carry,
            jnp.asarray(t_offset, dtype=jnp.int32))
    got = _chunk_fn(kernel, False)(*args)
    want = _chunk_fn(kernel, True)(*args)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # The comparison is of carries that moved (a handful of rows may
    # hold no pattern's first byte).
    live = int((np.asarray(lens) > np.asarray(t_offset)).sum())
    moved = any(np.asarray(w).any() for w in want)
    assert moved if live >= 8 else (live or not moved)
    return got


# A batch below, at, off a multiple of and at a multiple of ROW_TILE.
# The driver is the kernels' one: each kernel takes one batch that walks
# statically and one that walks tile by tile (the DFA's the one with
# pad rows: its carry has a leaf of each rank), so a batch size is one
# kernel's to compile.
BELOW, AT, OFF, MULTIPLE = (ROW_TILE - 56, ROW_TILE, 2 * ROW_TILE + 88,
                            4 * ROW_TILE)
_KERNEL_OF = {BELOW: "dfa", AT: "pf", OFF: "dfa", MULTIPLE: "pf"}
_LIVE = {"0": lambda B: 0, "1": lambda B: 1,
         "tile-1": lambda B: ROW_TILE - 1, "tile": lambda B: ROW_TILE,
         "tile+1": lambda B: ROW_TILE + 1, "175": lambda B: 175,
         "B-1": lambda B: B - 1, "B": lambda B: B}
# Every live-row edge on both batches that are tiled; on the two of one
# walk, the edges that batch has.
_EDGES = {BELOW: ("0", "175", "B"), AT: ("1", "tile-1", "B"),
          OFF: tuple(_LIVE), MULTIPLE: tuple(_LIVE)}
_PACKED = [pytest.param(B, _LIVE[name](B), id=f"B{B}-live_{name}")
           for B, names in _EDGES.items() for name in names]


def _packed_lengths(B, n, seed=0):
    """`n` live rows at the front, as the sidecar packs them: lengths
    1..CHUNK, the longest somewhere among them."""
    lens = np.zeros((B,), dtype=np.int32)
    lens[:n] = np.random.default_rng(seed).integers(1, CHUNK + 1, size=n)
    return lens


def _placements(B):
    """Live rows that are NOT packed at the front."""
    gap = np.zeros((B,), dtype=np.int32)
    gap[[0, 2]] = (CHUNK, 5)               # an empty row between two
    last = np.zeros((B,), dtype=np.int32)
    last[B - 1] = CHUNK - 1                # the only live row is the last
    tile_gap = _packed_lengths(B, B)
    tile_gap[:min(ROW_TILE, B - 1)] = 0    # a first tile with no live row
    return {"gap": gap, "last_row": last, "empty_first_tile": tile_gap}


_PLACED = [pytest.param(B, place, id=f"B{B}-{place}")
           for B in (BELOW, AT, OFF, MULTIPLE)
           for place in ("gap", "last_row", "empty_first_tile")
           if B > ROW_TILE or place != "empty_first_tile"]


@pytest.mark.parametrize("B, n_live", _PACKED)
def test_carry_is_the_fixed_walks_for_packed_rows(small_tables, B, n_live):
    kernel = _KERNEL_OF[B]
    _assert_same_carry(kernel, small_tables[kernel == "pf"],
                       _bytes(B, CHUNK, B + n_live),
                       _packed_lengths(B, n_live, seed=n_live))


@pytest.mark.parametrize("B, place", _PLACED)
def test_carry_is_the_fixed_walks_for_any_placement(small_tables, B, place):
    kernel = _KERNEL_OF[B]
    _assert_same_carry(kernel, small_tables[kernel == "pf"],
                       _bytes(B, CHUNK, B), _placements(B)[place])


@pytest.mark.parametrize("B", [BELOW, OFF, MULTIPLE])
def test_rows_dead_in_one_chunk_are_live_in_the_next(small_tables, B):
    """bodyscan's use: a per-row `t_offset`, the carry threaded across
    windows. In the first window only the front rows have bytes (the
    bound is on `remaining`, so the tiles behind them are not walked);
    in the second every row has, the back rows their first."""
    kernel = _KERNEL_OF[B]
    tables = small_tables[kernel == "pf"]
    rng = np.random.default_rng(B)
    front = np.arange(B) < min(40, B // 2)
    seen = np.where(front, rng.integers(1, CHUNK + 1, size=B), 0)
    start = np.zeros((B,), dtype=np.int32)
    assert walked_rows(seen - start, B) == min(ROW_TILE, B)
    carry = _assert_same_carry(kernel, tables, _bytes(B, CHUNK, 1),
                               seen, start)
    start, seen = seen, seen + rng.integers(1, CHUNK + 1, size=B)
    assert walked_rows(seen - start, B) == B
    _assert_same_carry(kernel, tables, _bytes(B, CHUNK, 2), seen, start,
                       carry=carry)


def _row_lengths_on_every_edge():
    for param in _PACKED:
        B, n_live = param.values
        yield pytest.param(_packed_lengths(B, n_live, seed=1), id=param.id)
    for param in _PLACED:
        B, place = param.values
        yield pytest.param(_placements(B)[place], id=param.id)


_COUNT_FNS: dict = {}


def _steps_taken(lens):
    """Per row, the steps a step that counts itself, live or not, is
    taken through; one trace per batch size."""
    B = len(lens)
    if B not in _COUNT_FNS:
        _COUNT_FNS[B] = jax.jit(lambda d, l: scan_live_columns(
            lambda count, col, live: count + 1,
            jnp.zeros((B,), dtype=jnp.int32), d, l, 0))
    return np.asarray(_COUNT_FNS[B](
        jnp.zeros((B, CHUNK), dtype=jnp.uint8), jnp.asarray(lens)))


@pytest.mark.parametrize("lens", _row_lengths_on_every_edge())
def test_rows_walked_are_the_hosts_count(lens):
    """`walked_rows` is what the device does: its tile count gives the
    host's extent, and each row is taken through its OWN tile's blocks
    inside that extent and no row outside it."""
    B = len(lens)
    extent = walked_rows(lens, B)
    n_tiles = int(live_tiles(jnp.asarray(lens)))
    assert n_tiles == -(-int(np.flatnonzero(lens).max(initial=-1) + 1)
                        // ROW_TILE)
    assert extent == min(ROW_TILE * n_tiles, B)
    want = np.zeros((B,), dtype=np.int32)
    for row in range(0, extent, ROW_TILE):
        rows = slice(row, min(row + ROW_TILE, extent))
        want[rows] = walked_columns(lens[rows], CHUNK)
    np.testing.assert_array_equal(_steps_taken(lens), want)


def test_a_batch_sharded_over_a_mesh_takes_one_walk(small_tables):
    """Under the serving mesh's dp > 1 a row tile would be a dynamic
    slice along the sharded axis (every shard would gather and walk
    it): the trace sees the mesh on its bytes' type and takes the one
    walk over all rows, the counter's twin counts every row, and the
    carries are the single device's."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pingoo_tpu.parallel.mesh import make_mesh

    dt, _ = small_tables
    B = 2 * ROW_TILE
    lens = _packed_lengths(B, 40)
    data, lens_dev = jnp.asarray(_bytes(B, CHUNK, 7)), jnp.asarray(lens)
    assert not rows_sharded(data)
    want = jax.jit(dfa_scan)(dt, data, lens_dev)
    for dp, tp in ((2, 1), (1, 2)):
        mesh = make_mesh(dp=dp, tp=tp)
        placed = (jax.device_put(data, NamedSharding(mesh, P("dp", None))),
                  jax.device_put(lens_dev, NamedSharding(mesh, P("dp"))))
        assert rows_sharded(placed[0]) == (dp > 1)
        loops = _loops(jax.make_jaxpr(dfa_scan)(dt, *placed).jaxpr)
        assert [depth for kind, _, depth in loops if kind == "while"] \
            == ([0] if dp > 1 else [0, 1]), loops
        np.testing.assert_array_equal(
            np.asarray(jax.jit(dfa_scan)(dt, *placed)), np.asarray(want))
    assert walked_rows(lens, B, sharded=True) == B
    assert walked_rows(lens, B) == ROW_TILE
    assert walked_rows(np.zeros_like(lens), B, sharded=True) == 0


def _rule(name, expr):
    return RuleConfig(name=name, expression=compile_expression(expr),
                      actions=[Action.BLOCK])


def test_sidecar_walked_count_is_the_devices():
    """On a batch the staging encoder made (compact: url and path staged
    at 2,048, a row's TRUE length may exceed the width), the counter's
    `walked` is 8 x the helper's block count and `staged` the width."""
    plan = compile_ruleset([
        _rule("u", 'http_request.url.contains("union select")'),
        _rule("p", 'http_request.path.matches("(?i)etc/passwd")'),
        _rule("h", 'http_request.host.starts_with("admin.")')], {})
    counters = ScanColumnCounters("test", plan)
    assert counters.fields == ("path", "url")
    caps = dict(plan.staging_caps)
    assert caps["url"] == caps["path"] == WIDTH
    enc = StagingEncoder(8, plan.field_specs, stage_caps=caps)
    reqs = [RequestTuple(host="a.test", path="/p" * n, url="/u?" + "q" * m,
                         method="GET", user_agent="ua", ip="10.0.0.1")
            for n, m in ((1, 10), (30, 61), (2, 0), (4, 300))]
    batch = enc.encode_requests(reqs, pad_to=8)
    got = scan_columns(batch.arrays, counters.fields)
    for field, (staged, walked) in got.items():
        lens = batch.arrays[f"{field}_len"]
        assert staged == batch.arrays[f"{field}_bytes"].shape[1] == WIDTH
        assert walked == BLOCK * int(live_blocks(jnp.asarray(lens), staged))
    assert got["url"] == (WIDTH, 304) and got["path"] == (WIDTH, 64)

    def value(field, kind):
        return REGISTRY.counter(
            "pingoo_scan_columns_total",
            labels={"plane": "test", "field": field, "kind": kind}).value

    before = {k: value(*k) for k in (("url", "staged"), ("url", "walked"))}
    counters.note(batch.arrays)
    assert value("url", "staged") - before["url", "staged"] == WIDTH
    assert value("url", "walked") - before["url", "walked"] == 304


def test_sidecar_walked_rows_are_the_devices():
    """`pingoo_scan_rows_total` on both planes' labels, for batches of
    known lengths: `staged` the padded batch's rows, `walked` the row
    tiles up to the field's last row with a byte; every row on a plane
    whose mesh shards batches."""
    plan = compile_ruleset([
        _rule("u", 'http_request.url.contains("union select")'),
        _rule("a", 'http_request.user_agent.matches("(?i)sqlmap")')], {})
    rows = 4 * ROW_TILE
    enc = StagingEncoder(rows, plan.field_specs,
                         stage_caps=dict(plan.staging_caps))

    def batch_of(n, agents):
        return enc.encode_requests(
            [RequestTuple(host="a.test", path="/", url="/?q=1",
                          method="GET", ip="10.0.0.1",
                          user_agent="curl" if i < agents else "")
             for i in range(n)], pad_to=rows)

    def value(plane, field, kind):
        return REGISTRY.counter(
            "pingoo_scan_rows_total",
            labels={"plane": plane, "field": field, "kind": kind}).value

    for plane in ("sidecar", "python"):
        counters = ScanColumnCounters(plane, plan)
        assert counters.fields == ("url", "user_agent")
        for n, agents, url_rows, ua_rows in (
                (ROW_TILE + 1, 3, 2 * ROW_TILE, ROW_TILE),
                (rows - 5, 0, rows, 0), (1, 0, ROW_TILE, 0)):
            batch = batch_of(n, agents)
            got = scan_rows(batch.arrays, counters.fields)
            assert got == {"url": (rows, url_rows),
                           "user_agent": (rows, ua_rows)}
            for field, (staged, walked) in got.items():
                lens = jnp.asarray(batch.arrays[f"{field}_len"])
                assert walked == ROW_TILE * int(live_tiles(lens))
            before = {k: value(plane, *k) for k in (
                ("url", "staged"), ("url", "walked"),
                ("user_agent", "staged"), ("user_agent", "walked"))}
            counters.note(batch.arrays)
            after = {k: value(plane, *k) - v for k, v in before.items()}
            assert after == {
                ("url", "staged"): rows, ("url", "walked"): url_rows,
                ("user_agent", "staged"): rows,
                ("user_agent", "walked"): ua_rows}
        sharded = ScanColumnCounters(plane, plan, rows_sharded=True)
        before = value(plane, "url", "walked"), \
            value(plane, "user_agent", "walked")
        sharded.note(batch_of(1, 0).arrays)
        assert (value(plane, "url", "walked") - before[0],
                value(plane, "user_agent", "walked") - before[1]) \
            == (rows, 0)
