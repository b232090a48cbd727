"""The byte loops' shared driver: walk a staged [B, Lc] byte matrix only
as far as the batch's longest live row (ISSUE 29), and only over the
row tiles up to its last live row (ISSUE 32).

ops/bitsplit_dfa.dfa_scan_chunk and ops/prefilter.prefilter_scan_chunk
advance a per-row carry one byte column at a time, every step masked by
"this column lies inside the row". Compact staging fixes Lc at the
plan's caps (2,048 for a url), while a batch's longest url is a few
hundred bytes: every column at or past the longest row is the identity
on every carry. So the trip count is computed ON THE DEVICE from the
lengths, in blocks of BLOCK columns:

    n_blocks = ceil(clip(max(lengths - t_offset), 0, Lc) / BLOCK)

and the loop is a `while` over blocks whose body is BLOCK steps written
out, so XLA still fuses across them as it did under `lax.scan(...,
unroll=8)`.

The row axis is bounded by the same argument turned a quarter. The
sidecar pads every batch to `max_batch` rows (1,024) and packs the
requests at the front; a row whose `remaining = clip(lengths -
t_offset, 0, Lc)` is 0 is the identity on its carry at every column,
and a column's price is its gathers', linear in the rows of the matrix
whatever they hold (TPU v5e, `dfa/url`: ~0.7 us + ~15 ns a row). So,
again on the device:

    last    = max(where(remaining > 0, arange(B) + 1, 0))
    n_tiles = ceil(last / ROW_TILE)

and an outer `while` walks row tiles 0 .. n_tiles-1: each trip slices
ROW_TILE lanes out of the column-major bytes, out of `remaining` and
out of every leaf of the carry, runs the block loop on them with THE
TILE'S OWN `live_blocks` (a tile of short rows stops early), and writes
the carry back. `prepare(block)` sees [BLOCK, ROW_TILE], so the DFA's
byte -> class gather shrinks with it. The bound is on `remaining`, not
on `lengths`: engine/bodyscan.py threads carries across ring windows
with a per-row `t_offset`, and a row dead in one chunk is live in the
next.

The form, and why (PERF.md section 5 has the sweep): one loop nest,
no second walk beside it. A tile loop pays a column's fixed part once
a TILE, so a batch whose every row is the batch's longest costs a
little more over four tiles than one walk of all rows did (`dfa/url` +
`pf/url` together +1 % at 1,024 rows of 470 bytes, `pf/url` alone
+20 %); a full batch of web-like lengths costs LESS (0.85 x), because
each tile stops at its own longest row, and a batch that fills a
quarter of the rows 0.26 x. Choosing the untiled walk on the device
when every tile is live (one `lax.cond`) would buy back that corner,
which no cell sends, for a second copy of every bank's loop nest in
the program: not done. ROW_TILE is 256, two lane tiles, not one: with
no second walk to fall back on, the width is what holds a flood of
long rows in a full batch to +1 % (eight tiles of 128 read +15 %
there: `pf/url`'s column is mostly its fixed part); the price is ~1 ms
a `lanes` call under 129 live rows (the two kernels 2.59 ms a tile
against 1.63), which no cell's end-to-end metric follows today.
`B <= ROW_TILE` takes the one walk statically; a `B` that is no
multiple of ROW_TILE pads rows of `remaining` 0. One program serves
every batch: no ladder of widths or batch sizes, no recompile, no
argument a caller sets. Skipped columns and rows change nothing, so
carries and verdicts are bit-identical to the fixed walk for ANY
placement of the live rows (a live row at index B-1 makes every tile
live: correct, just not faster;
tests/test_live_columns.py and the parity suites of both kernels).

What still grows with Lc: the one uint8 transpose of the staged matrix
to column-major ([Lc, B], so that a block is BLOCK contiguous rows and
the row axis is minor: a tile is lane-aligned). The byte -> class
gather of the DFA runs per block, inside the loop.

The serving mesh (parallel/mesh.py, sched/mesh_exec.py) jits the
verdict program over a dp-sharded batch with NamedShardings, not
`shard_map`. The column bound is a `max` over the batch axis: one
all-reduce per scan there, and every shard walks the global longest
row. The row bound is NOT taken under dp > 1: a tile would be a dynamic
slice ALONG the sharded axis, the partitioner cannot know which shard
holds it, so it would gather the bytes and the carry to every shard and
every shard would walk every live tile (work that dp divides,
replicated). `rows_sharded` reads the mesh off the traced bytes' type
(the one thing a trace can see of a NamedSharding on automatic axes:
the mesh, not the spec) and a batch traced for a mesh whose `dp` axis
is larger than 1 takes the one walk over its own rows, as before
ISSUE 32; `walked_rows(..., sharded=True)` counts that. No cell runs
the mesh, and no chip run has timed it: a mesh that serves padded
batches wants the tile loop inside a `shard_map`, where both bounds
would be local to the shard (parallel/ring.py's runs
ops/nfa_scan.scan_chunk, which has the same loop form and is not
bounded here).

`walked_columns` and `walked_rows` are the host's twins of the device
formulas, for the planes' `pingoo_scan_columns_total{kind="walked"}`
and `pingoo_scan_rows_total{kind="walked"}` counters.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

# Columns per loop trip: the unroll factor the fixed-length scans used.
BLOCK = 8

# Rows per tile of the row loop: two lane tiles, so a tile of `cols`
# ([Lc, B], rows minor) is lane-aligned (the module docstring has why
# not one).
ROW_TILE = 256

# The mesh axis batches are sharded on (parallel/mesh.py).
BATCH_AXIS = "dp"


def walked_columns(lengths: np.ndarray, width: int) -> int:
    """Host twin of `live_blocks` at offset 0: the columns the device
    walks for a field staged `width` wide whose rows are `lengths` long
    (true lengths; a depth-capped row's may exceed the width)."""
    return BLOCK * -(-min(int(lengths.max()), int(width)) // BLOCK)


def walked_rows(lengths: np.ndarray, rows: int, sharded: bool = False) -> int:
    """Host twin of `live_tiles` at offset 0: the rows the device's
    loops walk for a `rows`-row batch whose rows are `lengths` long: the
    tiles up to the last live row, 0 for a batch with no live row; every
    row where the batch is `sharded` over a mesh (`rows_sharded`)."""
    live = np.flatnonzero(np.asarray(lengths) > 0)
    if not live.size:
        return 0
    if sharded:
        return int(rows)
    n_tiles = -(-(int(live[-1]) + 1) // ROW_TILE)
    return min(ROW_TILE * n_tiles, int(rows))


def live_blocks(remaining: jax.Array, width: int) -> jax.Array:
    """Blocks to walk: `remaining` [B] int32 is each row's live columns
    from this chunk's first column on (may be negative or exceed the
    chunk); -> int32 scalar ceil(clip(max(remaining), 0, width) / BLOCK)."""
    longest = jnp.clip(jnp.max(remaining), 0, width)
    return (longest + (BLOCK - 1)) // BLOCK


def live_tiles(remaining: jax.Array) -> jax.Array:
    """Row tiles to walk: -> int32 scalar ceil(last / ROW_TILE), `last`
    one past the last row of `remaining` [B] that has a live column."""
    rows = jnp.arange(1, remaining.shape[0] + 1, dtype=jnp.int32)
    last = jnp.max(jnp.where(remaining > 0, rows, 0))
    return (last + (ROW_TILE - 1)) // ROW_TILE


def rows_sharded(x: jax.Array) -> bool:
    """Whether `x` is traced for a mesh that shards batches: static, off
    the tracer's type (empty mesh on one device)."""
    return jax.typeof(x).sharding.mesh.shape.get(BATCH_AXIS, 1) > 1


def scan_live_columns(step: Callable, carry, data: jax.Array,
                      lengths: jax.Array, t_offset,
                      prepare: Optional[Callable] = None):
    """Fold `step(carry, col, live) -> carry` over the columns of `data`
    [B, Lc] uint8 that lie inside at least one row, in order, for the
    rows up to the last one that has such a column.

    `lengths` [B] are the rows' total live bytes at global positions and
    `t_offset` (scalar or [B]) the global position of column 0, as in
    the chunk kernels. `col` is one column [rows] of `prepare(block)`
    (`block`: [BLOCK, rows] uint8, default itself) and `live` [rows]
    bool is false where the column is padding for that row; `step` must
    leave such rows' carry untouched. `rows` is B or ROW_TILE: `step`
    and `prepare` are row-wise, and every leaf of `carry` has the rows
    as its leading axis."""
    B, Lc = data.shape
    if B == 0 or Lc == 0:
        return carry
    remaining = jnp.clip(
        lengths.astype(jnp.int32) - jnp.asarray(t_offset, dtype=jnp.int32),
        0, Lc)
    cols = data.T  # [Lc, B]: a block is BLOCK contiguous rows
    if Lc % BLOCK:
        # Pad columns are past `remaining` (clipped to Lc) for every row.
        cols = jnp.pad(cols, ((0, -Lc % BLOCK), (0, 0)))

    def walk(carry, cols, remaining):
        """The column loop over the rows it is given."""
        def block_body(b, carry):
            base = b * BLOCK
            block = jax.lax.dynamic_slice_in_dim(cols, base, BLOCK, axis=0)
            if prepare is not None:
                block = prepare(block)
            for j in range(BLOCK):
                carry = step(carry, block[j], (base + j) < remaining)
            return carry

        return jax.lax.fori_loop(0, live_blocks(remaining, Lc),
                                 block_body, carry)

    if B <= ROW_TILE or rows_sharded(data):
        return walk(carry, cols, remaining)
    pad = -B % ROW_TILE
    if pad:
        # Pad rows have `remaining` 0: the identity on their carry.
        cols = jnp.pad(cols, ((0, 0), (0, pad)))
        remaining = jnp.pad(remaining, (0, pad))
        carry = jax.tree.map(
            lambda x: jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)),
            carry)

    def tile_body(i, carry):
        row = i * ROW_TILE
        cut = lambda x, axis=0: jax.lax.dynamic_slice_in_dim(
            x, row, ROW_TILE, axis=axis)
        tile = walk(jax.tree.map(cut, carry), cut(cols, 1), cut(remaining))
        return jax.tree.map(
            lambda x, t: jax.lax.dynamic_update_slice_in_dim(
                x, t, row, axis=0), carry, tile)

    carry = jax.lax.fori_loop(0, live_tiles(remaining), tile_body, carry)
    if pad:
        carry = jax.tree.map(lambda x: x[:B], carry)
    return carry
