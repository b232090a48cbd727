"""The byte loops' shared driver: walk a staged [B, Lc] byte matrix only
as far as the batch's longest live row (ISSUE 29).

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
unroll=8)`. One program serves every batch: no ladder of staged widths,
no recompile. Skipped columns change nothing, so carries and verdicts
are bit-identical to the fixed-length walk (tests/test_live_columns.py
and the parity suites of both kernels).

What still grows with Lc: the one uint8 transpose of the staged matrix
to column-major ([Lc, B], so that a block is BLOCK contiguous rows). The
byte -> class gather of the DFA runs per block, inside the loop.

The bound is a `max` over the batch axis. The serving mesh
(parallel/mesh.py, sched/mesh_exec.py) jits the verdict program over a
dp-sharded batch with NamedShardings, not `shard_map`: there the `max`
is one all-reduce per scan and every shard walks the global longest
row. Inside a `shard_map` it would be local to the shard
(parallel/ring.py's runs ops/nfa_scan.scan_chunk, which has the same
loop form and is not bounded here).

`walked_columns` is the host's twin of the device formula, for the
planes' `pingoo_scan_columns_total{kind="walked"}` counter.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

# Columns per loop trip: the unroll factor the fixed-length scans used.
BLOCK = 8


def walked_columns(lengths: np.ndarray, width: int) -> int:
    """Host twin of `live_blocks` at offset 0: the columns the device
    walks for a field staged `width` wide whose rows are `lengths` long
    (true lengths; a depth-capped row's may exceed the width)."""
    return BLOCK * -(-min(int(lengths.max()), int(width)) // BLOCK)


def live_blocks(remaining: jax.Array, width: int) -> jax.Array:
    """Blocks to walk: `remaining` [B] int32 is each row's live columns
    from this chunk's first column on (may be negative or exceed the
    chunk); -> int32 scalar ceil(clip(max(remaining), 0, width) / BLOCK)."""
    longest = jnp.clip(jnp.max(remaining), 0, width)
    return (longest + (BLOCK - 1)) // BLOCK


def scan_live_columns(step: Callable, carry, data: jax.Array,
                      lengths: jax.Array, t_offset,
                      prepare: Optional[Callable] = None):
    """Fold `step(carry, col, live) -> carry` over the columns of `data`
    [B, Lc] uint8 that lie inside at least one row, in order.

    `lengths` [B] are the rows' total live bytes at global positions and
    `t_offset` (scalar or [B]) the global position of column 0, as in
    the chunk kernels. `col` is one column [B] of `prepare(block)`
    (`block`: [BLOCK, B] uint8, default itself) and `live` [B] bool is
    false where the column is padding for that row; `step` must leave
    such rows' carry untouched."""
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

    def block_body(b, carry):
        base = b * BLOCK
        block = jax.lax.dynamic_slice_in_dim(cols, base, BLOCK, axis=0)
        if prepare is not None:
            block = prepare(block)
        for j in range(BLOCK):
            carry = step(carry, block[j], (base + j) < remaining)
        return carry

    return jax.lax.fori_loop(0, live_blocks(remaining, Lc), block_body,
                             carry)
