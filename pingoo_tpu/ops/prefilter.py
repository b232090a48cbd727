"""Packed multi-literal shift-AND prefilter — Stage A of the verdict
cascade (docs/PREFILTER.md, ISSUE 4).

Hyperscan and the FPGA DPI line (arXiv:1904.10786) get their
order-of-magnitude from a cheap approximate pass that over-approximates
the match set before exact automata run; arXiv:1312.4188 shows the same
cascade vectorizes on SIMD hardware. This module is that pass for the
TPU verdict engine: each byte field is scanned ONCE per batch against
every *necessary literal factor* the compiler extracted
(compiler/repat.necessary_factor), and the resulting [B, F] hit bitmap
gates the serial NFA banks in engine/verdict.py — skipping or
compacting them when no candidate survives.

The kernel is deliberately much cheaper than the NFA scan it gates:

  * plain shift-AND over byte CLASSES (case folds ride the class table
    for free) — no optional-skip closure, no rep self-loops, no
    cross-word carry, no multi-pass propagation;
  * factors never span words (FACTOR_MAX_LEN = 12 << 31 bits), so
    packing is dense first-fit and the step is 4 uint32 vector ops plus
    one [256, Wp] row gather;
  * NO guard bits: bit0 of every factor is re-armed by `init` each
    step, so a neighboring factor's top bit shifting in is absorbed by
    the OR — factors pack at exactly their own width.

Per step, with S = in-progress positions and H = sticky hit
accumulator (both [B, Wp] uint32 carries):

    S' = ((S << 1) | init) & B[c]
    H' = H | S'

A factor hit is its LAST position's bit in H. Inputs beyond each
request's length are gated exactly like the NFA scan (padding can never
arm a factor), and columns beyond EVERY request's length are not walked
at all: the loop (ops/live_columns.py) runs
ceil(min(max(lengths), L) / 8) blocks of 8 steps, a bound read on the
device from the batch's longest row, not the staged width L
(`pingoo_scan_columns_total{kind="walked"}` is the same count on the
host). Rows past the batch's last live row are not walked either: the
loop runs per ROW_TILE rows over the tiles up to it
(`pingoo_scan_rows_total{kind="walked"}`).

`scan_numpy` is the pure-numpy oracle used by the differential property
tests (tests/test_prefilter.py); `prefilter_scan` is the device op;
`backend="pallas"` routes through a fused kernel keeping both carries
in VMEM for the whole field (interpret=True off-TPU, the same program
a chip would compile — mirroring ops/pallas_scan.py).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .live_columns import scan_live_columns

WORD_BITS = 32


@dataclass
class PrefilterBank:
    """Host/numpy build product — pickles with the RulesetPlan artifact.

    factors are packed first-fit into uint32 words; factor f occupies
    `width(f)` consecutive bits of one word, accepts at its top bit."""

    num_words: int
    num_factors: int
    byte_table: np.ndarray  # [256, Wp] uint32 class masks
    init: np.ndarray  # [Wp] uint32: bit0 of every factor
    accept_word: np.ndarray  # [F] int32
    accept_mask: np.ndarray  # [F] uint32


@dataclass(frozen=True)
class PrefilterTables:
    """Device-resident tables (registered pytree; static meta fields
    steer trace-time control flow only)."""

    byte_table: jax.Array  # [256, Wp] uint32
    tab_u16: jax.Array  # [256, 2*Wp] f32 u16 halves (pallas lookup)
    init: jax.Array  # [Wp] uint32
    accept_word: jax.Array  # [F] int32
    accept_mask: jax.Array  # [F] uint32
    num_words: int = 1
    num_factors: int = 0


jax.tree_util.register_dataclass(
    PrefilterTables,
    data_fields=["byte_table", "tab_u16", "init", "accept_word",
                 "accept_mask"],
    meta_fields=["num_words", "num_factors"],
)


def build_prefilter_bank(
        factors: list[tuple[frozenset[int], ...]]) -> PrefilterBank:
    """First-fit pack factor byte-class runs into uint32 words."""
    assert factors, "prefilter bank needs at least one factor"
    used: list[int] = []
    rows: list[dict[int, int]] = []
    init: list[int] = []
    acc_word: list[int] = []
    acc_mask: list[int] = []
    for fac in factors:
        m = len(fac)
        assert 0 < m <= WORD_BITS
        w = -1
        for idx, u in enumerate(used):
            if u + m <= WORD_BITS:
                w = idx
                break
        if w == -1:
            used.append(0)
            rows.append({})
            init.append(0)
            w = len(used) - 1
        base = used[w]
        for i, cls in enumerate(fac):
            bit = 1 << (base + i)
            for b in cls:
                rows[w][b] = rows[w].get(b, 0) | bit
        init[w] |= 1 << base
        acc_word.append(w)
        acc_mask.append(1 << (base + m - 1))
        used[w] += m
    W = len(used)
    table = np.zeros((256, W), dtype=np.uint32)
    for w in range(W):
        for b, mask in rows[w].items():
            table[b, w] = mask
    return PrefilterBank(
        num_words=W,
        num_factors=len(factors),
        byte_table=table,
        init=np.array(init, dtype=np.uint32),
        accept_word=np.array(acc_word, dtype=np.int32),
        accept_mask=np.array(acc_mask, dtype=np.uint32),
    )


def bank_to_prefilter_tables(bank: PrefilterBank) -> PrefilterTables:
    tab_u16 = np.concatenate(
        [(bank.byte_table & 0xFFFF).astype(np.float32),
         (bank.byte_table >> 16).astype(np.float32)], axis=1)
    return PrefilterTables(
        byte_table=jnp.asarray(bank.byte_table),
        tab_u16=jnp.asarray(tab_u16),
        init=jnp.asarray(bank.init),
        accept_word=jnp.asarray(bank.accept_word),
        accept_mask=jnp.asarray(bank.accept_mask),
        num_words=bank.num_words,
        num_factors=bank.num_factors,
    )


def scan_numpy(bank: PrefilterBank, data: np.ndarray,
               lengths: np.ndarray) -> np.ndarray:
    """Reference shift-AND scan (oracle). data [B, L] uint8 -> [B, F]."""
    B, L = data.shape
    S = np.zeros((B, bank.num_words), dtype=np.uint32)
    H = np.zeros_like(S)
    for t in range(L):
        bc = bank.byte_table[data[:, t].astype(np.int64)]
        S_new = (((S << np.uint32(1)) | bank.init[None, :]) & bc).astype(
            np.uint32)
        S = np.where((t < lengths)[:, None], S_new, S)
        H |= S
    lanes = H[:, bank.accept_word]
    return (lanes & bank.accept_mask[None, :]) != 0


def prefilter_init_state(
        B: int, num_words: int) -> tuple[jax.Array, jax.Array]:
    """Fresh (S, H) carry pair for a chunked scan, both [B, Wp]."""
    zero = jnp.zeros((B, num_words), dtype=jnp.uint32)
    return zero, zero


def prefilter_scan_chunk(tables: PrefilterTables, data: jax.Array,
                         lengths: jax.Array, S: jax.Array, H: jax.Array,
                         t_offset) -> tuple[jax.Array, jax.Array]:
    """Advance the (S, H) shift-AND carry over one [B, Lc] chunk whose
    first column sits at global position `t_offset` (scalar or per-row
    [B] int32). S holds every factor's in-progress positions, so a
    literal straddling the chunk boundary completes exactly on the
    carry-in — no overlap-tail re-scan needed for the prefilter itself
    (engine/bodyscan.py relies on this to decide lazy NFA starts).
    `lengths` is each row's TOTAL live byte count in global positions;
    `prefilter_scan` below is one chunk at offset 0. The walk stops at
    the longest row's remainder (ceil(clip(max(lengths - t_offset), 0,
    Lc) / 8) blocks) and covers the row tiles up to the last row with a
    remainder (ops/live_columns.py: `step` sees B or ROW_TILE rows);
    carried-in H already holds carried-in S."""
    init = tables.init
    one = jnp.uint32(1)

    def step(carry, c, live):
        S, H = carry
        bc = jnp.take(tables.byte_table, c.astype(jnp.int32), axis=0)
        S_new = ((S << one) | init[None, :]) & bc
        # Rows past their length keep S unchanged, so H | S adds
        # nothing for them — no second gate needed.
        S = jnp.where(live[:, None], S_new, S)
        return S, H | S

    return scan_live_columns(step, (S, H), data, lengths, t_offset)


def prefilter_extract(tables: PrefilterTables, H: jax.Array) -> jax.Array:
    """[B, Wp] sticky accumulator -> [B, F] factor hits."""
    lanes = jnp.take(H, tables.accept_word, axis=1)
    return (lanes & tables.accept_mask[None, :]) != 0


def prefilter_scan(tables: PrefilterTables, data: jax.Array,
                   lengths: jax.Array,
                   backend: str | None = None) -> jax.Array:
    """Scan one byte field against every packed factor.

    data: [B, L] uint8 (zero-padded), lengths: [B] int32
    returns: hits [B, F] bool — factor f appears in request b's field.
    """
    if backend == "pallas":
        H = _fused_prefilter(tables, data, lengths)
        return prefilter_extract(tables, H)
    B, L = data.shape
    S, H = prefilter_init_state(B, tables.init.shape[0])
    S, H = prefilter_scan_chunk(tables, data, lengths, S, H, 0)
    return prefilter_extract(tables, H)


# -- fused Pallas variant -----------------------------------------------------

# Status (PR 21, TPU v5e): Mosaic refuses `_pf_kernel` as written
# (`dynamic_index_in_dim` on a loaded value), so no knob routes Stage A
# here; `backend="pallas"` runs it (interpret mode off the TPU) or
# raises. See ops/pallas_scan.py for the repair sketch.

B_TILE = 128  # VPU lane width, same tiling as ops/pallas_scan.py


def _pf_kernel(byte_ref, len_ref, init_ref, tab_ref, out_ref, *, W, Lc):
    """One batch tile: both carries live in VMEM for the whole field.
    The byte lookup is the exact one-hot u16-halves matmul from
    ops/pallas_scan.py (one-hot x u16-valued f32 is exact)."""
    bytes_all = byte_ref[...]  # [Lc, B_tile] int32
    lens = len_ref[...][:, 0]  # [B_tile]
    init = init_ref[...][0]  # [W] uint32
    tab = tab_ref[...]  # [256, 2W] f32
    one = jnp.uint32(1)

    def lookup(c):
        iota = jax.lax.broadcasted_iota(jnp.int32, (1, 256), 1)
        oh = (c[:, None] == iota).astype(jnp.float32)
        halves = jnp.dot(oh, tab, preferred_element_type=jnp.float32)
        return (halves[:, :W].astype(jnp.uint32)
                | (halves[:, W:].astype(jnp.uint32) << jnp.uint32(16)))

    def body(t, carry):
        S, H = carry
        c = jax.lax.dynamic_index_in_dim(bytes_all, t, 0, keepdims=False)
        S_new = ((S << one) | init[None, :]) & lookup(c)
        S = jnp.where((t < lens)[:, None], S_new, S)
        return S, H | S

    zero = jnp.zeros((lens.shape[0], W), dtype=jnp.uint32)
    _, H = jax.lax.fori_loop(0, Lc, body, (zero, zero))
    out_ref[...] = H


def _use_interpret() -> bool:
    import os

    env = os.environ.get("PINGOO_PALLAS_INTERPRET")
    if env is not None:
        return env != "0"
    return jax.default_backend() != "tpu"


def _fused_prefilter(tables: PrefilterTables, data: jax.Array,
                     lengths: jax.Array) -> jax.Array:
    """Fused shift-AND over one field -> hit-accumulator H [B, Wp]."""
    import functools

    B, Lc = data.shape
    W = tables.init.shape[0]
    lens = lengths.astype(jnp.int32)
    ints = data.astype(jnp.int32)
    Bp = -(-B // B_TILE) * B_TILE
    if Bp != B:
        padb = Bp - B
        ints = jnp.pad(ints, ((0, padb), (0, 0)))
        lens = jnp.pad(lens, (0, padb))  # length 0: rows never arm
    kernel = functools.partial(_pf_kernel, W=W, Lc=Lc)
    out = pl.pallas_call(
        kernel,
        grid=(Bp // B_TILE,),
        in_specs=[
            pl.BlockSpec((Lc, B_TILE), lambda i: (0, i)),
            pl.BlockSpec((B_TILE, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, W), lambda i: (0, 0)),
            pl.BlockSpec((256, 2 * W), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((B_TILE, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, W), jnp.uint32),
        interpret=_use_interpret(),
    )(ints.T, lens[:, None], tables.init[None, :], tables.tab_u16)
    return out[:B]
