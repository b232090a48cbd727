"""Bitsplit-DFA scan kernel (ISSUE 8): one gather per byte, no matmul.

compiler/nfa.py lowers small/hot NFA banks to byte-indexed DFA tables
(`lower_bank_to_dfa`). This module executes them three ways, mirroring
ops/prefilter.py's structure:

  * `scan_numpy`      — pure-numpy oracle for differential tests;
  * `dfa_scan`        — gather ladder: per byte, ONE flat-table
                        gather `trans[state * C + cls]` plus two accept
                        gathers into the sticky accumulator `H`, at ~4
                        lane-ops/byte — the dependent one-hot matmul
                        chain of the NFA path is gone. The dependent
                        chain is NOT the staged width L: the loop
                        (ops/live_columns.py) runs
                        ceil(min(max(lengths), L) / 8) blocks of 8
                        steps, a bound read on the device from the
                        batch's longest row, and the byte -> class
                        gather runs per block inside it
                        (`pingoo_scan_columns_total{kind="walked"}` is
                        the same count on the host). Nor do the
                        gathers span the padded batch: the loop runs
                        per ROW_TILE rows, over the tiles up to the
                        last row with a byte left
                        (`pingoo_scan_rows_total{kind="walked"}`);
  * `_fused_dfa`      — Pallas kernel keeping state + H in VMEM for the
                        whole byte loop (one-hot f32 matmul lookups,
                        exact for values < 2^16; same trick as
                        ops/pallas_scan.py), `interpret=True` off-TPU.
                        Mosaic refuses it as written (PR 21 chip run:
                        the f32 -> uint32 convert recurses in the
                        lowering), so no knob selects it.

Accept semantics (see DfaBank's docstring): sticky accepts fire per
consumed byte through `step_accept[state]` OR-ed into H; absolute-end
accepts read `end_accept` at the final state; the always/empty_ok slot
lanes are applied at extraction, identical to nfa_scan.extract_slots.
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..compiler.nfa import DfaBank
from .live_columns import scan_live_columns

# Batch tile for the fused kernel (matches the VPU lane width).
B_TILE = 128


def _use_interpret() -> bool:
    env = os.environ.get("PINGOO_PALLAS_INTERPRET")
    if env is not None:
        return env != "0"
    return jax.default_backend() != "tpu"


@dataclass(frozen=True)
class DfaTables:
    """Device-resident DFA tables (registered pytree; rides np_tables
    through RulesetPlan.device_tables() and the artifact cache)."""

    trans_flat: jax.Array    # [S * C] int32, row-major (state, class)
    byte_cls: jax.Array      # [256] int32
    step_accept: jax.Array   # [S, Wh] uint32
    end_accept: jax.Array    # [S, Wh] uint32
    trans_f32: jax.Array     # [S, C] f32 (fused one-hot path; ids < 2^16)
    step_u16: jax.Array      # [S, 2*Wh] f32 u16 halves of step_accept
    end_u16: jax.Array       # [S, 2*Wh] f32 u16 halves of end_accept
    slot_word: jax.Array     # [P] int32 H word per pattern slot
    slot_mask: jax.Array     # [P] uint32 bit per pattern slot
    slot_always: jax.Array   # [P] bool
    slot_empty_ok: jax.Array  # [P] bool
    num_states: int
    num_classes: int
    num_words: int
    num_slots: int
    exact: bool


jax.tree_util.register_dataclass(
    DfaTables,
    data_fields=["trans_flat", "byte_cls", "step_accept", "end_accept",
                 "trans_f32", "step_u16", "end_u16", "slot_word",
                 "slot_mask", "slot_always", "slot_empty_ok"],
    meta_fields=["num_states", "num_classes", "num_words", "num_slots",
                 "exact"],
)


def _u16_halves(words: np.ndarray) -> np.ndarray:
    """[S, W] uint32 -> [S, 2W] f32 (lo halves then hi halves)."""
    lo = (words & np.uint32(0xFFFF)).astype(np.float32)
    hi = (words >> np.uint32(16)).astype(np.float32)
    return np.concatenate([lo, hi], axis=1)


def dfa_to_tables(bank: DfaBank) -> DfaTables:
    S, C = bank.trans.shape
    P = bank.num_slots
    slot_word = np.arange(P, dtype=np.int32) // 32
    slot_mask = (np.uint32(1) << (np.arange(P, dtype=np.uint32) % 32))
    return DfaTables(
        trans_flat=jnp.asarray(bank.trans.astype(np.int32).reshape(-1)),
        byte_cls=jnp.asarray(bank.byte_cls.astype(np.int32)),
        step_accept=jnp.asarray(bank.step_accept.astype(np.uint32)),
        end_accept=jnp.asarray(bank.end_accept.astype(np.uint32)),
        trans_f32=jnp.asarray(bank.trans.astype(np.float32)),
        step_u16=jnp.asarray(_u16_halves(bank.step_accept.astype(np.uint32))),
        end_u16=jnp.asarray(_u16_halves(bank.end_accept.astype(np.uint32))),
        slot_word=jnp.asarray(slot_word),
        slot_mask=jnp.asarray(slot_mask),
        slot_always=jnp.asarray(bank.slot_always.astype(bool)),
        slot_empty_ok=jnp.asarray(bank.slot_empty_ok.astype(bool)),
        num_states=S, num_classes=C, num_words=bank.num_words,
        num_slots=P, exact=bool(bank.exact),
    )


# -- numpy oracle ------------------------------------------------------------


def scan_numpy(bank: DfaBank, data: np.ndarray,
               lengths: np.ndarray) -> np.ndarray:
    """Reference DFA scan. data: [B, L] uint8 -> matched [B, P] bool."""
    B, L = data.shape
    state = np.zeros(B, dtype=np.int64)
    H = np.zeros((B, bank.num_words), dtype=np.uint32)
    for t in range(L):
        live = t < lengths
        H[live] |= bank.step_accept[state[live]]
        c = bank.byte_cls[data[:, t].astype(np.int64)]
        state[live] = bank.trans[state[live], c[live]]
    H |= bank.end_accept[state]
    return _extract_np(bank, H, lengths)


def _extract_np(bank: DfaBank, H: np.ndarray,
                lengths: np.ndarray) -> np.ndarray:
    P = bank.num_slots
    idx = np.arange(P)
    lanes = H[:, idx // 32]
    hit = (lanes & (np.uint32(1) << (idx % 32).astype(np.uint32))) != 0
    hit |= bank.slot_always[None, :]
    hit |= bank.slot_empty_ok[None, :] & (lengths == 0)[:, None]
    return hit


# -- lax.scan ladder ---------------------------------------------------------


def dfa_init_state(B: int,
                   num_words: int) -> tuple[jax.Array, jax.Array]:
    """Fresh per-row carry for a chunked scan: (state [B] int32,
    H [B, Wh] uint32)."""
    return (jnp.zeros((B,), dtype=jnp.int32),
            jnp.zeros((B, num_words), dtype=jnp.uint32))


def dfa_scan_chunk(tables: DfaTables, data: jax.Array, lengths: jax.Array,
                   state: jax.Array, H: jax.Array,
                   t_offset) -> tuple[jax.Array, jax.Array]:
    """Advance the (state, H) carry over one [B, Lc] byte chunk whose
    first column sits at global position `t_offset` (scalar or per-row
    [B] int32). Chunks compose: the streaming body scanner
    (engine/bodyscan.py) threads the carry across ring windows, and
    `dfa_scan` below is literally one chunk plus `dfa_finalize` — so a
    payload split at any byte boundary walks the identical state
    sequence as the contiguous scan. `lengths` is each row's TOTAL live
    byte count at global positions (columns with t_offset + i >=
    lengths are padding and leave the carry untouched); `end_accept` is
    deliberately NOT applied here — it reads the final state, which
    only `dfa_finalize` knows. The walk stops at the longest row's
    remainder, ceil(clip(max(lengths - t_offset), 0, Lc) / 8) blocks: a
    chunk wholly past every row runs none. It covers the row tiles up
    to the last row with a remainder (ops/live_columns.py), so `step`
    and `classes` see B or ROW_TILE rows: both are row-wise."""
    C = tables.num_classes

    def classes(block):
        # Byte -> class ids for the block being walked only (byte_cls
        # is [256]); never over the whole staged matrix.
        return jnp.take(tables.byte_cls, block.astype(jnp.int32))

    def step(carry, c, live):
        state, H = carry
        fire = jnp.take(tables.step_accept, state, axis=0)  # [rows, Wh]
        H = jnp.where(live[:, None], H | fire, H)
        nxt = jnp.take(tables.trans_flat, state * C + c)
        state = jnp.where(live, nxt, state)
        return state, H

    return scan_live_columns(step, (state, H), data, lengths, t_offset,
                             prepare=classes)


def dfa_finalize(tables: DfaTables, state: jax.Array, H: jax.Array,
                 lengths: jax.Array) -> jax.Array:
    """Apply absolute-end accepts at the final carried state and extract
    per-slot hits — the closing half of a chunked scan."""
    H = H | jnp.take(tables.end_accept, state, axis=0)
    return dfa_extract(tables, H, lengths.astype(jnp.int32))


def dfa_scan(tables: DfaTables, data: jax.Array, lengths: jax.Array,
             backend: str | None = None) -> jax.Array:
    """Scan one field's [B, L] bytes -> per-slot hits [B, P] bool."""
    if backend == "pallas":
        return _fused_dfa(tables, data, lengths)
    B, L = data.shape
    lens = lengths.astype(jnp.int32)
    state, H = dfa_init_state(B, tables.num_words)
    state, H = dfa_scan_chunk(tables, data, lens, state, H, 0)
    return dfa_finalize(tables, state, H, lens)


def dfa_extract(tables: DfaTables, H: jax.Array,
                lengths: jax.Array) -> jax.Array:
    """[B, Wh] accumulator -> [B, P] slot hits (always/empty lanes in)."""
    lanes = jnp.take(H, tables.slot_word, axis=1)  # [B, P]
    hit = (lanes & tables.slot_mask[None, :]) != 0
    hit = hit | tables.slot_always[None, :]
    hit = hit | (tables.slot_empty_ok[None, :] & (lengths == 0)[:, None])
    return hit


def dfa_skip_hits(tables: DfaTables, lengths: jax.Array) -> jax.Array:
    """Hits for rows that never scan: the always/empty_ok base only
    (the DFA analogue of verdict's bank_skip_result)."""
    B = lengths.shape[0]
    H = jnp.zeros((B, tables.num_words), dtype=jnp.uint32)
    return dfa_extract(tables, H, lengths.astype(jnp.int32))


def dfa_row_candidates(tables: DfaTables, hits: jax.Array,
                       lengths: jax.Array) -> jax.Array:
    """[B] bool: rows whose DFA hits exceed the skip base — the rows an
    approximate (over-approximating) DFA must hand to the exact-NFA
    recheck. Rows below the base are PROVABLY clean (candidates ⊇
    matches), so pruning them is sound."""
    base = dfa_skip_hits(tables, lengths)
    return jnp.any(hits & ~base, axis=1)


# -- fused Pallas kernel -----------------------------------------------------


def _dfa_kernel(cls_ref, len_ref, trans_ref, step_ref, end_ref, out_ref,
                *, S, C, Wh, Lc):
    """One batch tile: walk Lc byte columns with state + H in VMEM.

    The state id is carried as a one-hot [B_tile, S] f32 row (ids stay
    < 2^16, so every table value is f32-exact); per byte: the one-hot
    row gathers the state's transition row and its step-accept halves
    in two matmuls, the class one-hot selects the next state, and H
    accumulates in uint32 lanes.
    """
    cls_all = cls_ref[...]       # [Lc, B_tile] int32
    lens = len_ref[...][:, 0]    # [B_tile]
    trans = trans_ref[...]       # [S, C] f32
    step_tab = step_ref[...]     # [S, 2Wh] f32
    end_tab = end_ref[...]       # [S, 2Wh] f32
    B = lens.shape[0]
    s_iota = jax.lax.broadcasted_iota(jnp.float32, (1, S), 1)
    c_iota = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)

    def halves_to_u32(halves):
        return (halves[:, :Wh].astype(jnp.uint32)
                | (halves[:, Wh:].astype(jnp.uint32) << jnp.uint32(16)))

    def body(i, carry):
        state, H = carry  # state: [B] f32 ids, H: [B, Wh] uint32
        oh = (state[:, None] == s_iota).astype(jnp.float32)  # [B, S]
        live = i < lens
        fire = halves_to_u32(jnp.dot(
            oh, step_tab, preferred_element_type=jnp.float32))
        H = jnp.where(live[:, None], H | fire, H)
        rows = jnp.dot(oh, trans, preferred_element_type=jnp.float32)
        c = jax.lax.dynamic_index_in_dim(cls_all, i, 0, keepdims=False)
        oh_c = (c[:, None] == c_iota).astype(jnp.float32)  # [B, C]
        nxt = jnp.sum(rows * oh_c, axis=1)
        state = jnp.where(live, nxt, state)
        return state, H

    state0 = jnp.zeros((B,), dtype=jnp.float32)
    H0 = jnp.zeros((B, Wh), dtype=jnp.uint32)
    state, H = jax.lax.fori_loop(0, Lc, body, (state0, H0))
    oh = (state[:, None] == s_iota).astype(jnp.float32)
    H = H | halves_to_u32(jnp.dot(
        oh, end_tab, preferred_element_type=jnp.float32))
    out_ref[...] = H


def _fused_dfa(tables: DfaTables, data: jax.Array, lengths: jax.Array,
               interpret: bool | None = None) -> jax.Array:
    """Fused-kernel variant of dfa_scan (same contract + extraction)."""
    B, L = data.shape
    lens = lengths.astype(jnp.int32)
    if L == 0:
        return dfa_scan(tables, data, lengths)
    if interpret is None:
        interpret = _use_interpret()
    cls = jnp.take(tables.byte_cls, data.astype(jnp.int32))  # [B, L]
    Bp = -(-B // B_TILE) * B_TILE
    lens_p = lens
    if Bp != B:
        padb = Bp - B
        cls = jnp.pad(cls, ((0, padb), (0, 0)))
        lens_p = jnp.pad(lens_p, (0, padb))  # len-0 rows never advance
    S, C, Wh = tables.num_states, tables.num_classes, tables.num_words
    kernel = functools.partial(_dfa_kernel, S=S, C=C, Wh=Wh, Lc=L)
    H = pl.pallas_call(
        kernel,
        grid=(Bp // B_TILE,),
        in_specs=[
            pl.BlockSpec((L, B_TILE), lambda i: (0, i)),
            pl.BlockSpec((B_TILE, 1), lambda i: (i, 0)),
            pl.BlockSpec((S, C), lambda i: (0, 0)),
            pl.BlockSpec((S, 2 * Wh), lambda i: (0, 0)),
            pl.BlockSpec((S, 2 * Wh), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((B_TILE, Wh), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, Wh), jnp.uint32),
        interpret=interpret,
    )(cls.T, lens_p[:, None], tables.trans_f32, tables.step_u16,
      tables.end_u16)
    return dfa_extract(tables, H[:B], lens)
