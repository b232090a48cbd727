"""Fused Pallas NFA scan kernel — the whole byte loop in ONE kernel.

The roofline (docs/ROOFLINE.md) shows the lax.scan verdict kernel
serial-latency-bound: ~7.3 us per dependent scan step against a ~0.5 us
execution floor, because each loop iteration's gather/advance round-trips
through XLA's while-loop machinery. This kernel executes an entire
field's byte loop inside one `pl.pallas_call`:

  * the [B_tile, W] state vector stays in VMEM (a fori_loop carry) for
    the whole chunk — nothing round-trips HBM between bytes;
  * the byte-class lookup is fused with the ~7-op advance per byte: a
    one-hot [B_tile, C] x [C, 2W] f32 matmul against the u16-halved
    class table (exact — every value < 2^16 is f32-representable and a
    one-hot row selects exactly one table row, the same trick as the
    `oh_f32` strategy in nfa_scan.py), recombined into uint32 lanes;
  * the grid tiles the batch dimension only; each grid step owns its
    rows end to end, so there is no cross-tile communication.

Semantics are bit-identical to `nfa_scan.scan_chunk` (differentially
enforced by tests/test_pallas_scan.py and the corpus parity suite):
per-row global offsets `t_offset` (the halo split's stacked chunks),
negative-t warm-up gating, cross-word carry, multi-pass opt
propagation, and per-row length gating all behave identically.

`pair=True` advances TWO bytes per loop iteration (two fused
lookup+advance half-steps), halving the loop-iteration count the same
way the `pair` lookup strategy does for lax.scan — inside a fused
kernel the win is loop bookkeeping rather than gather dispatch, but it
keeps the dependent-step accounting of the two strategies aligned.

Status (PR 21, TPU v5e, jax 0.9.0): Mosaic REFUSES this kernel as
written — `dynamic_index_in_dim` on a loaded value ("Unimplemented
primitive ... dynamic_slice") and the f32 -> uint32 convert in `lookup`
(RecursionError inside the lowering) — so nothing selects it: it has
no default cost (compiler/plan.select_scan_strategy) and no knob. Off
the TPU it runs under `interpret=True` (pallas' jax-level interpreter;
override with PINGOO_PALLAS_INTERPRET), which is what the differential
parity suite covers. ROADMAP Design 5 (`pallas-trio`) owns the repair:
a [W, B_TILE] layout (batch on lanes) reads a byte column as a sublane
slice of the ref and keeps every lane op int32.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .nfa_scan import NfaTables

# Batch tile: grid steps own [B_TILE, W] state slabs. 128 matches the
# VPU lane width; small test batches pad up to one tile.
B_TILE = 128


def _use_interpret() -> bool:
    env = os.environ.get("PINGOO_PALLAS_INTERPRET")
    if env is not None:
        return env != "0"
    return jax.default_backend() != "tpu"


def _kernel(cls_ref, len_ref, toff_ref, state_ref, tab_ref, vec_ref,
            out_ref, *, W, C, Lc, passes, has_carry, pair, odd, gate_neg):
    """One batch tile: scan Lc byte columns with the state in VMEM."""
    cls_all = cls_ref[...]  # [Lc(+pad), B_tile] int32 class ids
    lens = len_ref[...][:, 0]  # [B_tile]
    toff = toff_ref[...][:, 0]  # [B_tile] global offset of column 0
    tab = tab_ref[...]  # [C, 2W] f32 u16 halves
    vecs = vec_ref[...]  # [5, W] uint32
    init_a, init_u = vecs[0], vecs[1]
    opt, rep, carry = vecs[2], vecs[3], vecs[4]
    one = jnp.uint32(1)

    def shift_words(x):
        return jnp.pad(x[:, :-1], ((0, 0), (1, 0)))

    def lookup(c):
        """Class ids [B_tile] -> byte-class masks [B_tile, W] uint32."""
        iota = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
        oh = (c[:, None] == iota).astype(jnp.float32)
        halves = jnp.dot(oh, tab, preferred_element_type=jnp.float32)
        return (halves[:, :W].astype(jnp.uint32)
                | (halves[:, W:].astype(jnp.uint32) << jnp.uint32(16)))

    def advance(S, bc, t):
        """One byte of the sticky-accept algebra at global positions t."""
        inj = init_u[None, :] | jnp.where(
            (t == 0)[:, None], init_a[None, :], jnp.uint32(0))
        adv = (S << one) | inj
        if has_carry:
            adv = adv | (shift_words((S >> jnp.uint32(31)) & one)
                         & carry[None, :])
        for p in range(passes):
            x = (adv & opt[None, :]) + opt[None, :]
            adv = adv | (x ^ opt[None, :])
            if has_carry and p + 1 < passes:
                esc = (x < opt[None, :]).astype(jnp.uint32)
                adv = adv | (shift_words(esc) & carry[None, :])
        S_new = (adv | (S & rep[None, :])) & bc
        live = t < lens
        if gate_neg:
            live = (t >= 0) & live
        return jnp.where(live[:, None], S_new, S)

    def column(i):
        return jax.lax.dynamic_index_in_dim(cls_all, i, 0, keepdims=False)

    if pair:
        Lp = (Lc + 1) // 2

        def body(i, S):
            t0 = toff + 2 * i
            S1 = advance(S, lookup(column(2 * i)), t0)
            S2 = advance(S1, lookup(column(2 * i + 1)), t0 + 1)
            if odd:
                # The pad column is SYNTHETIC (see scan_chunk's pair
                # path): in chunked callers its global position can lie
                # inside the request, so it is skipped structurally, not
                # by the live gate.
                S2 = jnp.where(i == Lp - 1, S1, S2)
            return S2

        S = jax.lax.fori_loop(0, Lp, body, state_ref[...])
    else:
        def body(i, S):
            return advance(S, lookup(column(i)), toff + i)

        S = jax.lax.fori_loop(0, Lc, body, state_ref[...])
    out_ref[...] = S


def fused_scan_chunk(
    tables: NfaTables,
    data: jax.Array,
    lengths: jax.Array,
    state: jax.Array,
    t_offset,
    pair: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Drop-in replacement for `nfa_scan.scan_chunk` (same contract):
    advance the NFA over one [B, Lc] byte chunk whose first column sits
    at global position `t_offset` (int, traced scalar, or per-row [B]),
    returning the new [B, W] state."""
    B, Lc = data.shape
    W = tables.opt.shape[0]
    if Lc == 0:
        return state
    if interpret is None:
        interpret = _use_interpret()

    # Byte -> class ids ONCE, outside the loop (cls_map is [256]).
    cls = jnp.take(tables.cls_map, data.astype(jnp.int32))  # [B, Lc]
    odd = bool(Lc % 2) if pair else False
    if odd:
        cls = jnp.pad(cls, ((0, 0), (0, 1)))

    if isinstance(t_offset, int):
        toff = jnp.full((B,), t_offset, dtype=jnp.int32)
        gate_neg = t_offset < 0
    else:
        toff = jnp.broadcast_to(
            jnp.asarray(t_offset, dtype=jnp.int32), (B,))
        gate_neg = True  # traced offsets (halo) may be negative

    lens = lengths.astype(jnp.int32)
    Bp = -(-B // B_TILE) * B_TILE
    if Bp != B:
        padb = Bp - B
        cls = jnp.pad(cls, ((0, padb), (0, 0)))
        lens = jnp.pad(lens, (0, padb))  # length 0: rows never advance
        toff = jnp.pad(toff, (0, padb))
        state = jnp.pad(state, ((0, padb), (0, 0)))

    C = tables.cls_table.shape[0]
    vecs = jnp.stack([tables.init_anchored, tables.init_unanchored,
                      tables.opt, tables.rep, tables.carry_mask])  # [5, W]
    Lcp = cls.shape[1]
    kernel = functools.partial(
        _kernel, W=W, C=C, Lc=Lc, passes=1 + tables.extra_passes,
        has_carry=tables.has_carry, pair=pair, odd=odd, gate_neg=gate_neg)
    out = pl.pallas_call(
        kernel,
        grid=(Bp // B_TILE,),
        in_specs=[
            # data transposed to [Lc, B]: the per-iteration column read
            # indexes the SUBLANE axis, which Mosaic slices cheaply.
            pl.BlockSpec((Lcp, B_TILE), lambda i: (0, i)),
            pl.BlockSpec((B_TILE, 1), lambda i: (i, 0)),
            pl.BlockSpec((B_TILE, 1), lambda i: (i, 0)),
            pl.BlockSpec((B_TILE, W), lambda i: (i, 0)),
            pl.BlockSpec((C, 2 * W), lambda i: (0, 0)),
            pl.BlockSpec((5, W), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((B_TILE, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, W), jnp.uint32),
        interpret=interpret,
    )(cls.T, lens[:, None], toff[:, None], state, tables.cls_u16, vecs)
    return out[:B]
