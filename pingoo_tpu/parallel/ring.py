"""Sequence-parallel NFA scan: byte-dimension sharding over the sp axis.

Long-field handling (SURVEY.md §5 "Long-context / sequence parallelism"):
the byte dimension of a field is split into contiguous chunks across the
`sp` mesh axis. Two strategies:

`halo_nfa_scan` — TRUE sequence parallelism: every device scans its own
chunk CONCURRENTLY, prefixed by a fixed halo of the previous chunk's
trailing bytes (one ppermute before any scanning). Correct whenever the
automaton has bounded memory — every self-loop is a sticky ACCEPT
accumulator (compiler/nfa.py tracks this as `halo_ok`), so the
non-accept state at byte t depends only on the last `max_footprint`
bytes, and a zero-state warm-up over the halo reconstructs it. Sticky
(floating) accepts OR across devices via psum; positional accepts
(`$`-anchored) are taken only from the device whose CHUNK (not halo)
owns each request's final byte, where the warm-up is complete. Wall
clock: L/sp + H per device instead of L.

`ring_nfa_scan` — the sequential-state fallback for banks with real
self-loops (x+ / x*), whose state memory is unbounded: the carried
state travels the ring via ppermute, one stage at a time (distributes
memory 1/sp, but stages serialize).

`sp_nfa_scan` picks per bank. Both are bit-identical to
ops/nfa_scan.nfa_scan (differentially tested on the 8-device CPU mesh).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.nfa_scan import NfaTables, extract_slots, init_scan_state, scan_chunk


def ring_nfa_scan(
    mesh: Mesh,
    tables: NfaTables,
    data: jax.Array,
    lengths: jax.Array,
) -> jax.Array:
    """nfa_scan with the byte axis sharded over mesh axis 'sp' (and the
    batch axis over 'dp'). data: [B, L] with L % sp == 0."""
    sp = mesh.shape["sp"]
    B, L = data.shape
    assert L % sp == 0, "byte axis must divide evenly over sp"
    Lc = L // sp

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P("dp", "sp"), P("dp")),
        out_specs=P("dp", None),
        check_vma=False,
    )
    def kernel(tables_local: NfaTables, chunk: jax.Array, lengths_local: jax.Array):
        sp_idx = jax.lax.axis_index("sp")
        Bl = chunk.shape[0]
        W = tables_local.opt.shape[0]
        state = init_scan_state(Bl, W)

        perm = [(i, (i + 1) % sp) for i in range(sp)]
        hits = jnp.zeros(
            (Bl, tables_local.slot_always.shape[0]), dtype=jnp.int32)
        for stage in range(sp):
            my_turn = sp_idx == stage
            s2 = scan_chunk(tables_local, chunk, lengths_local, state,
                            stage * Lc)
            # Only the stage owner's result is real this round (the owner
            # of stage s is the device holding byte offset s*Lc).
            state = jnp.where(my_turn, s2, state)
            if stage == sp - 1:
                final_hits = extract_slots(
                    tables_local, state, lengths_local)
                hits = jnp.where(my_turn, final_hits.astype(jnp.int32), hits)
            else:
                state = jax.lax.ppermute(state, "sp", perm)

        # Broadcast the final-stage device's verdicts to the ring.
        return jax.lax.psum(hits, "sp") > 0

    return kernel(tables, data, lengths)


def halo_nfa_scan(
    mesh: Mesh,
    tables: NfaTables,
    data: jax.Array,
    lengths: jax.Array,
) -> jax.Array:
    """Concurrent sequence-parallel scan (see module docstring).

    data: [B, L] with L % sp == 0; requires tables.halo_ok.
    """
    assert tables.halo_ok, "bank has unbounded self-loops; use ring_nfa_scan"
    sp = mesh.shape["sp"]
    B, L = data.shape
    assert L % sp == 0, "byte axis must divide evenly over sp"
    Lc = L // sp
    # Halo = the largest pattern footprint (>= its byte memory). It must
    # fit inside one chunk — the exchange is a single hop from the
    # immediate predecessor. Longer patterns than a chunk need the
    # sequential ring (sp_nfa_scan dispatches accordingly).
    H = int(tables.max_footprint)
    assert H <= Lc, f"halo {H} exceeds chunk {Lc}; use ring_nfa_scan"

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P("dp", "sp"), P("dp")),
        out_specs=P("dp", None),
        check_vma=False,
    )
    def kernel(tables_local: NfaTables, chunk: jax.Array,
               lengths_local: jax.Array):
        sp_idx = jax.lax.axis_index("sp")
        Bl = chunk.shape[0]
        W = tables_local.opt.shape[0]
        lengths32 = lengths_local.astype(jnp.int32)

        if H > 0:
            # ONE exchange up front: my chunk's trailing H bytes feed my
            # successor's warm-up prefix; then every stage scans
            # concurrently (vs. the ring's serialized stages).
            tail = chunk[:, Lc - H:]
            halo = jax.lax.ppermute(
                tail, "sp", [(i, (i + 1) % sp) for i in range(sp)])
            ext = jnp.concatenate([halo, chunk], axis=1)  # [B, H + Lc]
        else:
            ext = chunk
        # Global position of ext[:, 0]; negative on device 0, where the
        # wrapped-around halo bytes are gated off by the t >= 0 check in
        # scan_chunk (so its warm-up is a no-op and t == 0 injection
        # happens exactly once).
        t0 = sp_idx * Lc - H
        state = scan_chunk(tables_local, ext, lengths32,
                           init_scan_state(Bl, W), t0)

        # Accept split: sticky accumulator bits OR across devices (a
        # floating match is detected by whichever device scanned its
        # final byte with enough context — at least its chunk owner);
        # positional accepts ($-anchored) are valid only on the device
        # whose CHUNK owns the request's last byte, where warm-up is
        # complete by construction. The pair->slot reduction itself is
        # extract_slots', so both paths stay bit-identical.
        lanes = jnp.take(state, tables_local.accept_word, axis=1)  # [B, J]
        masks = tables_local.accept_mask[None, :]
        sticky_j = jnp.take(tables_local.sticky,
                            tables_local.accept_word)[None, :]
        sticky_hit = (lanes & masks & sticky_j) != 0
        owner = jnp.clip((lengths32 - 1) // Lc, 0, sp - 1)  # [B]
        is_owner = (owner == sp_idx)[:, None]
        end_hit = ((lanes & masks & ~sticky_j) != 0) & is_owner
        hits = extract_slots(tables_local, state, lengths32,
                             pair_hit=sticky_hit | end_hit)
        return jax.lax.psum(hits.astype(jnp.int32), "sp") > 0

    return kernel(tables, data, lengths)


def sp_scan_mode(tables: NfaTables, L: int, sp: int) -> str:
    """'halo' when the bank's memory is bounded AND the largest pattern
    fits inside one chunk, else 'ring' — the single source of truth for
    the sp dispatch (also used for diagnostics)."""
    if tables.halo_ok and int(tables.max_footprint) <= L // sp:
        return "halo"
    return "ring"


def sp_nfa_scan(mesh: Mesh, tables: NfaTables, data: jax.Array,
                lengths: jax.Array) -> jax.Array:
    """Sequence-parallel scan: concurrent halo strategy when eligible
    (sp_scan_mode), sequential state ring otherwise."""
    if sp_scan_mode(tables, data.shape[1], mesh.shape["sp"]) == "halo":
        return halo_nfa_scan(mesh, tables, data, lengths)
    return ring_nfa_scan(mesh, tables, data, lengths)


def shard_batch_for_ring(mesh: Mesh, data, lengths):
    """Place [B, L] bytes with B over dp and L over sp; lengths over dp."""
    data_s = jax.device_put(data, NamedSharding(mesh, P("dp", "sp")))
    lens_s = jax.device_put(lengths, NamedSharding(mesh, P("dp")))
    return data_s, lens_s
