"""The batched verdict function: one jitted XLA program per ruleset.

`make_verdict_fn(plan)` traces the static plan structure (compiler/
plan.py) into a function of (device_tables, batch_arrays) -> per-rule
match matrix [B, R_device] bool. This replaces the reference's
per-request sequential rules loop (pingoo/listeners/http_listener.rs:
251-264 + pingoo/rules.rs:37-51 tree-walk) with one batched evaluation:

  * string predicate groups run as broadcast byte compares,
  * contains/regex run as one bit-parallel NFA scan per field,
  * ip/list membership via masked compares / sorted-search tables,
  * numeric comparisons as int64 lanes with exact error tracking
    (div-by-zero, i64 overflow) so the fail-open semantics of
    pingoo/rules.rs:41-44 are reproduced bit-exactly.

`evaluate_batch` adds the host-interpreted fallback rules and returns
the full match matrix in original rule order, plus `first_action`
applies the reference's first-match action semantics.
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..compiler.lowering import (
    BAnd,
    BConst,
    BEqBool,
    BErrConst,
    BLeaf,
    BNot,
    BOr,
    NBin,
    NCol,
    NConst,
    NLen,
    NNeg,
    NumCmp,
)
from ..compiler.plan import NfaScanPlan, RulesetPlan, ScanStrategy
from ..config.schema import Action
from ..expr import execute_as_bool
from ..ops.bitsplit_dfa import dfa_row_candidates, dfa_scan, dfa_skip_hits
from ..ops.cidr import cidr_contains, int_set_contains, v4_buckets_contains
from ..ops.match_ops import eq_match, prefix_match, suffix_match
from ..ops.nfa_scan import (extract_slots, halo_split_k, halo_split_scan,
                            init_scan_state, packed_scan_states, scan_chunk)
from ..ops.prefilter import prefilter_scan
from ..ops.window_match import window_hits

I64_MIN = -(2**63)

# Device-trace scopes (docs/OBSERVABILITY.md "Spans and scopes"): every
# unit of device work traces under ONE fixed vocabulary, `<kind>/<key>`
# or a bare kind, so that a profiler trace's operations split by bank
# and not by HLO line. Metadata only: the compiled programs are the
# same. Scopes nest (`act/bool/nfa/url/...`); a reader takes the
# deepest component of the vocabulary.
SCOPE_KINDS = ("unpack", "pf", "nfa", "dfa", "win", "grp", "list", "num",
               "bool", "act")


def _bank_scope(kind: str, key: str):
    """`nfa/<bank>` / `dfa/<bank>`: an NFA bank's key without its
    `nfa_` prefix (`@short`/`@rest` sub-banks keep their suffix)."""
    return jax.named_scope(f"{kind}/{key.removeprefix('nfa_')}")

# Scan execution selection: the default comes from the PLAN-TIME
# strategy selector (compiler/plan.py select_scan_strategy — recorded in
# plan.scan_plans, persisted through the artifact cache, re-tunable from
# measurement via bench.py's autotune hook). The env knobs below are now
# OVERRIDES, not the source of defaults:
#
# PINGOO_SCAN_STRATEGY: force one strategy for every bank — "scan"
# (lax.scan single-byte), "pair" (lax.scan pair lookup), "halo" (keep
# the selected kind, force the halo-split attempt). The fused Pallas
# kernels (ops/pallas_scan.py, ops/prefilter._fused_prefilter,
# ops/bitsplit_dfa._fused_dfa) have no knob: Mosaic refuses all three
# as written (PR 21 chip run), so they are reachable only by calling
# them (the interpret-mode parity tests) or, for the NFA kernel,
# through a cost MEASURED for it on the backend at hand
# (compiler/plan.select_scan_strategy).
#
# PINGOO_SCAN_PACK: legacy lane/row grouping for lax.scan banks
# (ops/nfa_scan.pack_scan_groups / _batch_stacked_states): "field" (one
# scan per field, the default), "length"/"fill" lane-packing, "single",
# "batch" row-stacking. A non-"field" value routes non-split banks
# through the legacy packed path.
#
# PINGOO_HALO_SPLIT: legacy knob forcing the within-device halo-split
# attempt for bounded-memory banks (the strategy's halo_k normally
# gates this).
#
# PINGOO_NFA_LOOKUP (read in ops/nfa_scan.py): byte-class lookup
# strategy per lax.scan step — take / cls_take / oh_f32 / pair / auto.
import os as _os

SCAN_PACK_MODE = _os.environ.get("PINGOO_SCAN_PACK", "field")
HALO_SPLIT = _os.environ.get("PINGOO_HALO_SPLIT", "0") != "0"

_ENV_STRATEGIES = {
    "scan": ("scan", False),
    "pair": ("scan", True),
}


def _resolve_strategy(strat: ScanStrategy) -> ScanStrategy:
    """Apply the PINGOO_SCAN_STRATEGY override (read per trace so tests
    can monkeypatch it)."""
    env = _os.environ.get("PINGOO_SCAN_STRATEGY", "")
    if not env:
        return strat
    if env == "halo":
        return ScanStrategy(kind=strat.kind, pair=strat.pair, halo_k=8,
                            source="env")
    if env not in _ENV_STRATEGIES:
        raise ValueError(
            f"PINGOO_SCAN_STRATEGY={env!r}: expected one of "
            f"{sorted(_ENV_STRATEGIES) + ['halo']}")
    kind, pair = _ENV_STRATEGIES[env]
    return ScanStrategy(kind=kind, pair=pair, halo_k=strat.halo_k,
                        source="env")


# -- literal-prefilter cascade (Stage B wiring) -------------------------------
#
# PINGOO_PREFILTER (read per trace; the plan's autotuned default_mode
# applies when unset):
#   off     — Stage A never runs; every bank scans unconditionally (the
#             pre-cascade behavior, the parity baseline).
#   banks   — one packed shift-AND pass per field; a gated NFA bank is
#             SKIPPED (lax.cond, shapes static) when no request in the
#             batch has a candidate for any of its patterns.
#   compact — banks, plus: a sparse gated bank gathers its candidate
#             rows into the smallest power-of-2-ish bucket that holds
#             them (a static ladder -> lax.switch), scans the compacted
#             rows, and scatters the hits back.
# PINGOO_PREFILTER_LEVELS caps the compaction ladder depth (default 4
# halvings). Soundness is structural: candidates over-approximate
# matches, so pruning can never change a verdict (tests/test_prefilter).


def _resolve_pf_mode(plan: RulesetPlan) -> str:
    pf = getattr(plan, "prefilter", None)
    if pf is None or not pf.fields:
        return "off"
    mode = _os.environ.get("PINGOO_PREFILTER", "") or pf.default_mode
    return mode if mode in ("off", "banks", "compact") else "banks"


# -- bitsplit-DFA lowering dispatch (compiler/nfa.lower_bank_to_dfa) ----------
#
# PINGOO_DFA (read per trace; the plan's dfa_default_mode applies when
# unset):
#   off   — always run the NFA tables (the parity baseline).
#   auto  — use the lowered DFA for a bank when the cost model (or the
#           bench.py micro-autotune) selected it (entry.dfa_auto) and no
#           PINGOO_SCAN_STRATEGY override pins the NFA backend.
#   force — use the DFA for every bank that lowered within budget.
# An EXACT DFA replaces the NFA scan outright (bit-identical by
# construction — tests/test_bitsplit_dfa proves parity). An
# APPROXIMATE DFA (merged states) is gate-only: its
# hits over-approximate per-slot matches, so candidate rows are
# rechecked through the exact NFA bank via the compact argsort-gather
# ladder and pruned rows take the skip base — prefilter prune-only
# soundness, one level deeper.


def _resolve_dfa_mode(plan: RulesetPlan) -> str:
    mode = _os.environ.get("PINGOO_DFA", "") \
        or getattr(plan, "dfa_default_mode", "auto")
    return mode if mode in ("off", "auto", "force") else "auto"


def _dfa_bank_active(plan: RulesetPlan, entry, mode: str) -> bool:
    """Host-static: does this bank run its lowered DFA under `mode`?
    Split banks keep their per-sub-bank NFA strategies (the partition
    already beat the whole-bank scan, and slot recombination happens on
    NFA hits), so lowering only dispatches on non-split entries."""
    if mode == "off" or entry.split is not None:
        return False
    if not entry.dfa_key or entry.dfa_key not in plan.np_tables:
        return False
    if mode == "force":
        return True
    return bool(entry.dfa_auto) \
        and not _os.environ.get("PINGOO_SCAN_STRATEGY")


def _dfa_win_active(plan: RulesetPlan, key: str, mode: str) -> bool:
    """Whether window bank `key` dispatches through its lowered DFA.

    The window conv is deliberately serial-free on the MXU (its whole
    reason to exist — ops/window_match.py), so `auto` only swaps in the
    DFA gather ladder where per-row work dominates the per-step
    dependency chain: the CPU diagnostic backend. `force` takes it
    everywhere (parity/bench A/B)."""
    dkey = getattr(plan, "win_dfa", {}).get(key)
    if not dkey or dkey not in plan.np_tables or mode == "off":
        return False
    if mode == "force":
        return True
    import jax

    return jax.default_backend() == "cpu"


def dfa_dispatch_counts(plan: RulesetPlan) -> tuple[str, int, int]:
    """(resolved mode, banks running their DFA, approx banks taking the
    exact-NFA recheck path) — host-static per plan+env, counted once per
    batch by the service metrics (pingoo_dfa_banks_total{mode=} /
    pingoo_dfa_recheck_total)."""
    mode = _resolve_dfa_mode(plan)
    banks = recheck = 0
    for entry in getattr(plan, "scan_plans", {}).values():
        if not _dfa_bank_active(plan, entry, mode):
            continue
        banks += 1
        if not plan.np_tables[entry.dfa_key].exact:
            recheck += 1
    for key, dkey in getattr(plan, "win_dfa", {}).items():
        if not _dfa_win_active(plan, key, mode):
            continue
        banks += 1
        if not plan.np_tables[dkey].exact:
            recheck += 1
    return mode, banks, recheck


def _pf_compact_sizes(B: int) -> list[int]:
    """Static compaction ladder: [B, B/2, ...] bounded by the level cap
    and a 32-row floor (below that the scan cost is all fixed)."""
    levels = int(_os.environ.get("PINGOO_PREFILTER_LEVELS", "4"))
    sizes = [B]
    while len(sizes) <= levels and sizes[-1] // 2 >= 32:
        sizes.append(sizes[-1] // 2)
    return sizes


CASCADE_STATS = ("candidate", "candidate_bucket", "recheck",
                 "recheck_bucket")


def _bank_gated(pf, key: str) -> bool:
    """Does Stage A gate bank `key`: a gated bank with a factor mask
    over a field the prefilter scans."""
    return (bool(pf.bank_gated.get(key)) and key in pf.bank_masks
            and pf.bank_field.get(key) in pf.fields)


def cascade_banks(plan: RulesetPlan) -> tuple[str, ...]:
    """Host-static: the banks whose rows the cascade counts, in the
    order the lanes program reports them (`CASCADE_STATS` each): every
    bank Stage A gates (`make_prefilter_fn(...).gated`'s test) and
    every bank behind an APPROXIMATE DFA, whose recheck ladder runs
    gated or not. Read under the env the trace will see
    (PINGOO_PREFILTER / PINGOO_DFA)."""
    pf = getattr(plan, "prefilter", None)
    gate = pf is not None and _resolve_pf_mode(plan) != "off"
    dfa_mode = _resolve_dfa_mode(plan)
    banks: list[str] = []

    def gated(key):
        return gate and _bank_gated(pf, key)

    for key, entry in getattr(plan, "scan_plans", {}).items():
        approx = (_dfa_bank_active(plan, entry, dfa_mode)
                  and not plan.np_tables[entry.dfa_key].exact)
        banks.extend(k for k in (entry.split or (key,))
                     if gated(k) or approx)
    wins = {b.table_key for b in plan.bindings.values()
            if b.kind == "window"}
    for key in sorted(wins):
        dkey = getattr(plan, "win_dfa", {}).get(key)
        approx = (_dfa_win_active(plan, key, dfa_mode)
                  and not plan.np_tables[dkey].exact)
        if gated(key) or approx:
            banks.append(key)
    return tuple(banks)


# -- numeric IR evaluation ---------------------------------------------------


def _eval_num(ir, arrays, B):
    """-> (val int64 [B], err bool [B]) with Rust-i64 error semantics."""
    if isinstance(ir, NConst):
        return (jnp.full((B,), ir.value, dtype=jnp.int64),
                jnp.zeros((B,), dtype=bool))
    if isinstance(ir, NCol):
        return arrays[ir.name].astype(jnp.int64), jnp.zeros((B,), dtype=bool)
    if isinstance(ir, NLen):
        return (arrays[f"{ir.field}_len"].astype(jnp.int64),
                jnp.zeros((B,), dtype=bool))
    if isinstance(ir, NNeg):
        v, e = _eval_num(ir.x, arrays, B)
        return -v, e | (v == I64_MIN)
    if isinstance(ir, NBin):
        lv, le = _eval_num(ir.left, arrays, B)
        rv, re_ = _eval_num(ir.right, arrays, B)
        err = le | re_
        if ir.op == "+":
            s = lv + rv
            of = ((lv ^ s) & (rv ^ s)) < 0
            return s, err | of
        if ir.op == "-":
            s = lv - rv
            of = ((lv ^ rv) & (lv ^ s)) < 0
            return s, err | of
        if ir.op == "*":
            s = lv * rv
            l_safe = jnp.where(lv == 0, 1, lv)
            of = (lv != 0) & (jax.lax.div(s, l_safe) != rv)
            of = of | ((lv == -1) & (rv == I64_MIN))
            of = of | ((rv == -1) & (lv == I64_MIN))
            return s, err | of
        if ir.op in ("/", "%"):
            zero = rv == 0
            min_neg1 = (lv == I64_MIN) & (rv == -1)
            r_safe = jnp.where(zero | min_neg1, 1, rv)
            if ir.op == "/":
                # I64_MIN / -1 overflows (interp: checked_i64 raises).
                return jax.lax.div(lv, r_safe), err | zero | min_neg1
            # I64_MIN % -1 == 0 in the interpreter (the final checked_i64
            # sees 0), so only division by zero errors here.
            val = jnp.where(min_neg1, 0, jax.lax.rem(lv, r_safe))
            return val, err | zero
        raise AssertionError(ir.op)
    raise AssertionError(f"bad num ir {ir!r}")


_CMP = {
    "==": lambda a, b: a == b,
    "!=": lambda a, b: a != b,
    "<": lambda a, b: a < b,
    "<=": lambda a, b: a <= b,
    ">": lambda a, b: a > b,
    ">=": lambda a, b: a >= b,
}


# -- leaf evaluation ---------------------------------------------------------


def _eval_leaves(plan: RulesetPlan, tables, arrays, B, pf_hits=None,
                 cascade=None):
    """Compute every leaf's ([B] val, [B] err) with shared group ops.

    `cascade`, a dict, receives per bank the row counts the cascade
    already holds as traced int32 scalars ({bank: {stat: scalar}},
    stats of CASCADE_STATS): what each ladder was asked to hold and the
    bucket it took.

    `pf_hits` optionally carries precomputed Stage-A prefilter hit maps
    ({field: [B, F] bool} from make_prefilter_fn — the service path
    dispatches Stage A as its own program so the stage is timeable);
    absent, the prefilter is traced inline into the same XLA program."""
    results: dict[int, tuple] = {}
    no_err = jnp.zeros((B,), dtype=bool)

    group_cols: dict[str, Any] = {}

    def group_result(key, field, kind):
        if key not in group_cols:
            table = tables[key]
            data = arrays[f"{field}_bytes"]
            lens = arrays[f"{field}_len"]
            match = {"eq": eq_match, "prefix": prefix_match}.get(
                kind, suffix_match)
            with jax.named_scope(f"grp/{key}"):
                group_cols[key] = match(data, lens, table)
        return group_cols[key]

    nfa_cache: dict[str, Any] = {}
    bank_kind: dict[str, str] = {}  # banks that ran their lowered DFA

    def nfa_result(key, field):
        return nfa_cache[key]  # pre-filled by run_packed_scans

    def bank_hits(bank, strat: ScanStrategy, data, lens):
        """One bank's [B, P] hits under its selected strategy: the
        trace-time halo-split attempt (when the strategy's halo_k and
        the bucketed length make it strictly cheaper than the base
        stepping), then the pair/single step on the lax.scan or fused
        Pallas backend."""
        B, L = data.shape
        backend = "pallas" if strat.kind == "pallas" else None
        lookup = "pair" if strat.pair else None
        k_cap = strat.halo_k if strat.halo_k > 1 else (8 if HALO_SPLIT else 1)
        if k_cap > 1:
            k = halo_split_k(bank, int(L), max_k=k_cap)
            base_iters = (L + 1) // 2 if strat.pair else L
            if k > 1 and (L // k + int(bank.max_footprint)) < base_iters:
                return halo_split_scan(bank, data, lens, k,
                                       lookup=lookup, backend=backend)
        state = scan_chunk(bank, data, lens,
                           init_scan_state(B, bank.opt.shape[0]), 0,
                           lookup=lookup, backend=backend)
        return extract_slots(bank, state, lens)

    # -- Stage B: candidate gating over the Stage-A factor hits --------------

    pf = getattr(plan, "prefilter", None)
    pf_mode = _resolve_pf_mode(plan)
    dfa_mode = _resolve_dfa_mode(plan)
    pf_field_hits: dict[str, Any] = dict(pf_hits or {})

    def field_pf(field):
        """This field's [B, F] factor hits (from the caller-provided
        Stage-A pass, or traced inline exactly once per field)."""
        if field not in pf_field_hits:
            ff = pf.fields[field]
            with jax.named_scope(f"pf/{field}"):
                pf_field_hits[field] = prefilter_scan(
                    tables[ff.table_key], arrays[f"{field}_bytes"],
                    arrays[f"{field}_len"])
        return pf_field_hits[field]

    def bank_skip_result(bank, lens):
        """A skipped bank's exact result: zero scan state still yields
        the always-match and empty-input lanes; every factor-gated slot
        is False — sound because skipping only happens when no request
        holds any of the bank's factors (candidates ⊇ matches)."""
        Bsz = lens.shape[0]
        state = jnp.zeros((Bsz, bank.opt.shape[0]), dtype=jnp.uint32)
        return extract_slots(bank, state, lens)

    def bank_candidates(key, n_rows):
        """[n_rows] candidate-row vector for bank `key`, or None when
        the bank is ungated (no prefilter, mode off, or a slot without
        an extractable factor)."""
        if pf is None or pf_mode == "off":
            return None
        if not pf.bank_gated.get(key) or key not in pf.bank_masks:
            return None
        field = pf.bank_field[key]
        if field not in pf.fields:
            return None
        mask = pf.bank_masks[key]
        if not mask.any():
            # Only never-match slots: statically no candidates.
            return jnp.zeros((n_rows,), dtype=bool)
        return jnp.any(field_pf(field) & jnp.asarray(mask)[None, :],
                       axis=1)

    def compact_rows(scan_rows, base_fn, data, lens, cand):
        """Gather candidate rows into the smallest ladder bucket that
        holds them, scan the compacted rows, scatter hits back over the
        skipped-bank base. Every branch has static shapes (lax.switch);
        the last branch is the empty-candidate full skip. Returns
        (hits, candidate count, rows of the bucket taken: 0 for the
        skip)."""
        Bsz = data.shape[0]
        sizes = _pf_compact_sizes(Bsz)
        count = cand.sum(dtype=jnp.int32)
        order = jnp.argsort(jnp.where(cand, 0, 1))  # candidates first

        def full():
            return scan_rows(data, lens)

        def level(sz):
            def br():
                idx = order[:sz]
                h = scan_rows(jnp.take(data, idx, axis=0),
                              jnp.take(lens, idx))
                return base_fn().at[idx].set(h)
            return br

        branches = ([full] + [level(sz) for sz in sizes[1:]] + [base_fn])
        if len(sizes) > 1:
            lev = jnp.sum((jnp.asarray(sizes[1:], dtype=jnp.int32)
                           >= count).astype(jnp.int32))
        else:
            lev = jnp.int32(0)
        lev = jnp.where(count == 0, jnp.int32(len(branches) - 1), lev)
        bucket = jnp.take(jnp.asarray(sizes + [0], dtype=jnp.int32), lev)
        return jax.lax.switch(lev, branches), count, bucket

    def note_cascade(key, ladder, count, bucket):
        if cascade is not None:
            cascade.setdefault(key, {}).update(
                {ladder: count, f"{ladder}_bucket": bucket})

    def gated_scan(key, data, lens, scan_rows, base_fn):
        """Run one bank through the cascade: unconditional when the bank
        is ungated, cond-skipped in banks mode, row-compacted in compact
        mode."""
        cand = bank_candidates(key, data.shape[0])
        if cand is None:
            # Ungated: every row is scanned. -1 = "all live rows" (the
            # host knows how many those are; the program does not).
            note_cascade(key, "candidate", jnp.int32(-1),
                         jnp.int32(data.shape[0]))
            return scan_rows(data, lens)
        if pf_mode == "compact":
            hits, count, bucket = compact_rows(scan_rows, base_fn, data,
                                               lens, cand)
        else:
            count = cand.sum(dtype=jnp.int32)
            bucket = jnp.where(count > 0, jnp.int32(data.shape[0]), 0)
            hits = jax.lax.cond(count > 0,
                                lambda: scan_rows(data, lens),
                                base_fn)
        note_cascade(key, "candidate", count, bucket)
        return hits

    def gated_bank_hits(key, bank, strat, data, lens):
        with _bank_scope("nfa", key):
            return gated_scan(
                key, data, lens,
                lambda d, l: bank_hits(bank, strat, d, l),
                lambda: bank_skip_result(bank, lens))

    def dfa_cascade_hits(key, dtab, data, lens, recheck_rows,
                         recheck_base, recheck_scope):
        """One lowered bank's [B, P] hits via its bitsplit DFA.

        Exact DFA: a drop-in replacement for the bank's scan that rides
        the full prefilter cascade unchanged (cond-skip in banks mode,
        argsort-gather compaction in compact mode; the skip base is the
        DFA's own zero-input result — start-state accepts cover the
        always/empty lanes). Approximate DFA: the gather ladder itself
        rides the cascade (compacted onto Stage-A candidate rows —
        sparse end-to-end, the skip base makes pruned rows trivially
        non-candidates), then rows with any non-trivial hit are
        rechecked through the bank's EXACT scan (NFA tables / window
        conv) via a second, smaller compact ladder; pruned rows take
        the exact skip base. The recheck runs under `recheck_scope`
        (`nfa/<bank>`, nested in the caller's `dfa/<bank>`), so a device
        trace tells the exact re-scan from the DFA's own ladder. Either
        way the verdict is bit-identical to PINGOO_DFA=off
        (tests/test_bitsplit_dfa)."""
        dfa_rows = lambda d, l: dfa_scan(dtab, d, l)
        dfa_base = lambda: dfa_skip_hits(dtab, lens)
        if dtab.exact:
            return gated_scan(key, data, lens, dfa_rows, dfa_base)
        hits = gated_scan(key, data, lens, dfa_rows, dfa_base)
        cand = dfa_row_candidates(dtab, hits, lens)
        pf_cand = bank_candidates(key, data.shape[0])
        if pf_cand is not None:
            cand = cand & pf_cand
        with recheck_scope:
            hits, count, bucket = compact_rows(recheck_rows, recheck_base,
                                               data, lens, cand)
        note_cascade(key, "recheck", count, bucket)
        return hits

    def dfa_bank_hits(key, entry, bank, data, lens):
        strat = _resolve_strategy(entry.strategy)
        with _bank_scope("dfa", key):
            return dfa_cascade_hits(
                key, tables[entry.dfa_key], data, lens,
                lambda d, l: bank_hits(bank, strat, d, l),
                lambda: bank_skip_result(bank, lens),
                _bank_scope("nfa", key))

    def gated_window_hits(key, field):
        """The window bank under the same cascade: a gated win bank's
        slots are all factor-gated or never-match, so the skip base is
        simply all-False (window patterns carry no always/empty lanes
        once gating eligibility excludes min_len == 0 sources). When
        the bank's source patterns lowered to a bitsplit DFA and the
        dispatch mode takes it (_dfa_win_active: force anywhere, auto
        on the row-work-bound CPU backend), the gather ladder replaces
        the conv — guarded on slot-count agreement so the tp mesh path
        (which pads the conv table's pattern axis but not DfaTables)
        falls back to the conv."""
        data = arrays[f"{field}_bytes"]
        lens = arrays[f"{field}_len"]
        # P from the TABLE, not the plan: the tp mesh path pads the
        # pattern axis (parallel/mesh.pad_tables_for_tp) and pad rows
        # never match, so all-False covers them too.
        P = tables[key].kernel.shape[0]
        win_rows = lambda d, l: window_hits(tables[key], d, l)
        win_base = lambda: jnp.zeros((data.shape[0], P), dtype=bool)
        dkey = getattr(plan, "win_dfa", {}).get(key)
        if dkey and dkey in tables \
                and _dfa_win_active(plan, key, dfa_mode) \
                and tables[dkey].num_slots == P:
            with _bank_scope("dfa", key):
                return dfa_cascade_hits(key, tables[dkey], data, lens,
                                        win_rows, win_base,
                                        jax.named_scope(f"win/{field}"))
        with jax.named_scope(f"win/{field}"):
            if pf is None or key not in pf.slot_codes:
                return win_rows(data, lens)
            return gated_scan(key, data, lens, win_rows, win_base)

    def run_packed_scans(groups: dict[str, tuple[str, list]]) -> None:
        """Run every NFA bank through its plan-selected strategy
        (compiler/plan.py scan_plans; module-level knobs override).
        Partitioned banks run their halo-splittable @short sub-bank and
        pair-stepped @rest residual separately and recombine columns by
        the recorded slot permutation."""
        packed: dict[str, tuple] = {}  # legacy lane/row-packing jobs
        for key, (field, _members) in groups.items():
            data = arrays[f"{field}_bytes"]
            lens = arrays[f"{field}_len"]
            entry = plan.scan_plans.get(key) or NfaScanPlan(
                key=key, strategy=ScanStrategy())
            if entry.split is not None:
                skey, rkey = entry.split
                parts = [
                    gated_bank_hits(skey, tables[skey],
                                    _resolve_strategy(entry.short_strategy),
                                    data, lens),
                    gated_bank_hits(rkey, tables[rkey],
                                    _resolve_strategy(entry.rest_strategy),
                                    data, lens)]
                perm = jnp.asarray(entry.slot_perm, dtype=jnp.int32)
                with _bank_scope("nfa", key):
                    nfa_cache[key] = jnp.take(
                        jnp.concatenate(parts, axis=1), perm, axis=1)
                continue
            if _dfa_bank_active(plan, entry, dfa_mode) \
                    and entry.dfa_key in tables \
                    and tables[entry.dfa_key].num_slots \
                        == tables[key].accept_member.shape[1]:
                nfa_cache[key] = dfa_bank_hits(key, entry, tables[key],
                                               data, lens)
                bank_kind[key] = "dfa"
                continue
            strat = _resolve_strategy(entry.strategy)
            if strat.source != "env" and SCAN_PACK_MODE != "field":
                strat = ScanStrategy()  # legacy packed path wants lax.scan
            if strat.kind == "scan" and not strat.pair \
                    and SCAN_PACK_MODE != "field":
                if HALO_SPLIT:  # legacy halo-first, as before packing
                    k = halo_split_k(tables[key], int(data.shape[1]))
                    if k > 1:
                        with _bank_scope("nfa", key):
                            nfa_cache[key] = halo_split_scan(
                                tables[key], data, lens, k)
                        continue
                packed[key] = (tables[key], data, lens)
                continue
            nfa_cache[key] = gated_bank_hits(key, tables[key], strat,
                                             data, lens)
        if packed:  # several banks in one scan: no bank to name
            with jax.named_scope("nfa/@packed"):
                states = packed_scan_states(
                    {k: v[0] for k, v in packed.items()},
                    {k: v[1] for k, v in packed.items()},
                    {k: v[2] for k, v in packed.items()},
                    mode=SCAN_PACK_MODE)
            for k, (bank, _data, lens) in packed.items():
                with _bank_scope("nfa", k):
                    nfa_cache[k] = extract_slots(bank, states[k], lens)

    # Per-leaf NFA/window extraction: leaves own contiguous slot spans;
    # doing a per-leaf slice+any would issue hundreds of tiny ops, so
    # instead one [B, P] x [P, n_leaves] matmul reduces every span at
    # once (MXU does the OR as a count > 0).
    leaf_matrix_cache: dict[str, Any] = {}

    def span_leaf_matrix(key, hits_fn, spans, scope):
        if key not in leaf_matrix_cache:
            hits = hits_fn()
            P = hits.shape[1]
            member = np.zeros((P, len(spans)), dtype=np.float32)
            for j, (lo, hi) in enumerate(spans):
                member[lo:hi, j] = 1.0
            with scope:
                counts = jnp.dot(hits.astype(jnp.float32),
                                 jnp.asarray(member),
                                 preferred_element_type=jnp.float32)
                leaf_matrix_cache[key] = counts > 0.0
        return leaf_matrix_cache[key]

    ip_one_cache: Any = None

    # Group NFA/window leaves per bank so extraction is one matmul each.
    nfa_groups: dict[str, tuple[str, list]] = {}
    win_groups: dict[str, tuple[str, list]] = {}
    for leaf_id, binding in plan.bindings.items():
        if binding.kind == "nfa":
            entry = nfa_groups.setdefault(binding.table_key, (binding.field, []))
            entry[1].append((leaf_id, binding.span))
        elif binding.kind == "window":
            entry = win_groups.setdefault(binding.table_key, (binding.field, []))
            entry[1].append((leaf_id, binding.span))
    if nfa_groups:
        run_packed_scans(nfa_groups)
    nfa_leaf_col = {
        leaf_id: (key, j)
        for key, (field, members) in nfa_groups.items()
        for j, (leaf_id, _) in enumerate(members)
    }
    win_leaf_col = {
        leaf_id: (key, j)
        for key, (field, members) in win_groups.items()
        for j, (leaf_id, _) in enumerate(members)
    }

    for leaf_id, binding in plan.bindings.items():
        k = binding.kind
        if k == "str":
            cols = group_result(binding.table_key, binding.field, binding.group)
            results[leaf_id] = (cols[:, binding.col], no_err)
        elif k == "nfa":
            key, col = nfa_leaf_col[leaf_id]
            field, members = nfa_groups[key]
            mat = span_leaf_matrix(key, lambda key=key, field=field:
                                   nfa_result(key, field),
                                   [span for _, span in members],
                                   _bank_scope(bank_kind.get(key, "nfa"),
                                               key))
            results[leaf_id] = (mat[:, col], no_err)
        elif k == "window":
            key, col = win_leaf_col[leaf_id]
            field, members = win_groups[key]
            mat = span_leaf_matrix(
                key,
                lambda key=key, field=field: gated_window_hits(key, field),
                [span for _, span in members],
                jax.named_scope(f"win/{field}"))
            results[leaf_id] = (mat[:, col], no_err)
        elif k == "str_list":
            table = tables[binding.table_key]
            data = arrays[f"{binding.field}_bytes"]
            lens = arrays[f"{binding.field}_len"]
            lo, hi = binding.span
            if hi == lo:  # all entries were non-byte strings
                results[leaf_id] = (jnp.zeros((B,), dtype=bool), no_err)
            else:
                with jax.named_scope(f"list/{binding.table_key}"):
                    eqs = eq_match(data, lens, table)
                    results[leaf_id] = (jnp.any(eqs[:, lo:hi], axis=1),
                                        no_err)
        elif k == "ip_one":
            if ip_one_cache is None:
                t = tables["ip_preds"]
                ips = arrays["ip"]
                with jax.named_scope("list/ip_preds"):
                    diff = (ips[:, None, :] & t["masks"][None]) \
                        ^ t["nets"][None]
                    ip_one_cache = jnp.all(diff == 0, axis=2)  # [B, N]
            results[leaf_id] = (ip_one_cache[:, binding.col], no_err)
        elif k in ("ip_list_small", "ip_list_large"):
            contains = cidr_contains if k == "ip_list_small" \
                else v4_buckets_contains
            with jax.named_scope(f"list/{binding.table_key}"):
                results[leaf_id] = (
                    contains(tables[binding.table_key], arrays["ip"]),
                    no_err)
        elif k == "int_list":
            with jax.named_scope("num"):
                pv, pe = _eval_num(binding.pred, arrays, B)
            with jax.named_scope(f"list/{binding.table_key}"):
                hit = int_set_contains(tables[binding.table_key], pv)
            results[leaf_id] = (hit, pe)
        elif k == "num_cmp":
            cmp: NumCmp = binding.pred
            with jax.named_scope("num"):
                lv, le = _eval_num(cmp.left, arrays, B)
                rv, re_ = _eval_num(cmp.right, arrays, B)
                results[leaf_id] = (_CMP[cmp.op](lv, rv), le | re_)
        else:
            raise AssertionError(k)
    return results


# -- boolean IR evaluation ---------------------------------------------------


def _eval_bool(ir, leaves, B):
    """-> (val [B], err [B]) reproducing interpreter error semantics:
    && / || short-circuit left-to-right; == evaluates both sides."""
    if isinstance(ir, BConst):
        return (jnp.full((B,), ir.value, dtype=bool),
                jnp.zeros((B,), dtype=bool))
    if isinstance(ir, BErrConst):
        return (jnp.zeros((B,), dtype=bool), jnp.ones((B,), dtype=bool))
    if isinstance(ir, BLeaf):
        return leaves[ir.leaf_id]
    if isinstance(ir, BNot):
        v, e = _eval_bool(ir.x, leaves, B)
        return ~v, e
    if isinstance(ir, BAnd):
        lv, le = _eval_bool(ir.left, leaves, B)
        rv, re_ = _eval_bool(ir.right, leaves, B)
        return lv & rv, le | (lv & re_)
    if isinstance(ir, BOr):
        lv, le = _eval_bool(ir.left, leaves, B)
        rv, re_ = _eval_bool(ir.right, leaves, B)
        return lv | rv, le | (~lv & re_)
    if isinstance(ir, BEqBool):
        lv, le = _eval_bool(ir.left, leaves, B)
        rv, re_ = _eval_bool(ir.right, leaves, B)
        val = lv == rv
        if ir.negate:
            val = ~val
        return val, le | re_
    raise AssertionError(f"bad bool ir {ir!r}")


# -- public API --------------------------------------------------------------


@jax.named_scope("bool")
def _matched_cols(plan: RulesetPlan, tables, arrays, pf_hits=None,
                  cascade=None):
    """Traced body shared by the verdict/lane functions:
    (tables, arrays) -> [B, R_dev] bool in device_rule_indices order.
    `cascade`: _eval_leaves' collector of the cascade's row counts.

    Rules whose IR is a single leaf (the common WAF shape — one
    predicate per rule) read their column straight out of the stacked
    leaf matrix with one gather; only compound rules evaluate their
    boolean tree (error -> no-match per pingoo/rules.rs:41-44 either
    way)."""
    device_rules = [r for r in plan.rules if not r.host]
    n_leaves = len(plan.leaves)
    B = arrays["asn"].shape[0]
    leaves = _eval_leaves(plan, tables, arrays, B, pf_hits=pf_hits,
                          cascade=cascade)
    # Effective per-leaf match columns (+ const true / false).
    eff = [None] * n_leaves
    for leaf_id, (v, e) in leaves.items():
        eff[leaf_id] = v & ~e
    base = eff + [
        jnp.ones((B,), dtype=bool),  # column n_leaves: const true
        jnp.zeros((B,), dtype=bool),  # column n_leaves + 1: const false
    ]
    extra_cols = []
    rule_col: list[int] = []
    for rule in device_rules:
        if rule.always:
            rule_col.append(n_leaves)
        elif isinstance(rule.ir, BLeaf):
            rule_col.append(rule.ir.leaf_id)
        elif isinstance(rule.ir, BConst):
            rule_col.append(n_leaves if rule.ir.value else n_leaves + 1)
        elif isinstance(rule.ir, BErrConst):
            rule_col.append(n_leaves + 1)
        else:
            v, e = _eval_bool(rule.ir, leaves, B)
            rule_col.append(len(base) + len(extra_cols))
            extra_cols.append(v & ~e)
    if not rule_col:
        return jnp.zeros((B, 0), dtype=bool)
    allmat = jnp.stack(base + extra_cols, axis=1)  # [B, NL + 2 + extra]
    return jnp.take(allmat, jnp.asarray(rule_col, dtype=jnp.int32), axis=1)


def donate_batch_buffers() -> bool:
    """Whether the verdict/lane programs should mark their request
    arrays as donated inputs (ISSUE 9, docs/EXECUTOR.md). Donation
    lets XLA reuse the per-batch upload buffers in place across the
    pipelined executor's in-flight batches instead of allocating fresh
    device memory each launch — but it is only meaningful on a real
    accelerator backend: the CPU engine aliases host buffers and XLA
    just warns that the donation was unusable. So the planes request
    it exactly when the resolved backend is not `cpu` (honest gating —
    no pretend-donation on the diagnostic backend)."""
    try:
        import jax

        return jax.default_backend() != "cpu"
    except Exception:
        return False


def make_verdict_fn(plan: RulesetPlan, donate: bool = False):
    """Jitted device verdict: (tables, arrays) -> [B, R_dev] bool.

    `pf_hits` optionally feeds a separately-dispatched Stage-A prefilter
    pass (make_prefilter_fn); left None, Stage A traces inline under the
    active PINGOO_PREFILTER mode.

    `donate=True` marks the request arrays (arg 1) as donated buffers
    so each pipelined batch's upload can be recycled in place by XLA
    (see donate_batch_buffers for when that is honest to request)."""

    def verdict(tables, arrays, pf_hits=None):
        return _matched_cols(plan, tables, arrays, pf_hits=pf_hits)

    return jax.jit(verdict, donate_argnums=(1,) if donate else ())


# -- compact staging: device-side decode (ISSUE 15) ---------------------------


@jax.named_scope("unpack")
def unpack_staged(packed, layout):
    """Decode ONE packed staging buffer on device: [B, layout.width]
    uint8 -> the standard per-field arrays dict the traced evaluator
    bodies consume. Every offset/width is a static Python int from the
    (static-argument) PackedLayout, so each field comes out as a
    contiguous XLA slice — no gather, and the downstream predicate
    kernels trace exactly as they do over separately-staged arrays.

    Metadata tail: u16-LE true lens, 16 big-endian IP bytes -> [B, 4]
    uint32 words, i64-LE asn/remote_port reassembled through uint64
    shifts + a bitcast so negative values round-trip exactly."""
    arrays = {}
    for name, off, w in layout.fields:
        arrays[f"{name}_bytes"] = packed[:, off:off + w]
    for name, off in layout.lens:
        lo = packed[:, off].astype(jnp.int32)
        hi = packed[:, off + 1].astype(jnp.int32)
        arrays[f"{name}_len"] = lo | (hi << 8)
    B = packed.shape[0]
    ipb = packed[:, layout.ip_off:layout.ip_off + 16] \
        .astype(jnp.uint32).reshape(B, 4, 4)
    arrays["ip"] = ((ipb[:, :, 0] << 24) | (ipb[:, :, 1] << 16)
                    | (ipb[:, :, 2] << 8) | ipb[:, :, 3])

    def _i64(off):
        b = packed[:, off:off + 8].astype(jnp.uint64)
        v = b[:, 0]
        for k in range(1, 8):
            v = v | (b[:, k] << (8 * k))
        return jax.lax.bitcast_convert_type(v, jnp.int64)

    arrays["asn"] = _i64(layout.asn_off)
    arrays["remote_port"] = _i64(layout.port_off)
    return arrays


def make_packed_verdict_fn(plan: RulesetPlan, donate: bool = False):
    """Compact-staging twin of make_verdict_fn: (tables, packed,
    layout, pf_hits) -> [B, R_dev] bool. `layout` is a STATIC argument
    (engine/batch.PackedLayout is a hashable NamedTuple), so the traced
    body is literally _matched_cols over unpack_staged's slices — full
    and compact mode share every predicate kernel by construction, and
    plans whose caps land on the same rung-tuple share one XLA
    compile."""

    def verdict(tables, packed, layout, pf_hits=None):
        return _matched_cols(plan, tables,
                             unpack_staged(packed, layout),
                             pf_hits=pf_hits)

    return jax.jit(verdict, static_argnums=(2,),
                   donate_argnums=(1,) if donate else ())


class PrefilterProgram(NamedTuple):
    """make_prefilter_fn's bundle: the jitted Stage-A pass plus the
    static bank inventories the observability fold needs (gated = every
    cascade-gated bank; masked = the subset with a non-empty factor
    mask, in the aux vector's per-bank lane order)."""

    fn: Any
    gated: tuple[str, ...]
    masked: tuple[str, ...]


def _stage_a_banks(plan: RulesetPlan):
    """Host-static: (prefilter, gated bank keys, the gated keys with a
    non-empty factor mask) of the plan's Stage A, or None when the plan
    has no prefilter or the mode is off."""
    pf = getattr(plan, "prefilter", None)
    if pf is None or not pf.fields or _resolve_pf_mode(plan) == "off":
        return None
    # Bank keys the evaluator actually scans: NFA banks follow the scan
    # plan (split-aware); window banks are all registered win_* keys.
    scanned: list[str] = []
    for key, entry in plan.scan_plans.items():
        scanned.extend(entry.split if entry.split else (key,))
    scanned.extend(k for k in pf.bank_masks if k.startswith("win_"))
    gated = tuple(k for k in scanned if _bank_gated(pf, k))
    return pf, gated, tuple(k for k in gated if pf.bank_masks[k].any())


def _make_prefilter_body(plan: RulesetPlan):
    """UNJITTED Stage-A body: (stage_a, gated, masked) or None.

    Shared by make_prefilter_fn (which jits `stage_a` as its own
    dispatch so the stage is separately timeable) and its compact-
    staging twin make_packed_prefilter_fn."""
    banks = _stage_a_banks(plan)
    if banks is None:
        return None
    pf, gated, masked = banks
    # Hoisted device constants (analyze-lint recompile-const-upload).
    masks = {k: jnp.asarray(pf.bank_masks[k]) for k in masked}

    def stage_a(tables, arrays):
        hits = {}
        for field, ff in pf.fields.items():
            with jax.named_scope(f"pf/{field}"):
                hits[field] = prefilter_scan(
                    tables[ff.table_key], arrays[f"{field}_bytes"],
                    arrays[f"{field}_len"])
        cand_rows = jnp.int32(0)
        skipped = jnp.int32(len(gated) - len(masks))  # never-only banks
        bank_cands = []
        bank_skips = []
        for k, mask in masks.items():
            field = pf.bank_field[k]
            with jax.named_scope(f"pf/{field}"):  # the bank's candidates
                cand = jnp.any(hits[field] & mask[None, :], axis=1)
                n_cand = cand.sum(dtype=jnp.int32)
                skip = jnp.where(jnp.any(cand), 0, 1).astype(jnp.int32)
                cand_rows = cand_rows + n_cand
                skipped = skipped + skip
            bank_cands.append(n_cand)
            bank_skips.append(skip)
        return hits, jnp.stack([cand_rows, skipped]
                               + bank_cands + bank_skips)

    return stage_a, gated, masked


def make_prefilter_fn(plan: RulesetPlan):
    """Jitted Stage-A pass: (tables, arrays) -> (pf_hits, aux), where
    pf_hits is {field: [B, F] bool} (feed to the verdict/lane fn so the
    pipeline stage is separately timeable) and aux is an int32 vector
    [candidate_rows_total, banks_skipped, *per-bank candidate counts,
    *per-bank skip flags] (per-bank lanes in `masked` order — the
    banks-skipped ATTRIBUTION surface, obs/provenance.py). Returns a
    PrefilterProgram or None when the plan has no prefilter / the mode
    is off."""
    body = _make_prefilter_body(plan)
    if body is None:
        return None
    stage_a, gated, masked = body
    return PrefilterProgram(fn=jax.jit(stage_a), gated=gated,
                            masked=masked)


def make_packed_prefilter_fn(plan: RulesetPlan):
    """Compact-staging twin of make_prefilter_fn: the jitted Stage-A
    signature becomes (tables, packed, layout) with `layout` static, so
    the prefilter reads its fields straight out of the one-copy packed
    buffer (ISSUE 15). Same PrefilterProgram contract; None when the
    plan has no prefilter."""
    body = _make_prefilter_body(plan)
    if body is None:
        return None
    stage_a, gated, masked = body

    def stage_a_packed(tables, packed, layout):
        return stage_a(tables, unpack_staged(packed, layout))

    return PrefilterProgram(fn=jax.jit(stage_a_packed, static_argnums=(2,)),
                            gated=gated, masked=masked)


def make_pad_fn(batch: int):
    """Jitted row pad of compact staging: the first H rows of a packed
    batch, as shipped ([H, width] uint8) -> the [batch, width] buffer
    the packed program pair takes, zero rows below, on the chip the
    rows are on. The pair's input is byte for byte the full upload it
    replaces, so its programs and compile-cache entries stay as they
    are. One instance a rung (engine/batch.UPLOAD_ROWS); each compiles
    once per row stride and chip."""
    def pad_rows(packed):
        return jnp.pad(packed, ((0, batch - packed.shape[0]), (0, 0)))

    return jax.jit(pad_rows)


LANE_NONE = np.int32(2**30)  # "no rule": sorts after every real index


def make_lane_fn(plan: RulesetPlan, services: list[str] | None = None,
                 service_groups: list[list[str]] | None = None,
                 with_rule_hits: bool = False, donate: bool = False):
    """Jitted device ACTION-LANE reduction: (tables, arrays, pf_hits,
    n_valid, pf_aux) -> ONE stacked [rows, B] i32 array: first_act_idx,
    first_act_kind, first_block_idx, the route lane(s), indices in
    ORIGINAL rule-index space, then what `lane_rows` says rides the same
    device->host copy (the cascade's counts, the attribution lane,
    Stage A's aux vector).

    This is the transfer-thin form of the verdict for the ring sidecar:
    instead of shipping the [B, R_dev] match matrix off the device
    (half a megabyte per 1k batch), the first-match reduction the
    action semantics need runs on device and only a few int32 lanes
    return.
    Host-interpreted rules merge by index afterwards (merge_lanes).

    `services` (one listener's service names, in order) adds the ROUTE
    lane: the first service order whose route pseudo-column matched
    (the reference's service-selection loop, http_listener.rs:266-270),
    or LANE_NONE. `service_groups` generalizes to G DISTINCT listener
    service orders (the reference binds a service list PER listener,
    config.rs:241-253): one route lane per group, all computed from the
    same [B, C] match matrix in one pass — the sidecar picks each row's
    lane by the ring it came from. Services whose route predicate fell
    back to host interpretation are merged by the sidecar afterwards.

    `with_rule_hits` adds the PER-RULE ATTRIBUTION lane (ISSUE 5): the
    [C] int32 per-column hit counts, batch rows folded ON DEVICE with
    padding rows masked by the traced `n_valid` argument, in whole rows
    of the same stacked array (`rule_hit_counts` reads them), so
    provenance costs no transfer of its own; columns map to original
    rule indices via plan.device_rule_indices.

    `donate=True` marks the request arrays (arg 1) as donated buffers
    (ISSUE 9; see donate_batch_buffers for the backend gating)."""
    if service_groups is not None and services is not None:
        raise ValueError("pass services or service_groups, not both")
    groups = (service_groups if service_groups is not None
              else ([services] if services else []))
    lanes = _make_lane_body(plan, groups, with_rule_hits)
    return jax.jit(lanes, donate_argnums=(1,) if donate else ())


def make_packed_lane_fn(plan: RulesetPlan,
                        services: list[str] | None = None,
                        service_groups: list[list[str]] | None = None,
                        with_rule_hits: bool = False,
                        donate: bool = False):
    """Compact-staging twin of make_lane_fn (ISSUE 15): the jitted lane
    reduction takes (tables, packed, layout, pf_hits, n_valid, pf_aux)
    with `layout` static and decodes the one-copy packed buffer on device
    via unpack_staged. The traced body is the SAME _make_lane_body
    closure make_lane_fn jits, so per-batch lanes are bit-identical
    across staging modes by construction."""
    if service_groups is not None and services is not None:
        raise ValueError("pass services or service_groups, not both")
    groups = (service_groups if service_groups is not None
              else ([services] if services else []))
    lanes = _make_lane_body(plan, groups, with_rule_hits)

    def lanes_packed(tables, packed, layout, pf_hits=None, n_valid=None,
                     pf_aux=None):
        return lanes(tables, unpack_staged(packed, layout),
                     pf_hits=pf_hits, n_valid=n_valid, pf_aux=pf_aux)

    return jax.jit(lanes_packed, static_argnums=(2,),
                   donate_argnums=(1,) if donate else ())


def _make_lane_body(plan: RulesetPlan, groups: list[list[str]],
                    with_rule_hits: bool):
    """UNJITTED lane-reduction body: (tables, arrays, pf_hits, n_valid,
    pf_aux) -> ONE stacked [rows, B] i32 array. Shared by make_lane_fn
    and its compact-staging twin make_packed_lane_fn. Under the verdict
    and route lanes come the rows `lane_rows(plan, ...)` names: the
    cascade's row counts, the attribution lane and Stage A's aux vector
    (`pf_aux`, the second output of the Stage-A program, handed over as
    the device array it is; zeros where a caller hands none), so that a
    batch's results reach the host in one copy."""
    device_rules = [r for r in plan.rules if not r.host]
    orig_idx = np.array([r.index for r in device_rules], dtype=np.int32)
    first_kind = np.array(
        [(1 if r.actions[0] == Action.BLOCK else 2) if r.actions else 0
         for r in device_rules], dtype=np.int32)
    has_act = first_kind != 0
    has_block = np.array([Action.BLOCK in r.actions for r in device_rules],
                         dtype=bool)
    col_of_rule = {r.index: j for j, r in enumerate(device_rules)}
    # Per group: [(service order, matched column), ...]
    group_routes: list[list[tuple[int, int]]] = []
    for grp in groups:
        dev_route: list[tuple[int, int]] = []
        for order, name in enumerate(grp):
            ridx = plan.route_index.get(name)
            if ridx is not None and ridx in col_of_rule:
                dev_route.append((order, col_of_rule[ridx]))
        group_routes.append(dev_route)

    # Hoisted device constants (analyze-lint recompile-const-upload):
    # uploading these ONCE here keeps every retrace of `lanes` (one per
    # batch-shape bucket) from re-staging the same host arrays.
    idx_row = jnp.asarray(orig_idx)[None, :]
    has_act_row = jnp.asarray(has_act)[None, :]
    first_kind_vec = jnp.asarray(first_kind)
    has_block_row = jnp.asarray(has_block)[None, :]
    group_consts = [
        (jnp.asarray([c for _, c in dev_route], dtype=jnp.int32),
         jnp.asarray([o for o, _ in dev_route], dtype=jnp.int32))
        if dev_route else None
        for dev_route in group_routes]
    banks = cascade_banks(plan)
    rows = lane_rows(plan, len(groups), with_rule_hits)

    @jax.named_scope("act")
    def lanes(tables, arrays, pf_hits=None, n_valid=None, pf_aux=None):
        cascade: dict = {}
        matched = _matched_cols(plan, tables, arrays, pf_hits,
                                cascade)  # [B, C]
        B = arrays["asn"].shape[0]
        zero = jnp.int32(0)

        def rule_hits():
            # Attribution fold ON DEVICE: padded batch rows are inert
            # for the lanes (their verdicts are never read) but always-
            # match columns would count them, so mask by n_valid.
            m = matched
            if n_valid is not None:
                m = m & (jnp.arange(B) < n_valid)[:, None]
            return m.sum(axis=0, dtype=jnp.int32)

        def stack(lane_list):
            # ONE stacked array = ONE device->host transfer a batch
            return rows.stack(lane_list, {
                "cascade": lambda: jnp.stack(
                    [cascade.get(k, {}).get(stat, zero)
                     for k in banks for stat in CASCADE_STATS]),
                "rule_hits": rule_hits,
                "stage_a": lambda: (jnp.zeros(rows.stage_a, jnp.int32)
                                    if pf_aux is None else pf_aux)})

        none = jnp.full((B,), LANE_NONE, dtype=jnp.int32)
        if matched.shape[1] == 0:
            return stack([none, jnp.zeros((B,), jnp.int32), none]
                         + [none] * rows.n_route)
        act_idx = jnp.where(matched & has_act_row, idx_row, LANE_NONE)
        first_act_idx = jnp.min(act_idx, axis=1)
        arg = jnp.argmin(act_idx, axis=1)
        kind = jnp.where(first_act_idx < LANE_NONE,
                         jnp.take(first_kind_vec, arg), 0)
        blk_idx = jnp.where(matched & has_block_row, idx_row, LANE_NONE)
        first_block_idx = jnp.min(blk_idx, axis=1)
        route_lanes = []
        for consts in group_consts:
            if consts is not None:
                cols, orders = consts
                rm = jnp.take(matched, cols, axis=1)  # [B, S_dev]
                route_lanes.append(
                    jnp.min(jnp.where(rm, orders[None, :], LANE_NONE),
                            axis=1).astype(jnp.int32))
            else:
                route_lanes.append(none)
        if not route_lanes:
            route_lanes.append(none)
        return stack([first_act_idx, kind, first_block_idx] + route_lanes)

    return lanes


LANE_SEGMENTS = ("cascade", "rule_hits", "stage_a")


class LaneRows(NamedTuple):
    """The row layout of the lanes program's stacked [rows, B] int32
    output: 3 verdict lanes | `n_route` route lanes | then each of
    LANE_SEGMENTS in that order, a flat run of int32 zero-padded to
    whole rows of B (none for a count of 0). The program stacks by it
    (`stack`) and the host readers below slice by it (`read`); nothing
    else knows an offset."""

    n_route: int    # route lanes (one a listener group, at least one)
    cascade: int    # CASCADE_STATS for each of cascade_banks(plan)
    rule_hits: int  # a hit count a device column; 0 without attribution
    stage_a: int    # Stage A's aux vector; 0 for a plan with no prefilter

    def stack(self, lanes: list, segments: dict):
        """The program's side: the 3 + `n_route` lanes ([B] int32 each),
        then every segment that has a count, from `segments[name]()`
        (a flat int32 of that count), zero-padded to whole rows."""
        B = lanes[0].shape[0]
        tail = []
        for name in LANE_SEGMENTS:
            ints = getattr(self, name)
            if ints:
                x = -(-ints // B)
                tail.extend(jnp.pad(segments[name](), (0, x * B - ints))
                            .reshape(x, B))
        return jnp.stack(lanes + tail)

    def read(self, full: np.ndarray, segment: str) -> np.ndarray:
        """`segment`'s int32 off the stacked output, already on the
        host at full width (the padding columns included); a view."""
        first = 3 + self.n_route
        B = full.shape[1]
        for name in LANE_SEGMENTS:
            if name == segment:
                break
            first += -(-getattr(self, name) // B)
        return full[first:].reshape(-1)[:getattr(self, segment)]


def lane_rows(plan: RulesetPlan, n_groups: int,
              with_rule_hits: bool) -> LaneRows:
    """Host-static: what the lanes program built for `n_groups` listener
    groups stacks under its lanes. Read off the plan (its cascade banks,
    device columns and Stage A), under the env the trace will see
    (PINGOO_PREFILTER / PINGOO_DFA)."""
    stage_a = _stage_a_banks(plan)
    return LaneRows(
        n_route=max(n_groups, 1),
        cascade=len(cascade_banks(plan)) * len(CASCADE_STATS),
        rule_hits=len(plan.device_rule_indices) if with_rule_hits else 0,
        # [candidate rows, banks skipped, *per-bank candidates, *skips]
        stage_a=0 if stage_a is None else 2 + 2 * len(stage_a[2]))


def cascade_counts(full: np.ndarray, rows: LaneRows) -> list:
    """[banks][CASCADE_STATS] host ints off the lanes' stacked output."""
    # pingoo: allow(sync-tolist): host numpy already, four ints a bank
    return rows.read(full, "cascade").reshape(-1, len(CASCADE_STATS)).tolist()


def rule_hit_counts(full: np.ndarray, rows: LaneRows) -> np.ndarray:
    """The [C] attribution lane off the lanes' stacked output, in device-
    column order (plan.device_rule_indices maps it to rule indices)."""
    return rows.read(full, "rule_hits")


def stage_a_counts(full: np.ndarray, rows: LaneRows) -> np.ndarray:
    """Stage A's aux vector (make_prefilter_fn's layout) off the lanes'
    stacked output."""
    return rows.read(full, "stage_a")


def host_rule_lanes(plan: RulesetPlan, batch, lists):
    """Host-interpreted rules' contribution to the action lanes
    (same triple as make_lane_fn, original-index space)."""
    host_rules = plan.host_rules
    B = batch.size
    first_act = np.full(B, LANE_NONE, dtype=np.int32)
    kind = np.zeros(B, dtype=np.int32)
    first_block = np.full(B, LANE_NONE, dtype=np.int32)
    if not host_rules:
        return first_act, kind, first_block
    from .batch import batch_to_contexts

    contexts = batch_to_contexts(batch, lists)
    for rule in host_rules:
        r_kind = ((1 if rule.actions[0] == Action.BLOCK else 2)
                  if rule.actions else 0)
        r_block = Action.BLOCK in rule.actions
        if not r_kind and not r_block:
            continue
        prog = rule.program
        for i, ctx in enumerate(contexts):
            if rule.index >= first_act[i] and (not r_block
                                               or rule.index >= first_block[i]):
                continue  # cannot improve either lane for this request
            try:
                m = execute_as_bool(prog, ctx)
            except Exception:
                m = False
            if not m:
                continue
            if r_kind and rule.index < first_act[i]:
                first_act[i] = rule.index
                kind[i] = r_kind
            if r_block and rule.index < first_block[i]:
                first_block[i] = rule.index
    return first_act, kind, first_block


def merge_lanes(dev_lanes, host_lanes) -> tuple[np.ndarray, np.ndarray]:
    """Combine device + host lane triples into the per-request action
    pair (unverified 0/1/2, verified_block bool) — reproducing the
    reference loop's first-match order across BOTH rule populations.
    `dev_lanes` is the stacked [3, B] array from make_lane_fn."""
    # pingoo: allow(sync-asarray-hot): the sidecar's one deliberate sync
    stacked = np.asarray(dev_lanes)
    d_act, d_kind, d_blk = stacked[0], stacked[1], stacked[2]
    h_act, h_kind, h_blk = host_lanes
    host_wins = h_act < d_act
    act_idx = np.where(host_wins, h_act, d_act)
    kind = np.where(host_wins, h_kind, d_kind)
    unverified = np.where(act_idx < LANE_NONE, kind, 0).astype(np.int32)
    verified_block = np.minimum(d_blk, h_blk) < LANE_NONE
    return unverified, verified_block


def evaluate_batch(plan, verdict_fn, tables, batch, lists,
                   on_device_wait=None) -> np.ndarray:
    """Full match matrix [B, R] in original rule order (device + host)."""
    dev = verdict_fn(tables, batch.arrays)  # async dispatch (jax)
    return finish_batch(plan, dev, batch, lists,
                        on_device_wait=on_device_wait)


def _host_matrix(plan, batch, lists) -> np.ndarray:
    """[B, R] bool with only the host-interpreted rules' columns filled
    — finish_batch's interpreter half, run FIRST so it overlaps the
    asynchronous device execution."""
    R = len(plan.rules)
    B = batch.size
    out = np.zeros((B, R), dtype=bool)  # the per-batch result buffer
    host_rules = plan.host_rules
    if host_rules:
        from .batch import batch_to_contexts

        contexts = batch_to_contexts(batch, lists)
        for rule in host_rules:
            prog = rule.program
            col_vals = out[:, rule.index]
            for i, ctx in enumerate(contexts):
                col_vals[i] = execute_as_bool(prog, ctx)
    return out


def _await_device(dev, on_device_wait) -> None:
    if on_device_wait is None:
        return
    import time as _time

    t0 = _time.monotonic()
    block = getattr(dev, "block_until_ready", None)
    if block is not None:
        block()
    on_device_wait((_time.monotonic() - t0) * 1e3)


def finish_batch(plan, dev, batch, lists, on_device_wait=None) -> np.ndarray:
    """Combine an in-flight device verdict with the host-interpreted
    rules. Host rules run FIRST — jax dispatch is asynchronous, so the
    interpreter work overlaps the device execution (and any transport
    latency to a remote chip) instead of serializing after it.

    `on_device_wait(ms)` (optional) receives the residual wall time
    blocked on the device result AFTER the host-rule overlap — the
    per-stage `device_compute` histogram (obs/schema.VERDICT_STAGES)."""
    out = _host_matrix(plan, batch, lists)
    _await_device(dev, on_device_wait)
    # pingoo: allow(sync-asarray-hot): the python plane's one deliberate
    dev = np.asarray(dev)  # sync point, AFTER the host-rule overlap
    for col, idx in enumerate(plan.device_rule_indices):
        out[:, idx] = dev[:, col]
    return out


def interpret_rules_row(plan: RulesetPlan, ctx) -> np.ndarray:
    """One request's full match row via the host interpreter (the parity
    oracle): always-rules match, errors fail open (pingoo/rules.rs:41-44).
    Used for overflow rows whose fields exceeded device capacity."""
    row = np.zeros(len(plan.rules), dtype=bool)
    for rule in plan.rules:
        if rule.always:
            row[rule.index] = True
            continue
        try:
            row[rule.index] = execute_as_bool(rule.program, ctx)
        except Exception:
            row[rule.index] = False
    return row


def action_lanes(plan: RulesetPlan,
                 matched: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Per-request action decision as TWO lanes, reproducing the
    reference's rules/actions loop (http_listener.rs:251-264) for both
    captcha-verification states — a single collapsed action cannot,
    because the loop *continues past* Captcha actions for verified
    clients (a matched [Captcha, Block] rule or a later Block rule must
    still block them).

      unverified [B] int32: 0 none / 1 block / 2 captcha — the first
        matched rule with actions decides via its first action (for an
        unverified client both Block and Captcha terminate the loop).
      verified_block [B] bool: whether a VERIFIED client is blocked —
        true iff any matched rule carries a Block action anywhere in its
        action list (Captcha actions are skipped for verified clients).
    """
    rule_first = np.zeros(len(plan.rules), dtype=np.int32)
    rule_has_block = np.zeros(len(plan.rules), dtype=bool)
    for r in plan.rules:
        if r.actions:
            rule_first[r.index] = 1 if r.actions[0] == Action.BLOCK else 2
            rule_has_block[r.index] = Action.BLOCK in r.actions
    acting = matched & (rule_first != 0)[None, :]  # [B, R]
    any_hit = acting.any(axis=1)
    first = np.argmax(acting, axis=1)  # first True column (0 if none)
    unverified = np.where(any_hit, rule_first[first], 0).astype(np.int32)
    verified_block = (matched & rule_has_block[None, :]).any(axis=1)
    return unverified, verified_block


def first_action(plan: RulesetPlan, matched: np.ndarray) -> np.ndarray:
    """The unverified-client lane of `action_lanes` (0 none / 1 block /
    2 captcha). Consumers that can see captcha-verified clients must use
    both lanes."""
    return action_lanes(plan, matched)[0]
