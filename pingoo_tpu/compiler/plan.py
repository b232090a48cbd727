"""Ruleset plan: device table assembly + the batched evaluator builder.

`compile_ruleset` takes the validated rules (config/schema.py RuleConfig)
plus loaded lists and produces a `RulesetPlan`:

  * every device-lowerable rule becomes a BoolIR over deduplicated leaf
    predicates (compiler/lowering.py);
  * leaves are grouped into per-field pattern tables (ops/match_ops.py),
    per-field NFA banks (compiler/nfa.py -> ops/nfa_scan.py), CIDR/int
    membership tables (ops/cidr.py);
  * rules outside the subset keep their compiled Program and are
    interpreted on host over the same truncated request view, preserving
    exact verdict parity (the fallback split in SURVEY.md §7).

The plan's `device_tables()` returns one pytree of jnp arrays; the
verdict function over (tables, batch) lives in engine/verdict.py and is
traced from the static plan structure, so the whole ruleset compiles to
one XLA program per batch shape.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field as dc_field, replace as dc_replace
from typing import Any, Optional

import numpy as np

from ..config.schema import Action, RuleConfig
from ..expr import Program
from ..expr.values import Ip
from . import repat
from .lowering import (
    DEFAULT_FIELD_SPECS,
    IntListPred,
    IpListPred,
    IpPred,
    LeafRegistry,
    Lowerer,
    LowerError,
    NBin,
    NfaPred,
    NLen,
    NNeg,
    NumCmp,
    StrListPred,
    StrPred,
    nfa_leaf_patterns,
)
from .nfa import build_bank
from ..ops.cidr import build_cidr_table, build_int_set, build_v4_buckets, ip_to_words
from ..ops.match_ops import build_pattern_table, build_suffix_table
from ..ops.nfa_scan import bank_to_tables
from ..ops.window_match import build_window_table


# -- NFA scan strategy selection ---------------------------------------------
#
# The roofline (docs/ROOFLINE.md) showed the verdict kernel bound by the
# serial NFA scan chain: per-LOOP-ITERATION dispatch/dependency latency,
# not per-byte work. The levers that cut iterations (pair stepping, the
# within-device halo split) and the fused Pallas kernel that cuts
# per-iteration cost used to hang off env knobs (PINGOO_NFA_LOOKUP /
# PINGOO_HALO_SPLIT); they are now selected PER BANK at plan time, the
# choice travels with the plan through the ruleset artifact cache
# (compiler/cache.py), and bench.py's micro-autotune hook can re-select
# from measured per-iteration costs (`reselect_scan_strategies`).

# Relative cost of ONE scan-loop iteration per strategy kind. The
# defaults are placeholders that encode the dispatch-bound ordering the
# roofline modeled (a pair iteration slightly dearer than a single
# gather but half as many of them); bench.py --autotune replaces them
# with measured values on a live backend. The fused Pallas kernel
# (ops/pallas_scan.py) has NO default: it is a candidate only where the
# cost dict carries a value measured for it, so plan-time selection
# never routes a bank through a kernel nobody timed on this backend.
DEFAULT_STEP_COSTS = {
    "scan": 1.0,        # lax.scan, one [256/C, W] gather per byte
    "pair": 1.3,        # lax.scan, one [C^2, 2W] gather per TWO bytes
    # Bitsplit DFA (ISSUE 8): one [S, C]-row gather per byte, ~4
    # lane-ops/byte, no dependent matmul and no opt-propagation passes.
    "dfa": 0.15,
}

DFA_KIND = "dfa"

# -- Compact staging (ISSUE 15, docs/EXECUTOR.md "Compact staging") ----------
#
# The dispatch wall is bytes-proportional host staging (BENCH_pipeline:
# ~39.6 ms/batch at B=2048 is the staging copy, not launches). Most
# rulesets only inspect a small prefix of each string field, so the
# compile pass below derives, per field, the maximum byte position any
# compiled scanner can depend on, and `PINGOO_STAGING=compact` stages
# only that capped prefix. The cap is quantized to this pow2 rung
# ladder so hot-swapping between tenants whose caps
# land on the same rung reuses the XLA compile.
STAGING_RUNGS = (16, 32, 64, 128, 256, 512, 1024, 2048)


def quantize_stage_cap(depth: int, spec: int) -> int:
    """Smallest rung >= depth, clamped to the field's full spec (a
    2-byte country code never pads out to rung 16)."""
    for rung in STAGING_RUNGS:
        if rung >= depth:
            return min(rung, spec)
    return spec


def _kind_cost(c: dict, kind: str, default: float = 1.0) -> float:
    """Forward-compatible cost lookup: a measured/partial cost dict (or
    a cached plan from a build that didn't know `kind` yet) falls back
    to DEFAULT_STEP_COSTS, then to `default`, instead of KeyError-ing."""
    v = c.get(kind)
    if v is None:
        v = DEFAULT_STEP_COSTS.get(kind, default)
    return float(v)


@dataclass(frozen=True)
class ScanStrategy:
    """One bank's selected scan execution strategy (static plan metadata).

    kind    — "scan" (lax.scan) or "pallas" (fused kernel,
              ops/pallas_scan.py)
    pair    — advance two bytes per loop iteration (the pair lookup for
              lax.scan, 2x-unrolled stepping inside the Pallas kernel)
    halo_k  — maximum within-device halo split factor to ATTEMPT at
              trace time (halo_split_k re-checks eligibility against the
              actual bucketed length; 1 disables)
    source  — "default" (cost model), "measured" (bench autotune),
              "env" (PINGOO_SCAN_STRATEGY override)
    cost    — modeled relative per-iteration cost at selection time
    """

    kind: str = "scan"
    pair: bool = False
    halo_k: int = 1
    source: str = "default"
    cost: float = 0.0


@dataclass(frozen=True)
class NfaScanPlan:
    """Plan-time scan decisions for one field's NFA bank (static; rides
    the plan pickle into the artifact cache).

    When the halo partition is active, `split` names the two np_tables
    sub-bank keys ("<key>@short" halo-splittable, "<key>@rest"
    residual) and `slot_perm[p]` maps logical pattern slot p to its
    column in concat(short_hits, rest_hits); the whole-bank table stays
    at `key` for the parallel (mesh/ring) paths."""

    key: str
    strategy: ScanStrategy
    split: tuple[str, str] | None = None
    short_strategy: ScanStrategy | None = None
    rest_strategy: ScanStrategy | None = None
    slot_perm: tuple[int, ...] | None = None
    extended: bool = False  # footprint-extension rewrote the main bank
    # Bitsplit-DFA lowering (ISSUE 8): when the bank subset-constructed
    # within the state budget, `dfa_key` names its DfaTables in
    # np_tables and `dfa_strategy` carries the modeled cost. `strategy`
    # stays the best NON-DFA kind (the recheck/fallback path needs it);
    # `dfa_auto` records whether the cost model prefers the DFA —
    # PINGOO_DFA=auto honors it, =force overrides it per bank.
    dfa_key: str | None = None
    dfa_strategy: ScanStrategy | None = None
    dfa_auto: bool = False


def select_scan_strategy(tables, costs: dict | None = None,
                         source: str = "default") -> ScanStrategy:
    """Pick the cheapest (kind, pair) for one bank under a per-iteration
    cost model; iteration counts scale the pair variants by 1/2, so the
    ranking is independent of the (trace-time) field length. The fused
    Pallas kinds compete only with a measured cost in `costs`. halo_k
    is eligibility metadata: halo re-checks profitability at trace
    time."""
    c = dict(costs or {})
    cands = [("scan", False, _kind_cost(c, "scan")),
             ("scan", True, _kind_cost(c, "pair") / 2)]
    if c.get("pallas") is not None:
        cands.append(("pallas", False, float(c["pallas"])))
    if c.get("pallas_pair") is not None:
        cands.append(("pallas", True, float(c["pallas_pair"]) / 2))
    kind, pair, cost = min(cands, key=lambda x: x[2])
    halo_k = 8 if tables.halo_ok else 1
    return ScanStrategy(kind=kind, pair=pair, halo_k=halo_k,
                        source=source, cost=cost)


def select_dfa_strategy(costs: dict | None = None,
                        source: str = "default") -> ScanStrategy:
    """Strategy record for a lowered bank's bitsplit-DFA path. Same
    per-byte normalization as select_scan_strategy's candidates (one
    loop iteration consumes one byte, no pair variant)."""
    return ScanStrategy(kind=DFA_KIND, pair=False, halo_k=1, source=source,
                        cost=_kind_cost(costs or {}, DFA_KIND))


def strategy_steps(tables, L: int, strat: ScanStrategy) -> int:
    """Dependent-step count of `strat` on this bank at bucketed length L
    (the roofline convention: loop iterations x opt-propagation passes).
    Accounts for a trace-time halo split when the strategy would take
    it."""
    from ..ops.nfa_scan import halo_split_k

    if strat.kind == DFA_KIND:
        # One [S, C]-row gather per byte: no opt-propagation passes, no
        # pair variant — the dependent chain is exactly L steps.
        return L
    passes = 1 + tables.extra_passes
    iters = (L + 1) // 2 if strat.pair else L
    if strat.halo_k > 1:
        k = halo_split_k(tables, L, max_k=strat.halo_k)
        if k > 1:
            halo_iters = L // k + int(tables.max_footprint)
            if halo_iters < iters:
                iters = halo_iters
    return iters * passes


def _halo_fp_budget() -> int:
    return int(os.environ.get("PINGOO_HALO_FP_BUDGET", "16"))


def _split_enabled() -> bool:
    return os.environ.get("PINGOO_NFA_SPLIT", "0") != "0"


def _dfa_lower_enabled() -> bool:
    """PINGOO_DFA_LOWER=0 is the compile-time kill switch: no DFA tables
    are built at all (PINGOO_DFA=off merely skips them at trace time)."""
    return os.environ.get("PINGOO_DFA_LOWER", "1") != "0"


def split_config_token() -> str:
    """The plan-shaping env knobs, hashed into the artifact-cache
    fingerprint: plans built under different split settings have
    different np_tables layouts."""
    from .nfa import _dfa_merge_depths, _dfa_state_budget

    dfa = (f"dfa={int(_dfa_lower_enabled())}"
           f":s={_dfa_state_budget(None)}"
           f":m={','.join(str(d) for d in _dfa_merge_depths(None))}")
    return f"nfa_split={int(_split_enabled())}:fp={_halo_fp_budget()}:{dfa}"


def _halo_partition(patterns, field_len: int):
    """Footprint-extension pass + partition for one field's patterns.

    Each pattern is made halo-compatible when possible: rep-free already,
    or rewritten by repat.extend_footprint (exact over the field's
    device byte cap). Patterns whose bounded footprint fits the halo
    budget form the `short` (halo-splittable) set; the rest keep their
    original form. Returns (short_idx, rest_idx, short_pats, rest_pats)
    or None when the partition is degenerate (no residual bank needed —
    caller handles the all-short case via whole-bank extension)."""
    from .nfa import MAX_SCAN_BITS, pattern_footprint, scan_bits_needed

    budget = _halo_fp_budget()
    short_idx, rest_idx = [], []
    short_pats, rest_pats = [], []
    for i, lp in enumerate(patterns):
        cand = lp
        if repat.has_unbounded_rep(lp):
            cand = repat.extend_footprint(lp, field_len)
        ok = cand is not None and not repat.has_unbounded_rep(cand)
        if ok:
            try:
                ok = (pattern_footprint(cand) <= budget
                      and scan_bits_needed(cand) <= MAX_SCAN_BITS)
            except repat.Unsupported:
                ok = False
        if ok:
            short_idx.append(i)
            short_pats.append(cand)
        else:
            rest_idx.append(i)
            rest_pats.append(lp)
    if not short_idx or not rest_idx:
        return None
    return short_idx, rest_idx, short_pats, rest_pats


# -- literal-prefilter cascade (Stage A metadata) -----------------------------
#
# ISSUE 4: each contains/regex pattern gets a *necessary literal factor*
# at compile time (compiler/repat.necessary_factor) — a byte-class run
# that must appear in the field for the pattern to match. Factors are
# deduplicated per field and packed into one shift-AND bank
# (ops/prefilter.py) scanned ONCE per batch; engine/verdict.py consults
# the per-bank candidate masks to skip or compact the exact NFA scans.
# The prefilter may only PRUNE, never decide: final verdicts are
# bit-identical across PINGOO_PREFILTER=off|banks|compact
# (tests/test_prefilter.py asserts this structurally).

PF_ALWAYS = -1  # slot has no extractable factor: its bank always scans
PF_NEVER = -2  # slot never matches: contributes nothing to candidates

PREFILTER_MODES = ("off", "banks", "compact")


@dataclass
class FieldFactors:
    """One byte field's deduplicated factor inventory."""

    field: str
    table_key: str  # np_tables key of the PrefilterTables ("pf_<field>")
    num_factors: int
    # The factor byte-class tuples themselves (small; kept for the
    # differential property tests and plan introspection).
    factors: tuple[tuple[frozenset, ...], ...]


@dataclass
class PrefilterPlan:
    """Static Stage-A metadata riding the RulesetPlan into the artifact
    cache (FORMAT_VERSION bump in compiler/cache.py)."""

    fields: dict[str, FieldFactors] = dc_field(default_factory=dict)
    bank_field: dict[str, str] = dc_field(default_factory=dict)
    # np_tables bank key -> bool [F] mask over its field's factors.
    bank_masks: dict[str, Any] = dc_field(default_factory=dict)
    # bank key -> True when EVERY slot is factor-gated (or never-match):
    # only then may the whole bank be skipped/compacted.
    bank_gated: dict[str, bool] = dc_field(default_factory=dict)
    # bank key -> per-slot factor index (PF_ALWAYS / PF_NEVER sentinels).
    slot_codes: dict[str, tuple] = dc_field(default_factory=dict)
    # Strategy used when the PINGOO_PREFILTER env override is unset;
    # bench.py's autotune records the measured best mode here and
    # persists it through compiler.cache.update_cached_plan.
    default_mode: str = "banks"


def _plan_field_prefilter(plan: "RulesetPlan", field: str,
                          bank_slots: dict[str, list],
                          nfa_key: Optional[str] = None,
                          split_idx=None) -> None:
    """Extract + pack one field's factors; register per-bank masks.

    `bank_slots` maps each of the field's scan banks (the NFA bank AND
    the MXU window bank — both are gated by the cascade) to its per-slot
    source LinearPatterns. The factor table is shared per FIELD (one
    Stage-A scan feeds every bank); `split_idx` additionally registers
    the NFA halo-partition @short/@rest sub-bank subsets. Fields with no
    extractable factor get no table."""
    from ..ops.prefilter import (build_prefilter_bank,
                                 bank_to_prefilter_tables)

    pf = plan.prefilter
    if pf is None or not bank_slots:
        return
    factors: list = []
    index: dict = {}

    def code_of(lp) -> int:
        if lp.never_match:
            return PF_NEVER
        fac = repat.necessary_factor(lp)
        if fac is None:
            return PF_ALWAYS
        idx = index.get(fac)
        if idx is None:
            idx = len(factors)
            index[fac] = idx
            factors.append(fac)
        return idx

    bank_codes = {bkey: [code_of(lp) for lp in pats]
                  for bkey, pats in bank_slots.items()}
    if not factors:
        return
    bank = build_prefilter_bank(factors)
    table_key = f"pf_{field}"
    plan.np_tables[table_key] = bank_to_prefilter_tables(bank)
    pf.fields[field] = FieldFactors(
        field=field, table_key=table_key, num_factors=len(factors),
        factors=tuple(factors))

    def register(bank_key: str, codes) -> None:
        codes = tuple(codes)
        mask = np.zeros(len(factors), dtype=bool)
        for c in codes:
            if c >= 0:
                mask[c] = True
        pf.bank_field[bank_key] = field
        pf.bank_masks[bank_key] = mask
        pf.bank_gated[bank_key] = all(c != PF_ALWAYS for c in codes)
        pf.slot_codes[bank_key] = codes

    for bkey, codes in bank_codes.items():
        register(bkey, codes)
    if nfa_key is not None and split_idx is not None:
        nfa_codes = bank_codes[nfa_key]
        register(f"{nfa_key}@short",
                 [nfa_codes[i] for i in split_idx[0]])
        register(f"{nfa_key}@rest",
                 [nfa_codes[i] for i in split_idx[1]])


def reselect_scan_strategies(plan: "RulesetPlan",
                             costs: dict | None = None,
                             source: str = "measured") -> None:
    """Re-run strategy selection (e.g. with measured per-iteration costs
    from bench.py's autotune hook) and update the plan in place. Callers
    persist via compiler.cache.update_cached_plan."""
    for key, entry in list(plan.scan_plans.items()):
        strategy = select_scan_strategy(
            plan.np_tables[key], costs, source=source)
        kwargs = {"strategy": strategy}
        if entry.split:
            kwargs["short_strategy"] = select_scan_strategy(
                plan.np_tables[entry.split[0]], costs, source=source)
            kwargs["rest_strategy"] = select_scan_strategy(
                plan.np_tables[entry.split[1]], costs, source=source)
        if entry.dfa_key is not None:
            # Re-rank the DFA against the measured non-DFA best; the
            # cost dict may predate the "dfa" kind (_kind_cost falls
            # back to the model default instead of KeyError-ing).
            dfa_strategy = select_dfa_strategy(costs, source=source)
            kwargs["dfa_strategy"] = dfa_strategy
            kwargs["dfa_auto"] = dfa_strategy.cost < strategy.cost
        plan.scan_plans[key] = dc_replace(entry, **kwargs)


@dataclass
class PlannedRule:
    name: str
    actions: tuple[Action, ...]
    index: int  # original rule order (first-match semantics on host)
    ir: Optional[object]  # BoolIR when device-lowered
    program: Optional[Program]  # for host fallback / no-expression rules
    host: bool  # True -> interpret on host
    always: bool = False  # rule with no expression matches everything


@dataclass
class LeafBinding:
    """Where a leaf's [B] result comes from at eval time."""

    kind: str
    # kind-specific static metadata:
    field: str = ""
    group: str = ""  # 'eq' | 'prefix' | 'suffix'
    col: int = -1
    span: tuple[int, int] = (0, 0)  # NFA slot range / eq-col range
    table_key: str = ""  # key into plan tables dict
    pred: Any = None  # NumCmp / IntListPred probe IR


@dataclass
class RulesetPlan:
    field_specs: dict[str, int]
    rules: list[PlannedRule]
    leaves: list[object]
    bindings: dict[int, LeafBinding]
    # static (host-side numpy) table constructors' outputs:
    np_tables: dict[str, Any] = dc_field(default_factory=dict)
    stats: dict[str, int] = dc_field(default_factory=dict)
    # service name -> pseudo-rule column for its route predicate
    route_index: dict[str, int] = dc_field(default_factory=dict)
    # per-NFA-bank scan strategy decisions (static; cached with the plan)
    scan_plans: dict[str, NfaScanPlan] = dc_field(default_factory=dict)
    # Stage-A literal-prefilter metadata (None for factor-less rulesets)
    prefilter: Optional[PrefilterPlan] = None
    # Bitsplit-DFA mode when the PINGOO_DFA env override is unset
    # (off|auto|force); bench.py's --dfa arm records the measured best
    # and persists it through compiler.cache.update_cached_plan.
    dfa_default_mode: str = "auto"
    # Lowered MXU window banks (ISSUE 8): "win_<field>" ->
    # "dfa_win_<field>" in np_tables. The window conv is serial-free on
    # the MXU, so the DFA replaces it only where per-row work dominates
    # (CPU diagnostic backend under auto, any backend under force) —
    # engine/verdict._dfa_win_active.
    win_dfa: dict[str, str] = dc_field(default_factory=dict)
    # Compact staging (ISSUE 15): per-field raw dependent byte depth
    # and the quantized staged cap PINGOO_STAGING=compact copies.
    # Empty on plans cached before FORMAT_VERSION 11 — consumers fall
    # back to field_specs (full staging) via getattr.
    staging_required: dict[str, int] = dc_field(default_factory=dict)
    staging_caps: dict[str, int] = dc_field(default_factory=dict)

    def device_tables(self) -> dict[str, Any]:
        """Materialize all tables as device arrays (a pytree)."""
        import jax.numpy as jnp

        out: dict[str, Any] = {}
        for key, val in self.np_tables.items():
            if isinstance(val, np.ndarray):
                out[key] = jnp.asarray(val)
            elif isinstance(val, dict):
                out[key] = {k: jnp.asarray(v) for k, v in val.items()}
            else:
                out[key] = val  # already a NamedTuple pytree of jnp arrays
        return out

    @property
    def device_rule_indices(self) -> list[int]:
        return [r.index for r in self.rules if not r.host]

    @property
    def host_rules(self) -> list[PlannedRule]:
        return [r for r in self.rules if r.host]

    @property
    def rule_names(self) -> tuple[str, ...]:
        """Rule names in ORIGINAL index order (route pseudo-rules
        included) — the label space of the per-rule attribution lanes
        and the flight recorder (obs/provenance.py, ISSUE 5)."""
        return tuple(r.name for r in self.rules)

    def provenance_labels(self) -> dict:
        """Static label inventory the provenance layer exports against:
        rule names, the device-column -> original-index mapping for the
        on-device attribution fold, and the cascade-gated bank keys for
        banks-skipped attribution. Everything here is plan-static, so
        label cardinality is fixed at compile time."""
        pf = self.prefilter
        gated = tuple(k for k, g in pf.bank_gated.items() if g) \
            if pf is not None else ()
        return {
            "rules": self.rule_names,
            "device_cols": tuple(self.device_rule_indices),
            "gated_banks": gated,
        }


def compile_ruleset(
    rules: list[RuleConfig],
    lists: dict[str, list],
    field_specs: Optional[dict[str, int]] = None,
    routes: Optional[list[tuple[str, Optional[Program]]]] = None,
) -> RulesetPlan:
    """Compile WAF rules (+ optional service `route:` predicates) into
    one plan. Routes become extra actionless pseudo-rule columns of the
    SAME batched verdict — route semantics are exactly rule semantics
    (exact-true match, error -> no-match, no expression -> match-all;
    reference services/mod.rs match_request + http_proxy_service.rs:
    84-95), so the per-request route interpretation on the listener hot
    path collapses into the batch. `plan.route_index[name]` gives each
    service's column in the match matrix."""
    field_specs = dict(field_specs or DEFAULT_FIELD_SPECS)
    registry = LeafRegistry()
    lowerer = Lowerer(lists, registry, field_specs)

    def lower_one(name: str, actions, idx: int,
                  program: Optional[Program]) -> PlannedRule:
        if program is None:
            # No expression -> always matches (pingoo/rules.rs:48-50).
            return PlannedRule(name=name, actions=actions, index=idx,
                               ir=None, program=None, host=False, always=True)
        mark = registry.mark()
        try:
            ir = lowerer.lower_rule(program.root)
            return PlannedRule(name=name, actions=actions, index=idx,
                               ir=ir, program=program, host=False)
        except LowerError:
            registry.rollback(mark)  # don't ship a host rule's partial leaves
            return PlannedRule(name=name, actions=actions, index=idx,
                               ir=None, program=program, host=True)

    planned: list[PlannedRule] = []
    for idx, rule in enumerate(rules):
        planned.append(lower_one(rule.name, rule.actions, idx,
                                 rule.expression))
    route_index: dict[str, int] = {}
    for name, program in routes or []:
        idx = len(planned)
        route_index[name] = idx
        planned.append(lower_one(f"route:{name}", (), idx, program))

    plan = RulesetPlan(
        field_specs=field_specs,
        rules=planned,
        leaves=registry.leaves,
        bindings={},
        route_index=route_index,
        prefilter=PrefilterPlan(),
    )
    _assemble_tables(plan)
    if plan.prefilter is not None and not plan.prefilter.fields:
        plan.prefilter = None  # nothing extractable: Stage A is a no-op
    # Stats count REAL rules only — route pseudo-columns get their own
    # counters so bench/metrics numbers don't inflate with services.
    real = planned[: len(rules)]
    pseudo = planned[len(rules):]
    pf = plan.prefilter
    plan.stats = {
        "rules": len(real),
        "device_rules": sum(1 for r in real if not r.host),
        "host_rules": sum(1 for r in real if r.host),
        "routes": len(pseudo),
        "host_routes": sum(1 for r in pseudo if r.host),
        "leaves": len(registry.leaves),
        "prefilter_factors": (sum(f.num_factors for f in pf.fields.values())
                              if pf else 0),
        "prefilter_gated_banks": (sum(1 for g in pf.bank_gated.values() if g)
                                  if pf else 0),
        "dfa_banks": sum(
            1 for e in plan.scan_plans.values() if e.dfa_key)
        + len(plan.win_dfa),
        "dfa_states_total": sum(
            plan.np_tables[e.dfa_key].num_states
            for e in plan.scan_plans.values() if e.dfa_key)
        + sum(plan.np_tables[k].num_states
              for k in plan.win_dfa.values()),
    }
    derive_staging_caps(plan)
    return plan


def _num_ir_len_fields(ir) -> set[str]:
    """Fields whose length() an arithmetic IR reads (NLen nodes)."""
    out: set[str] = set()
    stack = [ir]
    while stack:
        node = stack.pop()
        if isinstance(node, NLen):
            out.add(node.field)
        elif isinstance(node, NBin):
            stack.append(node.left)
            stack.append(node.right)
        elif isinstance(node, NNeg):
            stack.append(node.x)
    return out


def derive_staging_caps(plan: RulesetPlan) -> None:
    """Per-field maximum dependent byte depth across every compiled
    scanner, -> plan.staging_required (raw) and plan.staging_caps
    (quantized to STAGING_RUNGS, clamped to the spec).

    Soundness is structural — the staged view must only ever PRUNE
    bytes no scanner reads, never change a verdict:

      * eq needs |pattern|+1 bytes: the device compares exact `lens`
        (full true values ride regardless of the staged width), and the
        +1 guard keeps interpreter contexts built from staged bytes
        exact too — a string truncated at cap >= |pat|+1 still has
        length > |pat|, so equality stays False either way.
      * prefix needs exactly |pattern| bytes.
      * suffix anchors at the END of the true string -> full spec.
      * contains/regex (NFA, bitsplit-DFA, window/MXU banks) and the
        Stage-A prefilter scan the whole field -> full spec.
      * length() inside device arithmetic (NLen) pins the field so the
        interpreter fallback/parity contexts — whose length() comes
        from the staged bytes — agree with the device's exact lens.
      * host rules and host route predicates re-evaluate on contexts
        built from the staged bytes, so every string field their AST
        references is pinned to full spec.

    Rows whose TRUE length exceeds a below-spec cap are flagged
    overflow by the encoder and re-interpreted from the untruncated
    source (the existing over-long backstop), which is what makes the
    caps verdict-preserving without per-rule reasoning at eval time."""
    specs = plan.field_specs
    required: dict[str, int] = {f: 0 for f in specs}

    def need(field: str, depth: int) -> None:
        if field in required:
            required[field] = max(required[field], int(depth))

    def pin(field: str) -> None:
        if field in required:
            required[field] = int(specs[field])

    for leaf in plan.leaves:
        if isinstance(leaf, StrPred):
            if leaf.kind == "eq":
                need(leaf.field, len(leaf.pattern) + 1)
            elif leaf.kind == "prefix":
                need(leaf.field, len(leaf.pattern))
            else:  # suffix: anchored at the true end of the string
                pin(leaf.field)
        elif isinstance(leaf, StrListPred):
            need(leaf.field, max(
                (len(e) for e in leaf.entries), default=0) + 1)
        elif isinstance(leaf, NfaPred):
            pin(leaf.field)
        elif isinstance(leaf, NumCmp):
            for f in _num_ir_len_fields(leaf.left):
                pin(f)
            for f in _num_ir_len_fields(leaf.right):
                pin(f)
        elif isinstance(leaf, IntListPred):
            for f in _num_ir_len_fields(leaf.probe):
                pin(f)
    from ..expr import ast as _east

    for rule in plan.rules:
        if rule.host and rule.program is not None:
            for node in _east.walk(rule.program.root):
                if not isinstance(node, _east.Member) \
                        or not isinstance(node.obj, _east.Ident):
                    continue
                if node.obj.name == "http_request" \
                        and node.attr in specs:
                    pin(node.attr)
                elif node.obj.name == "client" \
                        and node.attr == "country":
                    pin("country")
    plan.staging_required = dict(required)
    plan.staging_caps = {
        f: quantize_stage_cap(required[f], spec)
        for f, spec in specs.items()
    }


def _assemble_tables(plan: RulesetPlan) -> None:
    # Group string predicates per (field, kind).
    str_groups: dict[tuple[str, str], list[tuple[int, StrPred]]] = {}
    nfa_groups: dict[str, list[tuple[int, NfaPred]]] = {}
    ip_preds: list[tuple[int, IpPred]] = []

    for leaf_id, leaf in enumerate(plan.leaves):
        if isinstance(leaf, StrPred):
            str_groups.setdefault((leaf.field, leaf.kind), []).append(
                (leaf_id, leaf))
        elif isinstance(leaf, NfaPred):
            nfa_groups.setdefault(leaf.field, []).append((leaf_id, leaf))
        elif isinstance(leaf, IpPred):
            ip_preds.append((leaf_id, leaf))
        elif isinstance(leaf, StrListPred):
            key = f"strlist_{leaf_id}"
            plan.np_tables[key] = build_pattern_table(
                [(e, False) for e in leaf.entries] or [(b"\x00nevermatch", False)]
            )
            plan.bindings[leaf_id] = LeafBinding(
                kind="str_list", field=leaf.field, table_key=key,
                span=(0, len(leaf.entries)))
        elif isinstance(leaf, IpListPred):
            entries = [Ip(e) for e in leaf.entries]
            key = f"iplist_{leaf_id}"
            if len(entries) <= 2048:
                plan.np_tables[key] = build_cidr_table(entries)
                plan.bindings[leaf_id] = LeafBinding(
                    kind="ip_list_small", table_key=key)
            else:
                plan.np_tables[key] = build_v4_buckets(entries)
                plan.bindings[leaf_id] = LeafBinding(
                    kind="ip_list_large", table_key=key)
        elif isinstance(leaf, IntListPred):
            key = f"intlist_{leaf_id}"
            plan.np_tables[key] = build_int_set(list(leaf.values))
            plan.bindings[leaf_id] = LeafBinding(
                kind="int_list", table_key=key, pred=leaf.probe)
        elif isinstance(leaf, NumCmp):
            plan.bindings[leaf_id] = LeafBinding(kind="num_cmp", pred=leaf)
        else:
            raise AssertionError(f"unbound leaf {leaf!r}")

    for (field, kind), entries in str_groups.items():
        key = f"{kind}_{field}"
        pats = [(leaf.pattern, leaf.ci) for _, leaf in entries]
        if kind == "suffix":
            plan.np_tables[key] = build_suffix_table(pats)
        else:
            plan.np_tables[key] = build_pattern_table(pats)
        for col, (leaf_id, _) in enumerate(entries):
            plan.bindings[leaf_id] = LeafBinding(
                kind="str", field=field, group=kind, col=col, table_key=key)

    for field, entries in nfa_groups.items():
        patterns = []
        win_patterns: list = []
        win_srcs: list = []  # window slots' source LinearPatterns
        for leaf_id, leaf in entries:
            alts = nfa_leaf_patterns(leaf)
            # Fixed-shape literal-ish leaves skip the serial NFA scan
            # entirely: every alternative must lower to a window pattern
            # (ops/window_match.py — one MXU conv pair per field instead
            # of one VPU step per byte).
            live = [lp for lp in alts if not lp.never_match]
            wins = [repat.to_window(lp) for lp in live]
            if wins and all(w is not None for w in wins):
                start = len(win_patterns)
                win_patterns.extend(wins)
                win_srcs.extend(live)
                plan.bindings[leaf_id] = LeafBinding(
                    kind="window", field=field,
                    span=(start, len(win_patterns)),
                    table_key=f"win_{field}")
                continue
            start = len(patterns)
            patterns.extend(alts)
            plan.bindings[leaf_id] = LeafBinding(
                kind="nfa", field=field, span=(start, len(patterns)),
                table_key=f"nfa_{field}")
        split_idx = None
        if patterns:
            split_idx = _plan_nfa_bank(plan, field, patterns)
        if win_patterns:
            plan.np_tables[f"win_{field}"] = build_window_table(win_patterns)
            # Bitsplit-DFA lowering of the WINDOW bank (ISSUE 8): the
            # window slots' source LinearPatterns are fixed-shape
            # literal-ish, so the subset construction is small (an
            # Aho-Corasick-style multi-literal DFA) and almost always
            # exact. The conv table stays — it is the serial-free MXU
            # path and the recheck/fallback — the DFA replaces it only
            # where row work dominates (engine/verdict._dfa_win_active).
            if _dfa_lower_enabled():
                from .nfa import lower_bank_to_dfa
                from ..ops.bitsplit_dfa import dfa_to_tables

                win_dfa_bank = lower_bank_to_dfa(win_srcs)
                if win_dfa_bank is not None:
                    plan.np_tables[f"dfa_win_{field}"] = \
                        dfa_to_tables(win_dfa_bank)
                    plan.win_dfa[f"win_{field}"] = f"dfa_win_{field}"
        # Stage-A factor pass covers BOTH of the field's scan banks (the
        # serial NFA bank and the MXU window bank) from one shared
        # factor table; factors come from the ORIGINAL patterns (any
        # footprint-extended rewrites are match-equivalent over the
        # field cap, so necessity transfers unchanged).
        bank_slots: dict[str, list] = {}
        if patterns:
            bank_slots[f"nfa_{field}"] = patterns
        if win_patterns:
            bank_slots[f"win_{field}"] = win_srcs
        _plan_field_prefilter(
            plan, field, bank_slots,
            nfa_key=f"nfa_{field}" if patterns else None,
            split_idx=split_idx)

    if ip_preds:
        nets = np.zeros((len(ip_preds), 4), dtype=np.uint32)
        masks = np.zeros((len(ip_preds), 4), dtype=np.uint32)
        from ..ops.cidr import _prefix_masks

        for col, (leaf_id, leaf) in enumerate(ip_preds):
            m = _prefix_masks(leaf.prefix)
            nets[col] = np.array(leaf.words, dtype=np.uint32) & m
            masks[col] = m
            plan.bindings[leaf_id] = LeafBinding(kind="ip_one", col=col,
                                                 table_key="ip_preds")
        plan.np_tables["ip_preds"] = {"nets": nets, "masks": masks}


def _plan_nfa_bank(plan: RulesetPlan, field: str,
                   patterns: list):
    """Build one field's NFA tables + scan plan; returns the halo
    partition's (short_idx, rest_idx) slot subsets (None when the bank
    is not partitioned) for the prefilter sub-bank registration.

    Footprint-extension / halo pipeline (docs/ROOFLINE.md lever 1):

      * if EVERY pattern is halo-compatible after repat.extend_footprint
        (exact over the field's device byte cap), the main bank itself is
        rebuilt bounded — whole-bank halo_ok, no extra tables;
      * else, with PINGOO_NFA_SPLIT=1, the bank is PARTITIONED: patterns
        whose bounded footprint fits the halo budget form a
        halo-splittable `@short` sub-bank, the rest (wide spans,
        unboundable reps) a `@rest` residual sub-bank stepping by pairs —
        the whole-bank table stays for the mesh/ring parallel paths;
      * the scan strategy (lax.scan vs fused Pallas, single vs pair
        step) is selected per bank by the cost model and recorded in
        plan.scan_plans, so it persists through the artifact cache.
    """
    from .nfa import MAX_SCAN_BITS, pattern_footprint, scan_bits_needed

    key = f"nfa_{field}"
    field_len = plan.field_specs.get(field, 2048)
    bank = build_bank(patterns)
    tables = bank_to_tables(bank)
    extended = False
    if not tables.halo_ok:
        # Whole-bank footprint extension: only worth the extra width if
        # every rep pattern bounds within the device caps.
        cands = []
        for lp in patterns:
            cand = repat.extend_footprint(lp, field_len) \
                if repat.has_unbounded_rep(lp) else lp
            if cand is None or repat.has_unbounded_rep(cand):
                cands = None
                break
            try:
                if scan_bits_needed(cand) > MAX_SCAN_BITS:
                    cands = None
                    break
            except repat.Unsupported:
                cands = None
                break
            cands.append(cand)
        if cands is not None:
            ext_tables = bank_to_tables(build_bank(cands))
            if ext_tables.halo_ok:
                tables = ext_tables
                extended = True
    plan.np_tables[key] = tables

    # Bitsplit-DFA lowering (ISSUE 8): subset-construct the WHOLE bank
    # when it fits the state budget (exact first, then the approximate
    # merge ladder; compiler/nfa.lower_bank_to_dfa). The ORIGINAL
    # patterns are lowered — a footprint-extension rewrite above is
    # match-equivalent over the field's device byte cap, so per-slot
    # semantics line up. The @short/@rest halo partition keeps the NFA
    # path; the DFA dispatch in engine/verdict.py only takes the
    # non-split whole-bank branch.
    dfa_key = None
    dfa_strategy = None
    dfa_auto = False
    if _dfa_lower_enabled():
        from .nfa import lower_bank_to_dfa
        from ..ops.bitsplit_dfa import dfa_to_tables

        dfa_bank = lower_bank_to_dfa(patterns)
        if dfa_bank is not None:
            dfa_key = f"dfa_{field}"
            plan.np_tables[dfa_key] = dfa_to_tables(dfa_bank)
            dfa_strategy = select_dfa_strategy()

    split = None
    short_strategy = rest_strategy = None
    slot_perm = None
    split_idx = None
    if _split_enabled() and not tables.halo_ok:
        parts = _halo_partition(patterns, field_len)
        if parts is not None:
            short_idx, rest_idx, short_pats, rest_pats = parts
            split_idx = (short_idx, rest_idx)
            short_tables = bank_to_tables(build_bank(short_pats))
            rest_tables = bank_to_tables(build_bank(rest_pats))
            plan.np_tables[f"{key}@short"] = short_tables
            plan.np_tables[f"{key}@rest"] = rest_tables
            order = short_idx + rest_idx
            perm = [0] * len(order)
            for col, p in enumerate(order):
                perm[p] = col
            slot_perm = tuple(perm)
            split = (f"{key}@short", f"{key}@rest")
            short_strategy = select_scan_strategy(short_tables)
            rest_strategy = select_scan_strategy(rest_tables)
    strategy = select_scan_strategy(tables)
    if dfa_strategy is not None:
        dfa_auto = dfa_strategy.cost < strategy.cost
    plan.scan_plans[key] = NfaScanPlan(
        key=key,
        strategy=strategy,
        split=split,
        short_strategy=short_strategy,
        rest_strategy=rest_strategy,
        slot_perm=slot_perm,
        extended=extended,
        dfa_key=dfa_key,
        dfa_strategy=dfa_strategy,
        dfa_auto=dfa_auto,
    )
    return split_idx
