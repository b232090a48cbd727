"""Compiled-ruleset artifact cache.

The reference's persistent state is all auto-managed files re-read at
boot (SURVEY.md §5 checkpoint/resume). The TPU equivalent called for
there: a compiled-ruleset artifact cache — ruleset hash -> lowered plan
(device tables + predicate bindings + boolean IR) — so a restart skips
recompilation of large rulesets (regex parsing, NFA packing, bitset
construction for 1M-entry lists).

Artifacts are pickles of the RulesetPlan's numpy/static state keyed by a
fingerprint of (rule sources, actions, list contents, format version).
The cache directory is private to the server (like /etc/pingoo's
auto-managed files); artifacts are only ever loaded if their fingerprint
matches, so a stale or foreign file is simply ignored.

Since v12 every artifact also carries a `plan_proof` block — the
discharged soundness obligations from compiler/obligations.py, digest-
sealed against tampering. A cache hit with a valid proof is also a
proof hit (no re-prove at boot); a missing/tampered/failed block forces
a re-prove of the loaded plan, and a plan that fails its obligations is
REFUSED at compile time (ObligationError) rather than cached or served.
Set PINGOO_PROVE=off to skip proving (e.g. while bisecting a prover
regression); refusal semantics only apply when proving runs.
"""

from __future__ import annotations

import hashlib
import os
import pickle
from typing import Optional

from ..config.schema import RuleConfig
from ..expr.values import Ip
from .obligations import PlanProof, proof_block_valid, prove_plan, require
from .plan import RulesetPlan, compile_ruleset, split_config_token

FORMAT_VERSION = 13  # bump when plan/table layout changes
# v8: scan_plans (per-bank strategy selection, halo partition sub-banks)
# v9: PrefilterPlan + pf_<field> factor tables (literal-prefilter cascade)
# v10: bitsplit-DFA lowering — dfa_<field> DfaTables, NfaScanPlan
#      dfa_key/dfa_strategy/dfa_auto, RulesetPlan.dfa_default_mode
# v11: compact staging — RulesetPlan.staging_required/staging_caps
# v12: plan_proof block — discharged obligation ledger rides the artifact
# v13: default scan-strategy selection no longer picks the fused Pallas
#      kernel without a measured cost (a v12 artifact may carry it)


def _prove_enabled() -> bool:
    return os.environ.get("PINGOO_PROVE", "on").lower() not in (
        "off", "0", "no", "false")


def ruleset_fingerprint(rules: list[RuleConfig], lists: dict,
                        field_specs=None, routes=None,
                        tenant: str = "") -> str:
    from .lowering import DEFAULT_FIELD_SPECS

    h = hashlib.sha256()
    h.update(str(FORMAT_VERSION).encode())
    if tenant:
        # Multi-tenant hot-swap (ISSUE 11): identical rulesets under
        # different tenants stay distinct artifacts, so one tenant's
        # tuned plan (update_cached_plan) never leaks into another's.
        # Empty tenant hashes nothing — pre-tenant artifacts stay valid.
        h.update(b"\x04tenant:" + tenant.encode() + b"\x05")
    # Plan-shaping env knobs (halo partition on/off + footprint budget)
    # change the np_tables layout, so they are part of the identity.
    h.update(split_config_token().encode())
    h.update(repr(sorted((field_specs or DEFAULT_FIELD_SPECS).items())).encode())
    for rule in rules:
        h.update(rule.name.encode())
        h.update((rule.expression.source if rule.expression else "").encode())
        h.update(",".join(a.value for a in rule.actions).encode())
        h.update(b"\x00")
    for name, program in routes or []:
        h.update(b"\x02" + name.encode() + b"\x03")
        h.update((program.source if program else "").encode())
        h.update(b"\x00")
    for name in sorted(lists):
        h.update(name.encode())
        for item in lists[name]:
            if isinstance(item, Ip):
                h.update(str(item).encode())
            else:
                h.update(repr(item).encode())
            h.update(b"\x01")
    return h.hexdigest()


def compile_ruleset_cached(
    rules: list[RuleConfig],
    lists: dict,
    cache_dir: Optional[str] = None,
    field_specs=None,
    routes=None,
    tenant: str = "",
) -> RulesetPlan:
    """compile_ruleset with a transparent on-disk artifact cache.

    The cached path is also the PROVED path: a fresh compile discharges
    the soundness obligations before the artifact is written (a failure
    raises ObligationError), and a hit re-proves only when the stored
    plan_proof block is missing or fails its digest/fingerprint check.
    """
    if cache_dir is None:
        return compile_ruleset(rules, lists, field_specs, routes=routes)
    fingerprint = ruleset_fingerprint(rules, lists, field_specs,
                                      routes=routes, tenant=tenant)
    path = os.path.join(cache_dir, f"ruleset-{fingerprint[:32]}.plan")
    plan, proof_block = _load(path, fingerprint)
    if plan is not None:
        if _prove_enabled() and not proof_block_valid(proof_block,
                                                      fingerprint):
            # tampered/absent proof: re-prove the loaded plan in place
            # (same plan -> same verdict as a fresh compile would get).
            proof = require(prove_plan(plan, fingerprint))
            _save(path, fingerprint, plan, proof)
        return plan
    plan = compile_ruleset(rules, lists, field_specs, routes=routes)
    proof = None
    if _prove_enabled():
        proof = require(prove_plan(plan, fingerprint))
    _save(path, fingerprint, plan, proof)
    return plan


def update_cached_plan(
    rules: list[RuleConfig],
    lists: dict,
    plan: RulesetPlan,
    cache_dir: str,
    field_specs=None,
    routes=None,
    tenant: str = "",
) -> str:
    """Re-persist a (mutated) plan under its ruleset fingerprint — the
    path bench.py's micro-autotune uses to record measured scan-strategy
    selections (plan.scan_plans) into the artifact cache so the next
    boot starts from the tuned choice. Returns the artifact path."""
    fingerprint = ruleset_fingerprint(rules, lists, field_specs,
                                      routes=routes, tenant=tenant)
    path = os.path.join(cache_dir, f"ruleset-{fingerprint[:32]}.plan")
    proof = None
    if _prove_enabled():
        # tuned plans re-prove before re-persisting: the autotuner only
        # mutates scan strategies, but the artifact contract is that a
        # stored proof always covers the stored plan.
        proof = require(prove_plan(plan, fingerprint))
    _save(path, fingerprint, plan, proof)
    return path


def _save(path: str, fingerprint: str, plan: RulesetPlan,
          proof: Optional[PlanProof] = None) -> None:
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = path + ".tmp"
        doc = {"fingerprint": fingerprint, "plan": plan}
        if proof is not None:
            doc["plan_proof"] = proof.to_dict()
        with open(tmp, "wb") as f:
            pickle.dump(doc, f, protocol=pickle.HIGHEST_PROTOCOL)
        os.replace(tmp, path)  # atomic install (acme.rs-style persistence)
    except (OSError, pickle.PicklingError):
        pass  # cache is best-effort


def _load(path: str,
          fingerprint: str) -> tuple[Optional[RulesetPlan], Optional[dict]]:
    try:
        with open(path, "rb") as f:
            doc = pickle.load(f)
        if doc.get("fingerprint") != fingerprint:
            return None, None
        plan = doc.get("plan")
        if not isinstance(plan, RulesetPlan):
            return None, None
        return plan, doc.get("plan_proof")
    except Exception:
        return None, None
