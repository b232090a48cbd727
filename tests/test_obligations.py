"""Lowering-soundness obligations (ISSUE 18, docs/STATIC_ANALYSIS.md
"Prove"): the compile-time proof layer, its proof-block serialization,
the ring-protocol model checker, and the compile-surface membership
check. The full seed-corpus discharge + mutation battery lives in
`make prove` (tools/analyze/prove.py); these are the unit-level twins.
"""

import ast
import copy
import dataclasses
import json
import os

import pytest

from pingoo_tpu.compiler import obligations as ob
from pingoo_tpu.compiler import repat
from pingoo_tpu.compiler.plan import compile_ruleset
from pingoo_tpu.utils.crs import generate_ruleset


@pytest.fixture(scope="module")
def plan():
    rules, lists = generate_ruleset(80, with_lists=True,
                                    list_sizes=(64, 16))
    return compile_ruleset(rules, lists)


# -- pillar 1: plan proofs ---------------------------------------------------


def test_seed_plan_discharges(plan):
    proof = ob.prove_plan(plan, fingerprint="fp80")
    assert proof.ok, [o.to_dict() for o in proof.failures()]
    counts = proof.counts()
    assert counts["proved"] > 0 and counts["failed"] == 0
    assert proof.fingerprint == "fp80"


def test_body_plan_discharges():
    from pingoo_tpu.engine.bodyscan import compile_body_plan

    proof = ob.prove_body_plan(compile_body_plan())
    assert proof.ok, [o.to_dict() for o in proof.failures()]
    names = {o.name for o in proof.obligations}
    assert "body-carry-closure" in names and "body-tables" in names


def test_narrowed_staging_cap_refused(plan):
    m = copy.copy(plan)
    m.staging_caps = dict(plan.staging_caps)
    f = next(iter(plan.field_specs))
    m.staging_caps[f] = int(plan.field_specs[f]) + 1  # past the spec
    failed = [o for o in ob.check_staging(m) if o.status == "failed"]
    assert failed and f in failed[0].detail


def test_weakened_prefilter_factor_refused(plan):
    pf = plan.prefilter
    if not any(any(c >= 0 for c in cs) and "@" not in k
               for k, cs in pf.slot_codes.items()):
        pytest.skip("no factor-gated slot in the small seed plan")
    from tools.analyze.prove import _mutation_weakened_factor

    assert _mutation_weakened_factor(plan, ob)


def test_certify_extension_accepts_real_rewrite_and_rejects_tamper():
    orig = repat.compile_regex("ab*c")[0]
    assert repat.has_unbounded_rep(orig)
    ext = repat.extend_footprint(orig, 8)
    assert ext is not None
    assert ob.certify_extension(orig, ext, 8) is None
    # Dropping one justified optional is no longer the certified rewrite.
    tampered = dataclasses.replace(ext, positions=ext.positions[:-1])
    assert ob.certify_extension(orig, tampered, 8) is not None
    # Neither is flipping an anchor flag.
    flipped = dataclasses.replace(ext, anchor_start=not ext.anchor_start)
    assert ob.certify_extension(orig, flipped, 8) is not None


# -- proof-block serialization (the cache contract) --------------------------


def _proof(status="proved"):
    return ob.PlanProof(fingerprint="fp", obligations=[
        ob.Obligation("staging-caps", "caps", status, "detail")])


def test_proof_block_round_trip():
    proof = _proof()
    block = proof.to_dict()
    assert ob.proof_block_valid(block, "fp")
    assert ob.proof_block_valid(block, "")  # empty fp = unpinned
    back = ob.PlanProof.from_dict(block)
    assert back.to_dict() == block  # digest is reproducible


def test_proof_block_rejects_tampering():
    block = _proof().to_dict()
    bad = dict(block, digest="0" * 64)
    assert not ob.proof_block_valid(bad, "fp")
    renamed = json.loads(json.dumps(block))
    renamed["obligations"][0]["name"] = "tampered"
    assert not ob.proof_block_valid(renamed, "fp")
    assert not ob.proof_block_valid(dict(block, format=0), "fp")
    assert not ob.proof_block_valid(block, "other-fingerprint")
    assert not ob.proof_block_valid(_proof("failed").to_dict(), "fp")
    assert not ob.proof_block_valid("not a dict", "fp")


def test_require_raises_with_failure_names():
    ob.require(_proof())  # ok proof passes through
    with pytest.raises(ob.ObligationError) as ei:
        ob.require(_proof("failed"))
    assert "staging-caps" in str(ei.value)
    assert ei.value.proof.counts()["failed"] == 1


# -- pillar 3: ring-protocol model checker -----------------------------------


def test_ring_and_body_models_hold():
    from tools.analyze import ringcheck

    assert ringcheck.run(quiet=True) == 0


def test_ring_model_mutations_caught(capsys):
    from tools.analyze import ringcheck

    assert ringcheck.run(mutate="floor_before_post", quiet=True) != 0
    assert ringcheck.run(mutate="silent_gap", quiet=True) != 0
    out = capsys.readouterr().out
    assert "FAIL" in out  # the witness trace prints even when quiet


# -- pillar 2: compile surface ----------------------------------------------


def _event(**kw):
    base = {"plane": "python", "fn": "verdict", "kind": "cold"}
    base.update(kw)
    return base


def test_event_in_surface_membership():
    from pingoo_tpu.obs.perf import event_in_surface

    surf = {"planes": ["python", "sidecar"], "fns": ["verdict", "score"],
            "kinds": ["cold", "warm"], "batch_buckets": [8, 16]}
    assert event_in_surface(_event(), surf) is None
    assert event_in_surface(_event(batch_bucket=16), surf) is None
    assert "fn=" in event_in_surface(_event(fn="mystery"), surf)
    assert "plane=" in event_in_surface(_event(plane="gpu"), surf)
    assert "kind=" in event_in_surface(_event(kind="hot"), surf)
    assert event_in_surface(_event(batch_bucket=26), surf) \
        == "batch_bucket=26"
    # Widths gate only when the surface carries a widths key.
    assert event_in_surface(_event(widths=[[4, 8]]), surf) is None
    surf["widths"] = [[[4, 8]]]
    assert event_in_surface(_event(widths=[[4, 8]]), surf) is None
    assert event_in_surface(_event(widths=[[4, 99]]), surf) == "widths"


def test_unregistered_factory_fails_the_surface_walk():
    from tools.analyze import surface as surface_mod

    entries, problems = [], []
    tree = ast.parse("def make_bogus_fn(plan):\n    return None\n")
    surface_mod._scan_module(tree, "pingoo_tpu/engine/fake.py",
                             entries, problems)
    assert problems and "make_bogus_fn" in problems[0]


def test_unknown_instrument_label_fails_the_surface_walk():
    from tools.analyze import surface as surface_mod

    entries, problems = [], []
    tree = ast.parse("f = instrument_jit(g, 'mystery', plane='python')")
    surface_mod._scan_module(tree, "pingoo_tpu/engine/fake.py",
                             entries, problems)
    assert problems and "mystery" in problems[0]


def test_committed_surface_matches_the_tree():
    """COMPILE_SURFACE.json is generated (make prove / make surface);
    drift between the committed artifact and a fresh walk means someone
    added a jit entry point without regenerating it."""
    from tools.analyze import surface as surface_mod

    with open(surface_mod.DEFAULT_PATH, encoding="utf-8") as f:
        committed = json.load(f)
    assert committed == surface_mod.build_surface()
