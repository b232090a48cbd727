"""The byte loops' shared driver (ops/live_columns.py, ISSUE 29): the
trip count follows the batch's longest live row, not the staged width.

  * the block count on every edge of the bound (conftest's length
    patterns at the staged width of 2,048), and that the loop really
    takes that many trips;
  * structure: the jaxprs of `dfa_scan` and `prefilter_scan` at width
    2,048 hold no loop with a static trip count (2,048 columns, or 256
    blocks of 8) — one dynamic `while` each;
  * the planes' `pingoo_scan_columns_total{kind="walked"}` is the
    device's own count, on a batch the staging encoder made.

Value parity with the numpy oracles lives with each kernel's tests
(test_bitsplit_dfa.py, test_prefilter.py, test_bodyscan.py).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from pingoo_tpu.compiler import compile_ruleset  # noqa: E402
from pingoo_tpu.compiler.nfa import lower_bank_to_dfa  # noqa: E402
from pingoo_tpu.compiler.repat import literal_pattern  # noqa: E402
from pingoo_tpu.config.schema import Action, RuleConfig  # noqa: E402
from pingoo_tpu.engine.batch import (  # noqa: E402
    RequestTuple,
    ScanColumnCounters,
    StagingEncoder,
    scan_columns,
)
from pingoo_tpu.expr import compile_expression  # noqa: E402
from pingoo_tpu.obs import REGISTRY  # noqa: E402
from pingoo_tpu.ops.bitsplit_dfa import dfa_scan, dfa_to_tables  # noqa: E402
from pingoo_tpu.ops.live_columns import (  # noqa: E402
    BLOCK,
    live_blocks,
    scan_live_columns,
    walked_columns,
)
from pingoo_tpu.ops.prefilter import (  # noqa: E402
    bank_to_prefilter_tables,
    build_prefilter_bank,
    prefilter_scan,
)

WIDTH = 2048  # conftest.STAGED_WIDTH


def _expected_blocks(lens, width=WIDTH):
    return -(-min(int(lens.max()), width) // BLOCK)


def test_block_count_on_every_edge(live_lengths):
    lens, _ = live_lengths
    want = _expected_blocks(lens)
    assert int(live_blocks(jnp.asarray(lens), WIDTH)) == want
    assert walked_columns(lens, WIDTH) == BLOCK * want

    # ...and the loop takes exactly that many trips of BLOCK steps: a
    # step that counts itself, live or not.
    def step(count, col, live):
        return count + 1

    steps = jax.jit(lambda d, l: scan_live_columns(
        step, jnp.int32(0), d, l, 0))(
            jnp.zeros((len(lens), WIDTH), dtype=jnp.uint8),
            jnp.asarray(lens))
    assert int(steps) == BLOCK * want


@pytest.mark.parametrize("lens, t_off, want", [
    ([20, 7, 33], 0, 5), ([20, 7, 33], [0, 0, 0], 5),
    ([20, 7, 33], [16, 0, 30], 1), ([20, 7, 33], 40, 0),
    ([20, 7, 33], [40, 7, 33], 0), ([20, 7, 333], [0, 0, 80], 5)])
def test_block_count_follows_the_offset(lens, t_off, want):
    """A 40-column chunk: the bound is the longest REMAINDER, clipped to
    [0, width]; a chunk past every row runs no block (bodyscan's
    per-row offsets)."""
    rem = jnp.asarray(np.asarray(lens) - np.asarray(t_off), dtype=jnp.int32)
    assert int(live_blocks(rem, 40)) == want


def _loops(jaxpr):
    """(primitive name, static trip count or None) of every loop in a
    jaxpr, nested jaxprs included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(("scan", int(eqn.params["length"])))
        elif eqn.primitive.name == "while":
            out.append(("while", None))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_loops(sub))
    return out


@pytest.fixture(scope="module")
def small_tables():
    pats = [literal_pattern(b"union select", case_insensitive=True),
            literal_pattern(b"<script", case_insensitive=True)]
    dfa = lower_bank_to_dfa(pats, state_budget=4096, merge_depths=())
    pf = build_prefilter_bank([tuple(frozenset([b]) for b in b"union"),
                               tuple(frozenset([b]) for b in b"<scr")])
    return dfa_to_tables(dfa), bank_to_prefilter_tables(pf)


@pytest.mark.parametrize("kernel", ["dfa_scan", "prefilter_scan"])
def test_no_loop_with_a_static_trip_count(small_tables, kernel):
    dt, pt = small_tables
    fn, tables = {"dfa_scan": (dfa_scan, dt),
                  "prefilter_scan": (prefilter_scan, pt)}[kernel]
    data = jnp.zeros((16, WIDTH), dtype=jnp.uint8)
    lens = jnp.zeros((16,), dtype=jnp.int32)
    loops = _loops(jax.make_jaxpr(fn)(tables, data, lens).jaxpr)
    assert ("while", None) in loops
    static = [n for kind, n in loops if kind == "scan"]
    assert not [n for n in static if n >= WIDTH // BLOCK], loops


def _rule(name, expr):
    return RuleConfig(name=name, expression=compile_expression(expr),
                      actions=[Action.BLOCK])


def test_sidecar_walked_count_is_the_devices():
    """On a batch the staging encoder made (compact: url and path staged
    at 2,048, a row's TRUE length may exceed the width), the counter's
    `walked` is 8 x the helper's block count and `staged` the width."""
    plan = compile_ruleset([
        _rule("u", 'http_request.url.contains("union select")'),
        _rule("p", 'http_request.path.matches("(?i)etc/passwd")'),
        _rule("h", 'http_request.host.starts_with("admin.")')], {})
    counters = ScanColumnCounters("test", plan)
    assert counters.fields == ("path", "url")
    caps = dict(plan.staging_caps)
    assert caps["url"] == caps["path"] == WIDTH
    enc = StagingEncoder(8, plan.field_specs, stage_caps=caps)
    reqs = [RequestTuple(host="a.test", path="/p" * n, url="/u?" + "q" * m,
                         method="GET", user_agent="ua", ip="10.0.0.1")
            for n, m in ((1, 10), (30, 61), (2, 0), (4, 300))]
    batch = enc.encode_requests(reqs, pad_to=8)
    got = scan_columns(batch.arrays, counters.fields)
    for field, (staged, walked) in got.items():
        lens = batch.arrays[f"{field}_len"]
        assert staged == batch.arrays[f"{field}_bytes"].shape[1] == WIDTH
        assert walked == BLOCK * int(live_blocks(jnp.asarray(lens), staged))
    assert got["url"] == (WIDTH, 304) and got["path"] == (WIDTH, 64)

    def value(field, kind):
        return REGISTRY.counter(
            "pingoo_scan_columns_total",
            labels={"plane": "test", "field": field, "kind": kind}).value

    before = {k: value(*k) for k in (("url", "staged"), ("url", "walked"))}
    counters.note(batch.arrays)
    assert value("url", "staged") - before["url", "staged"] == WIDTH
    assert value("url", "walked") - before["url", "walked"] == 304
