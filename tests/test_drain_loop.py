"""The sidecar's drain loop, driven in-process: a Ring (or four), a
RingSidecar, a small plan, no httpd.

`RingSidecar.run` has one way from dequeue to posted verdict: dequeue,
`should_launch`, `_dispatch`, `_complete` on the oldest when its lanes
are ready, when the pipeline is full or when nothing launched, idle.
What that loop owes its callers, whatever `pipeline_depth` is:

  * every dequeued row gets exactly one verdict, the interpreter's;
  * batches in flight never exceed `pipeline_depth`;
  * a batch whose lanes are ready is completed at once, oldest first
    (ISSUE 34); lanes that are never ready early are held to the depth,
    launch for launch and completion for completion as before;
  * rows held under the launch threshold are posted by the flush;
  * the posted floor never passes an unposted ticket;
  * a batch's results reach the host in ONE device->host copy, whatever
    the lanes program stacks for the plan (ISSUE 37), and the counters
    fed from it read what separate copies would have;
  * a hot swap requested with batches in flight flips between batches;
  * the legacy encode chain under the ladder's `pipeline` rung serves
    the same verdicts;
  * a `COST_LEDGER.json` and an environment from before the K-window
    megastep was deleted (ISSUE 33) load and are ignored.

Every drive enqueues its whole burst BEFORE the loop starts, so each
pass finds a full batch and the launches are the same on every run.
When a batch's lanes are ready is the device's to say, so the drives
that test the completion rule put a stand-in in their place (`_Lanes`)
whose `is_ready()` the test decides.

The last two tests are the knob census: docs/configuration.md against
what the program reads.
"""

from __future__ import annotations

import ast
import functools
import glob
import os
import random
import re
import threading
import time

import numpy as np
import pytest

from pingoo_tpu import native_ring
from pingoo_tpu.engine.batch import RequestTuple, tuple_to_context
from pingoo_tpu.engine.verdict import (action_lanes, interpret_rules_row,
                                       make_prefilter_fn)
from pingoo_tpu.sched.scheduler import CostModel

needs_native = pytest.mark.skipif(not native_ring.ensure_built(),
                                  reason="native toolchain unavailable")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAX_BATCH = 8
BURST = 5 * MAX_BATCH + 3   # five full batches and an odd tail
KNOBS = ("PINGOO_CHAOS", "PINGOO_DFA", "PINGOO_MESH", "PINGOO_SCHED_MODE",
         "PINGOO_SCHED_FAILOPEN", "PINGOO_PARITY_SAMPLE", "PINGOO_PIPELINE",
         "PINGOO_PIPELINE_DEPTH", "PINGOO_DEADLINE_MS", "PINGOO_STAGING",
         "PINGOO_MEGASTEP", "PINGOO_MEGASTEP_K")


def _rule(name, action, source):
    from pingoo_tpu.config.schema import Action, RuleConfig
    from pingoo_tpu.expr import compile_expression

    return RuleConfig(name=name, actions=(Action[action],),
                      expression=compile_expression(source))


@functools.lru_cache(maxsize=None)
def _plan(blocked="/evil"):
    from pingoo_tpu.compiler import compile_ruleset

    return compile_ruleset([
        _rule("waf", "BLOCK",
              f'http_request.path.starts_with("{blocked}")'),
        _rule("bot", "CAPTCHA",
              'http_request.user_agent.contains("drainbot")'),
        _rule("sqli", "BLOCK", 'http_request.url.contains("union+select")'),
    ], {})


def _requests(n, seed=33):
    rng = random.Random(seed)
    out = []
    for i in range(n):
        path = rng.choice(("/evil", "/alpha", "/beta", "/ok")) + f"/{i}"
        query = rng.choice(("", "?q=1", "?q=union+select"))
        out.append(RequestTuple(
            host="d.test", url=path + query, path=path,
            user_agent=rng.choice(("Mozilla/5.0", "drainbot/2")),
            ip="127.0.0.1"))
    return out


def _want(plan, tup) -> int:
    """The interpreter's first-match action: 0 pass, 1 block, 2 captcha."""
    row = interpret_rules_row(plan, tuple_to_context(tup, {}))
    return int(action_lanes(plan, row[None, :])[0][0])


def _enqueue(ring, tup) -> int:
    ticket = ring.enqueue(
        host=tup.host.encode(), path=tup.path.encode(),
        url=tup.url.encode(), user_agent=tup.user_agent.encode())
    assert ticket is not None
    return ticket


def _verdicts(ring) -> dict:
    """ticket -> [verdict bytes], everything on this ring's queue."""
    got: dict = {}
    while (v := ring.poll_verdict()) is not None:
        got.setdefault(v[0], []).append(v[1])
    return got


def _run_to_end(sidecar, n):
    worker = threading.Thread(target=sidecar.run,
                              kwargs={"max_requests": n}, daemon=True)
    worker.start()
    worker.join(180)
    assert not worker.is_alive(), "the drain loop never finished the burst"


class _Lanes:
    """A batch's device lanes with the readiness a test gives them (as
    tests/test_resilience.py's `_NeverReady`); the sync still gets the
    program's own lanes."""

    def __init__(self, dev, ready: bool):
        self._dev, self._ready = dev, ready

    def is_ready(self) -> bool:
        return self._ready

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self._dev)


# what a drive's batches find when the loop asks `is_ready()`, by the
# batch's place in the launch order (1, 2, ...); "real" leaves the
# device's own arrays in, "none" is a batch with no device lanes at all
# (the ladder's device rung demoted: the interpreter serves it)
LANES = {
    "ready": lambda seq: True,
    "never": lambda seq: False,
    "even": lambda seq: seq % 2 == 0,  # ready ones BEHIND unready ones
}


def _stand_in_lanes(sidecar, lanes: str, events=None):
    """Wrap `_dispatch` (and `_complete`, for the order) of a sidecar."""
    dispatch, complete = sidecar._dispatch, sidecar._complete
    events = [] if events is None else events

    def staged_dispatch(*args, **kwargs):
        entry = dispatch(*args, **kwargs)
        seq = entry[-1].seq
        events.append(("launch", seq))
        if lanes == "none":
            entry = entry[:3] + (None,) + entry[4:]
        elif lanes != "real":
            entry = entry[:3] + (_Lanes(entry[3], LANES[lanes](seq)),) \
                + entry[4:]
        return entry

    def watched_complete(*entry, **kwargs):
        events.append(("complete", entry[-1].seq))
        return complete(*entry, **kwargs)

    sidecar._dispatch = staged_dispatch
    sidecar._complete = watched_complete


def _parents_order(batches: int, depth: int) -> list:
    """What the loop did before ISSUE 34 with a full batch on every
    pass: a launch a pass, the oldest completed once `depth` are in
    flight, the rest by the passes that launch nothing."""
    events, inflight = [], []
    for seq in range(1, batches + 1):
        events.append(("launch", seq))
        inflight.append(seq)
        if len(inflight) >= depth:
            events.append(("complete", inflight.pop(0)))
    return events + [("complete", seq) for seq in inflight]


class Drive:
    """One burst through one sidecar, with what the loop itself counts
    and calls recorded on the way."""

    def __init__(self, tmp, depth: int, n_rings: int, lanes: str = "real"):
        from pingoo_tpu.native_ring import Ring, RingSidecar

        self.plan = _plan()
        self.rings = [Ring(str(tmp / f"ring_{i}"), capacity=256,
                           create=True) for i in range(n_rings)]
        self.sent = [{} for _ in self.rings]        # ticket -> request
        for i, tup in enumerate(_requests(BURST)):
            r = i % n_rings
            self.sent[r][_enqueue(self.rings[r], tup)] = tup
        sidecar = RingSidecar(
            self.rings if n_rings > 1 else self.rings[0], self.plan, {},
            max_batch=MAX_BATCH, pipeline_depth=depth)
        # launches and completions in the order the loop made them
        self.events: list = []
        _stand_in_lanes(sidecar, lanes, self.events)
        # pingoo_pipeline_inflight as each launch leaves it
        self.inflight_seen: list = []
        dispatch = sidecar._dispatch

        def watched_dispatch(*args, **kwargs):
            entry = dispatch(*args, **kwargs)
            self.inflight_seen.append(sidecar._pipe.inflight.value)
            return entry

        sidecar._dispatch = watched_dispatch
        # every floor the loop sets, against what it had posted by then
        self.floor_passed_unposted: list = []
        self.floors = [0] * n_rings
        for r, ring in enumerate(self.rings):
            self._watch_ring(r, ring)
        try:
            _run_to_end(sidecar, BURST)
            self.batches = sidecar.batches
            self.completions = sidecar.stats()["completions"]
            self.got = [_verdicts(ring) for ring in self.rings]
        finally:
            sidecar.stop()
            for ring in self.rings:
                ring.close()

    def _watch_ring(self, r, ring):
        posted: set = set()
        post, set_floor = ring.post_verdicts, ring.set_posted_floor

        def post_verdicts(tickets, actions):
            done = post(tickets, actions)
            posted.update(int(t) for t in tickets[:done])
            return done

        def set_posted_floor(floor):
            missing = [t for t in self.sent[r]
                       if t < floor and t not in posted]
            if missing:
                self.floor_passed_unposted.append((r, floor, missing))
            self.floors[r] = max(self.floors[r], floor)
            set_floor(floor)

        ring.post_verdicts = post_verdicts
        ring.set_posted_floor = set_posted_floor


@pytest.fixture(scope="module")
def drive(tmp_path_factory):
    """(depth, rings, lanes) -> the Drive, made once a module."""
    made: dict = {}

    def get(depth, n_rings=1, lanes="real"):
        key = (depth, n_rings, lanes)
        if key not in made:
            saved = {k: os.environ.pop(k, None) for k in KNOBS}
            try:
                made[key] = Drive(
                    tmp_path_factory.mktemp(f"d{depth}r{n_rings}{lanes}"),
                    depth, n_rings, lanes)
            finally:
                os.environ.update(
                    {k: v for k, v in saved.items() if v is not None})
        return made[key]

    return get


@needs_native
@pytest.mark.parametrize("depth", [1, 2, 3])
def test_every_row_gets_the_interpreters_verdict_once(drive, depth):
    d = drive(depth)
    got, sent = d.got[0], d.sent[0]
    assert sorted(got) == sorted(sent)
    assert all(len(v) == 1 for v in got.values())
    wrong = {t: (got[t][0] & 3, _want(d.plan, sent[t])) for t in sent
             if got[t][0] & 3 != _want(d.plan, sent[t])}
    assert not wrong
    assert {_want(d.plan, tup) for tup in sent.values()} == {0, 1, 2}
    assert d.batches == -(-BURST // MAX_BATCH)   # the burst spanned them


def _assert_served_right(d):
    """Each ring's tickets answered once, with the interpreter's action,
    floors never ahead of a post, and every batch counted by one rule."""
    for got, sent in zip(d.got, d.sent):
        assert sorted(got) == sorted(sent)
        assert all(len(v) == 1 for v in got.values())
        assert {t: v[0] & 3 for t, v in got.items()} == \
            {t: _want(d.plan, tup) for t, tup in sent.items()}
    assert d.floor_passed_unposted == []
    assert d.floors == [len(sent) for sent in d.sent]
    assert sorted(d.completions) == ["depth", "drain", "ready", "staging"]
    # one chip completes in launch order: no batch outlasts the staging
    # encoder's rotation
    assert d.completions["staging"] == 0
    assert sum(d.completions.values()) == d.batches == -(-BURST // MAX_BATCH)
    # completed in the order launched, whichever rule chose the moment
    done = [seq for what, seq in d.events if what == "complete"]
    assert done == list(range(1, d.batches + 1))


@needs_native
@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize("lanes", ["real", "never"])
def test_batches_in_flight_stay_within_the_depth(drive, depth, lanes):
    d = drive(depth, lanes=lanes)
    assert len(d.inflight_seen) == d.batches
    assert max(d.inflight_seen) <= depth             # never more
    if lanes == "never":
        # with batches queued behind it and a device that is the pace,
        # the pipeline fills
        assert max(d.inflight_seen) == depth
    _assert_served_right(d)


@needs_native
@pytest.mark.parametrize("depth", [1, 2, 3])
def test_ready_lanes_are_completed_in_the_pass_that_launched_them(
        drive, depth):
    d = drive(depth, lanes="ready")
    assert d.completions == {"ready": d.batches, "depth": 0, "drain": 0,
                             "staging": 0}
    assert d.events == [(what, seq) for seq in range(1, d.batches + 1)
                        for what in ("launch", "complete")]
    assert max(d.inflight_seen) == 1
    _assert_served_right(d)


@needs_native
@pytest.mark.parametrize("depth", [1, 2, 3])
def test_lanes_never_ready_early_are_held_to_the_depth_as_before(
        drive, depth):
    """The device-paced case loses nothing: launch for launch and
    completion for completion what the loop did before ISSUE 34."""
    d = drive(depth, lanes="never")
    assert d.events == _parents_order(d.batches, depth)
    assert d.completions == {"ready": 0, "depth": d.batches - (depth - 1),
                             "drain": depth - 1, "staging": 0}
    _assert_served_right(d)


@needs_native
@pytest.mark.parametrize("depth", [1, 2, 3])
def test_a_ready_batch_behind_an_unready_one_waits_its_turn(drive, depth):
    d = drive(depth, lanes="even")
    _assert_served_right(d)     # FIFO, and no floor ahead of a post
    odd = -(-d.batches // 2)    # batches 1, 3, 5: never ready early
    if depth == 1:
        # the bound leaves nothing in flight for the ready rule to find
        # but the batch just launched
        assert d.completions == {"ready": d.batches - odd, "depth": odd,
                                 "drain": 0, "staging": 0}
    else:
        # an unready batch leaves by the bound or the drain, and only
        # then the ready one behind it by the ready rule
        assert d.completions["ready"] == d.batches - odd
        assert d.completions["depth"] + d.completions["drain"] == odd
        for seq in range(2, d.batches + 1, 2):
            assert d.events.index(("complete", seq - 1)) \
                < d.events.index(("complete", seq))
            assert d.events.index(("launch", seq)) \
                < d.events.index(("complete", seq - 1))


@needs_native
@pytest.mark.parametrize("depth", [1, 2, 3])
def test_a_batch_the_interpreter_serves_counts_as_ready(drive, depth):
    d = drive(depth, lanes="none")      # `dev is None`: nothing to wait for
    assert d.completions == {"ready": d.batches, "depth": 0, "drain": 0,
                             "staging": 0}
    _assert_served_right(d)


@needs_native
@pytest.mark.parametrize("n_rings", [1, 4])
@pytest.mark.parametrize("lanes", ["real", "never"])
def test_posted_floor_never_passes_an_unposted_ticket(drive, n_rings, lanes):
    d = drive(3, n_rings, lanes)
    assert max(d.inflight_seen) <= 3
    if lanes == "never":
        assert max(d.inflight_seen) == 3
    assert d.floor_passed_unposted == []
    # ... and it ends above every ticket of every ring
    assert d.floors == [len(sent) for sent in d.sent]
    for got, sent in zip(d.got, d.sent):
        assert sorted(got) == sorted(sent)
        assert all(len(v) == 1 for v in got.values())


# what the lanes program stacks under its lanes, by the environment the
# sidecar is built in; "none" is the device rung demoted (no device lanes)
COPIES = {
    "provenance-and-prefilter": ({}, "ready"),
    "provenance-off": ({"PINGOO_PROVENANCE": "0"}, "ready"),
    "prefilter-off": ({"PINGOO_PREFILTER": "off"}, "ready"),
    "both-off": ({"PINGOO_PROVENANCE": "0", "PINGOO_PREFILTER": "off"},
                 "ready"),
    "device-demoted": ({}, "none"),
}


@needs_native
@pytest.mark.parametrize("case", sorted(COPIES))
def test_a_batch_comes_to_the_host_in_one_copy(tmp_path, monkeypatch, case):
    """`_complete` materialises exactly one device array a batch: the
    stand-in's `__array__` is asked once, numpy is handed no other
    device array while a batch completes, and
    `pingoo_sidecar_host_copies_total` says the same. The attribution
    table, Stage A's gauges and counters and the cascade's counters read
    what Stage A's own output and the interpreter give for the same
    batches, computed apart."""
    import jax

    from pingoo_tpu.native_ring import Ring, RingSidecar

    env, lanes = COPIES[case]
    for k in KNOBS + ("PINGOO_PROVENANCE", "PINGOO_PREFILTER"):
        monkeypatch.delenv(k, raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    plan = _plan()
    ring = Ring(str(tmp_path / "ring"), capacity=256, create=True)
    reqs = _requests(BURST)
    sent = {_enqueue(ring, tup): tup for tup in reqs}
    sidecar = RingSidecar(ring, plan, {}, max_batch=MAX_BATCH,
                          pipeline_depth=2)
    batches = -(-BURST // MAX_BATCH)
    device_batches = 0 if lanes == "none" else batches

    asked: list = []        # the stand-in's __array__, by batch
    handed: list = []       # device arrays numpy was handed in _complete
    completing: list = []
    stage_a: list = []      # Stage A's own second output, a batch each

    class Counted(_Lanes):
        def __array__(self, dtype=None, copy=None):
            asked.append(completing[-1])
            return super().__array__(dtype, copy)

    dispatch, complete = sidecar._dispatch, sidecar._complete

    def staged_dispatch(*args, **kwargs):
        entry = dispatch(*args, **kwargs)
        # nothing of the device rides the tuple but the lanes
        assert not any(isinstance(x, jax.Array) for x in entry[4:])
        dev = None if lanes == "none" else Counted(entry[3], True)
        return entry[:3] + (dev,) + entry[4:]

    def watched_complete(*entry, **kwargs):
        completing.append(entry[-1].seq)
        try:
            return complete(*entry, **kwargs)
        finally:
            completing.pop()

    sidecar._dispatch, sidecar._complete = staged_dispatch, watched_complete
    for name in ("_pf_fn", "_packed_pf_fn"):
        fn = getattr(sidecar, name)
        if fn is not None:
            def recording(*args, _fn=fn):
                hits, aux = _fn(*args)
                stage_a.append(aux)
                return hits, aux
            setattr(sidecar, name, recording)
    asarray = np.asarray

    def watched_asarray(a, *args, **kwargs):
        if completing and isinstance(a, jax.Array):
            handed.append(completing[-1])
        return asarray(a, *args, **kwargs)

    monkeypatch.setattr(np, "asarray", watched_asarray)
    copies0 = sidecar._pipe.host_copies.value
    skipped0 = sidecar._pf_skip_counter.value
    cascade0 = sidecar._cascade.snapshot()
    pf_attr = sidecar._pf_attr
    bank_skips0 = [c.value for c in pf_attr._skip_counters] if pf_attr \
        else []
    try:
        _run_to_end(sidecar, BURST)
        got = _verdicts(ring)
        stats = sidecar.stats()
    finally:
        sidecar.stop()
        ring.close()
    monkeypatch.setattr(np, "asarray", asarray)

    # every row served right, whichever way the batch came home
    assert {t: [v & 3 for v in vs] for t, vs in got.items()} == \
        {t: [_want(plan, tup)] for t, tup in sent.items()}
    assert sidecar.batches == batches
    # ONE copy a batch
    want_seqs = list(range(1, device_batches + 1))
    assert asked == want_seqs
    assert handed == want_seqs          # the stand-in's own, nothing else
    assert sidecar._pipe.host_copies.value - copies0 == device_batches
    assert stats["host_copies"] == sidecar._pipe.host_copies.value

    # the attribution table: the interpreter's hits a rule
    rows = sidecar._lane_rows
    provenance = "PINGOO_PROVENANCE" not in env
    assert (rows.rule_hits > 0) == provenance
    if provenance:
        want_hits = np.stack([interpret_rules_row(
            plan, tuple_to_context(tup, {})) for tup in reqs]).sum(axis=0)
        if lanes == "none":
            want_hits[:] = 0    # the attribution lane never ran
        np.testing.assert_array_equal(sidecar._attribution._counts,
                                      want_hits)
    else:
        assert sidecar._attribution is None
    # Stage A's gauges and counters: its own output, copied apart
    prefilter = "PINGOO_PREFILTER" not in env
    assert (rows.stage_a > 0) == prefilter
    assert len(stage_a) == (batches if prefilter else 0)
    # (a batch the interpreter serves observes none of it)
    aux = np.stack([asarray(a) for a in stage_a]) \
        if stage_a and lanes != "none" else None
    if aux is not None:
        m = len(pf_attr.masked_keys) if pf_attr else 0
        assert aux.shape == (batches, rows.stage_a)
        assert sidecar._pf_skip_counter.value - skipped0 == aux[:, 1].sum()
        assert sidecar._pf_rate_gauge.value == pytest.approx(
            aux[-1, 0] / (MAX_BATCH * sidecar._pf_gated_banks))
        if provenance:
            assert m and [c.value - c0 for c, c0 in zip(
                pf_attr._skip_counters, bank_skips0)] == \
                list(aux[:, 2 + m:].sum(axis=0))
            assert [g.value for g in pf_attr._rate_gauges] == \
                [round(int(c) / MAX_BATCH, 4) for c in aux[-1, 2:2 + m]]
        # the cascade's own counts of the same candidates
        cascade = stats["cascade"]
        masked = make_prefilter_fn(plan).masked
        assert sorted(cascade) == sorted(
            k.removeprefix("nfa_") for k in masked)
        for i, key in enumerate(masked):
            bank = key.removeprefix("nfa_")
            assert cascade[bank]["live"] - cascade0[bank]["live"] == BURST
            assert cascade[bank]["candidate"] \
                - cascade0[bank]["candidate"] == aux[:, 2 + i].sum()
    else:
        assert sidecar._pf_skip_counter.value == skipped0
        assert stats["cascade"] == cascade0


@needs_native
def test_flush_posts_rows_held_under_the_launch_threshold(
        tmp_path, monkeypatch):
    from pingoo_tpu.native_ring import Ring, RingSidecar

    for k in KNOBS:
        monkeypatch.delenv(k, raising=False)
    # a deadline nothing here uses up: a partial batch is never due
    monkeypatch.setenv("PINGOO_DEADLINE_MS", "600000")
    plan = _plan()
    ring = Ring(str(tmp_path / "ring"), capacity=64, create=True)
    sidecar = RingSidecar(ring, plan, {}, max_batch=MAX_BATCH)
    held = _requests(MAX_BATCH - 3)
    sent = {_enqueue(ring, tup): tup for tup in held}
    worker = threading.Thread(target=sidecar.run, daemon=True)
    worker.start()
    try:
        deadline = time.monotonic() + 60
        while ring.liveness()["req_tail"] < len(held):
            assert time.monotonic() < deadline
            time.sleep(0.005)
        time.sleep(0.05)         # dequeued, and the loop keeps holding
        assert sidecar.batches == 0 and ring.poll_verdict() is None
        sidecar.stop()           # joins the loop: the flush has run
        assert not worker.is_alive()
        got = _verdicts(ring)
        assert sidecar.batches == 1 and sidecar.processed == len(held)
        assert {t: [v[0] & 3] for t, v in got.items()} == \
            {t: [_want(plan, tup)] for t, tup in sent.items()}
    finally:
        sidecar.stop()
        ring.close()


@needs_native
def test_swap_with_batches_in_flight_flips_between_batches(
        tmp_path, monkeypatch):
    """tests/test_hotswap.py swaps a quiet sidecar (every phase-A
    verdict polled first); here the swap is requested from inside the
    third launch, with two batches in flight and three more queued
    (lanes that are not ready early keep them in flight)."""
    from pingoo_tpu.native_ring import Ring, RingSidecar

    for k in KNOBS:
        monkeypatch.delenv(k, raising=False)
    plans = [_plan("/alpha"), _plan("/beta")]
    ring = Ring(str(tmp_path / "ring"), capacity=256, create=True)
    sidecar = RingSidecar(ring, plans[0], {}, max_batch=MAX_BATCH,
                          pipeline_depth=3)
    new_state = sidecar._build_plan_state(plans[1])
    sent = {_enqueue(ring, tup): tup for tup in _requests(BURST)}
    launched_in: dict = {}       # ticket -> ruleset epoch at its launch
    inflight_at_request: list = []
    handles: list = []
    _stand_in_lanes(sidecar, "never")
    dispatch = sidecar._dispatch

    def watched_dispatch(parts, *args, **kwargs):
        for _, part in parts:
            for t in part["ticket"]:
                launched_in[int(t)] = sidecar.ruleset_epoch
        entry = dispatch(parts, *args, **kwargs)
        if len(launched_in) == 3 * MAX_BATCH and not handles:
            inflight_at_request.append(sidecar._pipe.inflight.value)
            handles.append(sidecar.request_swap(plans[1], state=new_state))
        return entry

    sidecar._dispatch = watched_dispatch
    try:
        _run_to_end(sidecar, BURST)
        got = _verdicts(ring)
    finally:
        sidecar.stop()
        ring.close()
    assert inflight_at_request == [3]
    assert handles[0].wait(5) and handles[0].result == "ok"
    assert sorted(got) == sorted(sent) == sorted(launched_in)
    assert all(len(v) == 1 for v in got.values())
    old = [t for t, epoch in launched_in.items() if epoch == 0]
    assert len(old) == 3 * MAX_BATCH and len(sent) - len(old) > MAX_BATCH
    for t, tup in sent.items():
        assert got[t][0] & 3 == _want(plans[launched_in[t]], tup), \
            (t, launched_in[t], tup.path)
    # the two plans disagree on rows of both phases, or this proved nothing
    for phase in (0, 1):
        assert any(_want(plans[0], sent[t]) != _want(plans[1], sent[t])
                   for t in sent if launched_in[t] == phase)


@needs_native
@pytest.mark.parametrize("how", ["PINGOO_PIPELINE=off",
                                 "the staging encoder raises"])
def test_the_legacy_encode_chain_serves_the_same_verdicts(
        tmp_path, monkeypatch, how):
    """What the ladder's `pipeline` rung falls back TO (ROADMAP debt 3:
    PINGOO_PIPELINE=off is that chain, not a fork)."""
    from pingoo_tpu.native_ring import Ring, RingSidecar

    for k in KNOBS:
        monkeypatch.delenv(k, raising=False)
    if how == "PINGOO_PIPELINE=off":
        monkeypatch.setenv("PINGOO_PIPELINE", "off")
    plan = _plan()
    ring = Ring(str(tmp_path / "ring"), capacity=256, create=True)
    sidecar = RingSidecar(ring, plan, {}, max_batch=MAX_BATCH)
    if how != "PINGOO_PIPELINE=off":
        def broken(*args, **kwargs):
            raise RuntimeError("staging encoder fault")

        sidecar._staging.encode_slots = broken
    sent = {_enqueue(ring, tup): tup for tup in _requests(BURST)}
    try:
        _run_to_end(sidecar, BURST)
        got = _verdicts(ring)
        rung = sidecar.ladder.snapshot()["pipeline"]
    finally:
        sidecar.stop()
        ring.close()
    assert {t: [v[0] & 3] for t, v in got.items()} == \
        {t: [_want(plan, tup)] for t, tup in sent.items()}
    if how == "PINGOO_PIPELINE=off":
        assert sidecar._staging is None and rung["healthy"]
    else:
        assert not rung["healthy"] and rung["demotions"] == 1
        assert rung["fallback"] == "legacy-encode"


def _keys(doc, prefix=""):
    """Every key path of a nested dict of dicts."""
    out = set()
    for k, v in doc.items():
        out.add(f"{prefix}{k}")
        if isinstance(v, dict) and k not in ("ring_depth", "ring_rows"):
            out |= _keys(v, f"{prefix}{k}.")
    return out


@needs_native
def test_a_megastep_value_left_in_the_environment_is_ignored(
        tmp_path, monkeypatch):
    from pingoo_tpu.native_ring import Ring, RingSidecar

    def serve(tag):
        ring = Ring(str(tmp_path / tag), capacity=256, create=True)
        sidecar = RingSidecar(ring, _plan(), {}, max_batch=MAX_BATCH)
        sent = {_enqueue(ring, tup): tup for tup in _requests(BURST)}
        try:
            _run_to_end(sidecar, BURST)
            got = _verdicts(ring)
            stats = sidecar.stats()
        finally:
            sidecar.stop()
            ring.close()
        assert sorted(got) == sorted(sent)
        return got, stats

    for k in KNOBS:
        monkeypatch.delenv(k, raising=False)
    plain, plain_stats = serve("plain")
    monkeypatch.setenv("PINGOO_MEGASTEP", "force")
    monkeypatch.setenv("PINGOO_MEGASTEP_K", "4")
    forced, forced_stats = serve("forced")
    assert forced == plain
    assert forced_stats["batches"] == plain_stats["batches"] \
        == -(-BURST // MAX_BATCH)                      # served per batch
    keys = _keys(forced_stats)
    assert keys == _keys(plain_stats)
    assert not [k for k in keys if "mega" in k.lower()]


def test_cost_model_ignores_the_megastep_keys_of_an_older_ledger():
    legacy = {"megastep_ewma_ms": {"4x16": 1.5},
              "megastep_first_ms": {"4x16": 900.0}}
    cost = CostModel(max_batch=64)
    assert cost.restore({"ewma_ms": {"16": 2.0}, **legacy}) is True
    assert cost.estimate(16) == 2.0
    assert not set(cost.snapshot()) & set(legacy)
    # the two keys alone restore nothing
    assert CostModel(max_batch=64).restore(legacy) is False


# -- the knob census -----------------------------------------------------------

_KNOB = re.compile(r"PINGOO_[A-Z0-9_]+")


def _knobs_python_reads() -> set:
    """Every whole string literal of the program that is a PINGOO_*
    name: what it reads from the environment or hands to a child."""
    names = set()
    for path in glob.glob(os.path.join(REPO, "pingoo_tpu", "**", "*.py"),
                          recursive=True):
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read())
        names |= {n.value for n in ast.walk(tree)
                  if isinstance(n, ast.Constant) and isinstance(n.value, str)
                  and _KNOB.fullmatch(n.value)}
    return names


def _knobs_native_reads() -> set:
    names = set()
    for path in glob.glob(os.path.join(REPO, "pingoo_tpu", "native", "*.cc")):
        with open(path, encoding="utf-8") as f:
            names |= set(re.findall(r'"(PINGOO_[A-Z0-9_]+)"', f.read()))
    return names


def _knobs_documented() -> set:
    with open(os.path.join(REPO, "docs", "configuration.md"),
              encoding="utf-8") as f:
        return set(_KNOB.findall(f.read()))


def test_every_knob_the_program_reads_is_documented():
    assert sorted(_knobs_python_reads() - _knobs_documented()) == []


def test_every_documented_knob_is_read_by_something():
    assert sorted(_knobs_documented() - _knobs_python_reads()
                  - _knobs_native_reads()) == []
