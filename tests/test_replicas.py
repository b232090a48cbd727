"""`--replicas N` (ISSUE 39): one drain loop, N chips, every batch whole
on one of them. Driven in-process as tests/test_drain_loop.py drives the
loop (rings, a RingSidecar, no httpd), on the eight host devices
tests/conftest.py forces: generated CRS-family rules, a list rule, a
rule the host interprets and one past the staged url, over small lists,
three rings, seeded traffic with payloads, listed clients and long
urls, under PINGOO_STAGING=compact with the url and path staged short
(one program pair a chip).

What `--replicas` owes its callers:

  * every row gets the interpreter's verdict, and batch by batch the
    same verdicts as on one chip;
  * every chip gets batches, the per-chip counters add up to the
    batches, and no chip holds more than the depth in flight;
  * a batch whose chip is done is completed while another chip is still
    working (its chip's own batches stay in launch order), a pass with
    nothing to launch blocks on no batch, and the posted floor never
    passes an unposted ticket;
  * a batch on a slow chip keeps its staging views until it is done,
    however many batches the other chips run meanwhile;
  * each chip's copy of the tables, and each batch's lanes, live on
    that chip, and warming leaves no compile for the served batches;
  * a batch that ships fewer rows than it holds is padded on its own
    chip, by a program the boot compiled there;
  * a hot swap reaches every chip;
  * the boot refuses more chips than the host has, and a mesh beside.
"""

from __future__ import annotations

import dataclasses
import functools
import ipaddress
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from pingoo_tpu import native_ring
from pingoo_tpu.engine.batch import tuple_to_context
from pingoo_tpu.engine.verdict import action_lanes, interpret_rules_row

needs_native = pytest.mark.skipif(not native_ring.ensure_built(),
                                  reason="native toolchain unavailable")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZES = dict(num_rules=40, seed=20260728, list_sizes=(64, 16))
MAX_BATCH = 16
DEPTH = 2
N_RINGS = 3
BURST = 12 * MAX_BATCH + 5   # twelve full batches and a tail
KNOBS = ("PINGOO_CHAOS", "PINGOO_DFA", "PINGOO_MESH", "PINGOO_SCHED_MODE",
         "PINGOO_SCHED_FAILOPEN", "PINGOO_PIPELINE", "PINGOO_PIPELINE_DEPTH",
         "PINGOO_STAGING", "PINGOO_STAGING_DEPTH", "PINGOO_PREFILTER")
# staged url and path bytes: below what the generated rules need, so a
# longer row overflows and the interpreter re-serves it
STAGING = {"PINGOO_STAGING": "compact", "PINGOO_STAGING_DEPTH": "64"}


def _block(name, source):
    from pingoo_tpu.config.schema import Action, RuleConfig
    from pingoo_tpu.expr import compile_expression

    return RuleConfig(name=name, actions=(Action.BLOCK,),
                      expression=compile_expression(source))


@functools.lru_cache(maxsize=None)
def _corpus():
    """(rules, lists, traffic): the generator's rules and a list rule,
    and seeded traffic of which 30 % are payloads and every tenth
    request comes from a listed address."""
    from pingoo_tpu.utils.crs import generate_ruleset, generate_traffic

    rules, lists = generate_ruleset(**SIZES)
    rules = rules + [
        _block("listed", 'lists["blocked_ips"].contains(client.ip)'),
        # outside the device's subset: the host interprets it over the
        # batch's staged views
        _block("hosted", 'http_request.host + ":" == "hosted.example:"'),
        # past the staged url (STAGING_DEPTH): the interpreter re-serves
        # the rows the staged views flag as overflowing
        _block("deep", 'http_request.url.contains("/deep-marker")')]
    listed = [str(ip) for ip in lists["blocked_ips"] if "/" not in str(ip)]
    traffic = []
    for i, t in enumerate(generate_traffic(
            BURST, attack_fraction=0.3, seed=39, lists=lists)):
        if i % 10 == 0:
            t = dataclasses.replace(t, ip=listed[i % len(listed)])
        if i % 7 == 3:
            t = dataclasses.replace(t, host="hosted.example")
        if i % 5 == 2:
            t = dataclasses.replace(t, url=t.url + "/" + "d" * 80 + (
                "/deep-marker" if i % 10 == 2 else "/shallow"))
        traffic.append(t)
    return tuple(rules), lists, tuple(traffic)


@functools.lru_cache(maxsize=None)
def _plan(extra: str = ""):
    from pingoo_tpu.compiler import compile_ruleset

    rules, lists, _ = _corpus()
    if extra:
        rules = (_block("swapped", extra),) + rules
    return compile_ruleset(list(rules), lists)


def _want(plan, tup) -> int:
    """The interpreter's verdict byte: unverified action | verified
    block << 2, as the sidecar posts it with no service routing."""
    _, lists, _ = _corpus()
    row = interpret_rules_row(plan, tuple_to_context(tup, lists))
    unv, vblk = action_lanes(plan, row[None, :])
    return int(unv[0]) | (int(vblk[0]) << 2)


def _enqueue(ring, tup) -> int:
    ip = ipaddress.IPv6Address(f"::ffff:{tup.ip.split('/')[0]}").packed
    ticket = ring.enqueue(
        method=tup.method.encode(), host=tup.host.encode(),
        path=tup.path.encode(), url=tup.url.encode(),
        user_agent=tup.user_agent.encode(), ip=ip)
    assert ticket is not None
    return ticket


def _verdicts(ring) -> dict:
    got: dict = {}
    while (v := ring.poll_verdict()) is not None:
        got.setdefault(v[0], []).append(v[1])
    return got


def _batches_total() -> float:
    """pingoo_pipeline_batches_total{plane="sidecar"}: one counter for
    every sidecar of the process."""
    from pingoo_tpu.obs import REGISTRY, schema

    return REGISTRY.counter(
        "pingoo_pipeline_batches_total",
        schema.PIPELINE_METRICS["pingoo_pipeline_batches_total"],
        labels={"plane": "sidecar", "mode": "on"}).value


class _Lanes:
    """A batch's device lanes, ready `delay` seconds after its launch."""

    def __init__(self, dev, delay: float):
        self._dev, self._at = dev, time.monotonic() + delay

    def is_ready(self) -> bool:
        return time.monotonic() >= self._at

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self._dev)


# how long a batch's lanes take, by its chip, in seconds (far longer
# than the burst takes to launch): "real" is the device's own answer;
# "slow" holds every chip to the depth; "slow0" is chip 0 slow while
# the others are done at once
SLOW_S = 3.0
LANES = {"slow": lambda chip: SLOW_S,
         "slow0": lambda chip: SLOW_S if chip == 0 else 0.0}


class Drive:
    """One burst through one sidecar, with what the loop launched,
    completed and posted recorded on the way."""

    def __init__(self, tmp, replicas: int, lanes: str = "real"):
        from pingoo_tpu.native_ring import Ring, RingSidecar

        _, lists, traffic = _corpus()
        self.plan = _plan()
        self.rings = [Ring(str(tmp / f"ring_{i}"), capacity=256,
                           create=True) for i in range(N_RINGS)]
        self.sent = [{} for _ in self.rings]       # ticket -> request
        for i, tup in enumerate(traffic):
            r = i % N_RINGS
            self.sent[r][_enqueue(self.rings[r], tup)] = tup
        sidecar = RingSidecar(self.rings, self.plan, lists,
                              max_batch=MAX_BATCH, pipeline_depth=DEPTH,
                              replicas=replicas)
        self.sidecar = sidecar
        sidecar.warm_replicas()
        self.launches: list = []   # (seq, chip, {ring: tickets})
        self.completed: list = []  # seq, in completion order
        self.loads: list = []      # each chip's batches in flight
        self.lanes_on: list = []   # the devices each batch's lanes are on
        dispatch, complete = sidecar._dispatch, sidecar._complete

        def watched_dispatch(*args, **kwargs):
            entry = dispatch(*args, **kwargs)
            rec = entry[-1]
            self.launches.append((rec.seq, rec.device, {
                self.rings.index(r): tuple(int(t) for t in part["ticket"])
                for r, part in entry[0]}))
            self.loads.append(list(sidecar._replica_inflight))
            self.lanes_on.append(entry[3].devices())
            if lanes != "real":
                entry = entry[:3] + (_Lanes(
                    entry[3], LANES[lanes](rec.device)),) + entry[4:]
            return entry

        def watched_complete(*entry, **kwargs):
            self.completed.append(entry[-1].seq)
            return complete(*entry, **kwargs)

        sidecar._dispatch, sidecar._complete = watched_dispatch, \
            watched_complete
        self.floor_passed_unposted: list = []
        self.floors = [0] * N_RINGS
        for r, ring in enumerate(self.rings):
            self._watch_ring(r, ring)
        per_chip = [c.value for c in sidecar._pipe.replica_batches]
        batches = _batches_total()
        at_launch = sidecar._pipe.inflight_at_launch.value
        cache = self._cache_size()
        try:
            worker = threading.Thread(target=sidecar.run,
                                      kwargs={"max_requests": BURST},
                                      daemon=True)
            worker.start()
            worker.join(180)
            assert not worker.is_alive(), "the burst never finished"
            self.compiled_in_run = self._cache_size() - cache
            self.per_chip = [c.value - v for c, v in
                             zip(sidecar._pipe.replica_batches, per_chip)]
            self.batches = _batches_total() - batches
            self.at_launch = sidecar._pipe.inflight_at_launch.value - at_launch
            self.completions = sidecar.stats()["completions"]
            self.got = [_verdicts(ring) for ring in self.rings]
        finally:
            sidecar.stop()
            for ring in self.rings:
                ring.close()

    def _cache_size(self) -> int:
        fns = (self.sidecar._packed_lane_fn, self.sidecar._packed_pf_fn)
        return sum(fn._cache_size() for fn in fns if fn is not None)

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

    def batch_verdicts(self) -> list:
        """Per batch in launch order: its tickets and their verdicts."""
        return [{r: [(t, self.got[r][t][0]) for t in tickets]
                 for r, tickets in parts.items()}
                for _, _, parts in self.launches]


@pytest.fixture(scope="module")
def drive(tmp_path_factory):
    """(replicas, lanes) -> the Drive, made once a module."""
    made: dict = {}

    def get(replicas, lanes="real"):
        key = (replicas, lanes)
        if key not in made:
            saved = {k: os.environ.pop(k, None) for k in KNOBS}
            os.environ.update(STAGING)
            try:
                made[key] = Drive(tmp_path_factory.mktemp(
                    f"r{replicas}{lanes}"), replicas, lanes)
            finally:
                for k in STAGING:
                    os.environ.pop(k, None)
                os.environ.update(
                    {k: v for k, v in saved.items() if v is not None})
        return made[key]

    return get


def _assert_served_right(d):
    """Each ring's tickets answered once, with the interpreter's
    verdict, and no floor ahead of a post; the floors end above every
    ticket."""
    for got, sent in zip(d.got, d.sent):
        assert sorted(got) == sorted(sent)
        assert all(len(v) == 1 for v in got.values())
        assert {t: v[0] for t, v in got.items()} == \
            {t: _want(d.plan, tup) for t, tup in sent.items()}
    assert d.floor_passed_unposted == []
    assert d.floors == [max(sent) + 1 for sent in d.sent]
    assert sum(d.completions.values()) >= d.batches


@needs_native
@pytest.mark.parametrize("replicas", [2, 4])
def test_verdicts_are_the_interpreters_and_one_chips_batch_by_batch(
        drive, replicas):
    one, many = drive(1), drive(replicas)
    _assert_served_right(one)
    _assert_served_right(many)
    # the same batches, the same verdicts in each
    assert many.batch_verdicts() == one.batch_verdicts()
    assert many.batches == one.batches == -(-BURST // MAX_BATCH)
    wants = {_want(one.plan, t) & 3 for t in _corpus()[2]}
    assert wants == {0, 1}      # the burst both blocks and passes
    # ... and the host rule and the overflow rows decide some of it
    assert one.sidecar.plan.host_rules
    assert one.sidecar.depth_overflow_rows > 0


@needs_native
@pytest.mark.parametrize("replicas", [1, 4])
def test_every_chip_gets_batches_and_the_counts_add_up(drive, replicas):
    d = drive(replicas)
    assert len(d.per_chip) == replicas and min(d.per_chip) > 0
    assert sum(d.per_chip) == d.batches
    assert [chip for _, chip, _ in d.launches].count(0) == d.per_chip[0]
    # batches in flight, the one launched included: within each chip's
    # depth, and summed at each launch into the counter
    assert max(max(load) for load in d.loads) <= DEPTH
    assert d.at_launch == sum(sum(load) for load in d.loads)
    assert d.batches <= d.at_launch <= replicas * DEPTH * d.batches
    st = d.sidecar.stats()
    assert st["replicas"] == replicas
    assert sorted(st["replica_batches"]) == [str(c) for c in range(replicas)]


@needs_native
@pytest.mark.parametrize("lanes", ["slow", "slow0"])
def test_each_chip_holds_its_depth_and_none_waits_on_another(drive, lanes):
    d = drive(4, lanes)
    _assert_served_right(d)
    chips = {seq: chip for seq, chip, _ in d.launches}
    # a chip's batches complete in launch order
    for chip in range(4):
        mine = [seq for seq in d.completed if chips[seq] == chip]
        assert mine == sorted(mine)
    # a pass with nothing to launch polls the chips: it never blocks on
    # the oldest batch (the one-chip drain rule)
    assert d.completions["drain"] == 0
    if lanes == "slow":
        # device-paced: every chip fills to the depth before the loop
        # blocks, so up to 4 x depth are in flight
        assert max(sum(load) for load in d.loads) == 4 * DEPTH
        assert d.completions["depth"] > 0
    else:
        # chip 0's batches are slow, the others' done at once: theirs
        # leave by the ready rule, ahead of older ones on chip 0 ...
        late = [seq for seq in d.completed
                if chips[seq] != 0 and any(
                    chips[old] == 0 and old < seq
                    and d.completed.index(old) > d.completed.index(seq)
                    for old in chips)]
        assert late
        assert d.completions["ready"] >= sum(
            1 for seq in chips if chips[seq] != 0)
        # ... until chip 0's oldest holds the staging buffers that the
        # next batch is encoded into: it is completed first (the views
        # `_complete` reads stay that batch's own: host rules, overflow)
        assert d.completions["staging"] > 0


@needs_native
def test_each_chip_holds_its_own_tables_and_runs_its_batches(drive):
    import jax

    d = drive(4)
    local = jax.local_devices()
    for chip, tables in enumerate(d.sidecar._replica_tables):
        leaves = [x for x in jax.tree.leaves(tables)
                  if isinstance(x, jax.Array)]
        assert leaves
        assert all(x.devices() == {local[chip]} for x in leaves)
    for (_, chip, _), devices in zip(d.launches, d.lanes_on):
        assert devices == {local[chip]}
    # warm_replicas compiled the pair on every chip before the burst
    assert d.compiled_in_run == 0


@needs_native
def test_a_hot_swap_reaches_every_chip(tmp_path, monkeypatch):
    """Plan A passes every request of the second burst; plan B, swapped
    in between the bursts, blocks them all: every chip's batches of the
    second burst carry B's verdicts."""
    from pingoo_tpu.native_ring import Ring, RingSidecar

    for k in KNOBS:
        monkeypatch.delenv(k, raising=False)
    for k, v in STAGING.items():
        monkeypatch.setenv(k, v)
    _, lists, traffic = _corpus()
    plan_a, plan_b = _plan(), _plan('http_request.path.starts_with("/sw")')
    second = [_swap_request(i) for i in range(8 * MAX_BATCH)]
    assert {_want(plan_a, t) for t in second} == {0}
    assert {_want(plan_b, t) for t in second} == {5}  # block, both lanes
    rings = [Ring(str(tmp_path / f"ring_{i}"), capacity=512, create=True)
             for i in range(N_RINGS)]
    sidecar = RingSidecar(rings, plan_a, lists, max_batch=MAX_BATCH,
                          pipeline_depth=DEPTH, replicas=4)
    chips: dict = {}
    dispatch = sidecar._dispatch

    def watched_dispatch(*args, **kwargs):
        entry = dispatch(*args, **kwargs)
        for r, part in entry[0]:
            for t in part["ticket"]:
                chips[(rings.index(r), int(t))] = entry[-1].device
        return entry

    sidecar._dispatch = watched_dispatch
    worker = threading.Thread(target=sidecar.run, daemon=True)
    try:
        sidecar.warm_replicas()
        worker.start()

        def serve(burst):
            start = sidecar.processed
            sent = [{} for _ in rings]
            for i, tup in enumerate(burst):
                sent[i % N_RINGS][_enqueue(rings[i % N_RINGS], tup)] = tup
            deadline = time.monotonic() + 120
            while sidecar.processed < start + len(burst):
                assert time.monotonic() < deadline, "the burst never ended"
                time.sleep(0.01)
            return sent, [_verdicts(ring) for ring in rings]

        sent1, got1 = serve(traffic[:4 * MAX_BATCH])
        assert sidecar.request_swap(plan_b, lists).wait(60)
        assert sidecar.ruleset_epoch == 1
        sent2, got2 = serve(second)
    finally:
        sidecar.stop()
        worker.join(10)
        for ring in rings:
            ring.close()
    for sent, got, plan in ((sent1, got1, plan_a), (sent2, got2, plan_b)):
        for r in range(N_RINGS):
            assert {t: v for t, v in got[r].items()} == \
                {t: [_want(plan, tup)] for t, tup in sent[r].items()}
    # the second burst went to every chip, and each blocked it
    after = {chips[(r, t)] for r in range(N_RINGS) for t in sent2[r]}
    assert after == {0, 1, 2, 3}
    # ... on its own copy of plan B's tables
    assert len(sidecar._replica_tables) == 4
    assert sidecar.plan is plan_b


@needs_native
def test_each_chip_pads_the_rows_shipped_to_it(tmp_path, monkeypatch):
    """At a 512-row batch, a batch of up to 64 or 256 live rows ships
    that many and its chip pads them: each pad's input and output live
    on the batch's chip, `pingoo_staged_rows_total` and
    `pingoo_staged_bytes_total` add up to what was shipped, every row
    gets the interpreter's verdict, and after the boot's warm-up no
    batch at any rung adds to `pingoo_compile_total{plane="sidecar"}`."""
    from pingoo_tpu.engine.batch import upload_rows
    from pingoo_tpu.native_ring import Ring, RingSidecar
    from pingoo_tpu.obs import REGISTRY, schema
    from pingoo_tpu.obs.perf import (COMPILE_FN_KINDS,
                                     reset_compile_ledger_for_tests)

    for k in KNOBS:
        monkeypatch.delenv(k, raising=False)
    for k, v in STAGING.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setenv("PINGOO_PERF_LEDGER", str(tmp_path / "ledger"))
    reset_compile_ledger_for_tests()

    def compiles():
        return sum(REGISTRY.counter(
            "pingoo_compile_total",
            schema.PERF_METRICS["pingoo_compile_total"],
            labels={"plane": "sidecar", "fn": fn, "kind": kind}).value
            for fn in COMPILE_FN_KINDS for kind in ("cold", "warm"))

    batch, waves = 512, (5, 60, 100, 200, 300)
    _, lists, traffic = _corpus()
    plan = _plan()
    rings = [Ring(str(tmp_path / f"ring_{i}"), capacity=1024, create=True)
             for i in range(N_RINGS)]
    try:
        sidecar = RingSidecar(rings, plan, lists, max_batch=batch,
                              pipeline_depth=DEPTH, replicas=4)
        sidecar.warm_replicas()
        assert sorted(sidecar._pad_fns) == [64, 256]
        pads: list = []     # (rows, input devices, output devices)
        for rows, pad in list(sidecar._pad_fns.items()):
            def watched(x, pad=pad, rows=rows):
                out = pad(x)
                pads.append((rows, x.devices(), out.devices()))
                return out
            sidecar._pad_fns[rows] = watched
        launched: list = []  # (live rows, chip)
        dispatch = sidecar._dispatch

        def watched_dispatch(parts, n, *args, **kwargs):
            entry = dispatch(parts, n, *args, **kwargs)
            launched.append((n, entry[-1].device))
            return entry

        sidecar._dispatch = watched_dispatch
        compiled, st0 = compiles(), sidecar.stats()
        sent = [{} for _ in rings]
        total = 0
        for size in waves:
            for i in range(total, total + size):
                r = i % N_RINGS
                sent[r][_enqueue(rings[r], traffic[i % len(traffic)])] = \
                    traffic[i % len(traffic)]
            total += size
            sidecar.run(max_requests=total)
        st1 = sidecar.stats()
        got = [_verdicts(ring) for ring in rings]
    finally:
        sidecar.stop()
        for ring in rings:
            ring.close()
        reset_compile_ledger_for_tests()
    for r in range(N_RINGS):
        assert {t: v for t, v in got[r].items()} == \
            {t: [_want(plan, tup)] for t, tup in sent[r].items()}
    assert compiled > 0 and compiles() == compiled  # the boot's alone
    shipped = [upload_rows(n, batch) for n, _ in launched]
    assert {64, 256, batch} <= set(shipped)
    local = sidecar._replica_devices
    padded_on = [chip for (n, chip), rows in zip(launched, shipped)
                 if rows < batch]
    assert [rows for rows in shipped if rows < batch] == \
        [rows for rows, _, _ in pads]
    assert set(padded_on) == {0, 1, 2, 3}
    for chip, (_, into, out) in zip(padded_on, pads):
        assert into == out == {local[chip]}
    width = sidecar._staging.packed_width(sidecar._stage_caps)
    rows_delta = {k: st1["staged_rows"][k] - st0["staged_rows"][k]
                  for k in ("uploaded", "padded")}
    assert rows_delta == {"uploaded": sum(shipped),
                          "padded": batch * len(launched)}
    assert st1["staged_bytes"]["compact"] - st0["staged_bytes"]["compact"] \
        == sum(shipped) * width


def _swap_request(i):
    from pingoo_tpu.engine.batch import RequestTuple

    return RequestTuple(host="www.example.com", url=f"/swap/{i}?page={i}",
                        path=f"/swap/{i}", user_agent="Mozilla/5.0",
                        ip=f"10.0.{i // 250}.{i % 250 + 1}")


@pytest.mark.parametrize("case", ["more-than-the-host", "with-a-mesh"])
def test_the_sidecar_refuses_what_it_cannot_place(monkeypatch, case):
    import jax

    from pingoo_tpu.native_ring import replica_devices

    assert replica_devices(1) == [None]
    assert replica_devices(4) == jax.local_devices()[:4]
    with pytest.raises(ValueError, match="--replicas"):
        if case == "more-than-the-host":
            replica_devices(len(jax.local_devices()) + 1)
        else:
            monkeypatch.setenv("PINGOO_MESH", "2x1x1")
            replica_devices(2)


@pytest.mark.parametrize("case", ["more-than-the-host", "with-a-mesh"])
def test_the_boot_refuses_what_it_cannot_place(tmp_path, case):
    """`python -m pingoo_tpu --replicas N` exits 2 before the boot line
    where the host has fewer than N devices or PINGOO_MESH spans
    several: no code stands in for an absent chip."""
    cfg = tmp_path / "pingoo.yml"
    cfg.write_text(
        "listeners:\n  http:\n    address: http://127.0.0.1:9\n"
        "services:\n  app:\n    http_proxy: [http://127.0.0.1:9]\n"
        "rules:\n  env:\n"
        "    expression: http_request.path.starts_with(\"/.env\")\n"
        "    actions: [{action: block}]\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PINGOO_MESH", None)
    replicas = "5" if case == "more-than-the-host" else "2"
    if case == "with-a-mesh":
        env["PINGOO_MESH"] = "2x1x1"
    proc = subprocess.run(
        [sys.executable, "-m", "pingoo_tpu", "--config", str(cfg),
         "--no-docker", "--native-plane", "--state-dir",
         str(tmp_path / "state"), "--captcha-jwks",
         str(tmp_path / "jwks.json"), "--replicas", replicas],
        cwd=REPO, env=env, timeout=120, capture_output=True, text=True)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert f"--replicas {replicas}" in proc.stderr
    assert "starting pingoo-tpu" not in proc.stderr
