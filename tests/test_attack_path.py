"""The served path under a scanning campaign (ISSUE 35): a third of the
rows are attacks, a tenth of the clients are on the IP list, and every
403 ends its connection.

  * a sidecar over three rings: every ticket's verdict is the
    interpreter's, and the cascade's row counters (`sidecar.stats()`
    `cascade`, the registry's `pingoo_cascade_*`) add up to the rows
    posted: live = every row once a bank, recheck <= candidate <= live;
  * two httpd workers, 64 client connections that reconnect after each
    403: every status is the interpreter's, `accepted` counts the
    connections the clients opened, `closed_after_block` and `blocked`
    the 403s, whichever worker answers the scrape.
"""

from __future__ import annotations

import random
import socket
import threading

import pytest

from pingoo_tpu import native_ring
from pingoo_tpu.engine.batch import RequestTuple
from pingoo_tpu.obs import REGISTRY
from test_native_plane import NativeStack, recv_one_response
from test_native_workers import (_check_totals, _enqueue, _rings, _scrape,
                                 _verdicts, _want_action)

pytestmark = pytest.mark.skipif(
    not native_ring.ensure_built(), reason="native toolchain unavailable")

# 60 rules: the url and path DFAs are merged ones, so rows are rechecked
SIZES = dict(num_rules=60, seed=20260728, list_sizes=(64, 16))


@pytest.fixture(scope="module")
def ruleset():
    from pingoo_tpu.compiler import compile_ruleset
    from pingoo_tpu.utils.crs import generate_ruleset

    from pingoo_tpu.config.schema import Action, RuleConfig
    from pingoo_tpu.expr import compile_expression

    rules, lists = generate_ruleset(**SIZES)
    # the generator's list rules come after its first 60: add the one
    # the campaign's listed clients meet
    rules = list(rules) + [RuleConfig(
        name="listed", actions=(Action.BLOCK,),
        expression=compile_expression(
            'lists["blocked_ips"].contains(client.ip)'))]
    plan = compile_ruleset(rules, lists)
    assert any(e.dfa_key and not plan.np_tables[e.dfa_key].exact
               for e in plan.scan_plans.values())
    return rules, lists, plan


def _campaign(lists, n: int, seed: int) -> list:
    """n requests, 30 % attack rows, every tenth from a listed address."""
    from pingoo_tpu.utils.crs import generate_traffic

    reqs = generate_traffic(n, attack_fraction=0.3, seed=seed, lists=lists)
    members = [str(ip.addr) for ip in lists["blocked_ips"]
               if ip.addr is not None]
    rng = random.Random(seed)
    for tup in reqs[::10]:
        tup.ip = rng.choice(members)
    return reqs


def test_three_rings_under_a_campaign(tmp_path, monkeypatch, ruleset):
    monkeypatch.setenv("PINGOO_STAGING", "compact")   # one program pair
    _, lists, plan = ruleset
    reqs = _campaign(lists, 600, seed=35)
    rings = _rings(tmp_path, 3)
    sidecar = native_ring.RingSidecar(rings, plan, lists, max_batch=64)
    before = sidecar.stats()["cascade"]
    assert set(before) >= {"url", "path"}
    drain = threading.Thread(target=sidecar.run, daemon=True,
                             kwargs={"max_requests": len(reqs)})
    sent: list = [{} for _ in rings]      # ring -> ticket -> request

    def enqueuer(w):
        for tup in reqs[w::3]:
            sent[w][_enqueue(rings[w], tup)] = tup

    feeders = [threading.Thread(target=enqueuer, args=(w,))
               for w in range(3)]
    drain.start()
    for t in feeders:
        t.start()
    try:
        for t in feeders:
            t.join(120)
        got = [_verdicts(ring, len(sent[w]), timeout=300.0)
               for w, ring in enumerate(rings)]
        drain.join(120)
    finally:
        sidecar.stop()
    blocked = 0
    for w in range(3):
        assert set(got[w]) == set(sent[w]), f"ring {w}"
        for ticket, tup in sent[w].items():
            want = _want_action(plan, lists, tup)
            assert got[w][ticket] == want, (w, ticket, tup)
            blocked += want == 1
    listed = sum(_want_action(plan, lists, tup) for tup in reqs[::10])
    assert listed == 60          # the list lane decided a tenth
    assert 0.2 * 600 < blocked < 0.6 * 600
    after = sidecar.stats()["cascade"]
    rechecked = 0
    for bank, now in after.items():
        d = {k: now[k] - before[bank][k] for k in now}
        assert d["live"] == 600, bank       # every row posted, once a bank
        assert 0 <= d["recheck"] <= d["candidate"] <= d["live"], (bank, d)
        assert d["recheck"] <= d["recheck_bucket"], (bank, d)
        assert d["candidate"] <= d["candidate_bucket"], (bank, d)
        for stage in ("live", "candidate", "recheck"):
            assert REGISTRY.counter(
                "pingoo_cascade_rows_total",
                labels={"plane": "sidecar", "bank": bank,
                        "stage": stage}).value == now[stage]
        rechecked += d["recheck"]
    assert rechecked > 0
    for ring in rings:
        ring.close()


def _client(port, requests, opened: list) -> list:
    """One client address's requests, one in flight, keep-alive; a 403
    closes the connection and the client reconnects for its next one."""
    statuses, c = [], None
    for path, ua in requests:
        if c is None:
            c = socket.create_connection(("127.0.0.1", port), timeout=300)
            opened.append(1)
        c.sendall(f"GET {path} HTTP/1.1\r\nhost: w.test\r\n"
                  f"user-agent: {ua}\r\n\r\n".encode())
        statuses.append(int(recv_one_response(c).split(b" ", 2)[1]))
        if statuses[-1] == 403:
            c.close()
            c = None
    if c is not None:
        c.close()
    return statuses


def test_two_workers_block_and_reconnect(tmp_path, monkeypatch, ruleset):
    from pingoo_tpu.utils.crs import generate_traffic

    rules, lists, plan = ruleset
    monkeypatch.setenv("PINGOO_STAGING", "compact")
    stack = NativeStack(tmp_path, rules, lists, workers=2, max_batch=16,
                        env={"PINGOO_VERDICT_TIMEOUT_MS": "300000",
                             "PINGOO_SIDECAR_TIMEOUT_MS": "300000"})
    try:
        reqs = [r for r in generate_traffic(400, attack_fraction=0.8,
                                            seed=37)
                if " " not in r.url and r.user_agent][:256]
        conns = [reqs[i::64] for i in range(64)]
        results: list = [None] * 64
        opened: list = []          # list.append is atomic

        def client(i):
            results[i] = _client(
                stack.port, [(r.url, r.user_agent) for r in conns[i]],
                opened)

        m0 = _scrape(stack.port)
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(64)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        sent = blocked = 0
        for i, statuses in enumerate(results):
            assert statuses is not None and len(statuses) == len(conns[i]), i
            for r, status in zip(conns[i], statuses):
                seen = RequestTuple(
                    host="w.test", url=r.url, path=r.url.split("?")[0],
                    method="GET", user_agent=r.user_agent, ip="127.0.0.1",
                    remote_port=0, asn=0, country="XX")
                want = 403 if _want_action(plan, lists, seen) == 1 else 200
                assert status == want, (i, r.url, r.user_agent)
                sent += 1
                blocked += want == 403
        assert 0.3 < blocked / sent < 0.7, (blocked, sent)
        # every 403 but a connection's last is followed by a reconnect
        assert 64 < len(opened) <= 64 + blocked

        scrapes = [_scrape(stack.port) for _ in range(12)]
        assert {m["answered_by"] for m in scrapes} == {0, 1}
        for k, m in enumerate(scrapes):
            _check_totals(m)
            assert m["fail_open"] == 0
            assert m["blocked"] - m0["blocked"] == blocked
            assert m["closed_after_block"] - m0["closed_after_block"] \
                == blocked
            # the clients' connections and the scrapes up to this one
            assert m["accepted"] - m0["accepted"] == len(opened) + k + 1
            for key in ("accepted", "closed_after_block"):
                assert m[key] == sum(p[key] for p in m["per_worker"]), key
        text = _raw_text(stack.port)
        last = _scrape(stack.port)
        assert (f'pingoo_closed_after_block_total{{plane="native"}} '
                f'{last["closed_after_block"]}') in text
        for w in range(2):
            assert f'pingoo_worker_accepted_total{{plane="native",' \
                   f'worker="{w}"}} ' in text
        assert sum(p["accepted"] > 0 for p in last["per_worker"]) == 2
    finally:
        stack.stop()


def _raw_text(port) -> str:
    from pingoo_tpu.obs.registry import lint_prometheus_text
    from test_native_httpd import _raw_get

    text = _raw_get(port, "/__pingoo/metrics").partition(
        b"\r\n\r\n")[2].decode()
    assert lint_prometheus_text(text) == []
    return text
