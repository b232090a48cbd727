"""The plain reference: its own semantics, its agreement with a second
witness (the program's interpreter - imported here, in a test, never by
the reference), and the control, which has to come out NOT correct."""

import json
import os

import numpy as np
import pytest

from conftest import BENCH
from lib import reduce
from lib.reference import Reference, ReferenceError_, client_of
from lib.rules import rule_sources
from lib.traffic import Mix


def _load(kind, name):
    with open(os.path.join(BENCH, kind, f"{name}.json"), encoding="utf-8") as f:
        return json.load(f)


def _req(url, ua="Mozilla/5.0", method="GET"):
    return {"method": method, "host": "www.example.com", "url": url,
            "user_agent": ua}


def test_semantics():
    ref = Reference([
        ("esc", r'http_request.url.matches("(?i)\\bunion\\s+select\\b")'),
        ("pre", 'http_request.path.starts_with("/.env")'),
        ("geo", '(client.country == "RU" || client.country == "XX") && '
                'http_request.path.starts_with("/admin")'),
        ("len", "http_request.path.length() > 20"),
        ("err", 'http_request.path > 3'),            # raises: a no-match
        ("lst", 'lists["ips"].contains(client.ip)'),
        ("quo", 'http_request.url.contains("a\\"b")'),
    ], {"ips": ["10.0.0.0/8", "127.0.0.2"]})
    assert ref.status(_req("/x?q=1 UNION  select 2")) == 403
    assert ref.status(_req("/.env")) == 403
    assert ref.status(_req("/x/.env")) == 200          # the path, not the url
    assert ref.status(_req("/admin/x")) == 403         # country XX on loopback
    assert ref.status(_req("/" + "a" * 20)) == 403
    assert ref.status(_req("/" + "a" * 19)) == 200
    assert ref.status(_req('/q?a"b')) == 403
    assert ref.status(_req("/"), dict(ref.client, ip="10.1.2.3")) == 403
    assert ref.status(_req("/"), dict(ref.client, ip="127.0.0.3")) == 200
    assert ref.status(_req("/"), client_of((127 << 24) + 2)) == 403


def test_many_pairs_at_once_read_as_one_by_one():
    """`statuses` splits the rules by what they read (the request, the
    client, both); it has to give what `status` gives pair by pair."""
    ref = Reference([
        ("pre", 'http_request.path.starts_with("/.env")'),
        ("both", 'lists["ips"].contains(client.ip) && '
                 'http_request.path.starts_with("/admin")'),
        ("lst", 'lists["bad"].contains(client.ip)'),
        ("port", "client.remote_port < 1024"),
    ], {"ips": ["127.9.0.0/16"], "bad": ["127.0.0.7", "127.1.2.0/24"]})
    pool = [_req("/"), _req("/.env"), _req("/admin/x"), _req("/a?b=/.env")]
    addrs = np.array([0, (127 << 24) + 7, (127 << 24) + (9 << 16) + 5,
                      (127 << 24) + (1 << 16) + (2 << 8) + 200,
                      (127 << 24) + 99], np.uint32)
    tmpl, addr = np.meshgrid(np.arange(len(pool)), addrs)
    tmpl, addr = tmpl.ravel(), addr.ravel()
    one_by_one = [ref.status(pool[t], None if a == 0 else client_of(a))
                  for t, a in zip(tmpl, addr)]
    assert ref.statuses(pool, tmpl, addr).tolist() == one_by_one
    assert sorted(set(one_by_one)) == [200, 403]
    cut = Reference(ref_sources := [("pre", 'http_request.path.starts_with("/.env")')],
                    {}, caps={"path": 3})
    assert cut.statuses(pool, np.arange(4), np.zeros(4, np.uint32)).tolist() \
        == [cut.status(t) for t in pool] == [200, 200, 200, 200]
    assert Reference(ref_sources, {}).status(pool[1]) == 403


def test_uncovered_grammar_is_an_error_not_a_no_match():
    with pytest.raises(ReferenceError_):
        Reference([("x", 'http_request.path.lower() == "a"')], {})
    with pytest.raises(ReferenceError_):
        Reference([("x", 'http_request.path in ["a"]')], {})


@pytest.mark.parametrize("config", ["crs500", "prefix10"])
def test_agrees_with_the_programs_interpreter(config):
    pytest.importorskip("pingoo_tpu.expr")
    from pingoo_tpu.engine.batch import RequestTuple, tuple_to_context
    from pingoo_tpu.expr import Ip, compile_expression, execute_as_bool

    sources, lists = rule_sources(_load("configs", config)["rules"])
    ref = Reference(sources, lists)
    programs = [compile_expression(src) for _, src in sources]
    their_lists = {k: [Ip(x) for x in v] if isinstance(v[0], str) else v
                   for k, v in lists.items()}
    spec = dict(_load("traffic/mixes", "web"))
    spec["pool"] = dict(spec["pool"], templates=400)
    blocked = 0
    for t in Mix(spec).templates(123456789012 % (2 ** 32)):
        tup = RequestTuple(host=t["host"], url=t["url"],
                           path=t["url"].split("?", 1)[0],
                           method=t["method"], user_agent=t["user_agent"],
                           ip="127.0.0.1", remote_port=40000, asn=0,
                           country="XX")
        ctx = tuple_to_context(tup, their_lists)
        theirs = 200
        for program in programs:
            try:
                if execute_as_bool(program, ctx):
                    theirs = 403
                    break
            except Exception:
                continue
        assert ref.status(t) == theirs, t
        blocked += theirs == 403
    assert blocked > 0


@pytest.mark.parametrize("cell", ["crs500.web_pooled", "prefix10.web_pooled",
                                  "crs500.web_steady"])
@pytest.mark.parametrize("seed", [1, 77, 3000000019])
def test_the_control_comes_out_not_correct(cell, seed):
    import control

    line = control.control_line(cell, seed, seconds=8.0)
    assert line["control_correct"] is False
    assert line["compared"]["missed_blocks"]["value"] > 0


def _records(statuses, waited_ms):
    from lib.harness import RECORD
    rec = np.zeros(len(statuses), dtype=RECORD)
    rec["status"] = statuses
    rec["done_ns"] = (np.asarray(waited_ms) * 1e6).astype(np.int64)
    return rec


def test_a_release_is_late_not_wrong_and_excuses_only_its_own_response():
    want = np.array([200, 403, 403, 200], np.uint16)
    # one missed block, answered in 12 ms: no release can have been that
    # quick, however many the native plane counted
    rec = _records([200, 200, 403, 200], [12, 12, 12, 12])
    cmp = reduce.compare(rec, want, releases=5, deadline_ms=3000)
    correct, compared = reduce.decide(cmp)
    assert correct is False and compared["missed_blocks"] == (1, 0)
    assert "fail_open" not in compared and cmp["fail_open"] == 5
    # the same answer after the deadline's wait, one release counted:
    # late and uninspected, as the configuration states; not wrong
    rec = _records([200, 200, 403, 200], [12, 3004, 12, 12])
    cmp = reduce.compare(rec, want, releases=1, deadline_ms=3000)
    assert reduce.decide(cmp)[0] is True
    assert (cmp["missed_blocks"], cmp["missed_released"]) == (0, 1)
    # no release counted: nothing is excused, whatever the wait
    assert reduce.decide(reduce.compare(rec, want, 0, 3000))[0] is False
    # counters unread: the wait alone speaks
    assert reduce.decide(reduce.compare(rec, want, None, 3000))[0] is True
    # two waited, one release counted: one of them is a wrong answer
    rec = _records([200, 200, 200, 200], [12, 3004, 3100, 12])
    cmp = reduce.compare(rec, want, releases=1, deadline_ms=3000)
    assert reduce.decide(cmp)[0] is False and cmp["missed_blocks"] == 1
    # degraded mode releases at once: then the count alone bounds them
    rec = _records([200, 200, 403, 200], [12, 40, 12, 12])
    assert reduce.decide(reduce.compare(rec, want, 1, 3000, True))[0] is True
    assert reduce.decide(reduce.compare(rec, want, 0, 3000, True))[0] is False
    # a false block is never excused
    rec = _records([403, 403, 403, 200], [3004, 3004, 3004, 3004])
    cmp = reduce.compare(rec, want, 9, 3000, True)
    assert reduce.decide(cmp)[0] is False and cmp["false_blocks"] == 1
    # a release alone, every answer right: correct, and counted
    rec = _records(want, [12, 12, 12, 3004])
    assert reduce.decide(reduce.compare(rec, want, 1, 3000))[0] is True


def test_an_answer_that_never_comes_is_not_correct():
    want = np.array([200, 403, 403, 200], np.uint16)
    rec = _records(want, [12, 12, 12, 12])
    assert reduce.decide(reduce.compare(rec, want, 0, 3000))[0] is True
    for outcome in (reduce.CONN_LOST, reduce.NO_ANSWER):
        rec["outcome"][3] = outcome
        correct, compared = reduce.decide(reduce.compare(rec, want, 0, 3000))
        assert correct is False and compared["never_answered"] == (1, 0)
    rec["outcome"][3] = reduce.UNSENT       # the generator's, not the system's
    assert reduce.decide(reduce.compare(rec, want, 0, 3000))[0] is True
    assert reduce.decide(reduce.compare(rec[:0], want[:0], 0))[0] is False
