"""The generator of traffic: the seed changes the order, not the work."""

import json
import os

import numpy as np

from conftest import BENCH
from lib.traffic import Mix, wire_request


def _mix(templates=256):
    with open(os.path.join(BENCH, "traffic", "mixes", "web.json"),
              encoding="utf-8") as f:
        spec = json.load(f)
    spec["pool"] = dict(spec["pool"], templates=templates)
    return Mix(spec)


def test_same_seed_same_inputs_and_large_seeds():
    mix = _mix()
    big = 2 ** 31 + 12345
    assert mix.templates(big) == mix.templates(big)
    a, b = mix.schedule(big, 500, 2.0), mix.schedule(big, 500, 2.0)
    assert (a[0] == b[0]).all() and (a[1] == b[1]).all()


def test_seeds_offer_the_same_work_in_another_order():
    mix = _mix()
    t1, t2 = mix.templates(1), mix.templates(2)
    assert t1 != t2
    for a, b in zip(t1, t2):                      # the same sizes, row by row
        assert (a["method"], a["host"], len(a["url"]), a["user_agent"]) == \
            (b["method"], b["host"], len(b["url"]), b["user_agent"])
    d1, r1 = mix.schedule(1, 500, 2.0)
    d2, r2 = mix.schedule(2, 500, 2.0)
    assert (r1 != r2).any()
    assert (np.sort(r1) == np.sort(r2)).all()     # the same multiset of ranks
    g1, g2 = np.diff(d1), np.diff(d2)
    assert np.allclose(np.sort(g1), np.sort(g2), atol=2)   # and of gaps (ns)
    assert len(d1) == 1000 and d1[0] == 0 and d1[-1] < 2e9
    assert (np.diff(d1) >= 0).all()


def test_a_closed_loops_sequence_is_the_same_multiset_in_another_order():
    mix = _mix()
    s1, s2 = mix.sequence(1, 5000), mix.sequence(2 ** 31 + 7, 5000)
    assert (s1 != s2).any() and (np.sort(s1) == np.sort(s2)).all()
    assert (mix.sequence(1, 5000) == s1).all() and s1.dtype == np.uint32
    assert (mix.sequence(1, 5000, salt=3) != s1).any()
    top = np.bincount(s1, minlength=mix.n)[:4].sum() / 5000
    assert top == np.float64(top) and 0.2 < top < 0.5    # Zipf's head


def test_lengths_follow_the_mix_file():
    mix = _mix(2048)
    pool = mix.templates(5)
    urls = np.array([len(t["url"]) for t in pool])
    paths = np.array([len(t["url"].split("?", 1)[0]) for t in pool])
    assert urls.max() <= 1024
    assert 20 <= np.median(paths) <= 30 and 30 <= np.median(urls) <= 60
    assert 150 <= np.quantile(urls, 0.95) <= 320
    uas = np.array([len(t["user_agent"]) for t in pool])
    assert 100 <= np.median(uas) <= 140 and 10 <= uas.min() and uas.max() <= 256
    shares = {m: sum(mix.weights[i] for i, t in enumerate(pool)
                     if t["method"] == m) for m in ("GET", "POST", "HEAD")}
    assert shares["GET"] > 0.8 and shares["POST"] > 0 and shares["HEAD"] > 0
    assert 0.01 < mix.weights[mix.attack].sum() < 0.04
    assert 0.02 < mix.weights[mix.scanner].sum() < 0.05


def test_clients_are_the_mixs_own_and_some_are_listed():
    mix = _mix()
    net = (127 << 24)
    listed = ["127.3.4.5", "127.200.1.0/24", "10.0.0.1", "8.8.8.0/24"]
    a = mix.addresses(4096, listed)
    assert (a == mix.addresses(4096, listed)).all()     # no --seed in it
    assert a.dtype == np.uint32 and len(a) == 4096
    assert ((a >> 24) == 127).all() and len(set(a.tolist())) == 1024
    assert (np.bincount(np.unique(a, return_inverse=True)[1]) == 4).all()
    on_list = [x for x in set(a.tolist())
               if x == net + (3 << 16) + (4 << 8) + 5
               or (x >> 8) == (net + (200 << 16) + (1 << 8)) >> 8]
    assert len(on_list) == 2          # both candidates inside the net, no other
    assert len(set(mix.addresses(64, []).tolist())) == 64
    spec = dict(mix.spec)
    del spec["clients"]
    assert (Mix(spec).addresses(8) == 0).all()


def test_wire_bytes():
    raw = wire_request({"method": "POST", "host": "h", "url": "/a b?x=<y>",
                        "user_agent": "ua"})
    assert raw == (b"POST /a b?x=<y> HTTP/1.1\r\nhost: h\r\nuser-agent: ua"
                   b"\r\ncontent-length: 0\r\n\r\n")
