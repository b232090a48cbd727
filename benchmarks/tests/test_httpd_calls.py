"""The readers of what a request costs the httpd workers
(`httpd_syscalls_per_request`, `httpd_worker_us_per_request`), on
hand-made snapshots of the native JSON."""

import os

import pytest

from lib import metrics

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")
NEW = ("httpd_syscalls_per_request", "httpd_worker_us_per_request")
PHASES = ("wait", "verdicts", "supervise", "accept", "client", "upstream",
          "tls", "close", "sweep")


def _native(loop, requests, io=None, ctl=None):
    doc = {"workers": 2, "loop_us": dict(loop), "passes": 0,
           "empty_passes": 0,
           "chain_us": dict.fromkeys(("read", "verdict", "upstream",
                                      "reply"), 0),
           "chain_requests": requests, "chain_upstream_requests": requests}
    if io is not None:
        doc.update(io_calls=io, epoll_ctl_calls=ctl,
                   upstream_direct_sends=requests)
    return doc


def _obs(calls=True):
    before = _native(dict.fromkeys(PHASES, 0), 0,
                     *((100, 40) if calls else (None, None)))
    # two workers over 2 s: 4 s of loop time, 3 s of it waiting, 1 s
    # (400 ms client, 450 upstream, 150 verdicts) over 5,000 requests
    loop = dict.fromkeys(PHASES, 0)
    loop.update(wait=3_000_000, client=400_000, upstream=450_000,
                verdicts=150_000)
    after = _native(loop, 5000,
                    *((100 + 25_000, 40 + 10_000) if calls else (None, None)))
    return {"before": {"at_mono": 101.0, "native": before},
            "after": {"at_mono": 103.0, "native": after}}


def _read(name, obs, suffix=".pooled"):
    return metrics.load_reader(METRICS, name + suffix)(obs)


@pytest.mark.parametrize("suffix", [".pooled", ".steady"])
def test_the_readers_read_the_calls_and_the_worker_time(suffix):
    obs = _obs()
    # 25,000 socket calls and 10,000 epoll_ctl over 5,000 requests
    assert _read("httpd_syscalls_per_request", obs, suffix) \
        == pytest.approx(7.0)
    # 1 s of work over 5,000 requests
    assert _read("httpd_worker_us_per_request", obs, suffix) \
        == pytest.approx(200.0)


def test_the_worker_time_reads_on_a_program_without_the_call_counters():
    """The parent's snapshots: a loop account, no call counters."""
    obs = _obs(calls=False)
    assert _read("httpd_syscalls_per_request", obs) is None
    assert _read("httpd_worker_us_per_request", obs) == pytest.approx(200.0)


@pytest.mark.parametrize("name", NEW)
def test_each_reader_reads_none_without_its_counters(name):
    """No loop account, no chain (the commit before either existed), or
    no snapshots at all (a run whose scrapes failed), or no request
    answered in the window."""
    obs = _obs()
    for side in ("before", "after"):
        for key in ("loop_us", "chain_requests", "io_calls",
                    "epoll_ctl_calls"):
            obs[side]["native"].pop(key, None)
    assert _read(name, obs) is None
    assert _read(name, {}) is None
    idle = _obs()
    idle["after"]["native"]["chain_requests"] = 0
    assert _read(name, idle) is None
