"""chip_smoke.py's non-device parts, on CPU (ISSUE 21).

The smoke itself needs the accelerator (and fails without one); what a
CPU run CAN hold it to: the deployment it writes is the deployment the
generator describes (round trip through load_and_validate), and its
status oracle — the `expr` interpreter over the rule sources — agrees
with the engine's own oracle, interpret_rules_row + action_lanes, on
seeded traffic as the server would see it.
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from pingoo_tpu.compiler import compile_ruleset  # noqa: E402
from pingoo_tpu.config import load_and_validate  # noqa: E402
from pingoo_tpu.config.schema import Action  # noqa: E402
from pingoo_tpu.engine.batch import tuple_to_context  # noqa: E402
from pingoo_tpu.engine.verdict import (action_lanes,  # noqa: E402
                                       interpret_rules_row)
from pingoo_tpu.lists import load_lists  # noqa: E402
from pingoo_tpu.utils.crs import (generate_rule_sources,  # noqa: E402
                                  generate_ruleset, generate_traffic)

SEED = 20260728
SIZES = dict(num_rules=80, seed=SEED, list_sizes=(256, 32))


def test_deployment_round_trips_through_the_config_loader(tmp_path):
    sources, lists = generate_rule_sources(**SIZES)
    path = chip_smoke.write_deployment(str(tmp_path), 18080, 18081,
                                       sources, lists)
    config = load_and_validate(path)
    assert [r.name for r in config.rules] == [n for n, _ in sources]
    assert all(r.actions == (Action.BLOCK,) for r in config.rules)
    (listener,) = config.listeners
    assert (listener.host, listener.port) == ("127.0.0.1", 18080)
    (service,) = config.services
    assert service.name == "pong" and service.route is None
    loaded = load_lists(config.lists)
    assert {k: [str(i) for i in v] for k, v in loaded.items()} == \
        {k: [str(i) for i in v] for k, v in lists.items()}
    # ... and it is the ruleset the tests' generator compiles.
    rules, _ = generate_ruleset(**SIZES)
    assert [r.name for r in rules] == [r.name for r in config.rules]


def test_status_oracle_agrees_with_the_engine_oracle():
    sources, lists = generate_rule_sources(**SIZES)
    rules, _ = generate_ruleset(**SIZES)
    plan = compile_ruleset(rules, lists)
    expected_status = chip_smoke.make_oracle(sources, lists)
    reqs = generate_traffic(200, attack_fraction=0.3, seed=SEED + 1,
                            lists=lists)
    blocked = 0
    for i, req in enumerate(reqs):
        # Both views of a request: as generated, and as the server sees
        # it on loopback (peer address, unknown asn/country).
        for tup in (req, chip_smoke.served_tuple(req, 40000 + i)):
            row = interpret_rules_row(plan, tuple_to_context(tup, lists))
            unverified, _ = action_lanes(plan, row[None, :])
            want = 403 if unverified[0] == 1 else 200
            assert expected_status(tup) == want, (i, tup)
            blocked += want == 403
    assert 0 < blocked < 2 * len(reqs)
    served = chip_smoke.served_tuple(reqs[0], 41234)
    assert (served.ip, served.remote_port, served.asn, served.country) == \
        ("127.0.0.1", 41234, 0, "XX")
    assert chip_smoke.wire_request(reqs[0]).startswith(
        f"{reqs[0].method} {reqs[0].url} HTTP/1.1\r\n".encode())


def test_prometheus_parse_and_loadgen_mirror():
    samples = chip_smoke.parse_prometheus(
        '# TYPE pingoo_degrade_total counter\n'
        'pingoo_degrade_total{plane="sidecar",rung="dfa"} 2\n'
        'pingoo_degrade_total{plane="python",rung="device"} 0\n'
        'pingoo_requests_total 7\n')
    assert chip_smoke.metric_sum(samples, "pingoo_degrade_total") == 2
    assert chip_smoke.metric_sum(samples, "pingoo_degrade_total",
                                 plane="python") == 0
    assert chip_smoke.metric_sum(samples, "pingoo_requests_total") == 7
    # The mirrored loadgen mix must be the C++ generator's, or the
    # burst's blocked count is held to the wrong number.
    src = open(os.path.join(REPO, "pingoo_tpu", "native",
                            "loadgen_http.cc")).read()
    for url in chip_smoke.LOADGEN_CLEAN + chip_smoke.LOADGEN_ATTACK:
        assert f'"{url}"' in src, url
    assert f"host: {chip_smoke.LOADGEN_HOST}" in src
    assert chip_smoke.LOADGEN_UA in src
    seen = {}
    blocked = chip_smoke.loadgen_expected_blocked(
        2000, 50, lambda tup: seen.setdefault(tup.url, 403 if "<script>"
                                              in tup.url else 200))
    assert blocked == 50  # attacks alternate; one of the two is blocked
    # The last stdout line carries these keys and no others (the driver
    # refuses anything else); the detail record goes on the line before.
    line = chip_smoke.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "extra": 0})
    assert line == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
