"""The `attack` request mix and the deployment it is offered to (PR 35):
the mix builds from a seed, the plain reference blocks a quarter of it
from the cell's 1,024 client addresses, a tenth of which are on the
million-entry list, and the six near-misses are answered 200."""

import json
import os

import numpy as np

from conftest import BENCH
from lib import harness
from lib.reference import Reference
from lib.rules import rule_sources
from lib.traffic import Mix

CELL = "crs500l1m.web_attack"
NEAR_MISSES = ("q=union+selection+committee", "page=selected-items-from-cart",
               "view=onloading-screen", "sort=group-by-having-fun",
               "u=wget-http-guide", "q=information-schema-design")


def _load(*parts):
    with open(os.path.join(BENCH, *parts), encoding="utf-8") as f:
        return json.load(f)


def test_the_cell_is_what_the_issue_names():
    cell = harness.Cell(CELL)
    assert cell.closed and cell.chips == 1
    assert cell.traffic["connections"] == 1024
    assert cell.traffic["sequence_rps"] == 16000
    assert cell.requests["payloads"]["share"] == 0.30
    assert cell.requests["clients"]["listed_share"] == 0.10
    assert cell.config["rules"]["list_sizes"] == [1048576, 65536]
    assert cell.config["native_workers"] == 4 and cell.config["reduced"] == []
    # crs500w4's deployment but for the lists, web's mix but for the campaign
    w4 = _load("configs", "crs500w4.json")
    for key in ("action", "max_batch", "fail_open_deadline_ms", "server_args",
                "env", "probe", "control", "native_workers"):
        assert cell.config[key] == w4[key], key
    assert {k: v for k, v in cell.config["rules"].items()
            if k != "list_sizes"} == \
        {k: v for k, v in w4["rules"].items() if k != "list_sizes"}
    assert cell.config["guarantees"][:len(w4["guarantees"])] == \
        w4["guarantees"]
    web = _load("traffic", "mixes", "web.json")
    mix = cell.requests
    for key in ("shape_seed", "pool", "methods", "hosts", "path", "query",
                "user_agents"):
        assert mix[key] == web[key], key
    assert mix["payloads"]["query"][:12] == web["payloads"]["query"]
    assert tuple(mix["payloads"]["query"][12:]) == NEAR_MISSES
    assert mix["payloads"]["path_head"] == web["payloads"]["path_head"]
    assert mix["payloads"]["path_tail"] == web["payloads"]["path_tail"]
    assert "sensitivity" not in mix
    names = set(cell.metric_names("per_layer"))
    assert {"blocked_share.pooled", "cascade_candidate_share.pooled",
            "recheck_rows_share.pooled", "recheck_bucket_fill.pooled",
            "recheck_device_ms_per_batch.pooled",
            "accepts_per_request.pooled", "lanes_roofline.pooled",
            "scan_rows_share.pooled", "rings_per_batch.pooled"} <= names
    assert cell.metric_names("end_to_end") == [
        "inspected_rps", "inspected_share", "setup_s"]


def test_the_reference_blocks_a_quarter_and_passes_the_near_misses():
    cell = harness.Cell(CELL)
    sources, lists = rule_sources(cell.config["rules"])
    assert len(lists["blocked_ips"]) == 1048576
    mix = Mix(cell.requests)
    seed = 2 ** 31 + 35
    pool = mix.templates(seed)
    assert pool == Mix(cell.requests).templates(seed)    # from the seed
    assert pool != mix.templates(seed + 1)
    listed = [i for items in lists.values() for i in items
              if isinstance(i, str)]
    addresses = mix.addresses(1024, listed)
    assert len(np.unique(addresses)) == 1024
    reference = Reference(sources, lists)
    # each template from an address that is on no list, then each of the
    # 1,024 addresses sending a template that no rule blocks
    unlisted = 0x7F090909          # 127.9.9.9
    every = np.arange(mix.n, dtype=np.uint32)
    by_rule = reference.statuses(
        pool, every, np.full(mix.n, unlisted, np.uint32)) == 403
    passed = int(np.flatnonzero(~by_rule)[0])
    by_list = reference.statuses(
        pool, np.full(1024, passed, np.uint32), addresses) == 403
    assert 100 <= by_list.sum() <= 110         # a tenth of the clients
    rule_share = float(mix.weights[by_rule].sum())
    share = rule_share + (1 - rule_share) * float(by_list.mean())
    assert 0.20 <= share <= 0.40, (rule_share, float(by_list.mean()))
    for miss in NEAR_MISSES:
        hits = [i for i, t in enumerate(pool) if miss in t["url"]]
        assert hits, miss
        assert not by_rule[hits].any(), miss
    # a closed loop's sequence sees the same share
    seq = mix.sequence(seed, 50000)
    seen = reference.statuses(pool, seq, addresses[np.arange(50000) % 1024])
    assert abs(float((seen == 403).mean()) - share) < 0.03
