#!/usr/bin/env python
"""The engine half of chip_smoke.py, in a process of its own (it holds
the chip while it runs, so chip_smoke starts it only after the servers
have exited):

  * the program the sidecar serves — make_prefilter_fn + make_lane_fn
    (route lane, rule-hit aux lane, donation as the backend gates it) —
    over one 1024-row config-2 batch, merged with the host-rule lanes
    and held row by row to the `expr` interpreter's action lanes;
  * the same program once per staging shape the smoke's checked window
    produced (`--shapes`, (path, url, user_agent) column buckets), each
    on a 1024-row batch cut to that shape;
  * the plan's scan selection: no bank may select the fused Pallas
    kernel, which Mosaic refuses (ops/pallas_scan.py), unless a cost
    was measured for it.

Prints one JSON line; `ok` is false on any mismatch. Runs on whatever
backend JAX gives it (the caller checks that it is the accelerator).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

MAX_BATCH = 1024


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seed", type=int, default=20260728)
    parser.add_argument("--rules", type=int, default=500)
    parser.add_argument("--ip-list", type=int, default=131072)
    parser.add_argument("--asn-list", type=int, default=4096)
    parser.add_argument("--shapes", default="[]",
                        help="JSON list of [path, url, user_agent] buckets")
    args = parser.parse_args(argv)

    from pingoo_tpu.backend import backend_info, place_compile_cache

    place_compile_cache()
    t0 = time.monotonic()
    backend = backend_info()

    import jax
    import numpy as np

    from pingoo_tpu.compiler import compile_ruleset
    from pingoo_tpu.engine.batch import (RequestBatch, batch_to_contexts,
                                         bucket_arrays, encode_requests,
                                         pad_batch)
    from pingoo_tpu.engine.verdict import (action_lanes,
                                           donate_batch_buffers,
                                           host_rule_lanes,
                                           interpret_rules_row,
                                           make_lane_fn, make_prefilter_fn,
                                           merge_lanes)
    from pingoo_tpu.utils.crs import generate_ruleset, generate_traffic

    rules, lists = generate_ruleset(
        args.rules, seed=args.seed,
        list_sizes=(args.ip_list, args.asn_list))
    plan = compile_ruleset(rules, lists)
    selected = {key: entry.strategy.kind + ("+pair" if entry.strategy.pair
                                            else "")
                for key, entry in plan.scan_plans.items()}
    unmeasured_pallas = [
        key for key, entry in plan.scan_plans.items()
        if entry.strategy.kind == "pallas"
        and entry.strategy.source != "measured"]
    tables = plan.device_tables()
    pf = make_prefilter_fn(plan)
    lane_fn = make_lane_fn(plan, service_groups=[["pong"]],
                           with_rule_hits=True,
                           donate=donate_batch_buffers())

    def lanes(arrays: dict, n: int):
        pf_hits, pf_aux = (pf.fn(tables, arrays) if pf is not None
                           else (None, None))
        dev = lane_fn(tables, arrays, pf_hits, np.int32(n), pf_aux)
        # the verdict and route lanes; the rows under them are counts
        # over the whole batch, not a row's verdict
        return np.asarray(jax.block_until_ready(dev))[:3 + 1]

    reqs = generate_traffic(MAX_BATCH, attack_fraction=0.05,
                            seed=args.seed + 2, lists=lists)
    batch = encode_requests(reqs, plan.field_specs)
    staged = pad_batch(RequestBatch(size=batch.size,
                                    arrays=bucket_arrays(batch.arrays)),
                       MAX_BATCH)
    t_first = time.monotonic()
    dev = lanes(staged.arrays, len(reqs))
    first_call_s = time.monotonic() - t_first
    host = host_rule_lanes(plan, staged, lists)
    got_unv, got_vb = merge_lanes(dev, host)
    want = np.stack([interpret_rules_row(plan, ctx)
                     for ctx in batch_to_contexts(batch, lists)])
    want_unv, want_vb = action_lanes(plan, want)
    mismatches = int((got_unv[:len(reqs)] != want_unv).sum()
                     + (got_vb[:len(reqs)] != want_vb).sum())

    # Each staging shape of the smoke's window, on the same rows cut to
    # that shape; the reference is the same program's answer for the
    # rows that fit the shape unchanged (the full-shape run above).
    shape_mismatches = 0
    shapes = [tuple(s) for s in json.loads(args.shapes)]
    for p_len, u_len, ua_len in shapes:
        arrays = dict(staged.arrays)
        for field, cols in (("path", p_len), ("url", u_len),
                            ("user_agent", ua_len)):
            arrays[f"{field}_bytes"] = np.ascontiguousarray(
                staged.arrays[f"{field}_bytes"][:, :cols])
        fits = ((staged.arrays["path_len"] <= p_len)
                & (staged.arrays["url_len"] <= u_len)
                & (staged.arrays["user_agent_len"] <= ua_len))
        cut = lanes(arrays, len(reqs))
        shape_mismatches += int((cut[:, fits] != dev[:, fits]).sum())

    ok = not mismatches and not shape_mismatches and not unmeasured_pallas
    print(json.dumps({
        "ok": ok,
        "backend": backend,
        "rows_checked": len(reqs),
        "blocked": int((want_unv == 1).sum()),
        "lane_mismatches": mismatches,
        "shapes_checked": len(shapes),
        "shape_mismatches": shape_mismatches,
        "scan_selection": selected,
        "dfa_banks": {key: {"states": int(plan.np_tables[e.dfa_key]
                                          .num_states),
                            "exact": bool(plan.np_tables[e.dfa_key].exact),
                            "auto": bool(e.dfa_auto)}
                      for key, e in plan.scan_plans.items() if e.dfa_key},
        "fused_pallas_kernels": (
            "not selectable: Mosaic refuses all three as written "
            "(ops/pallas_scan.py)" if not unmeasured_pallas
            else f"SELECTED WITHOUT A MEASUREMENT: {unmeasured_pallas}"),
        "first_call_s": round(first_call_s, 1),
        "wall_s": round(time.monotonic() - t0, 1),
    }), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
