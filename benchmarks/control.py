#!/usr/bin/env python3
"""The control of "how correct is decided": the plain reference put in
the program's place with one guarantee broken, judged by the same
comparison as a run. It has to come out as NOT correct.

    python3 benchmarks/control.py --workload <cell> --seed <n> [--seconds <s>]

The control answers every request of the cell's own window (same
templates, same schedule or sequence, same connections; a closed loop
is given `--closed-rps` requests a second, about what the chip
completes) from the configuration's `control`: `truncate` cuts each string field to a staging cap before
the rules see it - the shortcut that would tempt a later PR. No server
runs and nothing is timed: what is read is how many of the window's
answers the comparison catches. Prints one JSON line.
"""

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib import harness, reduce  # noqa: E402
from lib.reference import Reference  # noqa: E402
from lib.rules import rule_sources  # noqa: E402
from lib.traffic import Mix  # noqa: E402


def control_line(workload: str, seed: int, seconds: float,
                 templates: int = 0, closed_rps: float = 3000.0) -> dict:
    cell = harness.Cell(workload)
    spec = dict(cell.requests)
    if templates:
        spec["pool"] = dict(spec["pool"], templates=templates)
    mix = Mix(spec)
    pool = mix.templates(seed)
    sources, lists = rule_sources(cell.config["rules"])
    reference = Reference(sources, lists)
    control = Reference(sources, lists,
                        caps=cell.config["control"]["truncate"])
    if cell.closed:
        tmpl = mix.sequence(seed, int(closed_rps * seconds))
        due = (np.arange(len(tmpl)) / closed_rps * 1e9).astype(np.int64)
    else:
        due, tmpl = mix.schedule(
            seed, float(cell.traffic["arrival"]["rate_rps"]), seconds)
    records = np.zeros(len(due), dtype=harness.RECORD)
    records["due_ns"], records["tmpl"] = due, tmpl
    records["sent_ns"], records["done_ns"] = due, due + 1
    slots = int(cell.traffic["connections"])
    records["conn"] = np.arange(len(due)) % slots      # every client in turn
    address = mix.addresses(slots, [
        item for items in lists.values() for item in items
        if isinstance(item, str)])[records["conn"]]
    want = reference.statuses(pool, tmpl, address)
    records["status"] = control.statuses(pool, tmpl, address)  # outcome 0
    cmp = reduce.compare(records, want, 0,
                         cell.config.get("fail_open_deadline_ms"))
    correct, compared = reduce.decide(cmp)
    wrong = np.unique(tmpl[records["status"] != want])
    return {"workload": workload, "seed": seed, "control_correct": correct,
            "templates_answered_wrong": int(len(wrong)),
            "compared": {k: {"value": v, "limit": lim}
                         for k, (v, lim) in compared.items()},
            "counts": cmp}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--closed-rps", type=float, default=3000.0)
    args = parser.parse_args()
    print(json.dumps(control_line(args.workload, args.seed, args.seconds,
                                  closed_rps=args.closed_rps)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
