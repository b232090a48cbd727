#!/usr/bin/env python3
"""Runs of one cell back to back, each a new process as the driver
makes them, with every exit code and result line kept:

    python3 benchmarks/soak.py --workload <cell> --seeds 11,12,13 --seconds 20 [--trace 0|1] [--out FILE]

One JSON line per run on stdout (and appended to --out): seed, exit
code, wall seconds, the run's result line, the end of its stderr. The
soak's own exit code is 0 only if every run exited 0 and printed a
line. `--sets 2` repeats the whole list of seeds (the two sets of the
bound's rule) and prints each metric's spread per set.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def spread(values: list):
    """Interquartile range over the median, as the contract takes it;
    None for fewer than two values or a median of 0 (a counter that
    stayed 0)."""
    median = statistics.median(values)
    if len(values) < 2 or not median:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / median


def run_detail(workload: str, seed: int, trace: int) -> dict:
    """What the run left in .bench/out beside its line: set-up notes,
    more latency percentiles, latency by slice."""
    path = os.path.join(os.path.dirname(HERE), ".bench", "out",
                        f"{workload}-{seed}-t{trace}", "result.json")
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return {}
    return {k: doc.get(k) for k in ("notes", "latency_percentiles",
                                    "latency_by_5s", "answered_per_s_by_5s",
                                    "generator")}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, default=0)
    parser.add_argument("--sets", type=int, default=1)
    parser.add_argument("--out", default=None)
    args = parser.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    all_ok = True
    per_set = []
    for set_no in range(args.sets):
        values: dict = {}
        for seed in seeds:
            t0 = time.monotonic()
            proc = subprocess.run(
                [sys.executable, os.path.join(HERE, "run.py"),
                 "--workload", args.workload, "--seed", str(seed),
                 "--seconds", str(args.seconds), "--trace", str(args.trace)],
                capture_output=True, text=True)
            lines = proc.stdout.strip().splitlines()
            try:
                line = json.loads(lines[-1]) if lines else None
            except ValueError:
                line = None
            ok = proc.returncode == 0 and line is not None
            all_ok &= ok
            record = {"set": set_no, "seed": seed, "rc": proc.returncode,
                      "wall_s": round(time.monotonic() - t0, 1), "line": line,
                      "detail": run_detail(args.workload, seed, args.trace),
                      "stderr_tail": proc.stderr[-1500:]}
            text = json.dumps(record)
            print(text, flush=True)
            if args.out:
                with open(args.out, "a", encoding="utf-8") as f:
                    f.write(text + "\n")
            for name, m in ((line or {}).get("metrics") or {}).items():
                values.setdefault(name, []).append(m["value"])
        per_set.append(values)
    for set_no, values in enumerate(per_set):
        summary = {"set": set_no, "spread": {
            name: {"median": statistics.median(v),
                   "spread": spread(v),
                   "values": v} for name, v in values.items()}}
        print(json.dumps(summary), flush=True)
        if args.out:
            with open(args.out, "a", encoding="utf-8") as f:
                f.write(json.dumps(summary) + "\n")
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())
