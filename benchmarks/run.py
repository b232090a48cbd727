#!/usr/bin/env python3
"""One run of one benchmark cell, a new process each time:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. This process never initialises JAX: the
one chip-owning process is the served program, a child. The last line
of stdout is the result (see benchmarks/README.md); a set-up that could
not produce a window exits non-zero with no line on stdout, the reason
as the last line of stderr and in .bench/out/<run>/failure.json.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib import harness  # noqa: E402  (the path above comes first)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args()
    return harness.run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace))


if __name__ == "__main__":
    sys.exit(main())
