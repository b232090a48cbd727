#!/usr/bin/env python
"""Bench trajectory regression gate (make bench-regress; ISSUE 5
satellite).

`bench.py --history` appends every emitted result line to
BENCH_history.jsonl (one JSON object per run, wall-clock stamped).
This tool compares the LATEST run against the most recent previous run
with the SAME backend label (the platform the run names: a `cpu` run
is never comparable to a `tpu` number) under a configurable relative threshold:

    python tools/bench_regress.py [--threshold 0.10] [--file PATH]

Exit codes: 0 = no regression (or nothing comparable yet), 1 = at
least one tracked metric regressed past the threshold, 2 = usage/IO
error. Tracked metrics and their directions:

    value                higher is better (headline req/s/chip)
    p_batch_ms           lower  is better (the <2 ms budget)
    e2e_req_per_s        higher is better
    dataplane_req_per_s  higher is better
    blocklist_lookups_per_s  higher is better
    sched_continuous_req_per_s  higher is better (ISSUE 6 serving bench)
    sched_continuous_p99_ms     lower  is better
    sched_p99_slack_ms          higher is better (deadline headroom)
    sched_deadline_miss_rate    lower  is better
    dfa_auto_req_per_s   higher is better (ISSUE 8 bitsplit-DFA arm)
    pipeline_on_req_per_s  higher is better (ISSUE 9 pipelined executor)
    pipeline_on_p99_ms     lower  is better
    swap_pause_p99_ms    lower  is better (ISSUE 11 hot-swap pause)
    body_stream_mb_per_s higher is better (ISSUE 13 streaming body scan)
    staging_compact_req_per_s higher is better (ISSUE 15 compact staging)
    staged_bytes_per_req lower  is better

Metrics missing from either run are skipped (partial/error lines are
trajectory too, but only shared keys gate).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# (key, higher_is_better)
TRACKED = (
    ("value", True),
    ("p_batch_ms", False),
    ("e2e_req_per_s", True),
    ("dataplane_req_per_s", True),
    ("blocklist_lookups_per_s", True),
    # Continuous-batching serving bench (ISSUE 6, bench.py --mesh).
    ("sched_continuous_req_per_s", True),
    ("sched_continuous_p99_ms", False),
    ("sched_p99_slack_ms", True),
    ("sched_deadline_miss_rate", False),
    # Bitsplit-DFA lowering A/B (ISSUE 8, bench.py --dfa).
    ("dfa_auto_req_per_s", True),
    # Zero-copy pipelined executor A/B (ISSUE 9, bench.py --pipeline).
    ("pipeline_on_req_per_s", True),
    ("pipeline_on_p99_ms", False),
    # Sidecar supervision chaos smoke (ISSUE 10, tools/chaos_smoke.py):
    # p99 enqueue->resolution during a sidecar outage must stay within
    # the degraded fail-open bound.
    ("degraded_failopen_p99_ms", False),
    # Ruleset hot-swap storm (ISSUE 11, tools/chaos_smoke.py): the
    # drain+flip admission pause a swap costs at a batch boundary.
    ("swap_pause_p99_ms", False),
    # Streaming body-scan arm (ISSUE 13, bench.py --body): interleaved
    # multi-flow windowed scan throughput, verdict-identical to the
    # contiguous scan by construction.
    ("body_stream_mb_per_s", True),
    # Compact staging A/B (ISSUE 15, bench.py --staging): compact-arm
    # throughput and the staged bytes/request it exists to shrink.
    ("staging_compact_req_per_s", True),
    ("staged_bytes_per_req", False),
    # Lowering-soundness prover (ISSUE 18, python -m tools.analyze
    # prove --history): wall time to discharge every obligation on the
    # seed 500-rule plan — the compile-time proof budget. Prove runs
    # stamp backend="prove-<jax backend>" so they only ever compare
    # against other prove runs.
    ("prove_wall_s", False),
)

DEFAULT_THRESHOLD = 0.10


def load_history(path: str) -> list[dict]:
    entries = []
    with open(path) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                entries.append(json.loads(line))
            except json.JSONDecodeError:
                print(f"bench-regress: warning: {path}:{i}: "
                      f"unparseable line skipped", file=sys.stderr)
    return entries


def pick_baseline(entries: list[dict]) -> tuple[dict, dict | None]:
    """(latest, baseline) where baseline is the most recent PRIOR entry
    with the same backend label; None when no comparable prior run."""
    latest = entries[-1]
    backend = latest.get("backend")
    for prev in reversed(entries[:-1]):
        if prev.get("backend") == backend:
            return latest, prev
    return latest, None


def compare(latest: dict, baseline: dict,
            threshold: float) -> tuple[list[str], list[str]]:
    """-> (regressions, report lines)."""
    regressions: list[str] = []
    report: list[str] = []
    for key, higher_better in TRACKED:
        a, b = baseline.get(key), latest.get(key)
        if not isinstance(a, (int, float)) or not isinstance(
                b, (int, float)) or a <= 0:
            continue
        ratio = b / a
        delta_pct = (ratio - 1.0) * 100.0
        worse = ratio < (1.0 - threshold) if higher_better \
            else ratio > (1.0 + threshold)
        marker = "REGRESSION" if worse else "ok"
        report.append(
            f"  {marker:>10}  {key}: {a} -> {b} ({delta_pct:+.1f}%, "
            f"{'higher' if higher_better else 'lower'} is better)")
        if worse:
            regressions.append(key)
    return regressions, report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--threshold", type=float, default=float(
        os.environ.get("BENCH_REGRESS_THRESHOLD", DEFAULT_THRESHOLD)),
        help="relative regression threshold (default 0.10 = 10%%)")
    ap.add_argument("--file", default=os.environ.get(
        "BENCH_HISTORY_FILE", "BENCH_history.jsonl"))
    args = ap.parse_args(argv)
    if args.threshold <= 0 or args.threshold >= 1:
        print("bench-regress: threshold must be in (0, 1)",
              file=sys.stderr)
        return 2
    if not os.path.exists(args.file):
        print(f"bench-regress: no history at {args.file} "
              f"(run `python bench.py --history` first); nothing to "
              f"compare")
        return 0
    try:
        entries = load_history(args.file)
    except OSError as exc:
        print(f"bench-regress: cannot read {args.file}: {exc}",
              file=sys.stderr)
        return 2
    if len(entries) < 2:
        print(f"bench-regress: {len(entries)} run(s) in {args.file}; "
              f"need 2 comparable runs")
        return 0
    latest, baseline = pick_baseline(entries)
    if baseline is None:
        # Explicit cross-backend refusal (ISSUE 17 satellite): name
        # BOTH backends so "nothing comparable" is diagnosable from the
        # message alone, and treat a latest entry with no backend stamp
        # at all as an error — history_schema>=2 lines (bench.py
        # _append_history) always carry one, so its absence means the
        # file predates the stamp or was hand-edited.
        if latest.get("backend") is None:
            print(f"bench-regress: latest entry in {args.file} has no "
                  f"'backend' stamp (pre-schema-2 history?); refusing "
                  f"to guess a baseline — re-run `python bench.py "
                  f"--history` to append a stamped run", file=sys.stderr)
            return 2
        others = sorted({str(e.get("backend")) for e in entries[:-1]})
        print(f"bench-regress: REFUSED — latest run is backend="
              f"{latest.get('backend')!r} but every prior run is "
              f"backend in {others}; cross-backend numbers are not "
              f"comparable (a cpu run against a tpu run measures the "
              f"host, not the change)")
        return 0
    regressions, report = compare(latest, baseline, args.threshold)
    print(f"bench-regress: latest ts={latest.get('ts')} vs baseline "
          f"ts={baseline.get('ts')} (backend={latest.get('backend')!r}, "
          f"threshold {args.threshold:.0%})")
    for line in report:
        print(line)
    if not report:
        print("  (no shared tracked metrics between the two runs)")
    if regressions:
        print(f"bench-regress: FAIL — {len(regressions)} metric(s) "
              f"regressed: {', '.join(regressions)}", file=sys.stderr)
        return 1
    print("bench-regress: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
