"""Static compile-surface analyzer (ISSUE 18, docs/STATIC_ANALYSIS.md).

XLA compilation is the one unbounded latency hazard the serving path
has: any NEW (fn, shape) pair that reaches a jitted dispatch stalls a
live batch for seconds.  Every shape axis the engine exposes is
deliberately rung-quantized — pow2 batch buckets (engine/batch.py
pow2_batch_size, floor 8), quantized staging widths (compiler/plan.py
STAGING_RUNGS), and the DFA mode ladder — so the set of admissible
compilations per plan is CLOSED and statically enumerable.

This pass walks every `make_*_fn` / `instrument_jit` entry point in the
tree (AST, no imports), checks each against the registered label maps
(an unregistered entry point fails the pass — register it below or it
escapes the surface bound), and emits the closed admissible set as
COMPILE_SURFACE.json.  The runtime compile ledger (obs/perf.py) loads
that file via PINGOO_COMPILE_SURFACE and verifies every recorded
compile event is inside the surface — an out-of-surface compile flips
`pingoo_compile_unexpected_total` and fails `make timeline-smoke`.  The
AST linter's `unbounded-compile-axis` rule (lint.py) closes the loop at
review time: a len()/.shape-derived expression reaching a jitted
dispatch without passing through a registered quantizer fails lint.
"""

from __future__ import annotations

import ast
import json
import os
from typing import Any, Optional

from . import REPO_ROOT

SURFACE_VERSION = 1
DEFAULT_PATH = os.path.join(REPO_ROOT, "COMPILE_SURFACE.json")

# Every make_*_fn factory must map to its ledger fn label; scanning an
# unregistered factory fails the pass so a new entry point cannot ship
# outside the surface bound.
MAKE_FN_LABELS = {
    "make_verdict_fn": "verdict",
    "make_packed_verdict_fn": "verdict",
    "make_prefilter_fn": "prefilter",
    "make_packed_prefilter_fn": "prefilter",
    "make_lane_fn": "lanes",
    "make_packed_lane_fn": "lanes",
    "make_pad_fn": "pad",
}

PLANES = ("python", "sidecar")
KINDS = ("cold", "warm")
DFA_MODES = ("off", "auto", "force")

_SCAN_DIRS = ("pingoo_tpu",)
_EXCLUDE = {"__pycache__", ".git", "build", "dist", "native"}


def _pow2_ladder(lo: int, hi: int) -> list[int]:
    out, v = [], lo
    while v <= hi:
        out.append(v)
        v *= 2
    return out


def scan_entry_points(repo_root: str = REPO_ROOT):
    """AST-walk the tree for jit entry points.

    Returns (entry_points, problems): entry_points are provenance rows
    {file, line, kind, name, plane}; problems are strings — an
    unregistered make_*_fn, a non-literal/unknown instrument_jit name,
    or an unknown plane literal."""
    entries: list[dict] = []
    problems: list[str] = []
    for scan_dir in _SCAN_DIRS:
        base = os.path.join(repo_root, scan_dir)
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = [d for d in dirnames if d not in _EXCLUDE]
            for fname in sorted(filenames):
                if not fname.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fname)
                rel = os.path.relpath(path, repo_root)
                try:
                    with open(path, encoding="utf-8") as f:
                        tree = ast.parse(f.read(), filename=rel)
                except (OSError, SyntaxError) as exc:
                    problems.append(f"{rel}: unparseable ({exc})")
                    continue
                _scan_module(tree, rel, entries, problems)
    return entries, problems


def _scan_module(tree: ast.AST, rel: str, entries: list,
                 problems: list) -> None:
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            name = node.name
            if name.startswith("make_") and name.endswith("_fn"):
                label = MAKE_FN_LABELS.get(name)
                if label is None:
                    problems.append(
                        f"{rel}:{node.lineno}: unregistered jit factory "
                        f"{name} (add it to surface.MAKE_FN_LABELS)")
                else:
                    entries.append({"file": rel, "line": node.lineno,
                                    "kind": "factory", "name": name,
                                    "fn": label, "plane": None})
        elif isinstance(node, ast.Call):
            callee = node.func
            cname = callee.attr if isinstance(callee, ast.Attribute) \
                else getattr(callee, "id", "")
            if cname != "instrument_jit":
                continue
            if rel.replace(os.sep, "/") == "pingoo_tpu/obs/perf.py":
                continue  # the instrument layer itself
            fn_label: Optional[str] = None
            if len(node.args) >= 2:
                arg = node.args[1]
                if isinstance(arg, ast.Constant) and isinstance(
                        arg.value, str):
                    fn_label = arg.value
                elif isinstance(arg, ast.Name):
                    fn_label = f"<var:{arg.id}>"
            plane = None
            for kw in node.keywords:
                if kw.arg == "plane" and isinstance(kw.value, ast.Constant):
                    plane = kw.value.value
            if isinstance(fn_label, str) and not fn_label.startswith("<") \
                    and fn_label not in MAKE_FN_LABELS.values() \
                    and fn_label != "score":
                problems.append(
                    f"{rel}:{node.lineno}: instrument_jit label "
                    f"{fn_label!r} is not a registered fn kind")
            if plane is not None and plane not in PLANES:
                problems.append(
                    f"{rel}:{node.lineno}: unknown plane {plane!r}")
            entries.append({"file": rel, "line": node.lineno,
                            "kind": "site", "name": cname,
                            "fn": fn_label, "plane": plane})


def build_surface(plan: Any = None, max_batch: int = 8192,
                  repo_root: str = REPO_ROOT) -> dict:
    """Enumerate the closed admissible compile set; raises ValueError
    when the entry-point walk finds an unregistered factory/label (the
    surface would silently under-approximate otherwise)."""
    entries, problems = scan_entry_points(repo_root)
    if problems:
        raise ValueError("compile surface incomplete:\n  "
                         + "\n  ".join(problems))
    fns = sorted(set(MAKE_FN_LABELS.values()) | {"score"})
    surface = {
        "version": SURFACE_VERSION,
        "planes": list(PLANES),
        "fns": fns,
        "kinds": list(KINDS),
        # pow2_batch_size floors the python plane's batches at 8, but
        # the sidecar pads to its own max_batch, which may be any pow2
        # below that, so the admissible set is the full pow2 ladder.
        "batch_buckets": _pow2_ladder(1, max(8, max_batch)),
        "dfa_modes": list(DFA_MODES),
        "entry_points": entries,
    }
    if plan is not None:
        from pingoo_tpu.compiler.plan import STAGING_RUNGS
        from pingoo_tpu.obs.perf import staging_widths

        surface["staging_rungs"] = list(STAGING_RUNGS)
        surface["widths"] = [list(map(list, staging_widths(plan)))]
    return surface


def write_surface(surface: dict, path: str = DEFAULT_PATH) -> str:
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(surface, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path


def run(out_path: str = DEFAULT_PATH) -> int:
    """Emit COMPILE_SURFACE.json for the static (plan-agnostic) axes."""
    try:
        surface = build_surface()
    except ValueError as exc:
        print(f"surface: FAIL — {exc}")
        return 1
    write_surface(surface, out_path)
    sites = sum(1 for e in surface["entry_points"] if e["kind"] == "site")
    factories = sum(1 for e in surface["entry_points"]
                    if e["kind"] == "factory")
    print(f"surface: OK — {factories} factories + {sites} instrumented "
          f"sites -> {os.path.relpath(out_path, REPO_ROOT)} "
          f"({len(surface['batch_buckets'])} buckets x "
          f"{len(surface['fns'])} fns)")
    return 0


if __name__ == "__main__":
    raise SystemExit(run())
