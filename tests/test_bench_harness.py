"""The bench and the chip smoke never make a CPU run look like a chip run.

ISSUE 21 pins the ABSENCE of what earlier rounds pinned as guarantees:
bench.py no longer falls back to a labelled CPU rerun — with no
usable backend it exits non-zero and prints no value — and the bench.py
/ chip_smoke.py parents never touch JAX, because a parent that holds
the chip starves the children that need it. __graft_entry__'s dryrun
parent stays jax-free for the same reason.
"""

import ast
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_fails_when_the_backend_is_unavailable():
    """No fallback: under a platform JAX cannot initialise the bench
    exits non-zero, its one JSON line carries the error and no value,
    and nothing labels a CPU rerun as a result."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "bogus"
    out = subprocess.run(
        [sys.executable, "bench.py"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    lines = [ln for ln in out.stdout.strip().splitlines() if ln.strip()]
    assert lines, f"no output; stderr={out.stderr[-500:]}"
    data = json.loads(lines[-1])
    assert data["metric"] == "waf_requests_per_sec_per_chip_500rules"
    assert data["value"] == 0 and data["vs_baseline"] == 0
    assert "bogus" in data["error"]
    assert "backend" not in data and "platform" not in data
    assert not any(k.endswith("req_per_s") for k in data)


def _functions(path):
    tree = ast.parse(open(path).read())
    return tree, {n.name: n for n in tree.body
                  if isinstance(n, ast.FunctionDef)}


def _imports(node):
    """(module, lineno) for every import statement under `node`."""
    for n in ast.walk(node):
        if isinstance(n, ast.Import):
            for a in n.names:
                yield a.name, n.lineno
        elif isinstance(n, ast.ImportFrom):
            yield (n.module or ""), n.lineno


def test_bench_and_smoke_parents_make_no_jax_call():
    """One owner of the chip. bench.py: every function reachable from
    main() by name (children are reached through `-c` strings, never
    by name) imports neither jax nor any pingoo_tpu module but the
    ctypes-only native_ring. chip_smoke.py: no jax import at all, and
    from pingoo_tpu only the generators, the tuple helpers and the
    expr interpreter — nothing that compiles, places or runs."""
    tree, fns = _functions(os.path.join(REPO, "bench.py"))
    reach, todo = set(), ["main"]
    while todo:
        name = todo.pop()
        if name in reach or name not in fns:
            continue
        reach.add(name)
        todo.extend(n.id for n in ast.walk(fns[name])
                    if isinstance(n, ast.Name))
    assert {"_run_arms", "_run_child", "bench_pipeline", "bench_staging",
            "bench_sched", "bench_dataplane"} <= reach
    assert not {"_device_bench_child", "_pipeline_bench_child",
                "_staging_bench_child", "_sched_bench_child",
                "bench_e2e", "bench_body", "_child_backend"} & reach
    for name in sorted(reach):
        for mod, line in _imports(fns[name]):
            assert mod != "jax" and not mod.startswith("jax."), (name, line)
            assert not mod.startswith("pingoo_tpu") \
                or mod == "pingoo_tpu" or mod == "pingoo_tpu.native_ring", \
                (name, mod, line)
    for mod, line in _imports(ast.Module(
            body=[n for n in tree.body
                  if not isinstance(n, ast.FunctionDef)], type_ignores=[])):
        assert not mod.startswith(("jax", "pingoo_tpu")), (mod, line)

    smoke = ast.parse(open(os.path.join(REPO, "chip_smoke.py")).read())
    allowed = {"pingoo_tpu.utils.crs", "pingoo_tpu.engine.batch",
               "pingoo_tpu.expr"}
    for mod, line in _imports(smoke):
        assert mod != "jax" and not mod.startswith("jax."), line
        assert not mod.startswith("pingoo_tpu") or mod in allowed, (mod, line)
    names = {a.name for n in ast.walk(smoke)
             if isinstance(n, ast.ImportFrom)
             and (n.module or "").startswith("pingoo_tpu")
             for a in n.names}
    assert names <= {"generate_rule_sources", "generate_traffic",
                     "NORMAL_UAS", "RequestTuple", "tuple_to_context",
                     "bucket_len", "compile_expression",
                     "execute_as_bool"}, names


def test_dryrun_parent_never_touches_jax():
    """The parent half of dryrun_multichip must contain no jax import:
    a process that has initialised a backend must not start a child
    that needs one, so the parent re-execs before any jax use."""
    src = open(os.path.join(REPO, "__graft_entry__.py")).read()
    tree = ast.parse(src)
    fns = {n.name: n for n in ast.walk(tree)
           if isinstance(n, ast.FunctionDef)}

    def jax_import_lines(fn):
        lines = []
        for node in ast.walk(fn):
            if isinstance(node, ast.Import) and any(
                    a.name == "jax" or a.name.startswith("jax.")
                    for a in node.names):
                lines.append(node.lineno)
            if isinstance(node, ast.ImportFrom) and (
                    node.module or "").startswith("jax"):
                lines.append(node.lineno)
        return lines

    # _reexec_dryrun (pure parent code) must not import jax at all.
    assert not jax_import_lines(fns["_reexec_dryrun"])
    # dryrun_multichip may import jax only AFTER the child-env guard
    # (which returns/re-execs in the parent), never before it.
    dm = fns["dryrun_multichip"]
    guard_line = None
    for node in ast.walk(dm):
        if (isinstance(node, ast.Call) and
                isinstance(node.func, ast.Attribute) and
                node.func.attr == "get" and
                any(isinstance(a, ast.Constant) and
                    a.value == "PINGOO_DRYRUN_CHILD" for a in node.args)):
            guard_line = node.lineno
            break
    assert guard_line is not None, "child-env guard missing"
    for line in jax_import_lines(dm):
        assert line > guard_line, (
            "dryrun_multichip imports jax before the child guard — the "
            "parent would hold a backend its child needs")
