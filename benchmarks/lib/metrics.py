"""Per-layer metrics are files: `benchmarks/metrics/<name>.json` for a
reading of the program's counters, `benchmarks/metrics/<name>.py` for a
reader of its own (`read(obs) -> number or None`). The harness finds
them by the names BENCHMARK.json lists for the cell; a reader that finds
nothing to read returns None and the metric is left out of the line.

A metric's name may end in `.<suffix>` that says which end-to-end
metric it moves in its cells (`batch_fill.steady` moves a latency, a
`batch_fill.flood` would move the rate): both are read by the one file
`batch_fill.*`, found by the name without its suffix.

A `.json` reader has `reader` = `delta`, `ratio` or `gauge`, and terms
that name a counter each:
  {"native": "ring.wait_sum_ms"}                  the native plane's JSON
  {"registry": "pingoo_x_total", "labels": {..}}  the Prometheus text
The difference is taken between the snapshots at the window's two ends.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Optional


def _native_value(native: Optional[dict], dotted: str):
    node = native
    for key in dotted.split("."):
        if not isinstance(node, dict) or key not in node:
            return None
        node = node[key]
    return node if isinstance(node, (int, float)) else None


def term_value(snapshot: Optional[dict], term: dict):
    """One counter's value in one snapshot, or None."""
    if not snapshot:
        return None
    if "native" in term:
        return _native_value(snapshot.get("native"), term["native"])
    names = term["registry"]
    names = [names] if isinstance(names, str) else names
    labels = dict(term.get("labels", {}))
    any_of = term.get("any_of", {})    # label -> accepted values
    samples = snapshot.get("registry")
    if samples is None:
        return None
    total = 0.0
    for n, ls, v in samples:
        if n in names and all(ls.get(k) == w for k, w in labels.items()) \
                and all(ls.get(k) in ws for k, ws in any_of.items()):
            total += v
    return total


def delta(obs: dict, term: dict):
    a = term_value(obs.get("before"), term)
    b = term_value(obs.get("after"), term)
    if a is None or b is None:
        return None
    return b - a


def _json_reader(spec: dict) -> Callable:
    kind = spec["reader"]

    def read(obs: dict):
        if kind == "gauge":
            return term_value(obs.get("after"), spec["term"])
        if kind == "delta":
            return delta(obs, spec["term"])
        if kind == "ratio":
            num = delta(obs, spec["num"])
            den = delta(obs, spec["den"])
            if num is None or not den:
                return None
            return num / den
        raise ValueError(f"unknown reader {kind!r}")
    return read


def load_reader(metrics_dir: str, name: str) -> Callable:
    """The reader of one per-layer metric, by its name, or by its name
    without the suffix after its last dot."""
    path = os.path.join(metrics_dir, name)
    if not (os.path.isfile(path + ".json") or os.path.isfile(path + ".py")):
        path = os.path.join(metrics_dir, name.rsplit(".", 1)[0])
    if os.path.isfile(path + ".json"):
        with open(path + ".json", encoding="utf-8") as f:
            return _json_reader(json.load(f))
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"),
        path + ".py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
