"""Compile ledger (ISSUE 17): every jit trace/compile event, counted
and durable.

The engine's latency cliffs are XLA compiles: the first call of every
jitted program per abstract signature (pow2 batch bucket, staging
widths-tuple) blocks for seconds, and a *recompile storm*
— a plan swap or a bucket ladder walking shapes under live traffic —
is the difference between a 2 ms p99 and a multi-second outage. The
stage histograms can't see it (they attribute the stall to whatever
stage the call sat in); this module makes each compile a first-class
event:

  * `instrument_jit(fn, ...)` wraps a jitted callable returned by the
    `engine/verdict.make_*_fn` factories (the wrapper composes AFTER
    jax.jit, so donation and static_argnums semantics are untouched).
    Each call probes the pjit executable cache size before/after — two
    O(1) C calls, no device sync — and a growth means THIS call paid a
    trace+compile: the call wall is the compile wall (jit compiles
    synchronously before the async dispatch returns).
  * every event lands in the process-global `CompileLedger`: a bounded
    in-memory ring (`/__pingoo/compileledger` dumps it), the
    `pingoo_compile_total{plane,fn,kind}` counter +
    `pingoo_compile_ms{plane,fn}` histogram, and — when
    `PINGOO_PERF_LEDGER` names a file — one JSONL line per event in
    `COMPILE_LEDGER.jsonl`, so compile counts survive the process and
    cross-check against the counter.

Gating: unset/0 `PINGOO_PERF_LEDGER` makes `instrument_jit` return the
callable UNCHANGED — zero added work on the hot path (the metric
instruments are still created eagerly at zero so the inventory is
scrapeable either way). `1`/`on` enables with the default
`COMPILE_LEDGER.jsonl`; any other value is the ledger path.

`kind` classifies the event: `cold` = the wrapper's first compile (the
expected warm-up), `warm` = a later retrace (new shape under live
traffic — the alertable series).

`_InstrumentedJit.__call__` is registered hot in
tools/analyze/lint_config.py: nothing on the per-call path may
allocate arrays or sync the device — event assembly only runs on the
(rare) compile branch.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Optional

# The fn-kind label values the wrappers emit (verdict/lane/prefilter
# programs, their packed-staging twins under the same label, the
# packed batch's row pad and the bot-score program).
COMPILE_FN_KINDS = ("verdict", "lanes", "prefilter", "pad", "score")

# pingoo_compile_ms histogram bounds: sub-ms cache refreshes up to the
# multi-second cold compiles of a whole lane program.
COMPILE_BUCKETS_MS = (1.0, 5.0, 25.0, 100.0, 250.0, 500.0, 1000.0,
                      2500.0, 5000.0, 10000.0, 30000.0)

DEFAULT_LEDGER_FILE = "COMPILE_LEDGER.jsonl"
_EVENTS_CAP = 1024


def perf_ledger_path() -> Optional[str]:
    """The PINGOO_PERF_LEDGER gate: None = off (default), otherwise
    the JSONL path compile events persist to."""
    raw = os.environ.get("PINGOO_PERF_LEDGER", "").strip()
    if not raw or raw.lower() in ("0", "off", "false"):
        return None
    if raw.lower() in ("1", "on", "true"):
        return DEFAULT_LEDGER_FILE
    return raw


def plan_fingerprint(plan) -> str:
    """Cheap plan-derived ruleset-epoch fingerprint: hashes the
    plan-static content that changes a compiled program's identity
    (rule names, staging caps, DFA dispatch default) — NOT the full
    compiler cache key, but stable per adopted plan and computable
    without re-walking the ruleset. Versions both the compile ledger
    events and the durable cost ledger (sched/scheduler.py)."""
    import hashlib

    h = hashlib.sha256()
    for name in getattr(plan, "rule_names", None) or ():
        h.update(str(name).encode("utf-8", "replace"))
        h.update(b"\x00")
    caps = getattr(plan, "staging_caps", None) or {}
    for field in sorted(caps):
        h.update(f"{field}={caps[field]}".encode())
    h.update(str(getattr(plan, "dfa_default_mode", "")).encode())
    h.update(str(getattr(plan, "field_specs", "")).encode())
    return h.hexdigest()[:16]


def staging_widths(plan) -> tuple:
    """The plan's staging widths-tuple (sorted field -> cap), the
    shape-identity component of a compiled program's signature."""
    caps = getattr(plan, "staging_caps", None) or {}
    return tuple((f, int(caps[f])) for f in sorted(caps))


def _arg_shapes(args) -> list:
    """Array shapes across the call's pytree — only evaluated on the
    compile branch (rare), never per call."""
    shapes = []
    try:
        from jax import tree_util

        for leaf in tree_util.tree_leaves(args):
            shp = getattr(leaf, "shape", None)
            if shp is not None and len(shp):
                shapes.append(tuple(int(d) for d in shp))
                if len(shapes) >= 24:
                    break
    except Exception:
        pass
    return shapes


def _shape_context(shapes: list) -> Optional[int]:
    """The batch bucket, best-effort from the compile-time arg shapes:
    the most common leading dim of the 2-D request arrays."""
    from collections import Counter

    lead2 = Counter(s[0] for s in shapes if len(s) == 2)
    return lead2.most_common(1)[0][0] if lead2 else None


def load_compile_surface(path: str) -> Optional[dict]:
    """Read a COMPILE_SURFACE.json (tools/analyze/surface.py); None on
    an unreadable/malformed file — the ledger then skips surface checks
    rather than flagging every event."""
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        return doc if isinstance(doc, dict) and "fns" in doc else None
    except (OSError, ValueError):
        return None


def event_in_surface(event: dict, surface: dict) -> Optional[str]:
    """None when a compile event lies inside the statically-proved
    admissible surface; else the axis that escaped it."""
    if event.get("plane") not in surface.get("planes", ()):
        return f"plane={event.get('plane')!r}"
    if event.get("fn") not in surface.get("fns", ()):
        return f"fn={event.get('fn')!r}"
    if event.get("kind") not in surface.get("kinds", ()):
        return f"kind={event.get('kind')!r}"
    bucket = event.get("batch_bucket")
    if bucket is not None and bucket not in surface.get(
            "batch_buckets", ()):
        return f"batch_bucket={bucket}"
    widths = [list(w) for w in event.get("widths") or ()]
    if widths and "widths" in surface and widths not in surface["widths"]:
        return "widths"
    return None


_SURFACE_UNSET = object()

# Dispatchers stamp the TRUE padded launch shape here right before an
# instrumented call: the compact one-copy path bakes the batch into a
# flat packed blob + static layout, so arg-shape inspection alone
# recovers a rule-table dim, not the batch axis. Thread-local because
# the listener service and the ring sidecar dispatch on their own
# threads within one process.
_DISPATCH_TLS = threading.local()


def set_dispatch_context(batch: Optional[int] = None) -> None:
    _DISPATCH_TLS.batch = batch


def dispatch_context() -> Optional[int]:
    return getattr(_DISPATCH_TLS, "batch", None)


def batch_leading_dim(arrays) -> Optional[int]:
    """Padded launch batch from a per-field arrays mapping (the leading
    dim of any 2-D request array)."""
    for a in arrays.values():
        shape = getattr(a, "shape", ())
        if len(shape) >= 2:
            return int(shape[0])
    return None


class CompileLedger:
    """Process-global compile-event sink shared by both Python planes
    (the listener service and the ring sidecar are co-resident)."""

    def __init__(self, path: Optional[str] = None, registry=None):
        self.path = path
        self._lock = threading.Lock()
        self.events: deque = deque(maxlen=int(
            os.environ.get("PINGOO_PERF_LEDGER_N", _EVENTS_CAP)))
        self.totals: dict[tuple, int] = {}
        self._counters: dict[tuple, Any] = {}
        self._hists: dict[tuple, Any] = {}
        self._registry = registry
        self._io_errors = 0
        self._surface_doc: Any = _SURFACE_UNSET
        self._unexpected_ctrs: dict[tuple, Any] = {}
        self.unexpected_total = 0

    def _surface(self) -> Optional[dict]:
        # Resolved once per ledger: surface membership runs only on the
        # rare compile branch, but env/file reads still don't belong
        # there per-event.
        if self._surface_doc is _SURFACE_UNSET:
            path = os.environ.get("PINGOO_COMPILE_SURFACE")
            self._surface_doc = load_compile_surface(path) if path else None
        return self._surface_doc

    def _unexpected_counter(self, plane: str, fn: str):
        key = (plane, fn)
        ctr = self._unexpected_ctrs.get(key)
        if ctr is None:
            from . import schema

            ctr = self._reg().counter(
                "pingoo_compile_unexpected_total",
                schema.PERF_METRICS["pingoo_compile_unexpected_total"],
                labels={"plane": plane, "fn": fn})
            self._unexpected_ctrs[key] = ctr
        return ctr

    @property
    def enabled(self) -> bool:
        return self.path is not None

    def _reg(self):
        if self._registry is None:
            from . import REGISTRY

            self._registry = REGISTRY
        return self._registry

    def ensure_instruments(self, plane: str) -> None:
        """Create the plane's compile metric series at zero (boot-time,
        so the inventory is scrapeable before any compile event)."""
        for fn in COMPILE_FN_KINDS:
            for kind in ("cold", "warm"):
                self._counter(plane, fn, kind)
            self._hist(plane, fn)

    def _counter(self, plane: str, fn: str, kind: str):
        key = (plane, fn, kind)
        ctr = self._counters.get(key)
        if ctr is None:
            from . import schema

            ctr = self._reg().counter(
                "pingoo_compile_total",
                schema.PERF_METRICS["pingoo_compile_total"],
                labels={"plane": plane, "fn": fn, "kind": kind})
            self._counters[key] = ctr
        return ctr

    def _hist(self, plane: str, fn: str):
        key = (plane, fn)
        h = self._hists.get(key)
        if h is None:
            from . import schema

            h = self._reg().histogram(
                "pingoo_compile_ms",
                schema.PERF_METRICS["pingoo_compile_ms"],
                buckets=COMPILE_BUCKETS_MS,
                labels={"plane": plane, "fn": fn})
            self._hists[key] = h
        return h

    def note(self, *, plane: str, fn: str, kind: str, wall_ms: float,
             fingerprint: str = "", widths: tuple = (),
             shapes: Optional[list] = None,
             batch_bucket: Optional[int] = None) -> None:
        """One trace/compile event (called from the compile branch of
        an instrumented call — rare by construction). An explicit
        batch_bucket (from set_dispatch_context) wins over the
        arg-shape heuristic, which cannot see through packed blobs."""
        bucket = (batch_bucket if batch_bucket is not None
                  else _shape_context(shapes or []))
        event = {
            "ts": round(time.time(), 3),
            "plane": plane,
            "fn": fn,
            "kind": kind,
            "wall_ms": round(wall_ms, 3),
            "batch_bucket": bucket,
            "widths": [list(w) for w in widths],
            "fingerprint": fingerprint,
            "shapes": [list(s) for s in (shapes or [])[:12]],
        }
        surface = self._surface()
        if surface is not None:
            reason = event_in_surface(event, surface)
            if reason is not None:
                event["unexpected"] = reason
                self._unexpected_counter(plane, fn).inc()
        self._counter(plane, fn, kind).inc()
        self._hist(plane, fn).observe(wall_ms)
        with self._lock:
            self.events.append(event)
            tkey = (plane, fn, kind)
            self.totals[tkey] = self.totals.get(tkey, 0) + 1
            if event.get("unexpected"):
                self.unexpected_total += 1
        if self.path:
            try:
                with open(self.path, "a") as f:
                    f.write(json.dumps(event) + "\n")
            except OSError:
                self._io_errors += 1

    def snapshot(self) -> dict:
        """The /__pingoo/compileledger payload."""
        with self._lock:
            events = list(self.events)
            totals = {f"{p}/{fn}/{kind}": n
                      for (p, fn, kind), n in sorted(self.totals.items())}
        return {
            "enabled": self.enabled,
            "path": self.path,
            "compiles_total": sum(totals.values()),
            "totals": totals,
            "io_errors": self._io_errors,
            "surface_loaded": self._surface() is not None,
            "unexpected_total": self.unexpected_total,
            "events": events,
        }


_LEDGER: Optional[CompileLedger] = None
_LEDGER_LOCK = threading.Lock()


def get_compile_ledger() -> CompileLedger:
    global _LEDGER
    if _LEDGER is None:
        with _LEDGER_LOCK:
            if _LEDGER is None:
                _LEDGER = CompileLedger(path=perf_ledger_path())
    return _LEDGER


def reset_compile_ledger_for_tests() -> None:
    """Drop the singleton so a test can re-read PINGOO_PERF_LEDGER."""
    global _LEDGER
    with _LEDGER_LOCK:
        _LEDGER = None


class _InstrumentedJit:
    """Transparent wrapper over one jitted callable: per call, two
    executable-cache-size probes decide whether THIS call paid a
    trace+compile; the event branch runs only when it did. Attribute
    access (e.g. `.clear_cache`) delegates to the wrapped callable."""

    __slots__ = ("_fn", "_probe", "_plane", "_name", "_fingerprint",
                 "_widths", "_ledger", "_compiles")

    def __init__(self, fn: Callable, name: str, plane: str,
                 fingerprint: str, widths: tuple,
                 ledger: CompileLedger):
        self._fn = fn
        probe = getattr(fn, "_cache_size", None)
        self._probe = probe if callable(probe) else None
        self._plane = plane
        self._name = name
        self._fingerprint = fingerprint
        self._widths = widths
        self._ledger = ledger
        self._compiles = 0

    def __call__(self, *args):
        probe = self._probe
        if probe is not None:
            try:
                before = probe()
            except Exception:
                before = -1
        else:
            # No cache probe on this jax build: only the first call is
            # attributable (it is always a compile); later retraces go
            # uncounted rather than mis-counted.
            before = -1 if self._compiles else 0
        t0 = time.monotonic()
        out = self._fn(*args)
        if before >= 0:
            if probe is not None:
                try:
                    grew = probe() > before
                except Exception:
                    grew = False
            else:
                grew = True
            if grew:
                wall_ms = (time.monotonic() - t0) * 1e3
                kind = "cold" if self._compiles == 0 else "warm"
                self._compiles += 1
                self._ledger.note(
                    plane=self._plane, fn=self._name, kind=kind,
                    wall_ms=wall_ms, fingerprint=self._fingerprint,
                    widths=self._widths, shapes=_arg_shapes(args),
                    batch_bucket=dispatch_context())
        return out

    def __getattr__(self, item):
        return getattr(self._fn, item)


def instrument_jit(fn, name: str, *, plane: str, fingerprint: str = "",
                   widths: tuple = (), ledger=None):
    """Wrap one jitted callable for compile tracking. With the
    PINGOO_PERF_LEDGER gate off this returns `fn` UNCHANGED (zero
    hot-path delta); None passes through so optional programs
    (prefilter may be absent) wrap with no branching at call sites."""
    if fn is None:
        return None
    if ledger is None:
        ledger = get_compile_ledger()
    ledger.ensure_instruments(plane)
    if not ledger.enabled:
        return fn
    return _InstrumentedJit(fn, name, plane, fingerprint, widths, ledger)
