"""Degradation ladder: one explicit fallback state machine per plane.

(ISSUE 10, docs/RESILIENCE.md.) Both verdict planes used to scatter
their fallbacks across anonymous ``except Exception`` rungs — staging
encoder -> legacy encode, lowered DFA -> NFA scan, serving mesh ->
single device, device -> host interpreter — each silently sticky for
the process lifetime and none of them observable. The ladder
consolidates them: a demotion is counted
(``pingoo_degrade_total{rung=}``), logged with the triggering error,
and probed back with exponential backoff, so a transient fault (device
hiccup, driver reset, chaos injection) degrades service for seconds,
not forever.

Rung order (cheapest first — the order callers demote in):

  ==========  =====================================================
  pipeline    staging encoder -> legacy per-batch encode chain
  dfa         lowered bitsplit DFAs -> exact NFA scan
  mesh        sharded serving mesh -> single-device executor
  device      XLA device programs -> host interpreter
  body        streaming body inspection -> metadata-only verdicts
  ==========  =====================================================

Every rung except ``body`` serves bit-identical verdicts by
construction: each fallback IS the oracle its fast path is tested
against (tests/test_pipeline.py, tests/test_bitsplit_dfa.py,
tests/test_resilience.py), so a demotion changes latency, never
answers. The ``body`` rung is the one deliberate exception (ISSUE 13,
docs/BODY_STREAMING.md): its fallback drops a whole inspection
dimension — body verdicts fail open to action 0 and requests are
judged on metadata alone — because there is no cheaper oracle for
body bytes the sidecar cannot scan. The demotion counter is the
audit trail for that coverage loss.

Caller protocol, per batch::

    if ladder.try_rung("device"):   # healthy, or a backoff probe
        try:
            ... fast path ...
            ladder.note_success("device")
        except Exception as exc:
            ladder.note_failure("device", exc)
            ... fallback ...
    else:
        ... fallback (demoted, probe window not yet open) ...

``try_rung`` on a demoted rung returns True at most once per backoff
window (the probe); a probe that fails reports via ``note_failure``,
which doubles the backoff, and one that succeeds re-promotes via
``note_success``, which resets it.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

from ..logging_utils import get_logger

RUNGS = ("pipeline", "dfa", "mesh", "device", "body")

# What each rung falls back TO (log/snapshot surface only).
FALLBACKS = {
    "pipeline": "legacy-encode",
    "dfa": "nfa-scan",
    "mesh": "single-device",
    "device": "host-interpreter",
    # ISSUE 13: a broken body scanner demotes the plane to
    # metadata-only verdicts — body windows fail open (action 0) so
    # held requests never stall; backoff probes re-arm inspection.
    "body": "metadata-only",
}

log = get_logger(__name__)


class _Rung:
    __slots__ = ("name", "healthy", "errors", "demotions", "backoff_s",
                 "next_probe_at", "last_error")

    def __init__(self, name: str, base_backoff_s: float):
        self.name = name
        self.healthy = True
        self.errors = 0        # note_failure calls (lifetime)
        self.demotions = 0     # healthy -> demoted transitions
        self.backoff_s = base_backoff_s
        self.next_probe_at = 0.0
        self.last_error = ""


class DegradationLadder:
    """Per-plane rung registry with exponential-backoff re-promotion.

    Single-threaded by contract — each plane drives its ladder from its
    own drain loop (the same discipline as the scheduler/cost model).
    ``clock`` is injectable so tests can step probe windows without
    sleeping.
    """

    def __init__(self, plane: str, base_backoff_s: float = 1.0,
                 max_backoff_s: float = 60.0,
                 clock: Callable[[], float] = time.monotonic,
                 registry=None):
        if registry is None:
            from ..obs import REGISTRY

            registry = REGISTRY
        from ..obs.schema import RESILIENCE_METRICS

        self.plane = plane
        self.base_backoff_s = base_backoff_s
        self.max_backoff_s = max_backoff_s
        self._clock = clock
        self._rungs = {name: _Rung(name, base_backoff_s)
                       for name in RUNGS}
        self._counters = {
            name: registry.counter(
                "pingoo_degrade_total",
                RESILIENCE_METRICS["pingoo_degrade_total"],
                labels={"plane": plane, "rung": name})
            for name in RUNGS}

    # -- caller protocol ------------------------------------------------------

    def healthy(self, rung: str) -> bool:
        return self._rungs[rung].healthy

    def try_rung(self, rung: str) -> bool:
        """True if the caller should attempt this rung's fast path now:
        the rung is healthy, or it is demoted and its backoff window
        has elapsed (a probe). A probe with no success/failure report
        stays demoted and re-probes next window."""
        r = self._rungs[rung]
        if r.healthy:
            return True
        now = self._clock()
        if now >= r.next_probe_at:
            r.next_probe_at = now + r.backoff_s
            return True
        return False

    def note_failure(self, rung: str, exc: Optional[BaseException] = None
                     ) -> None:
        """Demote (or keep demoted): count, log, double the backoff."""
        r = self._rungs[rung]
        r.errors += 1
        r.last_error = repr(exc) if exc is not None else ""
        self._counters[rung].inc()
        if r.healthy:
            r.demotions += 1
            r.backoff_s = self.base_backoff_s
        else:
            r.backoff_s = min(self.max_backoff_s, r.backoff_s * 2.0)
        r.healthy = False
        r.next_probe_at = self._clock() + r.backoff_s
        log.warning(
            "ladder demote", extra={"fields": {
                "plane": self.plane, "rung": rung,
                "fallback": FALLBACKS[rung],
                "backoff_s": round(r.backoff_s, 3),
                "errors": r.errors, "error": r.last_error}})

    def note_success(self, rung: str) -> None:
        """Re-promote after a successful probe; no-op while healthy."""
        r = self._rungs[rung]
        if r.healthy:
            return
        r.healthy = True
        r.backoff_s = self.base_backoff_s
        r.next_probe_at = 0.0
        log.info(
            "ladder promote", extra={"fields": {
                "plane": self.plane, "rung": rung,
                "errors": r.errors}})

    # -- introspection --------------------------------------------------------

    def demoted(self) -> list[str]:
        return [n for n in RUNGS if not self._rungs[n].healthy]

    def snapshot(self) -> dict:
        """JSON twin of the pingoo_degrade_total series plus the live
        state the counters cannot carry (health, backoff, last error)."""
        return {
            name: {
                "healthy": r.healthy,
                "fallback": FALLBACKS[name],
                "errors": r.errors,
                "demotions": r.demotions,
                "backoff_s": round(r.backoff_s, 3),
                "last_error": r.last_error,
            }
            for name, r in self._rungs.items()
        }
