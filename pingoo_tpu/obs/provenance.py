"""Verdict provenance: per-rule attribution lanes and the shadow-parity
auditor (ISSUE 5, docs/OBSERVABILITY.md).

Three pieces, shared by both verdict-engine planes (the Python listener
service, plane="python", and the ring sidecar backing the native data
plane, plane="sidecar"):

  * `RuleAttribution` — cardinality-bounded per-rule hit counters. The
    fold input is either the host-side match matrix sum (the Python
    plane already ships the [B, R] matrix back for finish_batch, so the
    fold is one vector add) or the on-device [R_dev] hit-count lane
    that rides the sidecar's lanes in their one stacked output
    (engine/verdict.py make_lane_fn(with_rule_hits=True) — no transfer
    of its own). Exposition is bounded: the top-K rules by cumulative
    hits get labelled `pingoo_rule_hits_total{rule=...}` series,
    everything else folds into one `rule="_overflow"` series, so a
    500-rule plan costs K+1 series, not 500.

  * `PrefilterAttribution` — per-gated-bank candidate rates and skip
    counters from the Stage-A aux vector (engine/verdict.py
    make_prefilter_fn), labelled by bank key. Bank cardinality is small
    by construction (a handful of byte fields x at most three sub-banks
    each).

  * `ParityAuditor` — the always-on sampler: a configurable fraction
    (PINGOO_PARITY_SAMPLE, a 0..1 batch fraction) of live batches is
    re-evaluated through the host expression interpreter on a dedicated
    worker thread, OFF the dispatch hot path (the hot-path side of the
    auditor only flips a sampling accumulator and enqueues a reference;
    tools/analyze lint registers it hot so a bare device sync there
    fails `make analyze`). Verdict-bitmap diffs feed
    pingoo_parity_checked_total / pingoo_parity_mismatch_total plus a
    bounded per-rule breakdown, and mismatching requests are marked in
    the flight recorder with full provenance.

Fault injection (chaos/testing only): PINGOO_PARITY_FAULT_INJECT=<path
prefix> makes the auditor's ORACLE flip rule 0's bit for matching
requests — the served verdict is untouched; the knob exists so
`make metrics-smoke` and tests can prove an injected divergence is
observable end to end (metrics + flight-recorder dump).
"""

from __future__ import annotations

import os
import queue
import threading
import time
from typing import Callable, Optional

import numpy as np

from .flightrecorder import PARITY_MISMATCH, PARITY_OK

DEFAULT_TOP_K = 20
# Hard cap on distinct rule-labelled series EVER created per family:
# registry instruments cannot be removed, so top-K churn is allowed to
# create at most this many before new entrants stay in "_overflow".
RULE_SERIES_CAP = 64
OVERFLOW_LABEL = "_overflow"


def provenance_enabled() -> bool:
    return os.environ.get("PINGOO_PROVENANCE", "1") != "0"


def attribution_top_k() -> int:
    try:
        return max(1, int(os.environ.get("PINGOO_ATTR_TOP_K",
                                         str(DEFAULT_TOP_K))))
    except ValueError:
        return DEFAULT_TOP_K


def parity_sample_fraction() -> float:
    try:
        frac = float(os.environ.get("PINGOO_PARITY_SAMPLE", "0"))
    except ValueError:
        return 0.0
    return min(max(frac, 0.0), 1.0)


class RuleAttribution:
    """Per-rule hit counters with bounded exposition cardinality.

    Counts accumulate per ORIGINAL rule index in a flat int64 vector;
    the registry collector (runs at scrape time, off the hot path)
    materializes the top-K labelled series. A labelled series exports
    hits SINCE ITS CREATION (base-offset subtraction) so the
    "_overflow" remainder stays a monotone counter even as rules are
    promoted into the labelled set."""

    def __init__(self, rule_names, plane: str, registry=None,
                 top_k: Optional[int] = None):
        from . import schema

        if registry is None:
            from . import REGISTRY as registry  # noqa: N813
        self.rule_names = tuple(rule_names)
        self.plane = plane
        self.top_k = top_k or attribution_top_k()
        self._registry = registry
        self._counts = np.zeros(len(self.rule_names), dtype=np.int64)
        self._bases: dict[int, int] = {}  # rule idx -> count at creation
        self._series: dict[int, object] = {}  # rule idx -> Counter
        help_text = schema.PROVENANCE_METRICS["pingoo_rule_hits_total"]
        self._overflow = registry.counter(
            "pingoo_rule_hits_total", help_text,
            labels={"plane": plane, "rule": OVERFLOW_LABEL})
        self._help = help_text
        registry.register_collector(self._export)

    def close(self) -> None:
        self._registry.unregister_collector(self._export)

    def fold_batch(self, hit_counts, indices=None) -> None:
        """Fold one batch's per-rule hit counts (hot path: one vector
        add). `hit_counts` is a HOST int array: [R] in original-index
        order or, on the lane plane, the attribution lane in device-
        column order (engine/verdict.rule_hit_counts: a slice of the
        batch's one device->host copy) with `indices` mapping columns
        to original rule indices."""
        if indices is not None:
            np.add.at(self._counts, indices, hit_counts)
        else:
            self._counts += hit_counts

    @property
    def total_hits(self) -> int:
        return int(self._counts.sum())

    def snapshot(self, k: Optional[int] = None) -> dict:
        """Top-k rules by cumulative hits + the remainder (JSON view)."""
        k = k or self.top_k
        order = np.argsort(self._counts)[::-1][:k]
        top = [(self.rule_names[int(i)], int(self._counts[int(i)]))
               for i in order if self._counts[int(i)] > 0]
        covered = sum(c for _, c in top)
        return {"top": top, "other": self.total_hits - covered,
                "total": self.total_hits}

    def _export(self) -> None:
        """Registry collector: keep every existing labelled series
        current, promote new top-K entrants (bounded by
        RULE_SERIES_CAP), and fold the rest into "_overflow"."""
        if not len(self._counts):
            return
        order = np.argsort(self._counts)[::-1][: self.top_k]
        for i in order:
            i = int(i)
            if (self._counts[i] > 0 and i not in self._series
                    and len(self._series) < RULE_SERIES_CAP):
                self._bases[i] = int(self._counts[i])
                self._series[i] = self._registry.counter(
                    "pingoo_rule_hits_total", self._help,
                    labels={"plane": self.plane,
                            "rule": self.rule_names[i]})
                # The promoted rule's PAST hits stay in _overflow (its
                # base), so both series remain monotone.
        exported = 0
        for i, counter in self._series.items():
            since = int(self._counts[i]) - self._bases[i]
            counter.set_total(since)
            exported += self._bases[i] + since
        self._overflow.set_total(self.total_hits - exported
                                 + sum(self._bases.values()))


class PrefilterAttribution:
    """Per-gated-bank candidate rates + skip counters from the Stage-A
    aux vector (layout: [cand_total, skip_total, per-bank candidate
    counts..., per-bank skip flags...], engine/verdict.make_prefilter_fn)."""

    def __init__(self, masked_keys, plane: str, registry=None):
        from . import schema

        if registry is None:
            from . import REGISTRY as registry  # noqa: N813
        self.masked_keys = tuple(masked_keys)
        self._rate_gauges = [registry.gauge(
            "pingoo_prefilter_bank_candidate_rate",
            schema.PROVENANCE_METRICS[
                "pingoo_prefilter_bank_candidate_rate"],
            labels={"plane": plane, "bank": key})
            for key in self.masked_keys]
        self._skip_counters = [registry.counter(
            "pingoo_scan_bank_skipped_total",
            schema.PROVENANCE_METRICS["pingoo_scan_bank_skipped_total"],
            labels={"plane": plane, "bank": key})
            for key in self.masked_keys]

    def observe(self, aux_vals: np.ndarray, batch_rows: int) -> None:
        """`aux_vals` is the already-materialized host aux vector (the
        caller owns the one sanctioned sync for it)."""
        m = len(self.masked_keys)
        if m == 0 or len(aux_vals) < 2 + 2 * m or not batch_rows:
            return
        cand = aux_vals[2:2 + m]
        skip = aux_vals[2 + m:2 + 2 * m]
        for j in range(m):
            self._rate_gauges[j].set(round(int(cand[j]) / batch_rows, 4))
            self._skip_counters[j].inc(int(skip[j]))


class ParityAuditor:
    """Always-on shadow-parity sampler (see module docstring).

    Hot-path surface: `submit_matrix` / `submit_lanes` — O(1) sampling
    decision + a non-blocking bounded-queue put. All interpreter work
    happens on the auditor's worker thread."""

    def __init__(self, plan, lists, plane: str, recorder=None,
                 registry=None, sample: Optional[float] = None,
                 queue_max: int = 4):
        from . import schema

        if registry is None:
            from . import REGISTRY as registry  # noqa: N813
        self.plan = plan
        self.lists = lists
        self.plane = plane
        self.recorder = recorder
        self.sample = (parity_sample_fraction()
                       if sample is None else min(max(sample, 0.0), 1.0))
        self._acc = 0.0
        self._registry = registry
        lab = {"plane": plane}
        self.checked_total = registry.counter(
            "pingoo_parity_checked_total",
            schema.PARITY_METRICS["pingoo_parity_checked_total"],
            labels=lab)
        self.mismatch_total = registry.counter(
            "pingoo_parity_mismatch_total",
            schema.PARITY_METRICS["pingoo_parity_mismatch_total"],
            labels=lab)
        self.dropped_total = registry.counter(
            "pingoo_parity_dropped_total",
            schema.PARITY_METRICS["pingoo_parity_dropped_total"],
            labels=lab)
        self._rule_help = schema.PARITY_METRICS[
            "pingoo_parity_rule_mismatch_total"]
        self._rule_series: dict[str, object] = {}
        self._rule_overflow = registry.counter(
            "pingoo_parity_rule_mismatch_total", self._rule_help,
            labels={"plane": plane, "rule": OVERFLOW_LABEL})
        self._queue: queue.Queue = queue.Queue(maxsize=queue_max)
        self._pending = 0
        self._pending_lock = threading.Lock()
        self._worker: Optional[threading.Thread] = None
        self._stop = False
        self._fault_prefix = os.environ.get("PINGOO_PARITY_FAULT_INJECT")

    # -- hot-path side -------------------------------------------------------

    def _sampled(self) -> bool:
        if self.sample <= 0.0:
            return False
        self._acc += self.sample
        if self._acc < 1.0:
            return False
        self._acc -= 1.0
        return True

    def _enqueue(self, kind: str, payload: tuple) -> bool:
        with self._pending_lock:
            self._pending += 1
        try:
            self._queue.put_nowait((kind, payload))
        except queue.Full:
            with self._pending_lock:
                self._pending -= 1
            self.dropped_total.inc()
            return False
        self._ensure_worker()
        return True

    def submit_matrix(self, reqs, matched, trace_ids=None) -> bool:
        """Python-plane batch: full [n, R] match matrix vs the
        interpreter oracle. Sampling decision + queue put only — the
        lint registry keeps this free of device syncs."""
        if not self._sampled():
            return False
        return self._enqueue("matrix", (tuple(reqs), matched, trace_ids))

    def submit_lanes(self, contexts_builder: Callable, unverified,
                     verified_block, skip_mask=None,
                     trace_ids=None) -> bool:
        """Lane-plane batch (the sidecar ships no matrix off device):
        the oracle recomputes action lanes per row and diffs those.
        `contexts_builder` runs on the WORKER thread (building
        interpreter contexts is itself too dear for the drain loop);
        `skip_mask` excludes rows whose served verdict legitimately
        used a different view (truncated/spilled slots)."""
        if not self._sampled():
            return False
        return self._enqueue("lanes", (contexts_builder, unverified,
                                       verified_block, skip_mask,
                                       trace_ids))

    # -- worker side ---------------------------------------------------------

    def _ensure_worker(self) -> None:
        if self._worker is None or not self._worker.is_alive():
            self._worker = threading.Thread(
                target=self._run, name=f"parity-audit-{self.plane}",
                daemon=True)
            self._worker.start()

    def _run(self) -> None:
        while not self._stop:
            try:
                kind, payload = self._queue.get(timeout=0.5)
            except queue.Empty:
                continue
            try:
                if kind == "matrix":
                    self._audit_matrix(*payload)
                else:
                    self._audit_lanes(*payload)
            except Exception:
                # A broken audit must never take the worker down; the
                # batch simply goes un-audited.
                pass
            finally:
                with self._pending_lock:
                    self._pending -= 1

    def stop(self) -> None:
        self._stop = True

    def flush(self, timeout_s: float = 10.0) -> bool:
        """Wait until every submitted batch has been audited (tests and
        the metrics smoke use this for determinism)."""
        self._ensure_worker()
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._pending_lock:
                if self._pending == 0:
                    return True
            time.sleep(0.005)
        return False

    def _oracle_row(self, ctx, path: str) -> np.ndarray:
        from ..engine.verdict import interpret_rules_row

        row = interpret_rules_row(self.plan, ctx)
        if self._fault_prefix and path.startswith(self._fault_prefix) \
                and len(row):
            row[0] = not row[0]  # chaos knob: oracle-only divergence
        return row

    def _record_rule_mismatches(self, names) -> None:
        for name in names:
            series = self._rule_series.get(name)
            if series is None:
                if len(self._rule_series) < RULE_SERIES_CAP:
                    series = self._registry.counter(
                        "pingoo_parity_rule_mismatch_total",
                        self._rule_help,
                        labels={"plane": self.plane, "rule": name})
                    self._rule_series[name] = series
                else:
                    self._rule_overflow.inc()
                    continue
            series.inc()

    def _mark(self, trace_id, status: str, detail=None) -> None:
        if self.recorder is not None and trace_id:
            self.recorder.mark_parity(trace_id, status, detail)

    def _audit_matrix(self, reqs, matched, trace_ids) -> None:
        from ..engine.batch import tuple_to_context

        rule_names = [r.name for r in self.plan.rules]
        for i, req in enumerate(reqs):
            ctx = tuple_to_context(req, self.lists)
            want = self._oracle_row(ctx, req.path)
            got = np.asarray(matched[i], dtype=bool)
            self.checked_total.inc()
            trace_id = (trace_ids[i] if trace_ids is not None
                        else req.trace_id)
            diff = np.nonzero(want != got)[0]
            if len(diff) == 0:
                self._mark(trace_id, PARITY_OK)
                continue
            self.mismatch_total.inc()
            names = [rule_names[int(j)] for j in diff]
            self._record_rule_mismatches(names)
            self._mark(trace_id, PARITY_MISMATCH, {
                "rules": names,
                "interpreter": [bool(want[int(j)]) for j in diff],
                "device": [bool(got[int(j)]) for j in diff],
            })

    def _audit_lanes(self, contexts_builder, unverified, verified_block,
                     skip_mask, trace_ids) -> None:
        from ..engine.verdict import action_lanes

        contexts, paths = contexts_builder()
        for i, ctx in enumerate(contexts):
            if skip_mask is not None and skip_mask[i]:
                continue
            want_row = self._oracle_row(ctx, paths[i])[None, :]
            want_unv, want_vblk = action_lanes(self.plan, want_row)
            self.checked_total.inc()
            trace_id = trace_ids[i] if trace_ids is not None else None
            ok = (int(want_unv[0]) == int(unverified[i])
                  and bool(want_vblk[0]) == bool(verified_block[i]))
            if ok:
                self._mark(trace_id, PARITY_OK)
                continue
            self.mismatch_total.inc()
            # Lane audits attribute the divergence to the interpreter's
            # first acting matched rule (the lanes carry no bitmap).
            acting = [r.name for r in self.plan.rules
                      if r.actions and want_row[0, r.index]]
            names = acting[:1] or [OVERFLOW_LABEL]
            self._record_rule_mismatches(names)
            self._mark(trace_id, PARITY_MISMATCH, {
                "rules": names,
                "interpreter_action": int(want_unv[0]),
                "served_action": int(unverified[i]),
                "interpreter_verified_block": bool(want_vblk[0]),
                "served_verified_block": bool(verified_block[i]),
            })
