"""Epoch-switched ruleset hot-swap (ISSUE 11, docs/RESILIENCE.md).

The reference reloads rules by tearing the listener down; at batch
throughput that drops every in-flight request. Here a new RulesetPlan
is compiled AHEAD of the switch (through the artifact cache, off the
serving path) and each engine plane flips to it atomically at a batch
boundary: in-flight batches finish on the old plan, new admissions use
the new one, and every verdict is attributable to exactly one epoch
(`pingoo_ruleset_epoch`). A sidecar that serves from several chips
(`--replicas`) builds the new plan's tables on every one of them with
the rest of its state, off the loop, so that no chip is left on the
old plan after the flip. The swap pause — drain-of-inflight + pointer
flip, compile excluded by construction — is the number the
PINGOO_DEADLINE_MS budget must absorb (tracked as swap_pause_p99_ms in
bench_regress).

Multi-tenant scale-out rides the same mechanism: TenantPlanStore keeps
one compiled plan per tenant key (2k-10k rules total across isolated
tenants), fingerprinted tenant-scoped in the artifact cache so one
deployment serves many rulesets and swaps any of them independently.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Optional

from ..compiler.plan import RulesetPlan


def note_swap(plane: str, tenant: str, result: str) -> None:
    """Count one swap attempt on the shared registry
    (pingoo_ruleset_swap_total{plane,tenant,result})."""
    from ..obs import REGISTRY
    from ..obs.schema import HOTSWAP_METRICS

    REGISTRY.counter(
        "pingoo_ruleset_swap_total",
        HOTSWAP_METRICS["pingoo_ruleset_swap_total"],
        labels={"plane": plane, "tenant": tenant or "default",
                "result": result}).inc()


def set_epoch_gauge(plane: str, epoch: int) -> None:
    from ..obs import REGISTRY
    from ..obs.schema import HOTSWAP_METRICS

    REGISTRY.gauge(
        "pingoo_ruleset_epoch",
        HOTSWAP_METRICS["pingoo_ruleset_epoch"],
        labels={"plane": plane}).set(epoch)


@dataclass
class SwapHandle:
    """One requested swap, resolved by the serving loop at the next
    batch boundary. `wait()` blocks the requester (a config-reload
    thread, never the serving loop) until the flip happened; pause_ms
    is the drain+flip wall — the admission stall the swap cost."""

    plan: RulesetPlan
    tenant: str = "default"
    lists: Optional[dict] = None
    # Pre-built engine state (plan-derived jitted fns/tables), built by
    # the requester BEFORE the handle reaches the serving loop so the
    # loop's flip is pointer assignment, not compilation.
    state: Optional[dict] = None
    done: threading.Event = field(default_factory=threading.Event)
    epoch: int = -1
    pause_ms: float = 0.0
    result: str = "pending"
    error: Optional[BaseException] = None

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self.done.wait(timeout)

    def resolve(self, epoch: int, pause_ms: float,
                result: str = "ok",
                error: Optional[BaseException] = None) -> None:
        self.epoch = epoch
        self.pause_ms = pause_ms
        self.result = result
        self.error = error
        self.done.set()


@dataclass
class TenantPlan:
    tenant: str
    plan: RulesetPlan
    fingerprint: str
    lists: dict
    compiled_at: float


class TenantPlanStore:
    """Compile-ahead store: tenant key -> current RulesetPlan.

    `prepare()` compiles (or loads from the artifact cache, tenant-
    scoped fingerprint) WITHOUT touching what is being served — the
    caller then hands the returned plan to VerdictService.swap_plan /
    RingSidecar.request_swap. A tenant's plan is only replaced in the
    store once prepare() fully succeeded, so a broken ruleset push can
    never take a tenant's serving plan away."""

    def __init__(self, cache_dir: Optional[str] = None):
        self.cache_dir = cache_dir
        self._lock = threading.Lock()
        self._plans: dict[str, TenantPlan] = {}

    def prepare(self, tenant: str, rules: list, lists: dict,
                field_specs=None, routes=None) -> TenantPlan:
        from ..compiler.cache import (compile_ruleset_cached,
                                      ruleset_fingerprint)

        fingerprint = ruleset_fingerprint(
            rules, lists, field_specs, routes=routes, tenant=tenant)
        plan = compile_ruleset_cached(
            rules, lists, cache_dir=self.cache_dir,
            field_specs=field_specs, routes=routes, tenant=tenant)
        entry = TenantPlan(tenant=tenant, plan=plan,
                           fingerprint=fingerprint, lists=dict(lists),
                           compiled_at=time.time())
        with self._lock:
            self._plans[tenant] = entry
        return entry

    def get(self, tenant: str) -> Optional[TenantPlan]:
        with self._lock:
            return self._plans.get(tenant)

    def tenants(self) -> list[str]:
        with self._lock:
            return sorted(self._plans)

    def total_rules(self) -> int:
        with self._lock:
            return sum(len(e.plan.rules) for e in self._plans.values())
