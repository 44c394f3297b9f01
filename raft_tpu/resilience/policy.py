"""Recovery policies: bounded retry, and the graceful-degradation ladders.

Two recovery shapes, both observable and both terminating:

- **Retry** (:func:`run_with_policy`): re-run the same work a bounded
  number of times with optional backoff — right for transient device
  errors and for nth-call injected faults. Every retry is counted
  (``raft_tpu_recovery_retries_total{site}``), exhaustion is counted
  and re-raises the last classified error. A
  :class:`~raft_tpu.core.error.DeadlineExceededError` is NEVER retried:
  a deadline is the caller's global budget, not a transient.
- **Degrade** (:func:`fused_degradation_ladder` /
  :func:`degrade_merge`): when the failure is structural (HBM
  exhaustion, a collective that keeps failing), retrying the same
  program cannot help — instead walk a finite ladder of configurations
  that trade speed for survival, each rung re-validated against the
  production fit predicate (``_valid_cfg`` + ``fit_config`` unshrunk)
  and each step counted under
  ``raft_tpu_degradations_total{site,action}``. Correctness is part of
  the ladder contract: every rung returns bit-identical ids to the
  undegraded oracle (values within the pack-perturbation bound) — the
  ladder-equality tests in tests/test_resilience.py pin that down.

The fused ladder order (cheapest give-up first):

1. halve ``Qb`` (pure throughput knob — certificate untouched);
2. halve ``T`` (smaller tiles, weaker streaming);
3. halve ``g`` (smaller certificate groups → bigger candidate pool);
4. ``grid_order`` db/dbuf → "query" (the packed database-major kernels
   give way to the general query-major pipeline — the packed→unpacked
   rung);
5. double ``micro_batches`` (sharded path only: smaller per-block
   footprint, more merge rounds).

``tools/bench_report.py --check`` refuses to gate (or baseline) any
round whose artifact recorded a nonzero degradation counter — perf
evidence from a degraded run is history, not a baseline.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import Callable, Dict, Iterator, Optional, Tuple

from raft_tpu.core import env
from raft_tpu.core.error import (DeadlineExceededError, DeviceError,
                                 OutOfMemoryError, device_errors)

RETRIES = "raft_tpu_recovery_retries_total"
EXHAUSTED = "raft_tpu_recovery_exhausted_total"
DEGRADATIONS = "raft_tpu_degradations_total"
POISONED = "raft_tpu_output_poisoned_total"


class PoisonedOutputError(DeviceError):
    """Output validation found non-finite values where the contract
    promises finite ones (NaN poisoning — silent data corruption made
    loud). Recovered by bounded retry, not by degradation: the config
    was fine, the run was not."""


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded-retry parameters for one site. ``retry_on`` must name
    error classes (see core.error) — raw jaxlib exceptions are
    classified before matching."""

    max_retries: int = 2
    backoff_s: float = 0.0
    backoff_mult: float = 2.0
    retry_on: Tuple[type, ...] = (OutOfMemoryError, DeviceError)


DEFAULT_POLICY = RetryPolicy()

# site (or site prefix before the first dot) → policy
DEFAULT_POLICIES: Dict[str, RetryPolicy] = {
    "runtime": RetryPolicy(max_retries=2),
    "distance.knn_fused_sharded": RetryPolicy(max_retries=2),
}


class PolicyTable:
    """Per-handle recovery-policy registry — the ``res.resilience``
    resource slot. Lookup falls back site → site's first dotted prefix
    → :data:`DEFAULT_POLICY`; ``RAFT_TPU_RETRY_MAX`` (env) caps
    ``max_retries`` globally (0 disables retries entirely — every
    failure surfaces on the first attempt)."""

    def __init__(self, overrides: Optional[Dict[str, RetryPolicy]] = None):
        self._policies: Dict[str, RetryPolicy] = dict(DEFAULT_POLICIES)
        if overrides:
            self._policies.update(overrides)

    def set_policy(self, site: str, policy: RetryPolicy) -> None:
        self._policies[site] = policy

    def policy_for(self, site: str) -> RetryPolicy:
        pol = self._policies.get(site)
        if pol is None:
            pol = self._policies.get(site.split(".")[0], DEFAULT_POLICY)
        cap = env.get("RAFT_TPU_RETRY_MAX")
        if cap is not None:
            pol = dataclasses.replace(pol,
                                      max_retries=max(0, int(cap)))
        return pol


_global_table: Optional[PolicyTable] = None
_table_lock = threading.Lock()


def get_policy_table() -> PolicyTable:
    """Process-default policy table (the RESILIENCE slot's default)."""
    global _global_table
    with _table_lock:
        if _global_table is None:
            _global_table = PolicyTable()
        return _global_table


def _registry():
    from raft_tpu.observability import get_registry

    return get_registry()


def record_retry(site: str, error: BaseException,
                 attempt: int = 0) -> None:
    try:
        from raft_tpu.observability.timeline import emit_retry

        reg = _registry()
        reg.counter(RETRIES, {"site": site},
                    help="Recovery retries, by site").inc()
        reg.emit({"type": "retry", "site": site, "attempt": attempt,
                  "error": f"{type(error).__name__}: {error}"[:200]})
        emit_retry(site, attempt, f"{type(error).__name__}: {error}")
    except Exception:
        pass


def record_exhausted(site: str) -> None:
    try:
        _registry().counter(
            EXHAUSTED, {"site": site},
            help="Recovery attempts that ran out of retries").inc()
    except Exception:
        pass


def record_degradation(site: str, action: str) -> None:
    """Count one ladder step. ``action`` is a stable machine-readable
    label like ``merge:tournament->allgather`` or ``fit:Qb:256->128``.
    Also emitted as a ``degradation`` timeline event, so ladder walks
    are visible in a Perfetto trace — not just counters."""
    try:
        from raft_tpu.observability.timeline import emit_degradation

        reg = _registry()
        reg.counter(DEGRADATIONS, {"site": site, "action": action},
                    help="Graceful-degradation ladder steps taken").inc()
        reg.emit({"type": "degradation", "site": site, "action": action})
        emit_degradation(site, action)
    except Exception:
        pass
    from raft_tpu.core.logger import log_warn

    log_warn("resilience: degrading %s (%s)", site, action)


def degradation_count(registry=None) -> float:
    """Total degradation-ladder steps recorded in ``registry`` (default:
    the process-global one) — stamped into BENCH artifacts so
    ``bench_report --check`` can refuse degraded evidence."""
    reg = registry if registry is not None else _registry()
    total = 0.0
    for metric in reg.collect():
        if getattr(metric, "name", None) == DEGRADATIONS:
            total += metric.value
    return total


def degradation_reasons(registry=None) -> list:
    """The recorded ladder steps as ``"site:action ×count"`` strings —
    the evidence a measurement prints when it refuses to stand for a
    degraded round."""
    reg = registry if registry is not None else _registry()
    out = []
    for metric in reg.collect():
        if getattr(metric, "name", None) != DEGRADATIONS:
            continue
        if metric.value <= 0:
            continue
        labels = getattr(metric, "labels", {}) or {}
        site = labels.get("site", "?")
        action = labels.get("action", "?")
        out.append(f"{site}:{action} x{metric.value:g}")
    return sorted(out)


def run_with_policy(site: str, fn: Callable[[int], object],
                    policy: Optional[RetryPolicy] = None,
                    on_retry: Optional[Callable] = None):
    """Run ``fn(attempt)`` under ``policy``: device-layer exceptions are
    classified into the raft error classes, matching ones are retried up to
    ``max_retries`` with backoff, and exhaustion re-raises the last
    classified error. Deadline errors always propagate immediately."""
    if policy is None:
        policy = get_policy_table().policy_for(site)
    attempt = 0
    delay = policy.backoff_s
    while True:
        try:
            with device_errors(site):
                return fn(attempt)
        except DeadlineExceededError:
            raise
        except policy.retry_on as e:
            attempt += 1
            if attempt > policy.max_retries:
                record_exhausted(site)
                raise
            record_retry(site, e, attempt)
            from raft_tpu.core.logger import log_warn

            log_warn("resilience: %s failed (%s: %s) — retry %d/%d",
                     site, type(e).__name__, str(e)[:120], attempt,
                     policy.max_retries)
            if on_retry is not None:
                on_retry(attempt, e)
            if delay > 0:
                time.sleep(delay)
                delay *= policy.backoff_mult


# ---------------------------------------------------------------------
# degradation ladders
# ---------------------------------------------------------------------

#: collective-failure ladder for the sharded merge: butterfly rounds →
#: one all-gather → no collective at all (per-shard candidates gathered
#: and merged on host). Every rung is deterministic rank-major, so the
#: merged ids stay bit-identical across rungs.
MERGE_LADDER = ("tournament", "allgather", "host")


def degrade_merge(strategy: str) -> Optional[str]:
    """Next rung down the merge ladder, or None at the bottom."""
    try:
        i = MERGE_LADDER.index(strategy)
    except ValueError:
        return None
    return MERGE_LADDER[i + 1] if i + 1 < len(MERGE_LADDER) else None


@dataclasses.dataclass(frozen=True)
class FusedRung:
    """One validated rung of the fused OOM ladder."""

    T: int
    Qb: int
    g: int
    grid_order: str
    micro_batches: int
    action: str          # what changed vs the previous rung


def fused_degradation_ladder(T: int, Qb: int, g: int, grid_order: str,
                             d: int, passes: int,
                             micro_batches: int = 1,
                             max_micro_batches: int = 64
                             ) -> Iterator[FusedRung]:
    """Yield successively degraded fused configs (see module doc for
    the rung order). Every yielded rung passes the PRODUCTION validity
    chain — ``_valid_cfg`` and ``fit_config`` unshrunk at feature width
    ``d`` — so the runtime never silently reshapes a rung it is handed;
    invalid intermediate points are skipped, and the generator is
    finite (each knob shrinks monotonically), so the ladder always
    terminates."""
    from raft_tpu.distance.knn_fused import (_LANES, GRID_ORDERS,
                                             _valid_cfg, fit_config)

    if grid_order not in GRID_ORDERS:
        raise ValueError(f"grid_order must be one of {GRID_ORDERS}, "
                         f"got {grid_order!r}")

    def _ok(T_, Qb_, g_, order_):
        return (_valid_cfg(T_, Qb_, g_, order_)
                and fit_config(T_, Qb_, d, passes, g_, order_) == (T_, Qb_))

    cur = dict(T=T, Qb=Qb, g=g, grid_order=grid_order,
               micro_batches=micro_batches)
    while cur["Qb"] > 8:
        new = max(8, (cur["Qb"] // 2) // 8 * 8)
        action = f"fit:Qb:{cur['Qb']}->{new}"
        cur["Qb"] = new
        if _ok(cur["T"], cur["Qb"], cur["g"], cur["grid_order"]):
            yield FusedRung(action=action, **cur)
    while cur["T"] > 2 * _LANES:
        new = max(2 * _LANES, (cur["T"] // 2) // _LANES * _LANES)
        action = f"fit:T:{cur['T']}->{new}"
        cur["T"] = new
        if _ok(cur["T"], cur["Qb"], cur["g"], cur["grid_order"]):
            yield FusedRung(action=action, **cur)
    while cur["g"] > 1:
        new = max(1, cur["g"] // 2)
        action = f"fit:g:{cur['g']}->{new}"
        cur["g"] = new
        if _ok(cur["T"], cur["Qb"], cur["g"], cur["grid_order"]):
            yield FusedRung(action=action, **cur)
    if cur["grid_order"] in ("db", "dbuf"):
        action = f"fit:grid_order:{cur['grid_order']}->query"
        cur["grid_order"] = "query"
        if _ok(cur["T"], cur["Qb"], cur["g"], cur["grid_order"]):
            yield FusedRung(action=action, **cur)
    while cur["micro_batches"] < max_micro_batches:
        new = cur["micro_batches"] * 2
        action = f"fit:micro_batches:{cur['micro_batches']}->{new}"
        cur["micro_batches"] = new
        yield FusedRung(action=action, **cur)
