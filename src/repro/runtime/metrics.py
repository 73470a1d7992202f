"""Run metrics: counters, execution intervals and :class:`SimResult`.

One :class:`Metrics` instance per engine accumulates the machine-global
counters (transferred bytes, transfer/steal/event counts, per-worker busy
time, the interval timeline). Per-graph attribution lives on each
:class:`~repro.runtime.engine.GraphContext` (its own interval list and
completion time), from which the engine derives per-graph results for
multi-tenant streams.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

from repro.core.machine import MachineModel


@dataclass(slots=True)
class ScheduledInterval:
    tid: int
    rid: int
    start: float
    end: float


@dataclass
class SimResult:
    makespan: float
    total_bytes: int
    n_transfers: int
    n_steals: int
    busy: Dict[int, float]
    intervals: List[ScheduledInterval]
    strategy: str
    total_flops: float
    n_events: int = 0
    # fault/recovery counters (None for runs with no fault source active;
    # see Metrics.fault_summary and repro.runtime.faults)
    faults: Optional[Dict[str, float]] = None
    # serving-mode arrival accounting (engine.submit at= / admission)
    submit_at: float = 0.0
    admit_at: float = 0.0
    admitted: bool = True
    # demand-copy hops and bytes by route (Metrics.routes)
    routes: Dict[str, int] = field(default_factory=dict)

    @property
    def gflops(self) -> float:
        if self.makespan <= 0:
            return 0.0
        return self.total_flops / self.makespan / 1e9

    @property
    def gbytes(self) -> float:
        return self.total_bytes / 1e9


class Metrics:
    """Engine-global counters (shared across every submitted graph)."""

    __slots__ = (
        "total_bytes", "n_transfers", "n_steals", "n_events",
        "busy", "intervals", "n_evictions", "n_writebacks", "writeback_bytes",
        "n_detaches", "n_attaches", "n_killed", "n_requeued",
        "n_evacuations", "evacuated_bytes", "wasted_s",
        "n_notices", "n_proactive", "proactive_bytes",
        "n_retries", "n_timeouts", "retry_delay_s",
        "n_arrivals", "n_admitted", "n_rejected", "n_deferred",
        "hops_host", "hops_peer", "hops_staged",
        "bytes_host", "bytes_peer", "bytes_staged",
    )

    def __init__(self, machine: MachineModel) -> None:
        self.total_bytes = 0
        self.n_transfers = 0
        self.n_steals = 0
        self.n_events = 0
        self.busy: Dict[int, float] = {r.rid: 0.0 for r in machine.resources}
        self.intervals: List[ScheduledInterval] = []
        # eviction traffic (capacity-bounded memories only)
        self.n_evictions = 0
        self.n_writebacks = 0
        self.writeback_bytes = 0
        # fault/recovery counters (repro.runtime.faults)
        self.n_detaches = 0
        self.n_attaches = 0
        self.n_killed = 0  # running tasks aborted (kill-and-requeue)
        self.n_requeued = 0  # tasks re-activated off dead workers
        self.n_evacuations = 0  # dirty data salvaged to host at detach
        self.evacuated_bytes = 0  # reactive salvage traffic (at death)
        self.wasted_s = 0.0  # partial execution discarded by kills
        # proactive recovery (preemption notices) and flaky-link retries
        self.n_notices = 0  # advance warnings delivered
        self.n_proactive = 0  # sole copies replicated inside the notice
        self.proactive_bytes = 0
        self.n_retries = 0  # failed hops retried with backoff
        self.n_timeouts = 0  # retry budget exhausted -> re-sourced
        self.retry_delay_s = 0.0  # total backoff delay injected
        # serving-mode arrivals and admission control (repro.runtime.load)
        self.n_arrivals = 0  # tenant graphs that reached the machine
        self.n_admitted = 0  # ... admitted past admission control
        self.n_rejected = 0  # ... turned away (working set vs capacity)
        self.n_deferred = 0  # defer re-posts (one arrival may defer many times)
        # demand-copy hops by route (repro.runtime.transfers): host link,
        # peer fabric, and the two host-link legs of a staged copy
        self.hops_host = self.hops_peer = self.hops_staged = 0
        self.bytes_host = self.bytes_peer = self.bytes_staged = 0

    def routes(self) -> Dict[str, int]:
        return {k: getattr(self, k) for k in (
            "hops_host", "hops_peer", "hops_staged",
            "bytes_host", "bytes_peer", "bytes_staged")}

    def fault_summary(self) -> Dict[str, float]:
        """The fault counters as a plain dict (``SimResult.faults``)."""
        return {
            "n_detaches": self.n_detaches,
            "n_attaches": self.n_attaches,
            "n_killed": self.n_killed,
            "n_requeued": self.n_requeued,
            "n_evacuations": self.n_evacuations,
            "evacuated_bytes": self.evacuated_bytes,
            "wasted_s": self.wasted_s,
            "n_notices": self.n_notices,
            "n_proactive": self.n_proactive,
            "proactive_bytes": self.proactive_bytes,
            "n_retries": self.n_retries,
            "n_timeouts": self.n_timeouts,
            "retry_delay_s": self.retry_delay_s,
        }


def recovery_report(faulted: SimResult, baseline: SimResult) -> Dict[str, float]:
    """Recovery metrics of a faulted run against its clairvoyant no-fault
    baseline (same graph/machine/strategy/seed, no detach/attach events).

    ``recovery_makespan`` is the headline number (claim C8): the makespan
    the faults cost on top of the undisturbed schedule. ``extra_bytes``
    includes both evacuation traffic and the re-transfers that rebuilding
    affinity on the survivors required.

    Evacuation traffic is split by when it moved (claim C9):
    ``proactive_bytes`` — sole copies replicated to host inside a
    preemption-notice window, before the device died — versus
    ``reactive_evacuated_bytes`` — salvage at death, on the critical
    recovery path. Retry/timeout counters from flaky links are surfaced
    here too so benchmarks read one dict instead of re-deriving them
    from audit logs.
    """
    out: Dict[str, float] = {
        "makespan": faulted.makespan,
        "baseline_makespan": baseline.makespan,
        "recovery_makespan": faulted.makespan - baseline.makespan,
        "slowdown": (
            faulted.makespan / baseline.makespan
            if baseline.makespan > 0
            else float("inf")
        ),
        "extra_bytes": faulted.total_bytes - baseline.total_bytes,
    }
    if faulted.faults:
        out.update(faulted.faults)
        out["reactive_evacuated_bytes"] = faulted.faults.get(
            "evacuated_bytes", 0
        )
    return out


# ---------------------------------------------------------------------------
# serving-mode aggregates (multi-tenant open-loop load, repro.runtime.load)


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile (``q`` in [0, 100]); 0.0 for empty input.

    Nearest-rank (not interpolated) so a reported p99 is always a value
    some tenant actually experienced.
    """
    if not values:
        return 0.0
    if not (0.0 <= q <= 100.0):
        raise ValueError(f"percentile q must be in [0, 100], got {q!r}")
    s = sorted(values)
    rank = max(1, -(-len(s) * q // 100))  # ceil(len * q / 100), min 1
    return float(s[int(rank) - 1])


def jain_fairness(values: List[float]) -> float:
    """Jain's fairness index (Σx)² / (n·Σx²) — 1.0 means every tenant got
    identical treatment, 1/n means one tenant got everything; 1.0 for
    empty or all-zero input (nobody was treated unequally)."""
    if not values:
        return 1.0
    total = sum(values)
    sq = sum(v * v for v in values)
    if sq <= 0.0:
        return 1.0
    return (total * total) / (len(values) * sq)


def serving_report(tenants: List[Dict[str, float]]) -> Dict[str, float]:
    """Aggregate per-tenant serving rows (``repro.runtime.load.run_serving``)
    into the p50/p99 + fairness summary that ``benchmarks/serving_load.py``
    reports and gates on.

    Each row carries ``makespan``, ``slowdown`` (vs the tenant's
    empty-machine baseline) and ``queue_delay`` (first execution start
    minus submit time). Fairness is Jain's index over the slowdowns:
    equal slowdown = perfectly fair service, regardless of how different
    the tenants' graph sizes are.
    """
    slow = [float(r["slowdown"]) for r in tenants]
    qd = [float(r["queue_delay"]) for r in tenants]
    mk = [float(r["makespan"]) for r in tenants]
    n = len(tenants)
    return {
        "n_tenants": n,
        "p50_makespan": percentile(mk, 50),
        "p99_makespan": percentile(mk, 99),
        "p50_slowdown": percentile(slow, 50),
        "p99_slowdown": percentile(slow, 99),
        "mean_slowdown": (sum(slow) / n) if n else 0.0,
        "p50_queue_delay": percentile(qd, 50),
        "p99_queue_delay": percentile(qd, 99),
        "jain_fairness": jain_fairness(slow),
    }
