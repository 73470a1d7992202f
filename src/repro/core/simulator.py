"""The single-graph simulation facade over :class:`repro.runtime.Engine`.

Historically this module *was* the runtime — a 460-line monolith holding
the event loop, the worker queues, the transfer machinery, the metrics and
the steal protocol. Those layers now live in :mod:`repro.runtime`
(``events`` / ``queues`` / ``transfers`` / ``memory`` / ``engine`` /
``metrics``); :class:`Simulator` remains the stable single-graph surface:
construct with one graph, ``run()`` one :class:`SimResult`.

With capacity unbounded (the default) a ``Simulator`` run is bit-for-bit
identical to the pre-decomposition simulator — same event order, same
seeded stream consumption, same IEEE operation order — which is what the
equivalence suites against ``repro.core._reference`` enforce. Capacity
limits and eviction (``REPRO_SCHED_MEM_CAPACITY`` /
``REPRO_SCHED_EVICTION`` or the ``mem_capacity=`` / ``eviction=``
arguments) and stale-transfer cancellation (``REPRO_SCHED_CANCEL_STALE``)
are opt-in; multi-graph streaming is the engine's own surface
(``Engine.submit``).
"""
from __future__ import annotations

from typing import Optional

from repro.runtime.engine import Engine, GraphContext, Strategy
from repro.runtime.metrics import ScheduledInterval, SimResult

from .dag import TaskGraph
from .machine import MachineModel
from .perfmodel import TransferModel

__all__ = ["ScheduledInterval", "SimResult", "Simulator", "Strategy"]


class Simulator(Engine):
    """One task graph on one machine: the paper's simulation setup."""

    def __init__(
        self,
        graph: TaskGraph,
        machine: MachineModel,
        strategy: Strategy,
        seed: int = 0,
        noise: float = 0.03,
        transfer_model: Optional[TransferModel] = None,
        config=None,
        mem_capacity: Optional[int] = None,
        eviction: Optional[str] = None,
        cancel_stale: Optional[bool] = None,
        churn: Optional[float] = None,
        fault_mode: Optional[str] = None,
        fault_trace: Optional[str] = None,
        notice_s: Optional[float] = None,
        link_flake: Optional[float] = None,
        retry_max: Optional[int] = None,
        backoff_s: Optional[float] = None,
        audit: Optional[bool] = None,
    ) -> None:
        super().__init__(
            machine,
            strategy,
            seed=seed,
            noise=noise,
            transfer_model=transfer_model,
            config=config,
            mem_capacity=mem_capacity,
            eviction=eviction,
            cancel_stale=cancel_stale,
            churn=churn,
            fault_mode=fault_mode,
            fault_trace=fault_trace,
            notice_s=notice_s,
            link_flake=link_flake,
            retry_max=retry_max,
            backoff_s=backoff_s,
            audit=audit,
        )
        self._primary: GraphContext = self.submit(graph)
        # legacy aliases (instrumentation and benchmarks reset these
        # between measured placements)
        self._inflight = self._primary.inflight
        self._waiting = self._primary.waiting

    # ------------------------------------------------------------------
    def request_transfer(self, name: str, size: int, dst_mem: int):
        """Ensure a valid copy of ``name`` will exist at ``dst_mem``.

        Returns the completion time, or None if already resident.
        """
        return self.transfers.request(
            self._primary, name, size, dst_mem, self.now
        )

    # ------------------------------------------------------------------
    def run(self) -> SimResult:
        self._run_loop()
        m = self.metrics
        return SimResult(
            makespan=self.now,
            total_bytes=m.total_bytes,
            n_transfers=m.n_transfers,
            n_steals=m.n_steals,
            busy=dict(m.busy),
            intervals=m.intervals,
            strategy=self.strategy.name,
            total_flops=self._primary.graph.total_flops(),
            n_events=m.n_events,
            routes=m.routes(),
            faults=(
                m.fault_summary()
                if (self._faults_on or self._flake_on)
                else None
            ),
        )
