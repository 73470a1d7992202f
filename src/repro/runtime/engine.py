"""The event-driven XKaapi-like runtime engine.

Reproduces the paper's execution flow (§2.1-2.2):
  * each worker owns a local ready-queue (pop / push / steal),
  * completing a task triggers ``activate`` on its newly-ready successors —
    this is where the scheduling strategy runs,
  * idle workers emit steal requests to a randomly selected victim (enabled
    per strategy; HEFT/DADA place every ready task explicitly),
  * transfers to/from accelerator memories are prefetched when a task is
    pushed, overlap with computation, and contend on shared PCIe-switch
    links (FIFO per link group — :mod:`repro.runtime.transfers`),
  * the runtime observes real (noisy) durations and feeds the history-based
    performance model, which therefore calibrates online (§2.3).

Beyond the monolithic simulator this engine adds:

  * **multi-graph streams** — :meth:`Engine.submit` accepts any number of
    task graphs, before or during the run (``at=`` posts the arrival as an
    event), so many tenant DAGs interleave on one machine. Each graph gets
    its own :class:`GraphContext` (residency, calibration caches, interval
    timeline) and its own per-graph :class:`SimResult`;
  * **capacity-bounded memories** — opt-in via ``REPRO_SCHED_MEM_CAPACITY``
    / ``REPRO_SCHED_EVICTION`` (:mod:`repro.runtime.memory`): evictions,
    dirty write-backs and the pressure signal policies consume;
  * **stale-transfer cancellation** — opt-in via
    ``REPRO_SCHED_CANCEL_STALE=1``: an in-flight copy of data that is
    overwritten mid-flight no longer lands as a "valid" copy (the
    historical behavior, preserved by default for equivalence, is a known
    modeling artifact of the original simulator).

Determinism: all randomness flows through one seeded numpy Generator (the
per-task duration noise of each graph is drawn, in tid order, when the
graph is submitted).

With a single graph submitted and capacity unbounded, the engine is
bit-for-bit identical to the monolithic simulator it replaced — the same
event posting order, the same seeded stream consumption, the same IEEE
operation order. ``repro.core.Simulator`` is the thin single-graph facade;
``tests/test_equivalence*.py`` enforce the contract against the frozen
scalar references.
"""
from __future__ import annotations

import heapq
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from repro.core.dag import GraphArrays, Task, TaskGraph
from repro.core.machine import HOST_MEM, MachineModel, ResourceClass
from repro.core.perfmodel import (
    ClassPredictor,
    HistoryPerfModel,
    Residency,
    TransferModel,
)

from .events import EventQueue
from .faults import FaultManager
from .load import ADMISSION_MODES
from .memory import MemoryManager
from .metrics import Metrics, ScheduledInterval, SimResult
from .queues import Worker, eligible_victims
from .rescore import RESCORE_MODES, ServingScheduler
from .traces import FAULT_EVENTS, FAULT_MODES, load_trace
from .transfers import TransferEngine


class Strategy:
    """Scheduling strategy interface: placement happens in ``activate``."""

    name = "base"
    allow_steal = False
    owner_lifo = False

    def init(self, sim) -> None:  # pragma: no cover - default
        pass

    def place(
        self, sim, ready: List[Task], src: Optional[int]
    ) -> None:  # pragma: no cover - interface
        raise NotImplementedError


class GraphContext:
    """Per-submitted-graph state: one tenant DAG inside the engine."""

    __slots__ = (
        "gid", "graph", "arrays", "residency", "inflight", "waiting",
        "noise_mult", "preds", "succ", "done", "n_done", "n_tasks",
        "rid_static", "predictors", "submit_at", "finish", "intervals",
        "data_version", "readers_left", "attempt",
        "priority", "ws_bytes", "arrived", "admitted", "rejected",
        "admit_at",
    )

    def __init__(self, gid: int, graph: TaskGraph) -> None:
        self.gid = gid
        self.graph = graph
        self.arrays: GraphArrays = graph.arrays()
        self.residency = Residency()
        self.residency.attach(self.arrays)
        # all application data starts in host memory (paper setup)
        self.residency.initialize(self.arrays.data_names, HOST_MEM)
        # in-flight transfers indexed per data name: name -> {dst_mem: t}
        self.inflight: Dict[str, Dict[int, float]] = {}
        self.waiting: Dict[tuple, List[int]] = {}  # (name, mem) -> worker rids
        self.preds = [len(graph.pred[t.tid]) for t in graph.tasks]
        self.succ = [graph.succ[t.tid] for t in graph.tasks]
        self.done = [False] * len(graph)
        self.n_done = 0
        self.n_tasks = len(graph)
        self.predictors: Dict[str, ClassPredictor] = {}
        self.rid_static: List[List[float]] = []
        self.noise_mult: Optional[List[float]] = None
        self.submit_at = 0.0
        self.finish = 0.0
        self.intervals: List[ScheduledInterval] = []
        self.data_version: Dict[str, int] = {}  # bumped per write (cancel-stale)
        self.readers_left: List[int] = []  # per-did pending readers (bounded)
        # per-task execution attempt, bumped when a kill-mode detach aborts
        # the running task: the already-posted "done" event of the aborted
        # execution is recognized as stale by its recorded attempt
        self.attempt: List[int] = [0] * len(graph)
        # serving-mode tenancy state (repro.runtime.load): priority feeds
        # the fairness policies, ws_bytes the admission controller; the
        # arrival/admission flags are only ever set in Engine._arrive, so
        # default-loop runs never touch them
        self.priority = 1.0
        self.ws_bytes = int(self.arrays.data_sizes.sum())
        self.arrived = False
        self.admitted = False
        self.rejected = False
        self.admit_at = 0.0


class Engine:
    """The composable event loop: events + queues + transfers + memory.

    Strategies interact with the engine through the same surface the
    monolithic ``Simulator`` exposed (``push``, ``load_ts``, ``now``,
    ``predictor``, ``residency``, ``arrays``, ``graph``, ``machine``,
    ``transfer_model``, ``model``, ``config``, ``memory``); during an
    activation these views point at the graph whose tasks became ready.
    """

    def __init__(
        self,
        machine: MachineModel,
        strategy,
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
        rescore: Optional[str] = None,
        admission: Optional[str] = None,
        admit_defer_s: Optional[float] = None,
    ) -> None:
        self.machine = machine
        self.strategy = strategy
        # the typed scheduling configuration (repro.sched.SchedConfig);
        # strategies and instrumentation read engine.config instead of
        # scattering os.environ lookups through hot paths
        self._config = config
        self.rng = np.random.default_rng(seed)
        self.noise = noise
        self.model = HistoryPerfModel()
        self.transfer_model = transfer_model or TransferModel.of(machine)

        self.now = 0.0
        self.events = EventQueue()
        self._events = self.events.heap  # legacy alias (benchmarks reset it)
        self.workers = [Worker(r.rid) for r in machine.resources]
        # shared predicted-completion time-stamps (paper §2.3)
        self.load_ts = [0.0] * len(self.workers)
        # per-rid memory space / residency bit (avoids by_id() in hot paths)
        self._mem_of = [r.mem for r in machine.resources]
        self._bit_of = [1 << (r.mem + 1) for r in machine.resources]
        self._steal_on = strategy.allow_steal
        self._lifo = strategy.owner_lifo

        self.metrics = Metrics(machine)
        self.transfers = TransferEngine(
            machine, self.transfer_model, self.events, self.metrics
        )
        self._link_free = self.transfers.link_free  # legacy alias

        # opt-in layers: capacity-bounded memories + stale cancellation;
        # explicit arguments win over the (env-derived) SchedConfig
        cfg = self.config
        if mem_capacity is None:
            mem_capacity = cfg.mem_capacity
        if eviction is None:
            eviction = cfg.eviction
        if cancel_stale is None:
            cancel_stale = cfg.cancel_stale
        self.memory = MemoryManager(machine, mem_capacity, eviction)
        self.memory.transfers = self.transfers
        self.transfers.memory = self.memory
        self._bounded = self.memory.bounded
        self._cancel_stale = bool(cancel_stale)
        self.transfers.cancel_stale = self._cancel_stale

        # resource dynamics: detach/attach faults (repro.runtime.faults).
        # The manager is always present but inert until a fault source
        # registers — hot paths check `_faults_on` once, preserving the
        # zero-fault bit-for-bit equivalence contract.
        if fault_mode is None:
            fault_mode = cfg.fault_mode
        self.faults = FaultManager(machine, mode=fault_mode)
        self.transfers.faults = self.faults
        self._faults_on = False
        # preemption-notice window: detaches are announced this many
        # simulated seconds in advance (0 = no warning, the default)
        if notice_s is None:
            notice_s = cfg.notice_s
        self._notice_s = float(notice_s)
        if churn is None:
            churn = cfg.churn
        if churn:
            self.faults.enable_churn(
                churn, seed=seed, mode=fault_mode, notice_s=self._notice_s
            )
            self._faults_on = True
        if fault_trace is None:
            fault_trace = cfg.fault_trace
        if fault_trace:
            self.replay_trace(fault_trace)

        # transient link faults: seeded per-hop failure rate with capped
        # exponential retry backoff (repro.runtime.transfers). Zero-flake
        # engines never touch the flake stream — bit-for-bit identical.
        if link_flake is None:
            link_flake = cfg.link_flake
        if retry_max is None:
            retry_max = cfg.retry_max
        if backoff_s is None:
            backoff_s = cfg.backoff_s
        self._flake_on = float(link_flake) > 0.0
        if self._flake_on:
            self.transfers.enable_flake(
                float(link_flake), int(retry_max), float(backoff_s), seed
            )

        # opt-in structured audit log (repro.verify): placements, hops,
        # landing decisions, evictions and fault windows recorded for the
        # independent schedule verifier. Every hook is behind an
        # `is not None` check, so audit-off runs stay bit-for-bit
        # identical to uninstrumented behavior.
        if audit is None:
            audit = cfg.audit
        self.audit = None
        if audit:
            from repro.verify.audit import AuditLog

            self.audit = AuditLog(engine="exact")
            self.audit.log_machine(
                machine,
                host_mem=HOST_MEM,
                capacity=self.memory.capacity if self._bounded else 0,
                eviction=eviction,
                cancel_stale=self._cancel_stale,
                fault_mode=fault_mode,
                seed=seed,
                noise=noise,
            )
        self.transfers.audit = self.audit

        # serving mode (repro.runtime.rescore / repro.runtime.load):
        # a persistent ready pool with incremental dirty-row rescoring
        # replaces per-activation strategy.place, plus admission control
        # at arrival. rescore="off" (the default) leaves the classic
        # run loop — and its bit-for-bit contract — completely untouched.
        if rescore is None:
            rescore = cfg.rescore
        if admission is None:
            admission = cfg.admission
        if admit_defer_s is None:
            admit_defer_s = cfg.admit_defer_s
        if rescore not in RESCORE_MODES:
            raise ValueError(
                f"rescore mode must be one of {RESCORE_MODES}, got {rescore!r}"
            )
        if admission not in ADMISSION_MODES:
            raise ValueError(
                f"admission mode must be one of {ADMISSION_MODES}, "
                f"got {admission!r}"
            )
        self._serving: Optional[ServingScheduler] = None
        if rescore != "off":
            if strategy.allow_steal:
                raise ValueError(
                    f"serving mode (rescore={rescore!r}) places from the "
                    "shared ready pool; work-stealing strategies "
                    f"({strategy.name!r}) are not supported there"
                )
            self._serving = ServingScheduler(rescore)
        self._admission = admission
        if admission != "none" and self._serving is None:
            raise ValueError(
                f"admission={admission!r} requires serving mode "
                "(rescore='full' or 'incremental'); the classic loop "
                "activates every submitted graph unconditionally"
            )
        if not (float(admit_defer_s) > 0.0):
            raise ValueError(
                f"admit_defer_s must be > 0, got {admit_defer_s!r}"
            )
        self._admit_defer_s = float(admit_defer_s)
        # admission accounting: predicted working-set bytes of admitted,
        # unfinished graphs vs the total device capacity
        self._active_ws = 0
        n_dev = len({r.mem for r in machine.resources if r.mem != HOST_MEM})
        self._mem_total = self.memory.capacity * n_dev
        # optional per-tenant fairness hooks on the strategy (wfq)
        self._retire = getattr(strategy, "retire_tenant", None)

        # submitted graphs
        self._ctxs: List[GraphContext] = []
        self._ctx_of: Dict[int, GraphContext] = {}  # id(task) -> context
        self._cur: Optional[GraphContext] = None
        self._pending: List[GraphContext] = []  # roots placed at run() start
        self._running = False
        # strategy-facing views of the current activation's graph
        self.graph: Optional[TaskGraph] = None
        self.arrays: Optional[GraphArrays] = None
        self.residency: Optional[Residency] = None

    # ------------------------------------------------------------------
    @property
    def config(self):
        """The active ``repro.sched.SchedConfig`` for this engine."""
        if self._config is None:
            from repro.sched.config import current_config

            self._config = current_config()
        return self._config

    # legacy metric views (the counters live on ``self.metrics``)
    @property
    def total_bytes(self) -> int:
        return self.metrics.total_bytes

    @property
    def n_transfers(self) -> int:
        return self.metrics.n_transfers

    @property
    def n_steals(self) -> int:
        return self.metrics.n_steals

    @property
    def n_events(self) -> int:
        return self.metrics.n_events

    @property
    def busy(self) -> Dict[int, float]:
        return self.metrics.busy

    @property
    def intervals(self) -> List[ScheduledInterval]:
        return self.metrics.intervals

    # ------------------------------------------------------------------
    def submit(
        self,
        graph: TaskGraph,
        at: Optional[float] = None,
        priority: float = 1.0,
    ) -> GraphContext:
        """Add a task graph to the run (multi-tenant streaming).

        Before ``run()`` the graph's roots are placed when the run starts;
        with ``at`` (or mid-run) the arrival is an event at that simulated
        time, so tenant DAGs stream into a live machine. ``priority``
        (> 0) weights the tenant for priority/weighted-fair policies and
        is ignored by the classic strategies. Returns the graph's
        :class:`GraphContext` (its per-graph result handle).
        """
        if not (float(priority) > 0.0):
            raise ValueError(f"priority must be > 0, got {priority!r}")
        if graph.tasks and id(graph.tasks[0]) in self._ctx_of:
            raise ValueError(
                "this TaskGraph object is already submitted to the engine; "
                "build a fresh graph per tenant (task identity keys the "
                "per-graph state)"
            )
        ctx = GraphContext(len(self._ctxs), graph)
        # One multiplicative noise factor per task (each task executes
        # exactly once), drawn as a single batched normal at submit, in
        # tid order. For the first graph of a fresh engine this consumes
        # the seeded stream exactly like the monolithic simulator did.
        if self.noise > 0 and len(graph) > 0:
            ctx.noise_mult = np.exp(
                self.rng.normal(0.0, self.noise, size=len(graph))
            ).tolist()
        ctx.priority = float(priority)
        ctx.rid_static = [
            self._predictor(ctx, r.cls).static_list
            for r in self.machine.resources
        ]
        self.memory.attach_ctx(ctx)
        if self._serving is not None:
            self._serving.watch_ctx(ctx)
        ctx_of = self._ctx_of
        for t in graph.tasks:
            ctx_of[id(t)] = ctx
        self._ctxs.append(ctx)
        if self._cur is None:
            self._set_ctx(ctx)
        if at is not None and at > self.now:
            ctx.submit_at = at
            self.events.post(at, "submit", ctx)
        elif self._running:
            ctx.submit_at = self.now
            if self._serving is not None:
                self._arrive(ctx)
            else:
                self._activate_roots(ctx)
                if self._steal_on:
                    self._steal_round()
        else:
            ctx.submit_at = max(0.0, at if at is not None else 0.0)
            self._pending.append(ctx)
        if self.audit is not None:
            self.audit.log_graph(ctx.gid, ctx.submit_at, graph)
        return ctx

    # ------------------------------------------------------------------
    def _set_ctx(self, ctx: GraphContext) -> None:
        self._cur = ctx
        self.graph = ctx.graph
        self.arrays = ctx.arrays
        self.residency = ctx.residency

    def _predictor(self, ctx: GraphContext, cls: ResourceClass) -> ClassPredictor:
        p = ctx.predictors.get(cls.name)
        if p is None:
            p = ctx.predictors[cls.name] = ClassPredictor(
                self.model, cls, ctx.arrays
            )
        return p

    def predictor(self, cls: ResourceClass) -> ClassPredictor:
        """Cached vectorized HistoryPerfModel.predict for ``cls`` (of the
        current activation's graph)."""
        return self._predictor(self._cur, cls)

    # ------------------------------------------------------------------
    # fault injection (repro.runtime.faults)
    def inject(
        self,
        event: str,
        rid: int,
        at: Optional[float] = None,
        mode: Optional[str] = None,
        notice_s: Optional[float] = None,
    ) -> None:
        """Schedule a ``"detach"``/``"attach"`` fault for resource ``rid``.

        ``at`` is simulated time (default: now; past times clamp to now —
        simulated time never rewinds). ``mode`` selects the recovery mode
        for a detach (``"drain"``/``"kill"``; default: the engine's
        ``fault_mode``). ``notice_s`` (detach only; default: the engine's
        ``notice_s``) announces the death that many seconds in advance: a
        ``"notice"`` event fires at ``max(now, at - notice_s)``, opening
        the proactive-recovery window (no new work on the rid, sole-copy
        replication, finite pressure penalty). The fault fires as an
        event inside the run loop, interleaving deterministically with
        transfers and completions.
        """
        if event not in FAULT_EVENTS:
            raise ValueError(
                f"fault event must be one of {FAULT_EVENTS}, got {event!r}"
            )
        if mode is not None and mode not in FAULT_MODES:
            raise ValueError(
                f"fault mode must be one of {FAULT_MODES}, got {mode!r}"
            )
        if notice_s is not None:
            if event != "detach":
                raise ValueError(
                    "notice_s only applies to detach events, got "
                    f"event={event!r}"
                )
            if not (float(notice_s) >= 0.0):
                raise ValueError(f"notice_s must be >= 0, got {notice_s!r}")
        self.faults._check_rid(rid)
        at = self.now if at is None else max(float(at), self.now)
        self.faults.active = True
        self._faults_on = True
        if event == "detach":
            ns = float(notice_s) if notice_s is not None else self._notice_s
            if ns > 0.0:
                t_n = max(self.now, at - ns)
                if t_n < at:
                    # the mode slot carries (mode, scheduled death time)
                    self.events.post(
                        t_n, "fault", ("notice", int(rid), (mode, at))
                    )
        self.events.post(at, "fault", (event, int(rid), mode))

    def replay_trace(self, trace) -> None:
        """Inject every event of a JSONL preemption trace — a path for
        :func:`repro.runtime.traces.load_trace`, or an iterable of
        :class:`~repro.runtime.traces.FaultEvent`."""
        events = load_trace(trace) if isinstance(trace, str) else trace
        for ev in events:
            self.inject(
                ev.event, ev.rid, at=ev.t, mode=ev.mode,
                notice_s=ev.notice_s,
            )

    # ------------------------------------------------------------------
    # queue operations (pop / push / steal)
    def push(self, task: Task, rid: int) -> None:
        """Push ``task`` onto worker ``rid``'s queue (any worker may push
        into any other worker's queue, §2.2)."""
        if self._faults_on and not self.faults.alive[rid]:
            # backstop for fault-oblivious strategies (ws pushes to the
            # completing worker, score policies to an argmin): work aimed
            # at a dead worker lands on the next alive one instead
            rid = self.faults.redirect(rid)
        w = self.workers[rid]
        w.queue.append(task)
        ctx = self._ctx_of[id(task)]
        self.transfers.prefetch(
            ctx, task, self._mem_of[rid], self._bit_of[rid], self.now
        )
        self._try_start(w)

    def _steal(self, thief: Worker) -> bool:
        victims = eligible_victims(self.workers, thief.rid)
        if not victims:
            return False
        v = victims[int(self.rng.integers(len(victims)))]
        task = v.queue.popleft()  # thief takes the oldest task
        self.metrics.n_steals += 1
        thief.queue.append(task)
        ctx = self._ctx_of[id(task)]
        self.transfers.prefetch(
            ctx, task, self._mem_of[thief.rid], self._bit_of[thief.rid], self.now
        )
        return True

    def _steal_round(self) -> None:
        # callers guard on self._steal_on (strategy.allow_steal)
        progress = True
        faults_on = self._faults_on
        while progress:
            progress = False
            for w in self.workers:
                if w.running is None and not w.queue:
                    if faults_on and (
                        not self.faults.alive[w.rid]
                        or w.rid in self.faults.noticed
                    ):
                        continue  # dead/condemned workers do not steal
                    if self._steal(w):
                        self._try_start(w)
                        progress = True

    # ------------------------------------------------------------------
    def _unpin_worker(self, w: Worker) -> None:
        if w.pins is not None:
            mem, dids, ctx = w.pins
            unpin = self.memory.unpin
            for did in dids:
                unpin(ctx, did, mem)
            w.pins = None

    def _try_start(self, w: Worker) -> None:
        if w.running is not None or not w.queue:
            return
        rid = w.rid
        if self._faults_on and (
            not self.faults.alive[rid] or rid in self.faults.noticed
        ):
            # the engine never dispatches to a detached device, and a
            # noticed (condemned) worker starts no new work inside its
            # grace window — the running task drains, queued tasks are
            # re-activated on the survivors at death
            return
        task = w.queue[-1] if self._lifo else w.queue[0]
        ctx = self._ctx_of[id(task)]
        # make sure inputs are (going to be) resident
        mem = self._mem_of[rid]
        bit = self._bit_of[rid]
        mask_list = ctx.residency.mask_list
        inflight = ctx.inflight
        waiting = ctx.waiting
        request = self.transfers.request
        now = self.now
        bounded = self._bounded
        reads = ctx.arrays.task_reads[task.tid]
        if bounded:
            # re-pin this head's currently-resident inputs (and drop pins
            # from a previous head evaluation)
            self._unpin_worker(w)
            pinned: List[int] = []
            protect = frozenset(d for d, _, _ in reads)
        missing = 0
        for did, name, size in reads:
            if not mask_list[did] & bit:
                fl = inflight.get(name)
                if fl is None or mem not in fl:
                    request(ctx, name, size, mem, now,
                            protect if bounded else None)
                waiting.setdefault((name, mem), []).append(rid)
                missing += 1
            elif bounded and mem != HOST_MEM:
                self.memory.pin(ctx, did, mem)
                self.memory.touch(ctx, did, mem)
                pinned.append(did)
        if bounded and (pinned or missing):
            w.pins = (mem, pinned, ctx)
        if missing:
            w.blocked_on = missing
            return
        # pop + execute
        if self._lifo:
            w.queue.pop()
        else:
            w.queue.popleft()
        w.blocked_on = 0
        tid = task.tid
        # ground-truth duration: per-rid static flops/rate (the predictor's
        # cached vector, identical to cls.exec_time incl. the 1e-7 floor)
        # times the task's seeded noise factor
        dur = ctx.rid_static[rid][tid]
        if ctx.noise_mult is not None:
            dur *= ctx.noise_mult[tid]
        w.running = task
        w.run_start = now
        self.events.post(now + dur, "done", (rid, ctx, tid, dur, ctx.attempt[tid]))

    # ------------------------------------------------------------------
    def _complete(self, rid: int, ctx: GraphContext, tid: int, dur: float) -> None:
        w = self.workers[rid]
        res = self.machine.resources[rid]
        task = ctx.graph.tasks[tid]
        w.running = None
        ctx.done[tid] = True
        ctx.n_done += 1
        metrics = self.metrics
        metrics.busy[rid] += dur
        iv = ScheduledInterval(tid, rid, w.run_start, self.now)
        metrics.intervals.append(iv)
        ctx.intervals.append(iv)
        self.model.observe(task, res.cls, dur)
        bit = self._bit_of[rid]
        bounded = self._bounded
        # a drained worker finishing after its detach: its memory is gone,
        # so the outputs are written back to host inside the preemption
        # notice window (charged on the memory's link) instead of landing
        # on the vanished device
        dead_mem = None
        if self._faults_on and not self.faults.alive[rid]:
            m = self._mem_of[rid]
            if m != HOST_MEM and m in self.faults.dead_mems:
                dead_mem = m
        if bounded:
            self._unpin_worker(w)
            mem = self._mem_of[rid]
            if mem != HOST_MEM and dead_mem is None:
                # reserve space for the outputs this completion materializes
                incoming = 0
                mask_list = ctx.residency.mask_list
                for did, _, size in ctx.arrays.task_writes[tid]:
                    if not mask_list[did] & bit:
                        incoming += size
                if incoming:
                    protect = frozenset(
                        d for d, _, _ in ctx.arrays.task_writes[tid]
                    ) | frozenset(d for d, _, _ in ctx.arrays.task_reads[tid])
                    self.memory.ensure_capacity(
                        mem, incoming, self.now, ctx, protect
                    )
        write_id = ctx.residency.write_id
        inflight_pop = ctx.inflight.pop
        cancel_stale = self._cancel_stale
        versions = ctx.data_version
        for did, name, size in ctx.arrays.task_writes[tid]:
            if dead_mem is not None:
                self.transfers.one_hop(
                    size,
                    self.transfers.mem_link.get(dead_mem),
                    self.now,
                    kind="evacuate",
                )
                metrics.n_evacuations += 1
                metrics.evacuated_bytes += size
                write_id(did, name, 1)  # sole valid copy lands on host
            else:
                write_id(did, name, bit)
            # invalidate any stale dedup entries for this data (O(1): the
            # in-flight table is indexed per data name)
            inflight_pop(name, None)
            if cancel_stale:
                versions[name] = versions.get(name, 0) + 1
        if self.audit is not None:
            # logged after the write loop so eviction records emitted by
            # ensure_capacity above carry smaller seq than the write
            # effects the verifier applies at this record
            self.audit.log_exec(
                ctx.gid,
                tid,
                rid,
                self._mem_of[rid],
                w.run_start,
                self.now,
                wrote_host=dead_mem is not None,
            )
        if bounded:
            self.memory.note_task_done(ctx, tid)
        # load time-stamp correction (§2.3: runtime corrects predictions)
        if not w.queue:
            self.load_ts[rid] = self.now

        newly_ready: List[Task] = []
        preds = ctx.preds
        tasks = ctx.graph.tasks
        for s in ctx.succ[tid]:
            preds[s] -= 1
            if preds[s] == 0:
                newly_ready.append(tasks[s])
        if ctx.n_done == ctx.n_tasks:
            ctx.finish = self.now
            if self._serving is not None:
                self._graph_finished(ctx)
        if newly_ready:
            # the *activate* operation — where scheduling decisions happen
            self._place_ready(ctx, newly_ready, rid)
        self._try_start(w)
        if self._steal_on:
            self._steal_round()

    # ------------------------------------------------------------------
    def _place_ready(
        self, ctx: GraphContext, ready: List[Task], src: Optional[int]
    ) -> None:
        """Route an activation: the strategy's ``place`` (classic loop)
        or the serving pool (rescore mode). The one seam every
        newly-ready task flows through."""
        if self._serving is not None:
            self._serving.add_ready(self, ctx, ready)
        else:
            self._set_ctx(ctx)
            self.strategy.place(self, ready, src)

    def _activate_roots(self, ctx: GraphContext) -> None:
        roots = ctx.graph.roots()
        if roots:
            self._place_ready(ctx, roots, None)

    # ------------------------------------------------------------------
    # serving mode: arrivals, admission control, tenant teardown
    def _graph_finished(self, ctx: GraphContext) -> None:
        if self._admission != "none" and ctx.admitted:
            self._active_ws -= ctx.ws_bytes
        if self._retire is not None:
            self._retire(ctx)

    def _arrive(self, ctx: GraphContext) -> None:
        """A tenant graph arrives at ``self.now`` (serving mode only):
        log the arrival once, run admission control, then activate."""
        audit = self.audit
        if not ctx.arrived:
            ctx.arrived = True
            self.metrics.n_arrivals += 1
            if audit is not None:
                audit.log_arrival(ctx.gid, ctx.submit_at)
        if self._admission != "none" and self._bounded:
            ws = ctx.ws_bytes
            total = self._mem_total
            if ws > total:
                # can never fit, under any interleaving: reject outright
                # (defer would retry forever)
                ctx.rejected = True
                self.metrics.n_rejected += 1
                if audit is not None:
                    audit.log_reject(ctx.gid, self.now, "too_large")
                return
            if self._active_ws + ws > total:
                if self._admission == "defer":
                    self.metrics.n_deferred += 1
                    self.events.post(
                        self.now + self._admit_defer_s, "submit", ctx
                    )
                else:
                    ctx.rejected = True
                    self.metrics.n_rejected += 1
                    if audit is not None:
                        audit.log_reject(ctx.gid, self.now, "pressure")
                return
            self._active_ws += ws
        ctx.admitted = True
        ctx.admit_at = self.now
        self.metrics.n_admitted += 1
        if audit is not None:
            audit.log_admit(ctx.gid, self.now)
        self._activate_roots(ctx)

    def _run_loop(self) -> None:
        self._running = True
        self.strategy.init(self)
        self.faults.schedule_churn(self)
        pending, self._pending = self._pending, []
        for ctx in pending:
            self._activate_roots(ctx)
        if self._steal_on:
            self._steal_round()
        events = self.events.heap
        heappop = heapq.heappop
        workers = self.workers
        steal_on = self._steal_on
        bounded = self._bounded
        cancel_stale = self._cancel_stale
        faults = self.faults
        faults_on = self._faults_on
        audit = self.audit
        n_events = 0
        while events:
            t, _, kind, payload = heappop(events)
            self.now = t
            n_events += 1
            if kind == "xfer":
                ctx, name, mem, ver, epoch = payload
                inflight = ctx.inflight
                flights = inflight.get(name)
                if flights is not None:
                    flights.pop(mem, None)
                    if not flights:
                        del inflight[name]
                if bounded and mem != HOST_MEM:
                    self.memory.release(ctx, name, mem)
                if faults_on and mem != HOST_MEM and (
                    mem in faults.dead_mems
                    or epoch != faults.mem_epoch.get(mem, 0)
                ):
                    # the destination device detached while this copy was
                    # in flight: the DMA died with it — drop the landing
                    # (the memory was salvaged and its waiters scrubbed at
                    # detach; a re-attached device must not resurrect it)
                    if audit is not None:
                        audit.log_landing(ctx.gid, name, mem, t, False, "dead")
                elif cancel_stale and ver != ctx.data_version.get(name, 0):
                    # the data was overwritten while this copy was in
                    # flight: the landing is stale and is dropped (the
                    # blocked readers below re-request against the new
                    # version)
                    if audit is not None:
                        audit.log_landing(ctx.gid, name, mem, t, False, "stale")
                else:
                    # NOTE (pre-existing modeling artifact, preserved for
                    # equivalence when cancel-stale is off): a transfer in
                    # flight when its data was overwritten still lands as
                    # a "valid" copy — the simulated runtime does not
                    # cancel stale transfers unless REPRO_SCHED_CANCEL_STALE.
                    if bounded and mem != HOST_MEM:
                        did = ctx.arrays.name_to_id.get(name)
                        if did is not None and not (
                            ctx.residency.mask_list[did] & (1 << (mem + 1))
                        ):
                            self.memory.ensure_capacity(
                                mem,
                                ctx.residency._sizes[did],
                                t,
                                ctx,
                                (did,),
                            )
                    ctx.residency.add_copy(name, mem)
                    if audit is not None:
                        audit.log_landing(ctx.gid, name, mem, t, True, "ok")
                waiters = ctx.waiting.pop((name, mem), None)
                if waiters:
                    if bounded and mem != HOST_MEM:
                        did = ctx.arrays.name_to_id.get(name)
                    for rid in waiters:
                        w = workers[rid]
                        if w.blocked_on > 0:
                            w.blocked_on -= 1
                            if (
                                bounded
                                and mem != HOST_MEM
                                and did is not None
                                and w.pins is not None
                                and w.pins[0] == mem
                                and w.pins[2] is ctx
                                and w.blocked_on > 0
                            ):
                                # keep the freshly landed input of a
                                # still-blocked head pinned until its next
                                # head evaluation (only while the head is
                                # still this graph's task — a steal/LIFO
                                # re-head must not record the pin under
                                # another graph's key, which unpin could
                                # then never release)
                                self.memory.pin(ctx, did, mem)
                                w.pins[1].append(did)
                            if w.blocked_on == 0:
                                self._try_start(w)
                if steal_on:
                    self._steal_round()
            elif kind == "done":
                rid, ctx, tid, dur, att = payload
                # a stale attempt is an execution aborted by a kill-mode
                # detach: the task was re-activated elsewhere, this event
                # is the ghost of its first run
                if att == ctx.attempt[tid]:
                    self._complete(rid, ctx, tid, dur)
            elif kind == "fault":
                action, rid, mode = payload
                faults_on = True
                faults.handle(self, action, rid, mode)
            else:  # "submit": a streamed graph arrives
                ctx = payload
                self._activate_roots(ctx)
                if steal_on:
                    self._steal_round()
        self.metrics.n_events = n_events
        if audit is not None:
            audit.finalize(self)
        self._check_complete()

    def _run_loop_serving(self, max_events: Optional[int] = None) -> bool:
        """Serving-mode run loop: same-timestamp event batching plus one
        placement round per batch over the shared ready pool.

        Events of one simulated instant are drained together and the
        :class:`~repro.runtime.rescore.ServingScheduler` round runs once
        per distinct timestamp — one rescoring pass per instant instead
        of one per event.  Returns ``True`` when ``max_events`` capped
        the run (throughput probes measure a fixed amount of work);
        capped runs skip audit finalization and the completeness check.
        """
        serving = self._serving
        self._running = True
        self.strategy.init(self)
        self.faults.schedule_churn(self)
        pending, self._pending = self._pending, []
        for ctx in pending:
            self._arrive(ctx)
        serving.round(self)
        events = self.events.heap
        heappop = heapq.heappop
        workers = self.workers
        bounded = self._bounded
        cancel_stale = self._cancel_stale
        faults = self.faults
        audit = self.audit
        n_events = 0
        capped = False
        while events and not capped:
            t = events[0][0]
            self.now = t
            while events and events[0][0] == t:
                _, _, kind, payload = heappop(events)
                n_events += 1
                if kind == "xfer":
                    ctx, name, mem, ver, epoch = payload
                    inflight = ctx.inflight
                    flights = inflight.get(name)
                    if flights is not None:
                        flights.pop(mem, None)
                        if not flights:
                            del inflight[name]
                    if bounded and mem != HOST_MEM:
                        self.memory.release(ctx, name, mem)
                    if self._faults_on and mem != HOST_MEM and (
                        mem in faults.dead_mems
                        or epoch != faults.mem_epoch.get(mem, 0)
                    ):
                        if audit is not None:
                            audit.log_landing(
                                ctx.gid, name, mem, t, False, "dead"
                            )
                    elif cancel_stale and ver != ctx.data_version.get(name, 0):
                        if audit is not None:
                            audit.log_landing(
                                ctx.gid, name, mem, t, False, "stale"
                            )
                    else:
                        if bounded and mem != HOST_MEM:
                            did = ctx.arrays.name_to_id.get(name)
                            if did is not None and not (
                                ctx.residency.mask_list[did]
                                & (1 << (mem + 1))
                            ):
                                self.memory.ensure_capacity(
                                    mem,
                                    ctx.residency._sizes[did],
                                    t,
                                    ctx,
                                    (did,),
                                )
                        ctx.residency.add_copy(name, mem)
                        if audit is not None:
                            audit.log_landing(ctx.gid, name, mem, t, True, "ok")
                    waiters = ctx.waiting.pop((name, mem), None)
                    if waiters:
                        if bounded and mem != HOST_MEM:
                            did = ctx.arrays.name_to_id.get(name)
                        for rid in waiters:
                            w = workers[rid]
                            if w.blocked_on > 0:
                                w.blocked_on -= 1
                                if (
                                    bounded
                                    and mem != HOST_MEM
                                    and did is not None
                                    and w.pins is not None
                                    and w.pins[0] == mem
                                    and w.pins[2] is ctx
                                    and w.blocked_on > 0
                                ):
                                    self.memory.pin(ctx, did, mem)
                                    w.pins[1].append(did)
                                if w.blocked_on == 0:
                                    self._try_start(w)
                elif kind == "done":
                    rid, ctx, tid, dur, att = payload
                    if att == ctx.attempt[tid]:
                        self._complete(rid, ctx, tid, dur)
                elif kind == "fault":
                    action, rid, mode = payload
                    faults.handle(self, action, rid, mode)
                    # worker liveness / memory epochs moved: every cached
                    # row's eligible set is suspect — coarse invalidation
                    serving.epoch += 1
                else:  # "submit": a streamed tenant graph arrives
                    self._arrive(payload)
                if max_events is not None and n_events >= max_events:
                    capped = True
                    break
            serving.round(self)
        self.metrics.n_events = n_events
        if capped:
            return True
        if audit is not None:
            audit.finalize(self)
        self._check_complete()
        return False

    def _check_complete(self) -> None:
        for ctx in self._ctxs:
            if getattr(ctx, "rejected", False):
                continue  # admission control turned this tenant away
            if ctx.n_done != ctx.n_tasks:
                missing = [
                    t.tid for t in ctx.graph.tasks if not ctx.done[t.tid]
                ]
                raise RuntimeError(
                    f"simulation stalled: graph {ctx.gid} has "
                    f"{len(missing)} tasks unfinished, e.g. {missing[:5]}"
                    + (
                        " (capacity-bounded run: check REPRO_SCHED_MEM_CAPACITY)"
                        if self._bounded
                        else ""
                    )
                )

    # ------------------------------------------------------------------
    def _graph_result(self, ctx: GraphContext) -> SimResult:
        busy: Dict[int, float] = {r.rid: 0.0 for r in self.machine.resources}
        for iv in ctx.intervals:
            busy[iv.rid] += iv.end - iv.start
        return SimResult(
            makespan=(ctx.finish - ctx.submit_at) if not ctx.rejected else 0.0,
            submit_at=ctx.submit_at,
            admit_at=(
                ctx.admit_at if self._serving is not None else ctx.submit_at
            ),
            admitted=not ctx.rejected,
            # transfer/steal counters are machine-global (links and queues
            # are shared across tenant graphs)
            total_bytes=self.metrics.total_bytes,
            n_transfers=self.metrics.n_transfers,
            n_steals=self.metrics.n_steals,
            busy=busy,
            intervals=ctx.intervals,
            strategy=self.strategy.name,
            total_flops=ctx.graph.total_flops(),
            n_events=self.metrics.n_events,
            routes=self.metrics.routes(),
            faults=(
                self.metrics.fault_summary()
                if (self._faults_on or self._flake_on)
                else None
            ),
        )

    def run(self, max_events: Optional[int] = None) -> List[SimResult]:
        """Run every submitted graph to completion; one result per graph
        (submit order), with per-graph makespans and interval timelines.

        ``max_events`` (serving mode only) caps the number of processed
        events — throughput probes measure a fixed amount of work — and
        returns ``[]``, since per-graph results are meaningless for a
        truncated run."""
        if self._serving is not None:
            capped = self._run_loop_serving(max_events)
            if capped:
                return []
        else:
            if max_events is not None:
                raise ValueError(
                    "max_events requires serving mode "
                    "(rescore='full' or 'incremental')"
                )
            self._run_loop()
        return [self._graph_result(ctx) for ctx in self._ctxs]
