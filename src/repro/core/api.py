"""Public API of the scheduling core.

Strategy construction lives in ``repro.sched`` (the Policy registry);
``make_strategy`` and the string form of ``run_simulation`` survive here
as thin deprecated shims with bit-identical results.
"""
from __future__ import annotations

import math
import os
import pickle
import threading
import warnings
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import obs
from .dag import TaskGraph
from .machine import MachineModel
from .simulator import SimResult, Simulator, Strategy


def make_strategy(name: str, backend: Optional[str] = None, **kwargs) -> Strategy:
    """Deprecated shim: build a strategy from a short spec.

    Use :func:`repro.sched.resolve` instead — it accepts the same names
    (``heft`` | ``ws`` | ``dual`` | ``dada`` …) plus query-string kwargs
    (``"dada?alpha=0.5&use_cp=1"``) and the full registered-policy set.
    This wrapper delegates to the registry, so the constructed strategy —
    and every placement it makes — is bit-identical to ``resolve(name)``.
    """
    warnings.warn(
        "make_strategy() is deprecated; use repro.sched.resolve "
        "(same names, plus query-string kwargs like 'dada?alpha=0.5')",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro.sched import resolve
    from repro.sched.registry import get_factory, parse_spec

    # keep the historical error wording for unknown names only; real
    # validation errors (bad alpha, unknown affinity) must pass through
    try:
        get_factory(parse_spec(name)[0])
    except ValueError as exc:
        raise ValueError(f"unknown strategy {name.lower()!r}") from exc
    return resolve(name, backend=backend, **kwargs)


def run_simulation(
    graph: TaskGraph,
    machine: MachineModel,
    strategy,
    seed: int = 0,
    noise: float = 0.03,
    config=None,
) -> SimResult:
    if isinstance(strategy, str):
        warnings.warn(
            "passing a strategy name string to run_simulation() is "
            "deprecated; pass repro.sched.resolve(spec) instead",
            DeprecationWarning,
            stacklevel=2,
        )
        from repro.sched import resolve

        strategy = resolve(strategy)
    sim = Simulator(graph, machine, strategy, seed=seed, noise=noise, config=config)
    res = sim.run()
    if sim.audit is not None:
        # REPRO_SCHED_AUDIT=1: every simulation is re-checked by the
        # independent verifier (repro.verify) — precedence, hazards,
        # capacity, byte conservation, fault windows — and a violation is
        # a hard failure, not a benchmark footnote
        from repro.verify import errors as _verify_errors
        from repro.verify import verify_audit

        errs = _verify_errors(verify_audit(sim.audit))
        if errs:
            detail = "; ".join(f"{f.code}: {f.message}" for f in errs[:5])
            raise RuntimeError(
                f"schedule verification failed ({len(errs)} error(s)): {detail}"
            )
    return res


@dataclass
class Summary:
    """Mean + 95% confidence interval over repeated runs (paper methodology:
    >=30 runs per configuration, mean and 95% CI reported)."""

    strategy: str
    n: int
    gflops_mean: float
    gflops_ci95: float
    gbytes_mean: float
    gbytes_ci95: float
    makespan_mean: float
    steals_mean: float

    def row(self) -> str:
        return (
            f"{self.strategy},{self.n},{self.gflops_mean:.2f},{self.gflops_ci95:.2f},"
            f"{self.gbytes_mean:.3f},{self.gbytes_ci95:.3f},{self.makespan_mean:.4f},"
            f"{self.steals_mean:.1f}"
        )


# ---------------------------------------------------------------------------
# parallel seeded runs


_GRAPH_CACHE: Dict[tuple, TaskGraph] = {}


def cached_graph(factory) -> TaskGraph:
    """Memoize graphs built by ``functools.partial`` factories.

    A sweep runs many (strategy × machine) configurations over the *same*
    kernel graph; within one process the graph and its structure-of-arrays
    view are built once per distinct factory signature instead of once per
    configuration. Eviction is LRU one-at-a-time — a full-cache clear used
    to drop *every* graph the moment a 17th signature appeared, which made
    large sweeps (NT=64 interleaved with small kernels) rebuild identical
    multi-second graphs mid-flight. Non-partial factories (closures,
    lambdas) are not memoized.
    """
    try:
        key = (factory.func, factory.args, tuple(sorted(factory.keywords.items())))
        hash(key)
    except (AttributeError, TypeError):
        return factory()
    g = _GRAPH_CACHE.get(key)
    if g is None:
        while len(_GRAPH_CACHE) >= 16:
            _GRAPH_CACHE.pop(next(iter(_GRAPH_CACHE)))
        _GRAPH_CACHE[key] = g = factory()
    else:
        # refresh recency so steady sweep graphs outlive one-off builds
        _GRAPH_CACHE.pop(key)
        _GRAPH_CACHE[key] = g
    return g


_cached_graph = cached_graph  # historical private name


def _run_chunk(
    graph_factory, machine, strategy_factory, seeds: Sequence[int], noise: float
) -> List[Tuple[float, float, float, float, str]]:
    """A chunk of seeded simulations, reduced to summary metrics.

    The task graph is immutable during simulation (all mutable state —
    residency, queues, history model — lives in the Simulator), so one
    graph and its structure-of-arrays view are shared across the chunk's
    seeds (and memoized across chunks with the same partial-factory
    signature); per-run results are identical to building a fresh graph
    per seed.
    """
    graph = _cached_graph(graph_factory)
    out = []
    for seed in seeds:
        strat = strategy_factory()
        res = run_simulation(graph, machine, strat, seed=seed, noise=noise)
        out.append(
            (res.gflops, res.gbytes, res.makespan, float(res.n_steals), strat.name)
        )
    return out


def default_jobs(n_runs: int) -> int:
    """Worker count for ``n_runs`` runs when the caller names none:
    min(cpus, runs)."""
    return max(1, min(os.cpu_count() or 1, n_runs))


_POOL = None
_POOL_JOBS = 0
_POOL_LOCK = threading.Lock()


def get_pool(n_jobs: Optional[int] = None):
    """Public handle on the shared simulation process pool. The call that
    creates it sets its width: ``n_jobs``, or the CPU count when ``None``.

    Creating it early — before spawning any threads that will submit to
    it — also sidesteps the fork-after-threads hazard (forking workers
    while sibling threads hold allocator/stdio locks can deadlock the
    children on some platforms).
    """
    return _get_pool(n_jobs)


def _get_pool(n_jobs: Optional[int]):
    """Lazily build (and reuse) one process pool; fork context when available
    so repeated run_many calls don't pay per-call interpreter startup.

    Thread-safe: concurrent sweeps share the same executor. The pool is
    sized once, at first use: ``n_jobs`` workers where the caller names a
    width, else the CPU count. It is never resized or shut down
    afterwards, because cancelling would kill in-flight futures belonging
    to other threads."""
    global _POOL, _POOL_JOBS
    with _POOL_LOCK:
        if _POOL is not None:
            return _POOL
        import concurrent.futures as cf
        import multiprocessing as mp

        ctx = None
        if "fork" in mp.get_all_start_methods():
            ctx = mp.get_context("fork")
        # a caller that names no width gets the CPU count, so a small
        # first call doesn't pin concurrent sweeps to an undersized pool
        workers = n_jobs if n_jobs is not None else default_jobs(os.cpu_count() or 1)
        _POOL = cf.ProcessPoolExecutor(max_workers=workers, mp_context=ctx)
        _POOL_JOBS = workers
        return _POOL


def pool_allowed(strategy_factories: Sequence) -> bool:
    """Whether seeded runs of these strategies may fan out over the fork
    pool. A worker never touches JAX: forking after this process took the
    chip would hand the child a runtime it cannot use, and a jax-scored
    strategy in a child would claim the chip itself (one process per
    chip). Such runs stay in this process; results are identical."""
    from .backend import ScoringBackendMixin, accelerator_initialised, backend_name

    if accelerator_initialised():
        return False
    for factory in strategy_factories:
        s = factory()
        if isinstance(s, ScoringBackendMixin) and backend_name(
            s.backend_name, s.config
        ) == "jax":
            return False
    return True


def run_many(
    graph_factory,
    machine: MachineModel,
    strategy_factory,
    n_runs: int = 30,
    noise: float = 0.03,
    base_seed: int = 1234,
    n_jobs: Optional[int] = None,
) -> Summary:
    """Run ``n_runs`` seeded simulations and summarize (mean, 95% CI).

    ``graph_factory`` and ``strategy_factory`` are callables so each run gets
    fresh graph/strategy state (the history model calibrates within a run).

    Runs fan out over a process pool (``n_jobs`` workers; default
    min(CPU count, ``n_runs``)). Each run is independently
    seeded, so the summary is bit-identical to the serial path regardless
    of worker count; results are gathered in seed order. Falls back to the
    serial loop when ``n_jobs == 1``, when the factories are not picklable
    (e.g. test-local closures), when the pool cannot be created, or when
    :func:`pool_allowed` keeps JAX work in this process.
    """
    width = n_jobs  # the pool's width, where this call is the first
    if n_jobs is None:
        n_jobs = default_jobs(n_runs)
    seeds = [base_seed + i for i in range(n_runs)]

    futs = None
    if n_jobs > 1 and n_runs > 1 and pool_allowed([strategy_factory]):
        # contiguous seed chunks, one per worker; gathered in order, so the
        # summary is bit-identical to the serial path
        n_chunks = min(n_jobs, n_runs)
        bounds = [round(i * n_runs / n_chunks) for i in range(n_chunks + 1)]
        chunks = [seeds[a:b] for a, b in zip(bounds, bounds[1:]) if b > a]
        try:
            pickle.dumps((graph_factory, machine, strategy_factory))
            pool = _get_pool(width)
            futs = [
                pool.submit(_run_chunk, graph_factory, machine, strategy_factory, c, noise)
                for c in chunks
            ]
        except Exception:
            futs = None  # non-picklable factories or pool failure: go serial
    if futs is not None:
        # gathered outside the guard: a simulation error in a worker is a
        # real failure and must propagate, not trigger a serial re-run
        rows = [r for f in futs for r in f.result()]
    else:
        rows = _run_chunk(graph_factory, machine, strategy_factory, seeds, noise)

    gf = [r[0] for r in rows]
    gb = [r[1] for r in rows]
    mk = [r[2] for r in rows]
    st = [r[3] for r in rows]
    name = rows[-1][4] if rows else ""

    def ci95(xs: Sequence[float]) -> float:
        if len(xs) < 2:
            return 0.0
        return 1.96 * float(np.std(xs, ddof=1)) / math.sqrt(len(xs))

    return Summary(
        strategy=name,
        n=n_runs,
        gflops_mean=float(np.mean(gf)),
        gflops_ci95=ci95(gf),
        gbytes_mean=float(np.mean(gb)),
        gbytes_ci95=ci95(gb),
        makespan_mean=float(np.mean(mk)),
        steals_mean=float(np.mean(st)),
    )

# ---------------------------------------------------------------------------
# batched surrogate episodes (REPRO_SCHED_EXACT=0)


@dataclass(frozen=True)
class BatchResult:
    """One configuration's surrogate-episode outcome.

    Mirrors the :class:`SimResult` metric surface (``gflops`` / ``gbytes``
    derived the same way) so sweep code can consume either engine's
    results through one row schema.
    """

    strategy: str
    seed: int
    makespan: float
    total_bytes: float
    total_flops: float
    n_steals: int = 0

    @property
    def gflops(self) -> float:
        if self.makespan <= 0:
            return 0.0
        return self.total_flops / self.makespan / 1e9

    @property
    def gbytes(self) -> float:
        return self.total_bytes / 1e9


def run_batch(configs: Sequence[dict], config=None) -> List[BatchResult]:
    """Run a batch of scheduling configurations as a few compiled dispatches.

    Each item of ``configs`` is a mapping::

        {"graph": TaskGraph | partial-factory, "machine": MachineModel,
         "strategy": "dada?alpha=0.5&use_cp=1",  # heft | ws | dada | dual
         "seed": 1234, "noise": 0.03, "capacity": 0}

    Items are grouped by (graph, machine template) — machine *shapes*
    (GPU counts), strategy parameters, seeds and capacities are batch
    axes inside a group — then each group runs through the surrogate
    episode engine (:mod:`repro.core.episode`) in chunks of at most
    ``SchedConfig.batch`` (``REPRO_SCHED_BATCH``) configurations per
    dispatch. Results come back in input order.

    This is the approximate engine: placements relax the oracle's
    tie-breaking (see the module docstring of ``repro.core.episode``),
    so use it for sweeps and searches, and the exact engine
    (:func:`run_simulation` / :func:`run_many`) for verification.
    """
    with obs.span("batch.run"):
        return _run_batch(configs, config)


def _run_batch(configs: Sequence[dict], config) -> List[BatchResult]:
    from repro.core import episode as ep

    if config is None:
        from repro.sched.config import current_config

        config = current_config()
    with obs.span("batch.plan"):
        # resolve graphs and group by (graph, machine template)
        items = []
        for i, c in enumerate(configs):
            g = c["graph"]
            if not isinstance(g, TaskGraph):
                g = cached_graph(g)
            items.append((i, g, c))

        groups: Dict[tuple, list] = {}
        for i, g, c in items:
            m: MachineModel = c["machine"]
            cpu = next((r.cls for r in m.resources if not r.is_accelerator), None)
            gpu = next((r.cls for r in m.resources if r.is_accelerator), None)
            key = (
                id(g), len(m.resources),
                cpu.name if cpu else None, gpu.name if gpu else None,
                m.link.bandwidth, m.link.latency,
            )
            groups.setdefault(key, []).append((i, g, c))

    out: List[Optional[BatchResult]] = [None] * len(items)
    chunk_cap = max(1, int(config.batch))
    for group in groups.values():
        with obs.span("batch.plan"):
            g = group[0][1]
            machines = {}
            max_mem = -1
            for _, _, c in group:
                m = c["machine"]
                if id(m) not in machines:
                    machines[id(m)] = m
                max_mem = max(
                    max_mem,
                    max((r.mem for r in m.resources if r.is_accelerator), default=-1),
                )
            plan = ep.build_plan(g, group[0][2]["machine"], n_u=max_mem + 2)
            axes = {
                mid: ep.machine_axes(m, plan.n_res) for mid, m in machines.items()
            }
            # One dispatch shape for the whole group: episode cost is linear
            # in the batch axis (no fixed-overhead amortisation from bigger
            # batches), so split into same-shaped chunks — one compile per
            # (kernel, shape) key — and fan the dispatches out over threads
            # (XLA drops the GIL during execution).
            from repro.core.backend import _bucket

            # 16 rows per dispatch: episode cost per config is flat across
            # B∈{16..256} on CPU, so narrow chunks minimise padding waste and
            # let every group share one compiled shape; REPRO_SCHED_BATCH
            # caps it lower for memory-constrained runs
            n_workers = min(8, os.cpu_count() or 1)
            size = min(chunk_cap, 16)
            pad_to = _bucket(min(size, len(group)), lo=8)
            chunks = [group[lo : lo + size] for lo in range(0, len(group), size)]

        def dispatch(chunk):
            # the chunk's configuration arrays: the first part of its pack
            with obs.span("episode.pack"):
                isg, val, mc, lg = [], [], [], []
                al, cp, ws, nz, cap = [], [], [], [], []
                for _, _, c in chunk:
                    a, u, w = ep.surrogate_params(c["strategy"])
                    ig, vl, m_c, l_g = axes[id(c["machine"])]
                    isg.append(ig)
                    val.append(vl)
                    mc.append(m_c)
                    lg.append(l_g)
                    al.append(a)
                    cp.append(u)
                    ws.append(w)
                    nz.append(
                        ep.noise_factors(
                            int(c.get("seed", 0)), float(c.get("noise", 0.03)),
                            plan.n, plan.n_pad,
                        )
                    )
                    capacity = float(c.get("capacity", 0) or 0)
                    cap.append(capacity if capacity > 0 else np.inf)
                batch = ep.EpisodeBatch(
                    is_gpu=np.stack(isg), valid_res=np.stack(val),
                    mem_col=np.stack(mc), link_grp=np.stack(lg),
                    alpha=np.array(al),
                    use_cp=np.array(cp), ws_pref=np.array(ws, dtype=bool),
                    noise=np.stack(nz), cap=np.array(cap),
                )
            return ep.run_episodes(plan, batch, config=config, pad_to=pad_to)

        if len(chunks) > 1 and n_workers > 1:
            # warm the compile on the first chunk, then dispatch the rest
            # concurrently against the cached executable
            results = [dispatch(chunks[0])]
            with ThreadPoolExecutor(max_workers=n_workers) as tp:
                results += list(tp.map(obs.carry(dispatch), chunks[1:]))
        else:
            results = [dispatch(ch) for ch in chunks]

        for chunk, res in zip(chunks, results):
            for j, (i, _, c) in enumerate(chunk):
                out[i] = BatchResult(
                    strategy=c["strategy"],
                    seed=int(c.get("seed", 0)),
                    makespan=float(res["makespan"][j]),
                    total_bytes=float(res["total_bytes"][j]),
                    total_flops=plan.total_flops,
                )
    return out  # type: ignore[return-value]
