"""Scheduler-overhead microbenchmark: events/sec of the scheduling core.

The paper's sweeps are bottlenecked by the scheduler's own per-decision
cost, not by the simulated workload (cf. Amaris et al., arXiv:1711.06433 on
keeping dual-approximation decisions cheap). This benchmark isolates that
cost along two axes:

  * **whole-sim throughput** — for each strategy × backend it runs seeded
    simulations of the paper-shaped kernels (NT from ``REPRO_BENCH_NT``)
    and reports wall-clock, simulator events/sec and tasks/sec. Two extra
    row families gate the layered runtime: a **capacity-bounded** pass
    (32 MB device memories, affinity eviction — the eviction/write-back/
    pressure path), a **multi-graph streaming** row (four tenant DAGs
    interleaving on one ``repro.runtime.Engine``, with per-graph
    makespans), and a **churned** row family (seeded GPU detach/attach at
    ``CHURN_RATE`` under both recovery modes — the fault-handling path),
    a **recovery** row family (flaky links at ``FLAKE_RATE`` — the
    retry/backoff/re-source path — and churn with ``NOTICE_S`` preemption
    notices — grace windows and proactive replication),
    an **audited** row family (``audit=True``: the schedule-verifier's
    audit log live, with the measured ``audit_overhead`` ratio over the
    paired uninstrumented pass — gated by ``AUDIT_OVERHEAD_LIMIT``),
    and a **batched-sweep** row family (``exact=False``): whole strategy ×
    GPU-count × seed sweeps through ``repro.core.run_batch`` — the
    ``REPRO_SCHED_EXACT=0`` surrogate engine — reporting configs/sec,
    per-dispatch batch size and the speedup over the same configurations
    replayed through the exact engine;
  * **λ-probe placement** — one wide ready wave of an NT=64 Cholesky on
    the 32-resource scaled machine, timed through ``DADA.place`` per
    backend: this is the (ready × resources × λ-probes) scoring kernel the
    jax backend batches, and the metric the ≥3× acceptance gate reads. The
    wave's placement decisions are asserted identical across backends.

Results go to stdout (``name,us_per_call,derived`` contract) and to
``results/BENCH_sched.json`` (consumed by ``check_sched_regression.py``).

Knobs: REPRO_BENCH_GPUS (first entry, default 8), REPRO_BENCH_RUNS
(default 3), REPRO_BENCH_NT (comma list, default 16), REPRO_SCHED_BACKENDS
(default ``numpy,jax``),
REPRO_BENCH_LAMBDA (=0 skips the λ-probe section), REPRO_BENCH_LAMBDA_NT
(default 64), REPRO_BENCH_LAMBDA_REPS (default 3).
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

if __package__ in (None, ""):
    _repo = Path(__file__).resolve().parents[1]
    for p in (str(_repo), str(_repo / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)

from functools import partial

from repro.core import Simulator
from repro.sched import resolve

from benchmarks.common import graphs_for, machine_for, update_bench_json


def strategies(backend: str):
    """Backend-scored strategies, resolved through the policy registry
    (the same code path every other benchmark and the tests use)."""
    return {
        "heft": partial(resolve, "heft", backend=backend),
        "dada(0)": partial(resolve, "dada?alpha=0", backend=backend),
        "dada(a)": partial(resolve, "dada?alpha=0.5", backend=backend),
        "dada(a)+cp": partial(
            resolve, "dada?alpha=0.5&use_cp=1", backend=backend
        ),
    }


# strategies that use no scoring backend: measured once per kernel, under
# the stable backend label "none" (independent of the backend list).
# `random` and `locality` ride here as extra rows — same schema, so the
# committed baseline (which simply lacks these keys) is unaffected.
BACKEND_FREE_STRATEGIES = {
    "ws": partial(resolve, "ws"),
    "random": partial(resolve, "random"),
    "locality": partial(resolve, "locality"),
}


def available_backends() -> list:
    """Backends to measure, each built up front: an unknown name or a jax
    backend that cannot be built fails the run instead of being skipped."""
    from repro.core import backend_name, get_backend
    from repro.sched import current_config

    cfg = current_config()
    names = (
        list(cfg.bench_backends)
        if cfg.bench_backends is not None
        else ["numpy", "jax"]
    )
    for name in names:
        get_backend(backend_name(name))
    return names


# ---------------------------------------------------------------------------
# whole-simulation throughput


_MB = 1024 * 1024
# eviction-path row: device memories bounded to 32 MB (heavy pressure on
# the NT=16 trace), affinity victim selection — regression-gates the
# capacity-bounded engine path (memory manager + pressure scoring)
CAPACITY_ROW_BYTES = 32 * _MB
CAPACITY_ROW_STRATEGIES = ("heft", "dada(a)+cp")


def whole_sim_rows(nts, n_gpus: int, n_runs: int, backends) -> list:
    rows = []
    for nt in nts:
        machine = machine_for(n_gpus)
        for kernel, gfac in graphs_for(nt).items():
            # graph construction excluded: we are measuring the scheduler
            graphs = [gfac() for _ in range(n_runs)]
            passes = [("none", 0, BACKEND_FREE_STRATEGIES)] + [
                (backend, 0, strategies(backend)) for backend in backends
            ]
            if kernel == "cholesky":
                # the eviction path, measured once per NT on the numpy
                # scoring path (jax engages only on wide activations)
                passes.append((
                    "numpy",
                    CAPACITY_ROW_BYTES,
                    {
                        label: sfac
                        for label, sfac in strategies("numpy").items()
                        if label in CAPACITY_ROW_STRATEGIES
                    },
                ))
            for backend, capacity, strats in passes:
                for label, sfac in strats.items():
                    # best-of-2 passes: a transient stall (noisy neighbor,
                    # cgroup throttle) during one pass must not record a
                    # phantom 2× slowdown into the perf trajectory
                    dt = float("inf")
                    for _rep in range(2):
                        events = tasks = 0
                        t0 = time.perf_counter()
                        for i, g in enumerate(graphs):
                            sim = Simulator(
                                g, machine, sfac(), seed=1234 + i,
                                mem_capacity=capacity, eviction="affinity",
                            )
                            res = sim.run()
                            events += res.n_events
                            tasks += len(g)
                        dt = min(dt, time.perf_counter() - t0)
                    us = dt / n_runs * 1e6
                    row = dict(
                        kernel=kernel, strategy=label, backend=backend,
                        nt=nt, n_gpus=n_gpus, runs=n_runs, capacity=capacity,
                        churn=0.0, fault_mode="drain", flake=0.0, notice=0.0,
                        exact=True,
                        wall_s=round(dt, 4), events=events,
                        events_per_s=round(events / dt, 1) if dt > 0 else 0.0,
                        tasks_per_s=round(tasks / dt, 1) if dt > 0 else 0.0,
                    )
                    rows.append(row)
                    cap_tag = f"/cap{capacity // _MB}MB" if capacity else ""
                    print(
                        f"sched_overhead/{kernel}/{label}/gpus{n_gpus}/"
                        f"nt{nt}/{backend}{cap_tag},{us:.1f},"
                        f"events_per_s={row['events_per_s']};"
                        f"tasks_per_s={row['tasks_per_s']}"
                    )
    return rows


# ---------------------------------------------------------------------------
# multi-graph streaming throughput


def streaming_rows(nt: int, n_gpus: int, n_runs: int, n_graphs: int = 4) -> list:
    """Aggregate events/sec of ``n_graphs`` Cholesky DAGs interleaving on
    one engine (two tenants at t=0, the rest streamed in mid-run), plus
    per-graph makespans — the multi-tenant serving shape the layered
    runtime exists for."""
    from repro.runtime import Engine

    machine = machine_for(n_gpus)
    gfac = graphs_for(nt)["cholesky"]
    graph_sets = [
        [gfac() for _ in range(n_graphs)] for _ in range(n_runs)
    ]
    sfac = partial(resolve, "dada?alpha=0.5&use_cp=1", backend="numpy")
    dt = float("inf")
    per_run = []
    for _rep in range(2):
        events = tasks = 0
        per_run = []  # deterministic per seed: reps reproduce the same values
        t0 = time.perf_counter()
        for i, graphs in enumerate(graph_sets):
            eng = Engine(machine, sfac(), seed=1234 + i)
            for k, g in enumerate(graphs):
                # stagger half the tenants into the live run
                eng.submit(g, at=None if k < 2 else 0.002 * k)
            results = eng.run()
            events += eng.n_events
            tasks += sum(len(g) for g in graphs)
            per_run.append([r.makespan for r in results])
        dt = min(dt, time.perf_counter() - t0)
    import statistics

    # per-graph makespans summarized across every seeded run (a regression
    # visible only under one seed must not be masked by the last run)
    per_graph = [
        round(statistics.median(run[k] for run in per_run), 5)
        for k in range(n_graphs)
    ]
    row = dict(
        kernel=f"cholesky-x{n_graphs}stream", strategy="dada(a)+cp",
        backend="numpy", nt=nt, n_gpus=n_gpus, runs=n_runs, capacity=0,
        churn=0.0, fault_mode="drain", flake=0.0, notice=0.0, exact=True,
        n_graphs=n_graphs, wall_s=round(dt, 4), events=events,
        events_per_s=round(events / dt, 1) if dt > 0 else 0.0,
        tasks_per_s=round(tasks / dt, 1) if dt > 0 else 0.0,
        per_graph_makespans=per_graph,
    )
    print(
        f"sched_overhead/{row['kernel']}/dada(a)+cp/gpus{n_gpus}/nt{nt}/numpy,"
        f"{dt / n_runs * 1e6:.1f},events_per_s={row['events_per_s']};"
        f"per_graph_makespans={per_graph}"
    )
    return [row]


# ---------------------------------------------------------------------------
# fault-injected (churned) throughput


# seeded accelerator churn at this rate over the NT=16 Cholesky trace
# yields a handful of detach/attach cycles per run — enough to keep the
# recovery paths (requeue, evacuation, epoch invalidation) on the measured
# critical path without drowning the scheduler signal in fault handling
CHURN_RATE = 150.0
CHURN_STRATEGIES = ("heft", "dada(a)+cp")


def churn_rows(nt: int, n_gpus: int, n_runs: int) -> list:
    """Events/sec with seeded GPU churn live, for both recovery modes —
    regression-gates the fault path (detach/attach handling, kill-and-
    requeue, dirty-data evacuation) the same way the capacity row gates
    eviction. The scoring path is numpy: the fused jax path disengages
    while any resource is dead, so it would measure the wrong thing."""
    machine = machine_for(n_gpus)
    gfac = graphs_for(nt)["cholesky"]
    graphs = [gfac() for _ in range(n_runs)]
    strats = strategies("numpy")
    rows = []
    for mode in ("drain", "kill"):
        for label in CHURN_STRATEGIES:
            sfac = strats[label]
            dt = float("inf")
            faults = None
            for _rep in range(2):
                events = tasks = 0
                t0 = time.perf_counter()
                for i, g in enumerate(graphs):
                    sim = Simulator(
                        g, machine, sfac(), seed=1234 + i,
                        churn=CHURN_RATE, fault_mode=mode,
                    )
                    res = sim.run()
                    events += res.n_events
                    tasks += len(g)
                    faults = res.faults
                dt = min(dt, time.perf_counter() - t0)
            row = dict(
                kernel="cholesky", strategy=label, backend="numpy",
                nt=nt, n_gpus=n_gpus, runs=n_runs, capacity=0,
                churn=CHURN_RATE, fault_mode=mode, flake=0.0, notice=0.0,
                exact=True,
                wall_s=round(dt, 4), events=events,
                events_per_s=round(events / dt, 1) if dt > 0 else 0.0,
                tasks_per_s=round(tasks / dt, 1) if dt > 0 else 0.0,
                n_detaches=faults["n_detaches"] if faults else 0,
            )
            rows.append(row)
            print(
                f"sched_overhead/cholesky/{label}/gpus{n_gpus}/nt{nt}/"
                f"numpy/churn{CHURN_RATE:g}-{mode},{dt / n_runs * 1e6:.1f},"
                f"events_per_s={row['events_per_s']};"
                f"n_detaches={row['n_detaches']}"
            )
    return rows


# ---------------------------------------------------------------------------
# proactive-recovery (flaky links / preemption notices) throughput


# per-hop failure probability for the flake row: high enough that the
# retry/backoff/re-source path dominates the transfer machinery without
# starving the scheduler of real placement work
FLAKE_RATE = 0.2
# notice window for the noticed-churn row: about one task length, so the
# grace-window and proactive-replication paths both stay hot
NOTICE_S = 0.004
RECOVERY_STRATEGIES = ("heft", "dada(a)+cp")


def recovery_rows(nt: int, n_gpus: int, n_runs: int) -> list:
    """Events/sec with the proactive-recovery machinery live — a flaky-
    link family (seeded per-hop failures, retry with backoff, re-source
    on timeout) and a noticed-churn family (preemption notices ahead of
    each detach: grace windows, proactive replication, the decaying
    pressure penalty) — regression-gating those paths the way the churn
    rows gate blind detach/attach handling. Scoring stays on numpy: the
    fused path disengages while a notice is pending."""
    machine = machine_for(n_gpus)
    gfac = graphs_for(nt)["cholesky"]
    graphs = [gfac() for _ in range(n_runs)]
    strats = strategies("numpy")
    rows = []
    for family, kwargs in (
        ("flake", dict(link_flake=FLAKE_RATE)),
        ("notice", dict(churn=CHURN_RATE, fault_mode="drain",
                        notice_s=NOTICE_S)),
    ):
        for label in RECOVERY_STRATEGIES:
            sfac = strats[label]
            dt = float("inf")
            faults = None
            for _rep in range(2):
                events = tasks = 0
                t0 = time.perf_counter()
                for i, g in enumerate(graphs):
                    sim = Simulator(
                        g, machine, sfac(), seed=1234 + i, **kwargs
                    )
                    res = sim.run()
                    events += res.n_events
                    tasks += len(g)
                    faults = res.faults
                dt = min(dt, time.perf_counter() - t0)
            row = dict(
                kernel="cholesky", strategy=label, backend="numpy",
                nt=nt, n_gpus=n_gpus, runs=n_runs, capacity=0,
                churn=kwargs.get("churn", 0.0),
                fault_mode=kwargs.get("fault_mode", "drain"),
                flake=kwargs.get("link_flake", 0.0),
                notice=kwargs.get("notice_s", 0.0),
                exact=True,
                wall_s=round(dt, 4), events=events,
                events_per_s=round(events / dt, 1) if dt > 0 else 0.0,
                tasks_per_s=round(tasks / dt, 1) if dt > 0 else 0.0,
            )
            derived = (
                f"n_retries={faults['n_retries']}"
                if family == "flake"
                else f"n_notices={faults['n_notices']}"
            ) if faults else ""
            rows.append(row)
            print(
                f"sched_overhead/cholesky/{label}/gpus{n_gpus}/nt{nt}/"
                f"numpy/{family},{dt / n_runs * 1e6:.1f},"
                f"events_per_s={row['events_per_s']};{derived}"
            )
    return rows


# ---------------------------------------------------------------------------
# audited (schedule-verifier instrumented) throughput


AUDIT_STRATEGIES = ("heft", "dada(a)+cp")
# audit instrumentation is append-only record keeping on the event loop;
# anything past this factor over the uninstrumented run means the audit
# path grew real work (allocation storms, eager serialization) and the
# "free when off, cheap when on" contract broke
AUDIT_OVERHEAD_LIMIT = 3.0


def audit_rows(nt: int, n_gpus: int, n_runs: int) -> list:
    """Events/sec with ``REPRO_SCHED_AUDIT``-style instrumentation live,
    paired with an uninstrumented pass on the same graphs — the
    ``audit_overhead`` ratio regression-gates the audit log's cost the
    way the capacity/churn rows gate eviction and fault handling. The
    pairing is in-run, so the ratio is immune to machine speed."""
    machine = machine_for(n_gpus)
    gfac = graphs_for(nt)["cholesky"]
    graphs = [gfac() for _ in range(n_runs)]
    strats = strategies("numpy")
    rows = []
    for label in AUDIT_STRATEGIES:
        sfac = strats[label]
        walls = {}
        events = tasks = 0
        for audit in (False, True):
            dt = float("inf")
            for _rep in range(2):
                events = tasks = 0
                t0 = time.perf_counter()
                for i, g in enumerate(graphs):
                    sim = Simulator(
                        g, machine, sfac(), seed=1234 + i, audit=audit
                    )
                    res = sim.run()
                    events += res.n_events
                    tasks += len(g)
                dt = min(dt, time.perf_counter() - t0)
            walls[audit] = dt
        dt = walls[True]
        overhead = round(dt / walls[False], 3) if walls[False] > 0 else 0.0
        row = dict(
            kernel="cholesky", strategy=label, backend="numpy",
            nt=nt, n_gpus=n_gpus, runs=n_runs, capacity=0,
            churn=0.0, fault_mode="drain", flake=0.0, notice=0.0,
            exact=True, audit=True,
            wall_s=round(dt, 4), events=events,
            events_per_s=round(events / dt, 1) if dt > 0 else 0.0,
            tasks_per_s=round(tasks / dt, 1) if dt > 0 else 0.0,
            audit_overhead=overhead,
        )
        rows.append(row)
        print(
            f"sched_overhead/cholesky/{label}/gpus{n_gpus}/nt{nt}/"
            f"numpy/audit,{dt / n_runs * 1e6:.1f},"
            f"events_per_s={row['events_per_s']};"
            f"audit_overhead={overhead}"
        )
    return rows


# ---------------------------------------------------------------------------
# batched surrogate sweep throughput (REPRO_SCHED_EXACT=0 engine)


BATCHED_SWEEP_SPECS = (
    "heft", "ws", "dada?alpha=0", "dada?alpha=0.5", "dada?alpha=0.5&use_cp=1",
)


def batched_sweep_rows(nt: int, n_gpus: int, n_runs: int) -> list:
    """Configs/sec of whole sweeps through ``run_batch`` vs the exact engine.

    One strategy × GPU-count × seed sweep per kernel runs as a handful of
    compiled episode dispatches (the ``REPRO_SCHED_EXACT=0`` path), then
    the *same* configurations replay through ``run_simulation`` — the
    exact-vs-surrogate speedup is the number the batched engine exists
    for. Rows carry ``exact=False`` (the regression key separates the two
    engines) and the per-dispatch batch size.
    """
    from repro.core import cached_graph, run_batch, run_simulation
    from repro.sched import current_config

    cfg = current_config()
    gpu_counts = sorted({2, n_gpus})
    machines = {g: machine_for(g) for g in gpu_counts}
    rows = []
    for kernel, gfac in graphs_for(nt).items():
        graph = cached_graph(gfac)
        items = [
            {"graph": graph, "machine": machines[g], "strategy": spec,
             "seed": 1234 + i, "noise": 0.03}
            for g in gpu_counts
            for spec in BATCHED_SWEEP_SPECS
            for i in range(n_runs)
        ]
        run_batch(items, config=cfg)  # warm-up: compile once, measure dispatch
        dt = float("inf")
        for _rep in range(2):
            t0 = time.perf_counter()
            results = run_batch(items, config=cfg)
            dt = min(dt, time.perf_counter() - t0)
        t0 = time.perf_counter()
        for it in items:
            run_simulation(
                it["graph"], it["machine"], resolve(it["strategy"]),
                seed=it["seed"], noise=it["noise"],
            )
        dt_exact = time.perf_counter() - t0
        n_cfg = len(items)
        batch = min(max(1, int(cfg.batch)), 16)
        row = dict(
            kernel=kernel, strategy="sweep-mix", backend="jax",
            nt=nt, n_gpus=n_gpus, runs=n_runs, capacity=0,
            churn=0.0, fault_mode="drain", flake=0.0, notice=0.0,
            exact=False,
            batch=batch, n_configs=n_cfg,
            wall_s=round(dt, 4), events=0, events_per_s=0.0,
            tasks_per_s=round(n_cfg * len(graph) / dt, 1) if dt > 0 else 0.0,
            configs_per_s=round(n_cfg / dt, 2) if dt > 0 else 0.0,
            exact_wall_s=round(dt_exact, 4),
            speedup_vs_exact=round(dt_exact / dt, 2) if dt > 0 else 0.0,
        )
        rows.append(row)
        print(
            f"sched_overhead/{kernel}/sweep-mix/gpus{n_gpus}/nt{nt}/"
            f"jax/batched,{dt / n_cfg * 1e6:.1f},"
            f"configs_per_s={row['configs_per_s']};"
            f"speedup_vs_exact={row['speedup_vs_exact']};batch={batch}"
        )
        del results
    return rows


# ---------------------------------------------------------------------------
# λ-probe placement microbenchmark


def _widest_wave(graph):
    """The largest single ready wave: tasks at the most populous depth
    (for tile Cholesky this is the first syrk/gemm wave, ~NT²/2 tasks)."""
    depth = [0] * len(graph)
    for t in graph.tasks:
        preds = graph.pred[t.tid]
        depth[t.tid] = (max(depth[p] for p in preds) + 1) if preds else 0
    counts = {}
    for d in depth:
        counts[d] = counts.get(d, 0) + 1
    best = max(counts, key=lambda d: (counts[d], -d))
    return [t for t in graph.tasks if depth[t.tid] == best]


def _reset_placement_state(sim, load_ts_snapshot):
    sim.load_ts[:] = load_ts_snapshot
    for w in sim.workers:
        w.queue.clear()
        w.blocked_on = 0
    sim._inflight.clear()
    sim._link_free.clear()
    sim._waiting.clear()
    sim._events.clear()


def lambda_probe_rows(
    nt: int, n_cpus: int, n_gpus: int, reps: int, backends, kernel: str = "cholesky"
) -> list:
    graphs = graphs_for(nt)
    graph = graphs[kernel]()
    machine = machine_for(n_gpus, n_cpus)
    wave = _widest_wave(graph)
    rows = []
    placements = {}
    setups = {}
    for backend in backends:
        strat = resolve("dada?alpha=0.5&use_cp=1", backend=backend)
        sim = Simulator(graph, machine, strat, seed=0)
        # scatter a third of the tiles across GPU memories so affinity and
        # transfer scoring are exercised, not just durations
        for k, name in enumerate(sim.arrays.data_names):
            if k % 3 == 0 and n_gpus:
                sim.residency.write(name, k % n_gpus)
        # isolate the placement *decision* cost: queue pushes trigger the
        # simulator's prefetch/transfer machinery, which is workload
        # simulation (identical for every backend), not scheduler scoring
        placed = {}
        sim.push = lambda task, rid, _p=placed: _p.__setitem__(task.tid, rid)
        snapshot = list(sim.load_ts)
        strat.place(sim, wave, None)  # warm-up (jit compilation for jax)
        placements[backend] = dict(placed)
        _reset_placement_state(sim, snapshot)
        setups[backend] = (strat, sim, snapshot, [])
    # interleave the repetitions across backends: the wall clock on shared
    # boxes drifts, and interleaving keeps the comparison apples-to-apples
    for _ in range(reps):
        for backend in backends:
            strat, sim, snapshot, samples = setups[backend]
            t0 = time.perf_counter()
            strat.place(sim, wave, None)
            samples.append(time.perf_counter() - t0)
            _reset_placement_state(sim, snapshot)
    for backend in backends:
        samples = sorted(setups[backend][3])
        us = samples[len(samples) // 2] * 1e6  # median: the box is noisy
        rows.append(
            dict(
                bench="lambda_probe", kernel=kernel, nt=nt, n_cpus=n_cpus,
                n_gpus=n_gpus, resources=n_cpus + n_gpus, width=len(wave),
                strategy="dada(a)+cp", backend=backend, reps=reps,
                us_per_place=round(us, 1),
            )
        )
    base = next((r for r in rows if r["backend"] == "numpy"), None)
    for r in rows:
        # None (not True) when numpy was not measured: an honest "no
        # comparison happened", never a vacuous pass
        identical = (
            placements[r["backend"]] == placements["numpy"]
            if "numpy" in placements
            else None
        )
        r["decisions_match_numpy"] = identical
        if base is not None and r["us_per_place"] > 0:
            r["speedup_vs_numpy"] = round(
                base["us_per_place"] / r["us_per_place"], 2
            )
        print(
            f"sched_overhead/lambda_probe/{kernel}/nt{nt}/res{r['resources']}/"
            f"dada(a)+cp/{r['backend']},{r['us_per_place']:.1f},"
            f"width={r['width']};speedup_vs_numpy={r.get('speedup_vs_numpy', 1.0)};"
            f"decisions_match_numpy={identical}"
        )
    return rows


# ---------------------------------------------------------------------------


def calibration_score() -> float:
    """Fixed scheduler-independent workload scoring machine speed.

    The regression gate compares events/sec across machines (developer
    boxes, CI runners); dividing by this constant-workload score cancels
    most of the raw CPU-speed difference. Two properties matter: it
    touches none of the scheduler code under test (a uniform scheduler
    slowdown must not drag the calibration down with it, or the gate
    would self-cancel), and it is *interpreter-bound* — heap ops, dict
    lookups, float arithmetic — because that is what events/sec is bound
    by, so the normalisation tracks the right axis of machine speed
    (a box with fast BLAS but a slow interpreter must not look fast).
    """
    import heapq

    acc = 0.0
    best = float("inf")
    # best-of-5: each repetition is timed separately and the fastest one
    # scores (timeit practice) — a noisy-neighbor burst during one rep
    # must not halve the calibration and double every scaled baseline
    for _ in range(5):
        t0 = time.perf_counter()
        heap = []
        table = {}
        x = 1.0
        for i in range(20000):
            x = x * 1.0000001 + 0.5
            heapq.heappush(heap, (x % 97.0, i))
            table[i & 1023] = x
            if i & 7 == 0:
                acc += heapq.heappop(heap)[0]
        acc += sum(table.values())
        best = min(best, time.perf_counter() - t0)
    assert acc != 0.0
    return 2e4 / best if best > 0 else 0.0  # arbitrary units


def main() -> list:
    from repro.sched import current_config

    cfg = current_config()
    n_gpus = cfg.bench_gpus[0] if cfg.bench_gpus else 8
    n_runs = cfg.bench_runs if cfg.bench_runs is not None else 3
    nts = list(cfg.bench_nt)
    backends = available_backends()

    print("name,us_per_call,derived")
    rows = whole_sim_rows(nts, n_gpus, n_runs, backends)
    if nts:  # REPRO_BENCH_NT="" is a valid empty sweep
        rows += streaming_rows(nts[0], n_gpus, n_runs)
        rows += churn_rows(nts[0], n_gpus, n_runs)
        rows += recovery_rows(nts[0], n_gpus, n_runs)
        rows += audit_rows(nts[0], n_gpus, n_runs)
        if "jax" in backends:
            rows += batched_sweep_rows(nts[0], n_gpus, n_runs)
    total_ev = sum(r["events"] for r in rows if r.get("exact", True))
    total_s = sum(r["wall_s"] for r in rows if r.get("exact", True))
    if total_s > 0:
        print(
            f"sched_overhead/total,{total_s * 1e6:.1f},"
            f"events_per_s={total_ev / total_s:.1f}"
        )

    lam_rows = []
    diverged = []
    if cfg.bench_lambda:
        lam_rows = lambda_probe_rows(
            cfg.bench_lambda_nt, 8, 24, cfg.bench_lambda_reps, backends
        )
        diverged = [
            r["backend"] for r in lam_rows
            if r["decisions_match_numpy"] is False
        ]

    update_bench_json(
        "sched_overhead",
        dict(
            config=dict(n_gpus=n_gpus, runs=n_runs, nts=nts, backends=backends),
            calibration_score=round(calibration_score(), 2),
            whole_sim=rows,
            lambda_probe=lam_rows,
        ),
    )
    if diverged:
        # decision divergence is a correctness regression, not a perf
        # number — record it in the JSON above, then fail the run
        print(
            f"ERROR: backend(s) {diverged} placed the λ-probe wave "
            f"differently from numpy — decision identity broken"
        )
        sys.exit(1)
    return rows


if __name__ == "__main__":
    main()
