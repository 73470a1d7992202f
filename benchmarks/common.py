"""Shared benchmark harness for the paper-figure reproductions.

Methodology mirrors the paper (§4.1): each configuration is run repeatedly
with different seeds; we report mean and 95% CI of GFLOPS and total
transferred GB. Matrix 8192x8192, tile 512 (16x16 tiles), inner block 128,
fp64 item size — the paper's exact problem shape.

Environment knobs, parsed once into :class:`BenchConfig`:
  REPRO_BENCH_RUNS        repetitions per configuration (default 30, paper-level)
  REPRO_BENCH_GPUS        comma list of GPU counts       (default 1..8)
  REPRO_BENCH_FAST        =1 shrinks to 3 runs x {2,4,8} GPUs for smoke use
  REPRO_BENCH_JOBS        process-pool width for the seeded repetitions
                          (default: CPU count; 1 forces the serial path)
  REPRO_BENCH_ALLOW_FAIL  =1 exits 0 when a claim fails

Factories are ``functools.partial`` over module-level callables (not
lambdas) so ``run_many`` can ship them to its process pool.
"""
from __future__ import annotations

import csv
import os
from dataclasses import dataclass
from functools import partial
from pathlib import Path
from typing import Callable, Dict, List, Mapping, Optional, Tuple

from repro.configs.paper_machine import paper_machine
from repro.core import Summary, default_jobs, get_pool, pool_allowed, run_many
from repro.linalg.cholesky import cholesky_graph
from repro.sched import resolve
from repro.sched.config import _parse_flag, _parse_int
from repro.linalg.lu import lu_graph
from repro.linalg.qr import qr_graph

BENCH_PREFIX = "REPRO_BENCH_"

MATRIX = 8192
TILE = 512
NT = MATRIX // TILE
RESULTS_DIR = Path(__file__).resolve().parent / "results"
BENCH_JSON = RESULTS_DIR / "BENCH_sched.json"

def graphs_for(nt: int, tile: int = TILE) -> Dict[str, Callable]:
    """Paper-kernel graph factories at an arbitrary tile-grid size NT
    (scheduler-scaling sweeps use NT ∈ {32, 64}; the paper shape is 16)."""
    return {
        "cholesky": partial(cholesky_graph, nt, tile, with_fns=False),
        "lu": partial(lu_graph, nt, tile, with_fns=False),
        "qr": partial(qr_graph, nt, tile, with_fns=False),
    }


GRAPHS: Dict[str, Callable] = graphs_for(NT)


def machine_for(n_gpus: int, n_cpus: int = None):
    """The paper box for paper-sized configs, the scaled 32-resource-class
    platform beyond it (n_gpus > 8 or an explicit CPU count)."""
    from repro.configs.paper_machine import scaled_machine

    if n_cpus is None and 0 <= n_gpus <= 8:
        return paper_machine(n_gpus)
    return scaled_machine(n_gpus=n_gpus, n_cpus=8 if n_cpus is None else n_cpus)


def update_bench_json(section: str, payload) -> Path:
    """Merge one section into ``results/BENCH_sched.json``.

    The file records each script's last run (claims, figure rows,
    serving rows); each producing script owns one top-level key so
    ``paper_validation.py``, ``scenario_matrix.py`` and
    ``serving_load.py`` can update it independently.
    """
    import json

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    doc = {}
    if BENCH_JSON.exists():
        try:
            doc = json.loads(BENCH_JSON.read_text())
        except (ValueError, OSError) as exc:
            # never silently drop another producer's section: the file is
            # a cross-PR trajectory, so make the reset loud
            print(
                f"warning: {BENCH_JSON} was unreadable ({exc}); "
                f"starting a fresh trajectory file",
                flush=True,
            )
            doc = {}
    doc["schema"] = 1
    doc[section] = payload
    BENCH_JSON.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")
    return BENCH_JSON


def _parse_int_list(var: str, value: str) -> Tuple[int, ...]:
    # empty entries allowed: REPRO_BENCH_GPUS="" is an empty sweep
    return tuple(_parse_int(var, p.strip(), lo=0) for p in value.split(",") if p.strip())


@dataclass(frozen=True)
class BenchConfig:
    """The benchmark scripts' ``REPRO_BENCH_*`` knobs, parsed and validated
    once. ``None`` means "unset" where a script's default depends on other
    knobs (runs defaults to 3 under ``fast``, else 30 for the figures)."""

    fast: bool = False
    runs: Optional[int] = None
    gpus: Optional[Tuple[int, ...]] = None
    jobs: Optional[int] = None
    allow_fail: bool = False

    @classmethod
    def from_env(cls, env: Optional[Mapping[str, str]] = None) -> "BenchConfig":
        """Parse ``REPRO_BENCH_*``; a malformed value or an unknown name
        raises ``ValueError`` naming the variable."""
        env = os.environ if env is None else env
        kw = {}
        unknown = []
        for var, raw in env.items():
            if not var.startswith(BENCH_PREFIX):
                continue
            spec = _BENCH_ENV.get(var)
            if spec is None:
                unknown.append(var)
                continue
            field_name, parse = spec
            kw[field_name] = parse(var, raw)
        if unknown:
            raise ValueError(
                "unknown benchmark configuration variable(s): "
                f"{', '.join(sorted(unknown))} (known: {', '.join(sorted(_BENCH_ENV))})"
            )
        return cls(**kw)


_BENCH_ENV = {
    "REPRO_BENCH_FAST": ("fast", _parse_flag),
    "REPRO_BENCH_RUNS": ("runs", lambda var, v: _parse_int(var, v, lo=1)),
    "REPRO_BENCH_GPUS": ("gpus", _parse_int_list),
    "REPRO_BENCH_JOBS": ("jobs", lambda var, v: _parse_int(var, v, lo=1)),
    "REPRO_BENCH_ALLOW_FAIL": ("allow_fail", _parse_flag),
}


def bench_settings() -> Tuple[int, List[int], Optional[int]]:
    """(runs, gpu_counts, jobs) of the figure sweeps, from the environment."""
    cfg = BenchConfig.from_env()
    runs = cfg.runs if cfg.runs is not None else (3 if cfg.fast else 30)
    if cfg.gpus is not None:
        gpus = list(cfg.gpus)
    else:
        gpus = [2, 4, 8] if cfg.fast else [1, 2, 3, 4, 5, 6, 7, 8]
    return runs, gpus, cfg.jobs


# one code path for every consumer: specs resolved through the policy
# registry (repro.sched), identical objects to the old direct constructors
STRATEGIES: Dict[str, Callable] = {
    "heft": partial(resolve, "heft"),
    "ws": partial(resolve, "ws"),
    "dada(0)": partial(resolve, "dada?alpha=0"),
    "dada(a)": partial(resolve, "dada?alpha=0.5"),
    "dada(a)+cp": partial(resolve, "dada?alpha=0.5&use_cp=1"),
}


def _sweep_config(graph_factory, machine, sfac, n_runs: int) -> Summary:
    """One (strategy × machine) configuration, run serially (pool worker)."""
    return run_many(graph_factory, machine, sfac, n_runs=n_runs, n_jobs=1)


def spec_of(sfac) -> str:
    """Recover the registry spec string from a ``partial(resolve, spec)``.

    The batched surrogate path needs the *spec*, not a constructed policy
    object: strategy parameters become batch axes, so the episode engine
    re-derives (α, use_cp, ws) from the string."""
    if isinstance(sfac, partial) and sfac.func is resolve and sfac.args:
        return sfac.args[0]
    raise ValueError(
        "batched sweep (REPRO_SCHED_EXACT=0) needs partial(resolve, spec) "
        f"strategy factories, got {sfac!r}; run it on the exact path"
    )


def _ci95(xs) -> float:
    import math

    import numpy as np

    if len(xs) < 2:
        return 0.0
    return 1.96 * float(np.std(xs, ddof=1)) / math.sqrt(len(xs))


def _sweep_batched(configs, graph_factory, n_runs: int) -> List[Summary]:
    """Surrogate path: the whole figure sweep as a handful of dispatches.

    Every (strategy × GPU-count × seed) cell becomes one row of a
    ``run_batch`` call — seeds and strategy parameters are batch axes of
    a single compiled episode, so the sweep cost is a few ``lax.scan``
    dispatches instead of |configs| × n_runs Python event loops.
    """
    from repro.core import cached_graph, run_batch

    graph = cached_graph(graph_factory)
    machines = {}
    items = []
    for n_gpus, label, sfac in configs:
        m = machines.setdefault(n_gpus, machine_for(n_gpus))
        spec = spec_of(sfac)
        for i in range(n_runs):
            items.append(
                {"graph": graph, "machine": m, "strategy": spec,
                 "seed": 1234 + i, "noise": 0.03}
            )
    results = run_batch(items)
    summaries = []
    for k, (n_gpus, label, sfac) in enumerate(configs):
        rs = results[k * n_runs : (k + 1) * n_runs]
        gf = [r.gflops for r in rs]
        gb = [r.gbytes for r in rs]
        summaries.append(
            Summary(
                strategy=label, n=n_runs,
                gflops_mean=float(sum(gf) / len(gf)), gflops_ci95=_ci95(gf),
                gbytes_mean=float(sum(gb) / len(gb)), gbytes_ci95=_ci95(gb),
                makespan_mean=float(sum(r.makespan for r in rs) / len(rs)),
                steals_mean=0.0,
            )
        )
    return summaries


def sweep(
    fig: str,
    kernel: str,
    strategies: Dict[str, Callable],
    n_runs: int,
    gpu_counts: List[int],
    jobs: Optional[int] = None,
) -> List[dict]:
    """Run strategies x gpu-counts; persist CSV; return row dicts.

    Configurations fan out over the shared process pool (one pool task per
    strategy × GPU-count, each running its seeded repetitions serially —
    coarser tasks than per-seed fan-out, so 2 workers stay busy end to
    end). ``jobs`` (``REPRO_BENCH_JOBS``) sets the pool's width; unset, it
    is the CPU count. Each configuration is independently seeded, so
    results are bit-identical to the serial loop and are gathered in sweep
    order.

    An empty sweep (no strategies or no GPU counts, e.g. an empty
    ``REPRO_BENCH_GPUS``) returns ``[]`` with a warning instead of
    crashing on the CSV header row.
    """
    rows = []
    if not strategies or not gpu_counts:
        print(
            f"  {fig} {kernel}: empty sweep "
            f"({len(strategies)} strategies x {len(gpu_counts)} gpu counts) — skipping",
            flush=True,
        )
        return rows
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    out_path = RESULTS_DIR / f"{fig}.csv"
    graph_factory = GRAPHS[kernel]

    configs = [
        (n_gpus, label, sfac)
        for n_gpus in gpu_counts
        for label, sfac in strategies.items()
    ]

    from repro.sched import current_config

    batched = not current_config().exact
    summaries: List[Summary] = (
        _sweep_batched(configs, graph_factory, n_runs) if batched else []
    )
    n_jobs = jobs if jobs is not None else default_jobs(len(configs))
    futs = None
    if (
        not batched and n_jobs > 1 and len(configs) > 1
        and pool_allowed([sfac for _, _, sfac in configs])
    ):
        try:
            import pickle

            pickle.dumps([sfac for _, _, sfac in configs] + [graph_factory])
            pool = get_pool(jobs)
            futs = [
                pool.submit(
                    _sweep_config, graph_factory, paper_machine(n_gpus), sfac, n_runs
                )
                for n_gpus, label, sfac in configs
            ]
        except Exception:
            futs = None  # non-picklable factories: run serially below

    for k, (n_gpus, label, sfac) in enumerate(configs):
        if batched:
            s = summaries[k]
        elif futs is not None:
            s = futs[k].result()
        else:
            s = _sweep_config(graph_factory, paper_machine(n_gpus), sfac, n_runs)
        row = dict(
            fig=fig,
            kernel=kernel,
            strategy=label,
            n_gpus=n_gpus,
            n_runs=s.n,
            gflops=round(s.gflops_mean, 2),
            gflops_ci95=round(s.gflops_ci95, 2),
            gbytes=round(s.gbytes_mean, 4),
            gbytes_ci95=round(s.gbytes_ci95, 4),
            makespan_s=round(s.makespan_mean, 5),
            steals=round(s.steals_mean, 1),
        )
        rows.append(row)
        print(
            f"  {fig} {kernel} gpus={n_gpus} {label:12s} "
            f"{row['gflops']:8.1f} GF (±{row['gflops_ci95']}) "
            f"{row['gbytes']:7.3f} GB (±{row['gbytes_ci95']})",
            flush=True,
        )
    with out_path.open("w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=list(rows[0].keys()))
        w.writeheader()
        w.writerows(rows)
    return rows


def emit_csv_lines(rows: List[dict]) -> None:
    """Skeleton contract: ``name,us_per_call,derived`` lines on stdout."""
    for r in rows:
        name = f"{r['fig']}/{r['kernel']}/{r['strategy']}/gpus{r['n_gpus']}"
        us = r["makespan_s"] * 1e6
        print(f"{name},{us:.1f},gflops={r['gflops']};gbytes={r['gbytes']}")
