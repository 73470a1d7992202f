"""Fig. 1 — impact of the affinity control parameter alpha on Cholesky
(DPOTRF), matrix 8192x8192: performance and transfers vs #GPUs, for several
alpha values, with and without communication prediction."""
from __future__ import annotations

from functools import partial

from repro.sched import resolve

from .common import bench_settings, emit_csv_lines, sweep

ALPHAS = [0.0, 0.25, 0.5, 0.75, 1.0]


def main() -> list:
    runs, gpus, jobs = bench_settings()
    strategies = {}
    for a in ALPHAS:
        strategies[f"dada({a:g})"] = partial(resolve, f"dada?alpha={a:g}")
    for a in ALPHAS:
        strategies[f"dada({a:g})+cp"] = partial(
            resolve, f"dada?alpha={a:g}&use_cp=1"
        )
    rows = sweep("fig1_alpha_sweep", "cholesky", strategies, runs, gpus, jobs)
    emit_csv_lines(rows)
    return rows


if __name__ == "__main__":
    main()
