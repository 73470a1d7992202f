"""Fig. 2 — Cholesky (DPOTRF) 8192^2: HEFT vs DADA(0) vs DADA(a) vs
DADA(a)+CP (+ the work-stealing baseline discussed in §4.3)."""
from __future__ import annotations

from .common import STRATEGIES, bench_settings, emit_csv_lines, sweep


def main() -> list:
    runs, gpus, jobs = bench_settings()
    rows = sweep("fig2_cholesky", "cholesky", STRATEGIES, runs, gpus, jobs)
    emit_csv_lines(rows)
    return rows


if __name__ == "__main__":
    main()
