"""Fig. 3 — LU (DGETRF) 8192^2: HEFT vs dual-approximation variants.

Paper headline: DADA(a)+CP moves ~3.5x less data than HEFT at 8 GPUs for
only ~1.13x slowdown."""
from __future__ import annotations

from .common import STRATEGIES, bench_settings, emit_csv_lines, sweep


def main() -> list:
    runs, gpus, jobs = bench_settings()
    rows = sweep("fig3_lu", "lu", STRATEGIES, runs, gpus, jobs)
    emit_csv_lines(rows)
    return rows


if __name__ == "__main__":
    main()
