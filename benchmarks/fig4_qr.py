"""Fig. 4 — QR (DGEQRF) 8192^2: the kernel where HEFT outperforms every
dual-approximation variant (paper §4.3)."""
from __future__ import annotations

from .common import STRATEGIES, bench_settings, emit_csv_lines, sweep


def main() -> list:
    runs, gpus, jobs = bench_settings()
    rows = sweep("fig4_qr", "qr", STRATEGIES, runs, gpus, jobs)
    emit_csv_lines(rows)
    return rows


if __name__ == "__main__":
    main()
