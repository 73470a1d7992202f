"""Benchmark harness: one function per paper figure/table.

Prints ``name,us_per_call,derived`` CSV rows per configuration, followed by
the paper-claim validation summary. See common.py for env knobs
(REPRO_BENCH_FAST=1 for a quick pass).
"""
from __future__ import annotations

import sys


def main() -> None:
    from . import fig1_alpha_sweep, fig2_cholesky, fig3_lu, fig4_qr, fig_ws_discussion
    from .common import BenchConfig
    from .paper_validation import print_checks, validate

    print("name,us_per_call,derived")
    f1 = fig1_alpha_sweep.main()
    f2 = fig2_cholesky.main()
    f3 = fig3_lu.main()
    f4 = fig4_qr.main()
    print("== §4.3 work-stealing discussion ==")
    fig_ws_discussion.main()
    ok = print_checks(validate(f1, f2, f3, f4, jobs=BenchConfig.from_env().jobs))
    if not ok:
        print("WARNING: some paper claims did not reproduce — see above", file=sys.stderr)


if __name__ == "__main__":
    main()
