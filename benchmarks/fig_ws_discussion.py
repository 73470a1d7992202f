"""§4.3 "Comparison with work stealing" discussion, as a benchmark.

The paper reports (without a figure) that naive work stealing is cache-
unfriendly for small matrices while affinity policies handle them well, and
that model-oblivious stealing stays competitive at larger sizes. This
benchmark quantifies both halves across matrix sizes.
"""
from __future__ import annotations

from functools import partial

from repro.configs.paper_machine import paper_machine
from repro.core import run_many
from repro.sched import resolve
from repro.linalg.cholesky import cholesky_graph

from .common import bench_settings


def main() -> list:
    runs, _, jobs = bench_settings()
    machine = paper_machine(4)
    rows = []
    for n in (2048, 4096, 8192, 16384):
        nt = n // 512
        for label, fac in [
            ("ws", partial(resolve, "ws")),
            ("heft", partial(resolve, "heft")),
            ("dada(a)+cp", partial(resolve, "dada?alpha=0.5&use_cp=1")),
        ]:
            s = run_many(
                partial(cholesky_graph, nt, 512, with_fns=False),
                machine, fac, n_runs=max(3, runs // 3), n_jobs=jobs,
            )
            rows.append(dict(
                n=n, strategy=label, gflops=round(s.gflops_mean, 1),
                gbytes=round(s.gbytes_mean, 3), steals=s.steals_mean,
            ))
            print(f"  ws_discussion n={n:5d} {label:12s} "
                  f"{s.gflops_mean:7.1f} GF {s.gbytes_mean:7.3f} GB "
                  f"steals={s.steals_mean:.0f}", flush=True)
    return rows


if __name__ == "__main__":
    main()
