"""Churn scenario matrix: proactive recovery scored against the
clairvoyant bound (claim C9).

Sweeps churn rate × recovery mode × notice window × strategy over seeded
repetitions. Every faulted run is scored by ``recovery_report`` against
its own clairvoyant no-fault baseline — the same graph, machine,
strategy and seed with no detach/attach events — so slowdown and extra
bytes isolate what the faults cost, not what the strategy costs.

Claim C9, checked per (churn, mode, strategy) pair and per cell:

  * **notice helps** — with a preemption-notice window open the engine
    stops feeding the dying device and replicates its sole copies off
    proactively, so mean ``wasted_s`` (kill-mode lost work) and mean
    reactive evacuation bytes (death-time salvage on the critical
    recovery path) must not exceed the blind notice=0 run of the same
    strategy at the same churn level and recovery mode;
  * **C8 persists** — DADA's transfer-volume advantage over HEFT holds
    across the whole matrix: mean faulted total bytes of the
    notice-aware dada(a)+cp+rec (identical to dada(a)+cp while no
    notice is pending) stay at or below HEFT's in every (churn, mode,
    notice) cell. Plain dada(a)+cp is reported too: with a notice open
    its affinity objective keeps pulling work toward the condemned
    device, and the byte gap between the two variants is the measured
    cost of that trap — the reason ``recover=1`` exists.

Uncertainty is reported as seeded-bootstrap 95% CIs (percentile method
over seed means), not normal-theory CIs: slowdown under churn is heavy
tailed — one unlucky detach at the critical-path root dominates a seed.

Results go to ``results/scenario_matrix.csv`` and the
``scenario_matrix`` section of ``results/BENCH_sched.json``; the claim
table prints PASS/FAIL and the process exits 1 on any C9 failure unless
``REPRO_BENCH_ALLOW_FAIL=1``.

Knobs: ``REPRO_BENCH_RUNS`` (seeds per cell, default 20),
``REPRO_BENCH_FAST=1`` (3 seeds, 1×2×2 matrix, NT=6 — the CI smoke
shape), plus the fault knobs the engine itself validates.
"""
from __future__ import annotations

import csv
import sys
import time
from pathlib import Path
from typing import Dict, List, Tuple

if __package__ in (None, ""):  # `python benchmarks/scenario_matrix.py`
    _repo = Path(__file__).resolve().parents[1]
    for p in (str(_repo), str(_repo / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)

import numpy as np

from repro.configs.paper_machine import paper_machine
from repro.core import Simulator
from repro.linalg.lu import lu_graph
from repro.runtime import recovery_report
from repro.sched import resolve

from benchmarks.common import RESULTS_DIR, BenchConfig, update_bench_json

# matrix axes: a light and a heavy churn regime (events per unit sim
# time over a ~0.1 s trace: a few vs dozens of detach cycles), both
# recovery modes, notice window off vs ~one-task-length open
MODES = ("drain", "kill")
NOTICE_W = 0.008
STRATEGIES: Dict[str, str] = {
    "heft": "heft",
    "dada(a)+cp": "dada?alpha=0.5&use_cp=1",
    "dada(a)+cp+rec": "dada?alpha=0.5&use_cp=1&recover=1",
}
SEED0 = 1234
N_BOOT = 2000


def _settings() -> Tuple[int, int, Tuple[float, ...]]:
    """(n_seeds, nt, churn_levels) honouring the bench knobs."""
    cfg = BenchConfig.from_env()
    if cfg.fast:
        runs = cfg.runs if cfg.runs is not None else 3
        return runs, 6, (250.0,)
    runs = cfg.runs if cfg.runs is not None else 20
    return runs, 12, (40.0, 150.0)


def _boot_ci(xs: List[float], rng: np.random.Generator) -> Tuple[float, float]:
    """Seeded percentile-bootstrap 95% CI of the mean."""
    arr = np.asarray(xs, dtype=np.float64)
    if arr.size < 2:
        v = float(arr[0]) if arr.size else 0.0
        return v, v
    means = rng.choice(arr, size=(N_BOOT, arr.size), replace=True).mean(axis=1)
    lo, hi = np.percentile(means, (2.5, 97.5))
    return float(lo), float(hi)


def run_matrix() -> Tuple[List[dict], List[dict]]:
    n_seeds, nt, churn_levels = _settings()
    graph = lu_graph(nt, 512, with_fns=False)
    machine_gpus = 8
    print(
        f"scenario matrix: NT={nt}, {n_seeds} seeds, churn {churn_levels}, "
        f"modes {MODES}, notice (0.0, {NOTICE_W:g}), "
        f"{len(STRATEGIES)} strategies",
        flush=True,
    )

    # clairvoyant baselines: per (strategy, seed), fault-free — shared by
    # every cell of that strategy, which is what makes the bound a bound
    baselines = {}
    for label, spec in STRATEGIES.items():
        for i in range(n_seeds):
            baselines[(label, i)] = Simulator(
                graph, paper_machine(machine_gpus), resolve(spec),
                seed=SEED0 + i, noise=0.0,
            ).run()

    rows: List[dict] = []
    cells: Dict[Tuple[float, str, float, str], dict] = {}
    for churn in churn_levels:
        for mode in MODES:
            for notice in (0.0, NOTICE_W):
                for label, spec in STRATEGIES.items():
                    reports = []
                    bytes_f = []
                    for i in range(n_seeds):
                        res = Simulator(
                            graph, paper_machine(machine_gpus), resolve(spec),
                            seed=SEED0 + i, noise=0.0,
                            churn=churn, fault_mode=mode, notice_s=notice,
                        ).run()
                        reports.append(
                            recovery_report(res, baselines[(label, i)])
                        )
                        bytes_f.append(float(res.total_bytes))
                    rng = np.random.default_rng(
                        (SEED0, int(churn), MODES.index(mode),
                         int(notice * 1e6), sorted(STRATEGIES).index(label))
                    )
                    slow = [r["slowdown"] for r in reports]
                    extra = [r["extra_bytes"] for r in reports]
                    s_lo, s_hi = _boot_ci(slow, rng)
                    b_lo, b_hi = _boot_ci(extra, rng)
                    mean = lambda k: float(
                        np.mean([r.get(k, 0.0) for r in reports])
                    )
                    row = dict(
                        kernel="lu", nt=nt, n_gpus=machine_gpus,
                        churn=churn, fault_mode=mode, notice=notice,
                        strategy=label, n_seeds=n_seeds,
                        slowdown_mean=round(float(np.mean(slow)), 4),
                        slowdown_ci95=[round(s_lo, 4), round(s_hi, 4)],
                        extra_bytes_mean=round(float(np.mean(extra)), 1),
                        extra_bytes_ci95=[round(b_lo, 1), round(b_hi, 1)],
                        total_bytes_mean=round(float(np.mean(bytes_f)), 1),
                        wasted_s_mean=round(mean("wasted_s"), 6),
                        reactive_bytes_mean=round(
                            mean("reactive_evacuated_bytes"), 1
                        ),
                        proactive_bytes_mean=round(mean("proactive_bytes"), 1),
                        n_detaches_mean=round(mean("n_detaches"), 2),
                        n_notices_mean=round(mean("n_notices"), 2),
                    )
                    rows.append(row)
                    cells[(churn, mode, notice, label)] = row
                    print(
                        f"  churn={churn:g} {mode:5s} notice={notice:g} "
                        f"{label:14s} slowdown {row['slowdown_mean']:.3f} "
                        f"[{s_lo:.3f},{s_hi:.3f}]  wasted {row['wasted_s_mean']:.4g}s  "
                        f"reactive {row['reactive_bytes_mean'] / 1e6:.1f}MB  "
                        f"proactive {row['proactive_bytes_mean'] / 1e6:.1f}MB",
                        flush=True,
                    )

    # ---- claim C9 --------------------------------------------------------
    checks: List[dict] = []
    for churn in churn_levels:
        for mode in MODES:
            for label in STRATEGIES:
                blind = cells[(churn, mode, 0.0, label)]
                noted = cells[(churn, mode, NOTICE_W, label)]
                ok = (
                    noted["wasted_s_mean"] <= blind["wasted_s_mean"] + 1e-9
                    and noted["reactive_bytes_mean"]
                    <= blind["reactive_bytes_mean"] * 1.05 + 1.0
                )
                checks.append(
                    dict(
                        claim=(
                            f"C9 notice cuts waste: churn={churn:g} {mode} "
                            f"{label}"
                        ),
                        measured=(
                            f"wasted {blind['wasted_s_mean']:.4g}->"
                            f"{noted['wasted_s_mean']:.4g}s, reactive "
                            f"{blind['reactive_bytes_mean'] / 1e6:.1f}->"
                            f"{noted['reactive_bytes_mean'] / 1e6:.1f}MB "
                            f"(proactive {noted['proactive_bytes_mean'] / 1e6:.1f}MB)"
                        ),
                        passed=ok,
                    )
                )
            for notice in (0.0, NOTICE_W):
                heft = cells[(churn, mode, notice, "heft")]
                dada = cells[(churn, mode, notice, "dada(a)+cp+rec")]
                checks.append(
                    dict(
                        claim=(
                            f"C9/C8 dada+rec bytes <= heft: churn={churn:g} "
                            f"{mode} notice={notice:g}"
                        ),
                        measured=(
                            f"dada+rec {dada['total_bytes_mean'] / 1e9:.3f}GB "
                            f"vs heft {heft['total_bytes_mean'] / 1e9:.3f}GB"
                        ),
                        passed=(
                            dada["total_bytes_mean"]
                            <= heft["total_bytes_mean"] * 1.05
                        ),
                    )
                )
            # the recover variant must not lose to notice-blind dada while
            # a notice window is open (the affinity-trap cost it removes)
            cp = cells[(churn, mode, NOTICE_W, "dada(a)+cp")]
            rec = cells[(churn, mode, NOTICE_W, "dada(a)+cp+rec")]
            checks.append(
                dict(
                    claim=(
                        f"C9 recover beats notice-blind dada: churn={churn:g} "
                        f"{mode}"
                    ),
                    measured=(
                        f"bytes {cp['total_bytes_mean'] / 1e9:.3f}->"
                        f"{rec['total_bytes_mean'] / 1e9:.3f}GB, slowdown "
                        f"{cp['slowdown_mean']:.3f}->{rec['slowdown_mean']:.3f}"
                    ),
                    passed=(
                        rec["total_bytes_mean"]
                        <= cp["total_bytes_mean"] * 1.02
                    ),
                )
            )
    return rows, checks


def main() -> int:
    t0 = time.perf_counter()
    rows, checks = run_matrix()

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    out_csv = RESULTS_DIR / "scenario_matrix.csv"
    flat = [
        {
            k: (f"{v[0]}..{v[1]}" if isinstance(v, list) else v)
            for k, v in r.items()
        }
        for r in rows
    ]
    with out_csv.open("w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=list(flat[0].keys()))
        w.writeheader()
        w.writerows(flat)
    update_bench_json("scenario_matrix", {"rows": rows, "claims": checks})

    print("\n== scenario-matrix claims ==")
    ok = True
    for c in checks:
        status = "PASS" if c["passed"] else "FAIL"
        ok = ok and c["passed"]
        print(f"  [{status}] {c['claim']}\n         measured: {c['measured']}")
    print(f"\ntotal wall-clock {time.perf_counter() - t0:.1f}s -> {out_csv}")
    if not ok and not BenchConfig.from_env().allow_fail:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
