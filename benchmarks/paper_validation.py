"""Validate the reproduction against the paper's experimental claims
(C1-C6) plus the two runtime-extension claims: C7 (transfer-volume gap
under memory pressure) and C8 (transfer volume and recovery under GPU
churn). Consumes the rows produced by the fig1-fig4 benchmarks and
prints a PASS/FAIL table; quantitative factors are reported as measured.

Runnable directly: ``REPRO_BENCH_FAST=1 python benchmarks/paper_validation.py``
executes the fig1-fig4 sweeps (honouring the REPRO_BENCH_* knobs, see
common.py) and then the claim checks, printing total wall-clock at the end.
"""
from __future__ import annotations

import sys
from functools import partial
from pathlib import Path
from typing import Dict, List, Optional

if __package__ in (None, ""):  # `python benchmarks/paper_validation.py`
    _repo = Path(__file__).resolve().parents[1]
    for p in (str(_repo), str(_repo / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)

from repro.configs.paper_machine import paper_machine
from repro.core import run_many
from repro.sched import resolve
from repro.linalg.cholesky import cholesky_graph


def _get(rows: List[dict], strategy: str, n_gpus: int, field: str):
    for r in rows:
        if r["strategy"] == strategy and r["n_gpus"] == n_gpus:
            return r[field]
    raise KeyError((strategy, n_gpus, field))


def _verify_sim(sim) -> int | None:
    """Error count from the independent schedule verifier, or ``None``
    when the run was not audited (``REPRO_SCHED_AUDIT`` off)."""
    if sim.audit is None:
        return None
    from repro.verify import errors, verify_audit

    return len(errors(verify_audit(sim.audit)))


def validate(
    fig1: List[dict], fig2: List[dict], fig3: List[dict], fig4: List[dict],
    n_runs: int = 10, jobs: Optional[int] = None,
) -> List[dict]:
    checks: List[dict] = []
    if not (fig1 and fig2 and fig3 and fig4):
        # empty sweeps (e.g. REPRO_BENCH_GPUS=""): nothing to validate
        # against; C6 below runs its own simulations, so keep only that
        print("  (figure sweeps empty — skipping row-based claims C1-C5)")
        return _validate_c6(checks, n_runs, jobs)
    gpus = sorted({r["n_gpus"] for r in fig1})
    lo, hi = gpus[0], gpus[-1]

    # C1 — DADA(0) without CP stops scaling with many GPUs -----------------
    try:
        s0 = _get(fig1, "dada(0)", hi, "gflops") / _get(fig1, "dada(0)", lo, "gflops")
        s1 = _get(fig1, "dada(1)", hi, "gflops") / _get(fig1, "dada(1)", lo, "gflops")
        checks.append(
            dict(
                claim="C1 dada(0) scales worse than dada(1)",
                measured=f"speedup {lo}->{hi} gpus: dada(0) {s0:.2f}x vs dada(1) {s1:.2f}x",
                passed=s0 < s1,
            )
        )
    except KeyError:
        pass

    # C2 — higher alpha scales better --------------------------------------
    try:
        perf = [(_a, _get(fig1, f"dada({_a:g})", hi, "gflops")) for _a in (0.25, 0.5, 0.75, 1.0)]
        checks.append(
            dict(
                claim="C2 higher alpha => better at max gpus",
                measured="; ".join(f"a={a:g}:{g:.0f}GF" for a, g in perf),
                passed=perf[-1][1] >= perf[0][1],
            )
        )
    except KeyError:
        pass

    # C3 — LU: DADA(a)+CP moves much less data than HEFT -------------------
    heft_gb = _get(fig3, "heft", hi, "gbytes")
    dada_gb = _get(fig3, "dada(a)+cp", hi, "gbytes")
    heft_gf = _get(fig3, "heft", hi, "gflops")
    dada_gf = _get(fig3, "dada(a)+cp", hi, "gflops")
    factor = heft_gb / dada_gb
    slow = heft_gf / dada_gf
    checks.append(
        dict(
            claim="C3 LU: dada(a)+cp lowest transfers (paper: 3.5x, ~1.13x slowdown)",
            measured=f"transfer factor {factor:.2f}x, perf ratio {slow:.2f}x",
            passed=factor > 1.0 and slow < 1.25,
        )
    )

    # C4 — QR: HEFT outperforms every dual-approximation variant -----------
    duals = ["dada(0)", "dada(a)", "dada(a)+cp"]
    heft_qr = _get(fig4, "heft", hi, "gflops")
    worst = max(_get(fig4, d, hi, "gflops") for d in duals)
    checks.append(
        dict(
            claim="C4 QR: HEFT >= all dual approximations",
            measured=f"heft {heft_qr:.0f}GF vs best dual {worst:.0f}GF",
            passed=heft_qr >= worst * 0.97,
        )
    )

    # C5 — Cholesky: DADA(a) within range of HEFT (similar performance) ----
    heft_ch = _get(fig2, "heft", hi, "gflops")
    dada_ch = _get(fig2, "dada(a)", hi, "gflops")
    checks.append(
        dict(
            claim="C5 Cholesky: dada(a) ~ heft at max gpus",
            measured=f"dada(a) {dada_ch:.0f}GF vs heft {heft_ch:.0f}GF",
            passed=dada_ch >= heft_ch * 0.8,
        )
    )

    return _validate_c6(checks, n_runs, jobs)


def _validate_c6(checks: List[dict], n_runs: int, jobs: Optional[int]) -> List[dict]:
    # C6 — work stealing is cache-unfriendly on small matrices -------------
    machine = paper_machine(4)
    small = partial(cholesky_graph, 8, 512, with_fns=False)  # 4096^2
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=2) as tp:
        ws_f = tp.submit(
            run_many, small, machine, partial(resolve, "ws"), n_runs, n_jobs=jobs
        )
        da_f = tp.submit(
            run_many, small, machine, partial(resolve, "dada?alpha=0.5"), n_runs,
            n_jobs=jobs,
        )
        ws, da = ws_f.result(), da_f.result()
    checks.append(
        dict(
            claim="C6 small matrix: affinity beats work stealing",
            measured=f"ws {ws.gflops_mean:.0f}GF/{ws.gbytes_mean:.2f}GB vs "
            f"dada(a) {da.gflops_mean:.0f}GF/{da.gbytes_mean:.2f}GB",
            passed=da.gflops_mean > ws.gflops_mean,
        )
    )
    return _validate_c7(checks)


_MB = 1024 * 1024
# capacity sweep points: unbounded (0) down to 32 MB per GPU memory — the
# regime the paper's 2014 hardware forced (a handful of tiles per device)
C7_CAPACITIES = (0, 128 * _MB, 64 * _MB, 32 * _MB)


def capacity_sweep(capacities=C7_CAPACITIES) -> List[dict]:
    """Total transferred bytes of HEFT vs DADA(a)+CP on the Cholesky NT=16
    paper trace as device-memory capacity shrinks.

    The trace is deterministic (noise=0, fixed seed, affinity eviction)
    so the sweep isolates the *eviction/write-back* traffic — the cost
    Kumar et al. measure on real GPUs — from duration noise. One graph
    object is shared: the simulator never mutates it.
    """
    from repro.core import Simulator

    machine = paper_machine(8)
    graph = cholesky_graph(16, 512, with_fns=False)
    rows = []
    for cap in capacities:
        row = dict(capacity=cap)
        for label, spec in (("heft", "heft"), ("dada", "dada?alpha=0.5&use_cp=1")):
            sim = Simulator(
                graph, machine, resolve(spec), seed=0, noise=0.0,
                mem_capacity=cap, eviction="affinity",
            )
            res = sim.run()
            row[label] = res.total_bytes
            row[f"{label}_writeback"] = sim.metrics.writeback_bytes
            ve = _verify_sim(sim)
            if ve is not None:
                row[f"{label}_verify_errors"] = ve
        row["gap"] = row["heft"] - row["dada"]
        rows.append(row)
    return rows


def _validate_c7(checks: List[dict]) -> List[dict]:
    # C7 — the paper's Fig. 5 story under memory pressure: DADA moves no
    # more data than HEFT at every capacity point, and its advantage (the
    # transfer-volume gap) widens monotonically as capacity drops — the
    # affinity phase keeps working sets where they already live, so it
    # pays less eviction/write-back traffic.
    rows = capacity_sweep()
    le_everywhere = all(r["dada"] <= r["heft"] for r in rows)
    gaps = [r["gap"] for r in rows]
    non_shrinking = all(b >= a for a, b in zip(gaps, gaps[1:]))

    def _cap(c):
        return "inf" if c == 0 else f"{c // _MB}MB"

    checks.append(
        dict(
            claim="C7 capacity sweep: DADA bytes <= HEFT, gap non-shrinking as memory shrinks",
            measured="; ".join(
                f"{_cap(r['capacity'])}: heft {r['heft'] / 1e9:.3f}GB "
                f"dada {r['dada'] / 1e9:.3f}GB (gap {r['gap'] / 1e6:+.1f}MB)"
                for r in rows
            ),
            passed=le_everywhere and non_shrinking,
            rows=rows,
        )
    )
    return _validate_c8(checks)


# C8 fault script, as fractions of each strategy's own clairvoyant
# baseline makespan: lose 2 of the 8 GPUs mid-run (one graceful drain,
# one hard kill), get one back late
C8_FAULTS = ((0.25, "detach", 0, "drain"), (0.40, "detach", 1, "kill"),
             (0.60, "attach", 0, None))


def fault_recovery_runs() -> Dict[str, dict]:
    """HEFT vs DADA(a)+CP through the C8 fault script on the deterministic
    Cholesky NT=16 paper trace (seed 0, noise 0): per strategy, a
    clairvoyant no-fault baseline and the faulted run, reduced to the
    recovery report (makespan the faults cost, extra transferred bytes,
    evacuation/requeue counters)."""
    from repro.core import Simulator
    from repro.runtime import recovery_report

    graph = cholesky_graph(16, 512, with_fns=False)
    out = {}
    for label, spec in (("heft", "heft"), ("dada", "dada?alpha=0.5&use_cp=1")):
        base = Simulator(
            graph, paper_machine(8), resolve(spec), seed=0, noise=0.0
        ).run()
        sim = Simulator(
            graph, paper_machine(8), resolve(spec), seed=0, noise=0.0
        )
        gpus = [r.rid for r in sim.machine.gpus]
        for frac, event, gi, mode in C8_FAULTS:
            sim.inject(event, gpus[gi], at=base.makespan * frac, mode=mode)
        res = sim.run()
        out[label] = dict(
            recovery_report(res, base),
            bytes=res.total_bytes, baseline_bytes=base.total_bytes,
        )
        ve = _verify_sim(sim)
        if ve is not None:
            out[label]["verify_errors"] = ve
    return out


def _validate_c8(checks: List[dict]) -> List[dict]:
    # C8 — the paper's transfer-volume story survives resource churn: with
    # 2 of 8 GPUs detached mid-run (and one reattached), the affinity
    # criterion still moves no more data than HEFT — recovery re-transfers
    # and evacuations included — and both recover to completion.
    reps = fault_recovery_runs()
    dada_le = reps["dada"]["bytes"] <= reps["heft"]["bytes"]
    both_recover = all(
        r["slowdown"] > 0 and r["n_detaches"] == 2 for r in reps.values()
    )
    checks.append(
        dict(
            claim="C8 GPU churn: DADA bytes <= HEFT through detach/reattach, both recover",
            measured="; ".join(
                f"{k}: {r['bytes'] / 1e9:.3f}GB ({r['extra_bytes'] / 1e6:+.1f}MB "
                f"over no-fault), recovery +{r['recovery_makespan'] * 1e3:.2f}ms "
                f"({r['slowdown']:.2f}x), evac {r['evacuated_bytes'] / 1e6:.1f}MB, "
                f"requeued {r['n_requeued']:.0f}"
                for k, r in reps.items()
            ),
            passed=dada_le and both_recover,
            rows=reps,
        )
    )
    return _validate_verified(checks)


def _validate_verified(checks: List[dict]) -> List[dict]:
    # CV — with REPRO_SCHED_AUDIT=1, every claim schedule above is also
    # replayed through the independent verifier (repro.verify): the
    # run_simulation hook already hard-fails the fig1-fig4 sweeps on any
    # invariant violation, so here we re-run the claim strategies on the
    # C7/C8 trace with an explicit audit and report the error counts, and
    # do the same for the surrogate engine via emit_schedule.
    from repro.sched import current_config

    if not current_config().audit:
        return checks

    from repro.core import Simulator
    from repro.verify import errors, verify_audit

    graph = cholesky_graph(16, 512, with_fns=False)
    machine = paper_machine(8)
    parts, n_err = [], 0
    for spec in ("heft", "dada?alpha=0.5&use_cp=1", "ws"):
        sim = Simulator(
            graph, machine, resolve(spec), seed=0, noise=0.0, audit=True
        )
        sim.run()
        e = len(errors(verify_audit(sim.audit)))
        n_err += e
        parts.append(f"{spec}: {e} err")
    checks.append(
        dict(
            claim="CV exact-engine claim schedules pass the independent verifier",
            measured="; ".join(parts),
            passed=n_err == 0,
        )
    )

    import numpy as np

    from repro.core import episode as ep

    max_mem = max(r.mem for r in machine.resources if r.is_accelerator)
    plan = ep.build_plan(graph, machine, n_u=max_mem + 2)
    ig, vl, mc, lg = ep.machine_axes(machine, plan.n_res)
    specs = ("heft", "dada?alpha=0.5&use_cp=1", "ws")
    params = [ep.surrogate_params(s) for s in specs]
    B = len(specs)
    batch = ep.EpisodeBatch(
        is_gpu=np.stack([ig] * B), valid_res=np.stack([vl] * B),
        mem_col=np.stack([mc] * B), link_grp=np.stack([lg] * B),
        alpha=np.array([p[0] for p in params]),
        use_cp=np.array([p[1] for p in params]),
        ws_pref=np.array([p[2] for p in params], dtype=bool),
        noise=np.stack(
            [ep.noise_factors(0, 0.0, plan.n, plan.n_pad)] * B
        ),
        cap=np.full(B, np.inf),
    )
    out = ep.run_episodes(plan, batch, emit_schedule=True)
    parts, n_err = [], 0
    for spec, log in zip(specs, ep.episode_audit_logs(graph, batch, out)):
        e = len(errors(verify_audit(log)))
        n_err += e
        parts.append(f"{spec}: {e} err")
    checks.append(
        dict(
            claim="CV surrogate claim schedules pass the independent verifier",
            measured="; ".join(parts),
            passed=n_err == 0,
        )
    )
    return checks


def print_checks(checks: List[dict]) -> bool:
    ok = True
    print("\n== paper-claim validation ==")
    for c in checks:
        status = "PASS" if c["passed"] else "FAIL"
        ok &= c["passed"]
        print(f"  [{status}] {c['claim']}\n         measured: {c['measured']}")
    return ok


def main() -> bool:
    """Run the fig1-fig4 sweeps and validate the paper claims end-to-end.

    The four sweeps run on threads: each one mostly blocks on shared
    process-pool futures, so overlapping them keeps the pool saturated
    from the first configuration to the last (progress lines interleave
    across figures; CSVs and returned rows are per-figure as before).
    """
    import importlib
    import time
    from concurrent.futures import ThreadPoolExecutor

    from repro.core import get_pool

    from benchmarks.common import BenchConfig

    bench = BenchConfig.from_env()
    t0 = time.perf_counter()
    # create the shared process pool from the main thread, before any sweep
    # threads exist (fork-after-threads can deadlock forked children)
    get_pool(bench.jobs)
    mods = [
        importlib.import_module(f"benchmarks.{m}")
        for m in ("fig1_alpha_sweep", "fig2_cholesky", "fig3_lu", "fig4_qr")
    ]
    with ThreadPoolExecutor(max_workers=len(mods)) as tp:
        figs = [f.result() for f in [tp.submit(m.main) for m in mods]]
    checks = validate(*figs, jobs=bench.jobs)
    ok = print_checks(checks)
    wall = time.perf_counter() - t0
    print(f"\ntotal wall-clock: {wall:.2f}s")

    # record the run in results/BENCH_sched.json (see benchmarks/README.md)
    from repro.sched import current_config

    from benchmarks.common import update_bench_json

    cfg = current_config()
    update_bench_json(
        # the surrogate run owns its own section so the trajectory file
        # keeps both walls (exact oracle vs REPRO_SCHED_EXACT=0) side by
        # side for the speedup record
        "paper_validation" if cfg.exact else "paper_validation_surrogate",
        dict(
            wall_s=round(wall, 2),
            backend=cfg.backend,
            fast=bench.fast,
            exact=cfg.exact,
            claims=[
                dict(claim=c["claim"], passed=bool(c["passed"]),
                     measured=c["measured"])
                for c in checks
            ],
            figures={
                name: rows
                for name, rows in zip(("fig1", "fig2", "fig3", "fig4"), figs)
            },
        ),
    )
    return ok


if __name__ == "__main__":
    ok = main()
    if not ok:
        print("WARNING: some paper claims did not reproduce — see above", file=sys.stderr)
        # gate CI on claim regressions; REPRO_BENCH_ALLOW_FAIL=1 opts out
        # (e.g. deliberately tiny smoke configurations on noisy runners)
        from benchmarks.common import BenchConfig

        if not BenchConfig.from_env().allow_fail:
            sys.exit(1)
