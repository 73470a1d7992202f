"""Open-loop serving benchmark: multi-tenant load on one live engine.

Sweeps tenant count × arrival process × strategy over the mixed graph
catalog (``repro.runtime.load.default_catalog``), driving every
configuration through ``run_serving`` with incremental rescoring.  Each
row reports

  * rows built, and events/sec and wall seconds (host time: printed as
    information, never gated), and
  * tenant-visible tails — p50/p99 makespan and slowdown vs the
    empty-machine baseline, queueing delay, Jain fairness — plus the
    admission counters,

into the ``serving`` section of ``results/BENCH_sched.json``.

The **speedup probe** replays one arrival stream at 256 tenants twice in
this process — once with ``rescore="full"`` (rebuild every row, every
round: the naive O(R·M) baseline) and once with
``rescore="incremental"`` (dirty rows only) — both capped at the same
event count.  The two modes place bit-for-bit identically
(tests/test_load_property.py pins this), so the ratio of rows built is
the scoring work the incremental cache elides.

Two gates judge decisions, not host time, and the script exits 1 when
either fails:

  * each row's p99 slowdown — simulated time, so deterministic — may not
    grow past ``P99_SLOWDOWN_TOL`` over its committed value in
    ``results/serving_p99_baseline.json``;
  * the probe's full/incremental ``rows_built`` ratio must clear
    ``SPEEDUP_FLOOR`` (a cache that rebuilds every row reads ≈1).

Knobs: REPRO_BENCH_FAST=1 drops the 1024-tenant column.  The arrival
rate is fixed at 2000 arrivals/sec — deep open-loop backlog at every
swept tenant count, so the scheduler (not the load generator) is what
gets measured.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

if __package__ in (None, ""):
    _repo = Path(__file__).resolve().parents[1]
    for p in (str(_repo), str(_repo / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)

from benchmarks.common import RESULTS_DIR, BenchConfig, update_bench_json

TENANTS_FULL = (16, 64, 256, 1024)
TENANTS_FAST = (16, 64, 256)
ARRIVALS = ("poisson", "bursty", "diurnal")
STRATEGIES = ("heft", "dada?alpha=0.5&use_cp=1", "wfq")
# labels keep the regression key readable and stable across spec tweaks
STRATEGY_LABELS = {
    "heft": "heft",
    "dada?alpha=0.5&use_cp=1": "dada(a)+cp",
    "wfq": "wfq",
}
DEFAULT_RATE = 2000.0
# speedup probe: both rescore modes replay this many events of the same
# arrival stream — large enough that steady-state dirty-row behavior
# dominates, small enough that the full-rescore pass stays affordable
PROBE_EVENTS = 4000
# the probe runs at a fixed 256 tenants (present in fast and full sweeps
# alike): deep enough backlog that scoring dominates, small enough that
# the ready pool fits the cache's sweet spot — at 1024 tenants the pool
# itself (heap churn, dirty fan-out) eats into the win (≈2× vs ≈9×)
PROBE_TENANTS = 256
# the committed p99 slowdown of every (tenants, arrival, strategy) row
P99_BASELINE = RESULTS_DIR / "serving_p99_baseline.json"
# a row fails when its p99 slowdown exceeds the committed one by this much
P99_SLOWDOWN_TOL = 0.25
# floor on the probe's full/incremental rows_built ratio: deterministic,
# so host noise cannot fail it, while a cache that rebuilds every row
# (ratio ≈ 1) always does
SPEEDUP_FLOOR = 1.5


def serving_rows(tenant_counts, rate: float) -> list:
    from repro.configs.paper_machine import paper_machine
    from repro.runtime.load import make_arrivals, run_serving

    machine = paper_machine(4)
    rows = []
    # slowdown denominators are per (strategy, kind): share them across
    # the sweep so each is computed once
    baselines = {spec: {} for spec in STRATEGIES}
    for tenants in tenant_counts:
        for arrival in ARRIVALS:
            arr = make_arrivals(arrival, tenants, rate=rate, seed=7)
            for spec in STRATEGIES:
                label = STRATEGY_LABELS[spec]
                # best-of-2: a transient stall must not record a phantom
                # slowdown into the perf trajectory (simulated results
                # are seeded — repetitions reproduce the same schedule)
                dt = float("inf")
                out = None
                for _rep in range(2):
                    t0 = time.perf_counter()
                    out = run_serving(
                        arr, machine, spec, seed=0,
                        rescore="incremental",
                        baselines=baselines[spec],
                    )
                    dt = min(dt, time.perf_counter() - t0)
                rep = out["report"]
                row = dict(
                    tenants=tenants, arrival=arrival, strategy=label,
                    rescore="incremental", rate=rate,
                    wall_s=round(dt, 4), events=out["n_events"],
                    events_per_s=(
                        round(out["n_events"] / dt, 1) if dt > 0 else 0.0
                    ),
                    rows_built=out["rows_built"],
                    n_admitted=out["n_admitted"],
                    n_rejected=out["n_rejected"],
                    p50_makespan=rep["p50_makespan"],
                    p99_makespan=rep["p99_makespan"],
                    p50_slowdown=rep["p50_slowdown"],
                    p99_slowdown=rep["p99_slowdown"],
                    p50_queue_delay=rep["p50_queue_delay"],
                    p99_queue_delay=rep["p99_queue_delay"],
                    mean_slowdown=rep["mean_slowdown"],
                    jain_fairness=rep["jain_fairness"],
                )
                rows.append(row)
                print(
                    f"serving/{arrival}/{label}/tenants{tenants},"
                    f"{dt * 1e6:.1f},"
                    f"events_per_s={row['events_per_s']};"
                    f"p99_slowdown={row['p99_slowdown']:.2f};"
                    f"jain={row['jain_fairness']:.3f}"
                )
    return rows


def speedup_probe(tenants: int, rate: float) -> dict:
    """Full-rescore vs incremental on the same arrival stream, same
    process, same event cap: rows built (gated) and events/sec."""
    from repro.configs.paper_machine import paper_machine
    from repro.runtime.load import make_arrivals, run_serving

    machine = paper_machine(4)
    arr = make_arrivals("poisson", tenants, rate=rate, seed=7)
    probe = {}
    for mode in ("full", "incremental"):
        dt = float("inf")
        out = None
        for _rep in range(2):
            t0 = time.perf_counter()
            out = run_serving(
                arr, machine, "heft", seed=0,
                rescore=mode, max_events=PROBE_EVENTS,
            )
            dt = min(dt, time.perf_counter() - t0)
        probe[mode] = dict(
            wall_s=round(dt, 4), events=out["n_events"],
            events_per_s=round(out["n_events"] / dt, 1) if dt > 0 else 0.0,
            rows_built=out["rows_built"],
        )
    full_ev = probe["full"]["events_per_s"]
    incr_ev = probe["incremental"]["events_per_s"]
    speedup = round(incr_ev / full_ev, 2) if full_ev > 0 else 0.0
    full_rows = probe["full"]["rows_built"]
    incr_rows = probe["incremental"]["rows_built"]
    rows_ratio = full_rows / max(incr_rows, 1)
    result = dict(
        tenants=tenants, arrival="poisson", strategy="heft",
        max_events=PROBE_EVENTS, rate=rate,
        full=probe["full"], incremental=probe["incremental"],
        speedup=speedup, rows_ratio=round(rows_ratio, 2),
    )
    print(
        f"serving/speedup/tenants{tenants},"
        f"{probe['incremental']['wall_s'] * 1e6:.1f},"
        f"rows_built={full_rows}/{incr_rows};rows_ratio={rows_ratio:.2f}x;"
        f"incremental={incr_ev};full={full_ev};speedup={speedup}x"
    )
    return result


def gate(rows: list, probe: dict, baseline: list) -> bool:
    """The decision gates: each row's p99 slowdown against ``baseline``
    (rows absent from either side are skipped) and the probe's
    rows-built floor. True when both hold."""
    committed = {
        (b["tenants"], b["arrival"], b["strategy"]): b["p99_slowdown"]
        for b in baseline
    }
    ok = True
    for row in rows:
        key = (row["tenants"], row["arrival"], row["strategy"])
        base = committed.get(key)
        if base is None:
            continue
        if row["p99_slowdown"] > base * (1.0 + P99_SLOWDOWN_TOL):
            ok = False
            print(
                f"  [FAIL] serving p99 slowdown {'/'.join(map(str, key))}: "
                f"{row['p99_slowdown']:.2f} vs committed {base:.2f} "
                f"(limit +{P99_SLOWDOWN_TOL:.0%})"
            )
    ratio = probe["rows_ratio"]
    mark = "ok  " if ratio >= SPEEDUP_FLOOR else "FAIL"
    print(
        f"  [{mark}] incremental rescoring builds {ratio:.2f}x fewer rows "
        f"than full at {probe['tenants']} tenants (floor {SPEEDUP_FLOOR:.1f}x)"
    )
    return ok and ratio >= SPEEDUP_FLOOR


def main() -> int:
    fast = BenchConfig.from_env().fast
    tenant_counts = list(TENANTS_FAST if fast else TENANTS_FULL)
    rate = DEFAULT_RATE

    print("name,us_per_call,derived")
    rows = serving_rows(tenant_counts, rate)
    probe = speedup_probe(PROBE_TENANTS, rate)
    update_bench_json("serving", dict(
        config=dict(tenants=tenant_counts, arrivals=list(ARRIVALS),
                    strategies=list(STRATEGY_LABELS.values()), rate=rate),
        rows=rows,
        speedup=probe,
    ))
    if not gate(rows, probe, json.loads(P99_BASELINE.read_text())):
        print("serving gate FAILED")
        return 1
    print("serving gate passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
