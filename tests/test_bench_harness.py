"""Benchmark-harness behavior: empty sweeps, parallel run_many equivalence,
and the scheduler-overhead reporting contract."""
from functools import partial

from repro.configs.paper_machine import paper_machine
from repro.core import DADA, run_many
from repro.linalg.cholesky import cholesky_graph


def test_sweep_empty_gpu_list_returns_no_rows(capsys):
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks.common import STRATEGIES, sweep

    rows = sweep("tmp_empty", "cholesky", STRATEGIES, 3, [])
    assert rows == []
    assert "empty sweep" in capsys.readouterr().out
    rows = sweep("tmp_empty", "cholesky", {}, 3, [2])
    assert rows == []


def test_run_many_parallel_matches_serial():
    machine = paper_machine(2)
    gfac = partial(cholesky_graph, 4, 256, with_fns=False)
    sfac = partial(DADA, alpha=0.5)
    serial = run_many(gfac, machine, sfac, n_runs=4, n_jobs=1)
    parallel = run_many(gfac, machine, sfac, n_runs=4, n_jobs=2)
    assert serial == parallel  # bit-identical summaries


def test_run_many_falls_back_on_unpicklable_factories():
    machine = paper_machine(2)
    local = {"n": 0}

    def gfac():
        local["n"] += 1  # closure: not picklable
        return cholesky_graph(4, 256, with_fns=False)

    s = run_many(gfac, machine, lambda: DADA(alpha=0.5), n_runs=2, n_jobs=2)
    assert s.n == 2
    assert local["n"] >= 1  # ran in-process


def test_sched_overhead_reports_events_per_sec(capsys, monkeypatch, tmp_path):
    import json
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    monkeypatch.setenv("REPRO_BENCH_GPUS", "2")
    monkeypatch.setenv("REPRO_BENCH_RUNS", "1")
    monkeypatch.setenv("REPRO_BENCH_LAMBDA", "0")  # skip the NT=64 micro
    monkeypatch.setenv("REPRO_SCHED_BACKENDS", "numpy")
    import benchmarks.common as common
    import benchmarks.sched_overhead as so

    out_json = tmp_path / "BENCH_sched.json"
    monkeypatch.setattr(common, "BENCH_JSON", out_json)
    rows = so.main()
    out = capsys.readouterr().out
    assert "events_per_s=" in out
    assert all(r["events"] > 0 for r in rows)
    assert {r["kernel"] for r in rows} == {
        "cholesky", "lu", "qr", "cholesky-x4stream"
    }
    # backend-free ws is measured once under the stable "none" label
    assert {r["backend"] for r in rows} == {"numpy", "none"}
    assert all(
        r["backend"] == "none" for r in rows if r["strategy"] == "ws"
    )
    # the eviction path has its own capacity-bounded rows (gated by key)
    cap_rows = [r for r in rows if r["capacity"]]
    assert {r["strategy"] for r in cap_rows} == set(
        so.CAPACITY_ROW_STRATEGIES
    )
    assert all(r["capacity"] == so.CAPACITY_ROW_BYTES for r in cap_rows)
    # the 4-tenant streaming row reports per-graph makespans
    (stream,) = [r for r in rows if r["kernel"] == "cholesky-x4stream"]
    assert len(stream["per_graph_makespans"]) == 4
    assert all(m > 0 for m in stream["per_graph_makespans"])
    # the fault path has its own churned rows: both recovery modes, keyed
    # apart from the fault-free rows by the (churn, fault_mode) fields
    churned = [r for r in rows if r["churn"]]
    assert {(r["strategy"], r["fault_mode"]) for r in churned} == {
        (s, m) for s in so.CHURN_STRATEGIES for m in ("drain", "kill")
    }
    assert all(r["churn"] == so.CHURN_RATE for r in churned)
    assert all(r["fault_mode"] == "drain" and r["churn"] == 0.0
               for r in rows if r not in churned)
    # machine-readable perf trajectory (BENCH_sched.json satellite)
    doc = json.loads(out_json.read_text())
    sec = doc["sched_overhead"]
    assert sec["calibration_score"] > 0
    assert len(sec["whole_sim"]) == len(rows)
    assert {"kernel", "strategy", "backend", "nt", "capacity",
            "events_per_s", "wall_s"} <= set(sec["whole_sim"][0])


def test_sched_regression_gate(monkeypatch, tmp_path, capsys):
    """The CI gate fails on a >25% events/sec drop after machine-speed
    calibration, and passes when throughput merely tracks machine speed."""
    import json
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    import benchmarks.check_sched_regression as gate

    def write(path, cal, evs):
        path.write_text(json.dumps({
            "sched_overhead": {
                "calibration_score": cal,
                "whole_sim": [{
                    "kernel": "cholesky", "strategy": "heft",
                    "backend": "numpy", "nt": 16, "n_gpus": 8,
                    "events_per_s": evs,
                }],
            }
        }))

    cur = tmp_path / "cur.json"
    base = tmp_path / "base.json"
    monkeypatch.setattr(gate, "CURRENT", cur)
    monkeypatch.setattr(gate, "BASELINE", base)

    # a slower machine (half calibration) with proportional events/sec: OK
    write(base, 1000.0, 50000.0)
    write(cur, 500.0, 25500.0)
    assert gate.main() == 0
    # a >25% real regression on the same machine: FAIL
    write(cur, 1000.0, 36000.0)
    assert gate.main() == 1
    # missing baseline: skipped, not failed
    base.unlink()
    assert gate.main() == 0
    capsys.readouterr()


def test_jax_work_never_forks(monkeypatch):
    """A forked worker never touches JAX: jax-scored strategies, and any
    run once this process holds an accelerator, stay in-process (with
    summaries identical to the pool's)."""
    import repro.core.backend as backend_mod
    from repro.core import pool_allowed

    assert pool_allowed([partial(DADA, alpha=0.5)])
    assert not pool_allowed([partial(DADA, alpha=0.5, backend="jax")])
    monkeypatch.setenv("REPRO_SCHED_BACKEND", "jax")
    assert not pool_allowed([partial(DADA, alpha=0.5)])
    monkeypatch.delenv("REPRO_SCHED_BACKEND")
    monkeypatch.setattr(backend_mod, "accelerator_initialised", lambda: True)
    assert not pool_allowed([partial(DADA, alpha=0.5)])

    machine = paper_machine(2)
    gfac = partial(cholesky_graph, 4, 256, with_fns=False)
    sfac = partial(DADA, alpha=0.5)
    pools = []
    monkeypatch.setattr("repro.core.api._get_pool", pools.append)
    assert run_many(gfac, machine, sfac, n_runs=4, n_jobs=2) == run_many(
        gfac, machine, sfac, n_runs=4, n_jobs=1
    )
    assert pools == []  # the pool was never asked for
