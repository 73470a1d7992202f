"""Benchmark-harness behavior: empty sweeps, parallel run_many equivalence,
the scripts' ``REPRO_BENCH_*`` configuration and the serving decision gates."""
import sys
from functools import partial
from pathlib import Path

import pytest

from repro.configs.paper_machine import paper_machine
from repro.core import DADA, run_many
from repro.linalg.cholesky import cholesky_graph

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def test_sweep_empty_gpu_list_returns_no_rows(capsys):
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


def test_bench_config_parses_and_types():
    from benchmarks.common import BenchConfig

    assert BenchConfig.from_env({}) == BenchConfig()
    cfg = BenchConfig.from_env({
        "REPRO_BENCH_FAST": "1",
        "REPRO_BENCH_RUNS": "2",
        "REPRO_BENCH_GPUS": "2, 8,",
        "REPRO_BENCH_JOBS": "3",
        "REPRO_BENCH_ALLOW_FAIL": "1",
        "REPRO_SCHED_BACKEND": "jax",  # the library's, not the scripts'
        "UNRELATED": "ignored",
    })
    assert cfg == BenchConfig(
        fast=True, runs=2, gpus=(2, 8), jobs=3, allow_fail=True
    )
    # an empty GPU list is an empty sweep, not an error
    assert BenchConfig.from_env({"REPRO_BENCH_GPUS": ""}).gpus == ()


@pytest.mark.parametrize("var,bad", [
    ("REPRO_BENCH_RUNS", "many"),
    ("REPRO_BENCH_RUNS", "0"),
    ("REPRO_BENCH_JOBS", "x"),
    ("REPRO_BENCH_GPUS", "2,eight"),
    ("REPRO_BENCH_FAST", "yes"),
    ("REPRO_BENCH_ALLOW_FAIL", "2"),
])
def test_bench_config_rejects_malformed_values(var, bad):
    from benchmarks.common import BenchConfig

    with pytest.raises(ValueError, match=var):
        BenchConfig.from_env({var: bad})


def test_bench_config_rejects_unknown_vars():
    from benchmarks.common import BenchConfig

    # REPRO_BENCH_NT was read only by the retired host-timing benchmark
    for var in ("REPRO_BENCH_NT", "REPRO_BENCH_RUSN"):
        with pytest.raises(ValueError, match=var):
            BenchConfig.from_env({var: "1"})


@pytest.mark.parametrize("var", [
    "REPRO_SCHED_BACKENDS", "REPRO_SCHED_REGRESSION_TOL",
    "REPRO_SCHED_ROW_TOL", "REPRO_SCHED_TENANTS",
])
def test_sched_config_ignores_bench_vars_and_rejects_retired_names(var):
    from repro.sched import SchedConfig

    env = {"REPRO_BENCH_FAST": "1", "REPRO_BENCH_NT": "junk"}
    assert SchedConfig.from_env(env) == SchedConfig()
    with pytest.raises(ValueError, match=var):
        SchedConfig.from_env({var: "1"})


def test_serving_gate_on_committed_p99_rows_and_rows_built():
    import json

    import benchmarks.serving_load as sl

    committed = json.loads(sl.P99_BASELINE.read_text())
    assert len(committed) == 36
    probe = {"tenants": sl.PROBE_TENANTS, "rows_ratio": 154087 / 1882}
    rows = [dict(r) for r in committed]
    assert sl.gate(rows, probe, committed)
    # +26% on a single row's p99 slowdown fails the run
    rows[5]["p99_slowdown"] *= 1.26
    assert not sl.gate(rows, probe, committed)
    # a dirty-row cache that rebuilds every row fails the floor
    assert not sl.gate(committed, dict(probe, rows_ratio=1.0), committed)
    # rows measured in only one of run and baseline are skipped
    assert sl.gate(committed[:9], probe, committed[9:])


def test_bench_jobs_sets_pool_and_run_width(monkeypatch, tmp_path):
    """REPRO_BENCH_JOBS=2 gives the scripts a pool of width 2, and every
    sweep and run_many they start fans out over 2 workers."""
    from concurrent.futures import Future

    import benchmarks.common as common
    import repro.core.api as api

    class RecordingPool:
        widths = []
        submits = []

        def __init__(self, max_workers, mp_context=None):
            self.widths.append(max_workers)

        def submit(self, fn, *args):
            self.submits.append(fn.__name__)
            f = Future()
            f.set_result(fn(*args))
            return f

    monkeypatch.setenv("REPRO_SCHED_BACKEND", "numpy")
    monkeypatch.setattr("concurrent.futures.ProcessPoolExecutor", RecordingPool)
    monkeypatch.setattr(api, "_POOL", None)
    monkeypatch.setattr(common, "RESULTS_DIR", tmp_path)
    monkeypatch.setenv("REPRO_BENCH_JOBS", "2")
    monkeypatch.setenv("REPRO_BENCH_RUNS", "1")
    monkeypatch.setenv("REPRO_BENCH_GPUS", "2")

    runs, gpus, jobs = common.bench_settings()
    assert (runs, gpus, jobs) == (1, [2], 2)
    strategies = {k: common.STRATEGIES[k] for k in ("heft", "dada(a)")}
    rows = common.sweep("tmp_jobs", "cholesky", strategies, runs, gpus, jobs)
    assert len(rows) == 2
    assert RecordingPool.widths == [2]
    assert RecordingPool.submits == ["_sweep_config"] * 2
    gfac = partial(cholesky_graph, 4, 256, with_fns=False)
    run_many(gfac, paper_machine(2), partial(DADA, alpha=0.5), n_runs=4,
             n_jobs=jobs)
    assert RecordingPool.widths == [2]  # the one pool, reused
    assert RecordingPool.submits[2:] == ["_run_chunk"] * 2


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
