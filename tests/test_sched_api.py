"""The ``repro.sched`` policy API: registry resolution, typed SchedConfig,
back-compat shims, and the two new score-matrix policies.

The back-compat contract is the load-bearing part: ``make_strategy`` /
string specs in ``run_simulation`` must warn *and* produce placements
bit-identical to ``repro.sched.resolve`` — the redesign moves construction,
never decisions.
"""
import numpy as np
import pytest

import repro.sched as sched
from repro.configs.paper_machine import paper_machine
from repro.core import Simulator, make_strategy, run_simulation
from repro.linalg.cholesky import cholesky_graph
from repro.sched import (
    LocalityPolicy,
    Policy,
    RandomPolicy,
    SchedConfig,
    assign_from_scores,
    register,
    registered,
    resolve,
    unregister,
)
from repro.sched.config import _reset_config_cache


def _fingerprint(res):
    return (
        res.makespan,
        res.total_bytes,
        res.n_transfers,
        res.n_steals,
        tuple(sorted(res.busy.items())),
        tuple((iv.tid, iv.rid, iv.start, iv.end) for iv in res.intervals),
    )


# ---------------------------------------------------------------------------
# back-compat shims


LEGACY_NAMES = ["heft", "ws", "dual", "dada"]


@pytest.mark.parametrize("name", LEGACY_NAMES)
def test_make_strategy_shim_bit_identical(name):
    """Cholesky NT=16 trace: the deprecated shim and the registry build
    strategies whose full placement trace is bit-identical."""
    machine = paper_machine(4)
    with pytest.warns(DeprecationWarning, match="make_strategy"):
        legacy = make_strategy(name)
    a = run_simulation(
        cholesky_graph(16, 256, with_fns=False), machine, legacy, seed=0
    )
    b = run_simulation(
        cholesky_graph(16, 256, with_fns=False), machine, resolve(name), seed=0
    )
    assert _fingerprint(a) == _fingerprint(b)


@pytest.mark.parametrize("name", LEGACY_NAMES)
def test_run_simulation_string_shim(name):
    machine = paper_machine(2)
    with pytest.warns(DeprecationWarning, match="deprecated"):
        a = run_simulation(
            cholesky_graph(6, 256, with_fns=False), machine, name, seed=1
        )
    b = run_simulation(
        cholesky_graph(6, 256, with_fns=False), machine, resolve(name), seed=1
    )
    assert _fingerprint(a) == _fingerprint(b)


def test_make_strategy_kwargs_match_query_spec():
    machine = paper_machine(3)
    with pytest.warns(DeprecationWarning):
        legacy = make_strategy("dada", alpha=0.25, use_cp=True)
    spec = resolve("dada?alpha=0.25&use_cp=1")
    assert (legacy.alpha, legacy.use_cp) == (spec.alpha, spec.use_cp)
    a = run_simulation(cholesky_graph(6, 256, with_fns=False), machine, legacy, seed=2)
    b = run_simulation(cholesky_graph(6, 256, with_fns=False), machine, spec, seed=2)
    assert _fingerprint(a) == _fingerprint(b)


def test_make_strategy_unknown_name_keeps_error_shape():
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError, match="unknown strategy 'nope'"):
            make_strategy("nope")


# ---------------------------------------------------------------------------
# registry


def test_registered_names_include_builtins():
    names = registered()
    for expected in ("heft", "dada", "dual", "ws", "random", "locality"):
        assert expected in names


def test_duplicate_registration_rejected():
    with pytest.raises(ValueError, match="already registered"):
        register("heft", lambda: None)
    # explicit overwrite is allowed, and undone cleanly
    class Fake:
        name = "fake-heft"

    original = sched.get_factory("heft")
    try:
        register("heft", Fake, overwrite=True)
        assert sched.get_factory("heft") is Fake
    finally:
        register("heft", original, overwrite=True)


def test_register_decorator_and_unregister():
    @register("test-custom-policy")
    class Custom:
        name = "custom"

    try:
        assert "test-custom-policy" in registered()
        assert isinstance(resolve("test-custom-policy"), Custom)
    finally:
        unregister("test-custom-policy")
    assert "test-custom-policy" not in registered()
    with pytest.raises(ValueError, match="unknown policy"):
        resolve("test-custom-policy")


def test_query_string_kwargs_parsed_and_typed():
    s = resolve("dada?alpha=0.25&use_cp=1&max_iters=12&affinity=all_resident")
    assert s.alpha == 0.25 and isinstance(s.alpha, float)
    assert s.use_cp is True
    assert s.max_iters == 12 and isinstance(s.max_iters, int)
    assert s.affinity_name == "all_resident"
    s2 = resolve("dada?use_cp=false")
    assert s2.use_cp is False
    s3 = resolve("random?seed=9")
    assert s3.seed == 9 and isinstance(s3.seed, int)


def test_query_string_errors_are_loud():
    with pytest.raises(ValueError, match="not a number"):
        resolve("dada?alpha=banana")
    with pytest.raises(ValueError, match="not a boolean"):
        resolve("dada?use_cp=maybe")
    with pytest.raises(ValueError, match="unknown parameter"):
        resolve("dada?frobnicate=1")
    with pytest.raises(ValueError, match="unknown policy"):
        resolve("does-not-exist")


def test_resolve_passes_policies_through():
    s = resolve("heft")
    assert resolve(s) is s


def test_resolve_forwards_backend_only_where_accepted():
    s = resolve("heft", backend="numpy")
    assert s.backend_name == "numpy"
    # ws takes no backend parameter: the kwarg must not explode
    resolve("ws", backend="numpy")


# ---------------------------------------------------------------------------
# SchedConfig


def test_sched_config_from_env_defaults():
    cfg = SchedConfig.from_env(env={})
    assert cfg.backend == "numpy"
    assert cfg.jax_min == 32
    assert cfg.lambda_depth is None


def test_sched_config_parses_and_types(monkeypatch):
    cfg = SchedConfig.from_env(
        env={
            "REPRO_SCHED_BACKEND": "jax",
            "REPRO_SCHED_JAX_MIN": "4",
            "REPRO_SCHED_LAMBDA_DEPTH": "3",
            "REPRO_SCHED_AUDIT": "1",
            "REPRO_SCHED_CHURN": "2.5",
            "REPRO_SCHED_RESCORE": "Incremental",
            "UNRELATED": "ignored",
        }
    )
    assert cfg.backend == "jax"
    assert cfg.jax_min == 4
    assert cfg.lambda_depth == 3
    assert cfg.audit is True
    assert cfg.churn == 2.5
    assert cfg.rescore == "incremental"


def test_sched_config_rejects_malformed_values():
    with pytest.raises(ValueError, match="REPRO_SCHED_LAMBDA_DEPTH"):
        SchedConfig.from_env(env={"REPRO_SCHED_LAMBDA_DEPTH": "banana"})
    with pytest.raises(ValueError, match="REPRO_SCHED_JAX_MIN"):
        SchedConfig.from_env(env={"REPRO_SCHED_JAX_MIN": "junk"})
    with pytest.raises(ValueError, match="REPRO_SCHED_BACKEND"):
        SchedConfig.from_env(env={"REPRO_SCHED_BACKEND": "cuda"})
    with pytest.raises(ValueError, match="REPRO_SCHED_BATCH"):
        SchedConfig.from_env(env={"REPRO_SCHED_BATCH": "many"})
    with pytest.raises(ValueError, match="REPRO_SCHED_AUDIT"):
        SchedConfig.from_env(env={"REPRO_SCHED_AUDIT": "yes"})


def test_sched_config_env_items_round_trip():
    cfg = SchedConfig(backend="jax", jax_min=4, audit=True, churn=2.5)
    env = dict(cfg.env_items())
    assert env == {
        "REPRO_SCHED_BACKEND": "jax",
        "REPRO_SCHED_JAX_MIN": "4",
        "REPRO_SCHED_AUDIT": "1",
        "REPRO_SCHED_CHURN": "2.5",
    }
    assert SchedConfig.from_env(env=env) == cfg


def test_sched_config_rejects_unknown_vars():
    with pytest.raises(ValueError, match="REPRO_SCHED_LAMBDA_DEPTX"):
        SchedConfig.from_env(env={"REPRO_SCHED_LAMBDA_DEPTX": "3"})


def test_env_changes_reach_hot_paths(monkeypatch):
    """backend.py reads the memoized config, and monkeypatched env vars
    must be visible immediately (the memo keys on the env snapshot)."""
    from repro.core.backend import backend_name, jax_min_wide

    monkeypatch.delenv("REPRO_SCHED_BACKEND", raising=False)
    monkeypatch.delenv("REPRO_SCHED_JAX_MIN", raising=False)
    _reset_config_cache()
    assert backend_name() == "numpy"
    assert jax_min_wide() == 32
    monkeypatch.setenv("REPRO_SCHED_BACKEND", "jax")
    monkeypatch.setenv("REPRO_SCHED_JAX_MIN", "7")
    assert backend_name() == "jax"
    assert jax_min_wide() == 7
    monkeypatch.setenv("REPRO_SCHED_JAX_MIN", "junk")
    with pytest.raises(ValueError, match="REPRO_SCHED_JAX_MIN"):
        jax_min_wide()


def test_explicit_config_object_threads_through():
    cfg = SchedConfig(backend="jax", jax_min=5)
    from repro.core.backend import backend_name, jax_min_wide

    assert backend_name(config=cfg) == "jax"
    assert jax_min_wide(config=cfg) == 5
    sim = Simulator(
        cholesky_graph(3, 256, with_fns=False),
        paper_machine(1),
        resolve("ws"),
        config=cfg,
    )
    assert sim.config is cfg


# ---------------------------------------------------------------------------
# the generic score-matrix driver and the new policies


def test_assign_from_scores_basic_and_capacity():
    scores = np.array([[0.0, 1.0], [0.0, 1.0], [0.0, 1.0], [0.0, 1.0]])
    # unconstrained: everything goes to column 0
    assert assign_from_scores(scores).tolist() == [0, 0, 0, 0]
    # capacity 2 per column forces a split
    choice = assign_from_scores(scores, capacity=[2, 2])
    assert sorted(choice.tolist()) == [0, 0, 1, 1]
    with pytest.raises(ValueError, match="no eligible column"):
        assign_from_scores(scores, capacity=[1, 1])


def test_assign_from_scores_load_aware():
    scores = np.zeros((4, 2))
    costs = np.full((4, 2), 3.0)
    choice, loads = assign_from_scores(
        scores, loads=[0.0, 1.0], costs=costs, return_loads=True
    )
    # equal scores: items alternate by accumulated load, col 0 first
    assert choice.tolist() == [0, 1, 0, 1]
    assert loads.tolist() == [6.0, 7.0]


@pytest.mark.parametrize("spec", ["random", "random?seed=11", "locality"])
def test_new_policies_deterministic_under_seed(spec):
    machine = paper_machine(4)
    runs = [
        run_simulation(
            cholesky_graph(6, 256, with_fns=False), machine, resolve(spec), seed=3
        )
        for _ in range(2)
    ]
    assert _fingerprint(runs[0]) == _fingerprint(runs[1])
    assert runs[0].makespan > 0


def test_random_policies_differ_across_policy_seeds():
    machine = paper_machine(4)
    a = run_simulation(
        cholesky_graph(6, 256, with_fns=False), machine,
        resolve("random?seed=1"), seed=0,
    )
    b = run_simulation(
        cholesky_graph(6, 256, with_fns=False), machine,
        resolve("random?seed=2"), seed=0,
    )
    assert _fingerprint(a) != _fingerprint(b)


def test_policies_satisfy_protocol():
    for spec in ("heft", "dada", "dual", "ws", "random", "locality"):
        assert isinstance(resolve(spec), Policy), spec


def test_score_matrix_shapes_and_semantics():
    machine = paper_machine(3)
    graph = cholesky_graph(5, 256, with_fns=False)
    n_res = len(machine.resources)
    for spec in ("heft", "dada?use_cp=1", "locality", "random"):
        strat = resolve(spec)
        sim = Simulator(graph, machine, strat, seed=0)
        strat.init(sim)
        ready = graph.roots()
        S = strat.score_matrix(sim, ready)
        assert S is not None and S.shape == (len(ready), n_res), spec
        assert np.isfinite(S).all(), spec
    ws = resolve("ws")
    sim = Simulator(graph, machine, ws, seed=0)
    assert ws.score_matrix(sim, graph.roots()) is None


def test_locality_prefers_resident_data():
    """A task whose inputs sit on one GPU memory must score that GPU
    strictly cheaper than the other accelerators."""
    machine = paper_machine(4)
    graph = cholesky_graph(5, 256, with_fns=False)
    strat = LocalityPolicy()
    sim = Simulator(graph, machine, strat, seed=0)
    gpu = machine.gpus[0]
    root = graph.roots()[0]
    for _, name, _size in sim.arrays.task_reads[root.tid]:
        sim.residency.write(name, gpu.mem)
    S = strat.score_matrix(sim, [root])
    j_gpu = [i for i, r in enumerate(machine.resources) if r.rid == gpu.rid][0]
    other_gpus = [
        i for i, r in enumerate(machine.resources)
        if r.is_accelerator and r.rid != gpu.rid
    ]
    assert all(S[0, j_gpu] < S[0, j] for j in other_gpus)


def test_random_policy_uses_every_resource_eventually():
    machine = paper_machine(4)
    res = run_simulation(
        cholesky_graph(8, 256, with_fns=False), machine,
        RandomPolicy(seed=0), seed=0,
    )
    used = {iv.rid for iv in res.intervals}
    assert len(used) == len(machine.resources)
