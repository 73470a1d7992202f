"""The jax placement-scoring backend must be a pure speed refactor:
decisions, λ trajectories and score values bit-identical to the numpy
path, a hard failure when the jax backend cannot be built, bounded jit
retraces via padded shapes, and a Pallas transfer kernel that matches the
XLA fold."""
import numpy as np
import pytest

from repro.configs.paper_machine import CPU_CLASS, GPU_CLASS, paper_machine, scaled_machine
from repro.core import DADA, HEFT, Simulator, run_simulation
from repro.core.backend import (
    _reset_backend_cache,
    backend_name,
    get_backend,
    jax_min_wide,
)
from repro.core.machine import make_machine
from repro.linalg.cholesky import cholesky_graph
from repro.linalg.lu import lu_graph
from repro.linalg.qr import qr_graph

jax = pytest.importorskip("jax")

KERNELS = {
    "cholesky": cholesky_graph,
    "lu": lu_graph,
    "qr": qr_graph,
}

STRATEGIES = {
    "heft": lambda b: HEFT(backend=b),
    "dada(0)": lambda b: DADA(alpha=0.0, backend=b),
    "dada(0.5)": lambda b: DADA(alpha=0.5, backend=b),
    "dada(0.5)+cp": lambda b: DADA(alpha=0.5, use_cp=True, backend=b),
}


@pytest.fixture
def force_jax(monkeypatch):
    """Engage the jax path at every activation width."""
    monkeypatch.setenv("REPRO_SCHED_JAX_MIN", "1")


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
# decision identity


@pytest.mark.parametrize("kernel", sorted(KERNELS))
@pytest.mark.parametrize("strat", sorted(STRATEGIES))
@pytest.mark.parametrize("n_gpus", [0, 3, 8])
def test_jax_matches_numpy(force_jax, kernel, strat, n_gpus):
    machine = paper_machine(n_gpus)
    fac = STRATEGIES[strat]
    for seed in (0, 7):
        a = run_simulation(
            KERNELS[kernel](6, 256, with_fns=False), machine,
            fac("numpy"), seed=seed,
        )
        b = run_simulation(
            KERNELS[kernel](6, 256, with_fns=False), machine,
            fac("jax"), seed=seed,
        )
        assert _fingerprint(a) == _fingerprint(b)


def test_jax_lambda_and_loads_match(force_jax):
    """The accepted λ and the final per-resource loads must match too —
    they drive mid-simulation load_ts corrections."""
    machine = paper_machine(4)
    a = DADA(alpha=0.5, backend="numpy")
    b = DADA(alpha=0.5, backend="jax")
    run_simulation(cholesky_graph(6, 256, with_fns=False), machine, a, seed=3)
    run_simulation(cholesky_graph(6, 256, with_fns=False), machine, b, seed=3)
    assert a.last_lambda == b.last_lambda
    assert a.last_loads == b.last_loads


def test_jax_matches_numpy_all_gpu_machine(force_jax):
    machine = make_machine(
        n_cpus=4, n_gpus=4, cpu_class=CPU_CLASS, gpu_class=GPU_CLASS,
        gpu_pins_cpu=True,
    )
    a = run_simulation(
        cholesky_graph(6, 256, with_fns=False), machine,
        DADA(alpha=0.5, backend="numpy"), seed=2,
    )
    b = run_simulation(
        cholesky_graph(6, 256, with_fns=False), machine,
        DADA(alpha=0.5, backend="jax"), seed=2,
    )
    assert _fingerprint(a) == _fingerprint(b)


@pytest.mark.parametrize("affinity", ["write_resident", "all_resident",
                                      "missing_bytes", "accel_all"])
def test_jax_matches_numpy_nondefault_affinity(force_jax, affinity):
    """Fused resident-weighted scores and the missing_bytes fallback path
    must both reproduce numpy placements."""
    machine = paper_machine(3)
    a = run_simulation(
        cholesky_graph(6, 256, with_fns=False), machine,
        DADA(alpha=0.75, affinity=affinity, backend="numpy"), seed=9,
    )
    b = run_simulation(
        cholesky_graph(6, 256, with_fns=False), machine,
        DADA(alpha=0.75, affinity=affinity, backend="jax"), seed=9,
    )
    assert _fingerprint(a) == _fingerprint(b)


def test_jax_matches_numpy_area_bound(force_jax):
    machine = paper_machine(4)
    a = run_simulation(
        lu_graph(5, 256, with_fns=False), machine,
        DADA(alpha=0.5, area_bound=True, backend="numpy"), seed=1,
    )
    b = run_simulation(
        lu_graph(5, 256, with_fns=False), machine,
        DADA(alpha=0.5, area_bound=True, backend="jax"), seed=1,
    )
    assert _fingerprint(a) == _fingerprint(b)


def test_jax_matches_numpy_deep_lambda_tree(force_jax, monkeypatch):
    """depth>1 engages the vmapped speculative λ-grid — same trajectory."""
    monkeypatch.setenv("REPRO_SCHED_LAMBDA_DEPTH", "3")
    _reset_backend_cache()
    try:
        machine = paper_machine(4)
        a = run_simulation(
            cholesky_graph(6, 256, with_fns=False), machine,
            DADA(alpha=0.5, use_cp=True, backend="numpy"), seed=5,
        )
        b = run_simulation(
            cholesky_graph(6, 256, with_fns=False), machine,
            DADA(alpha=0.5, use_cp=True, backend="jax"), seed=5,
        )
        assert _fingerprint(a) == _fingerprint(b)
    finally:
        _reset_backend_cache()


# ---------------------------------------------------------------------------
# score-matrix bit-equality


def test_fused_matrices_bitwise_equal_numpy():
    from repro.core.affinity import affinity_rows

    graph = cholesky_graph(8, 256, with_fns=False)
    machine = scaled_machine(n_gpus=12, n_cpus=4)
    sim = Simulator(graph, machine, DADA(alpha=0.5, use_cp=True), seed=0)
    # seed residency so transfer hops and affinity scores are non-trivial
    for k, name in enumerate(sim.arrays.data_names):
        if k % 3 == 0:
            sim.residency.write(name, k % 12)
    ready = [t for t in graph.tasks if not graph.pred[t.tid]] + list(
        graph.tasks[:40]
    )
    tids = sorted({t.tid for t in ready})
    tasks = [graph.tasks[t] for t in tids]
    resources = machine.resources
    cpu_cls = machine.cpus[0].cls
    gpu_cls = machine.gpus[0].cls
    p_cpu = sim.predictor(cpu_cls).times(np.asarray(tids)).tolist()
    p_gpu = sim.predictor(gpu_cls).times(np.asarray(tids)).tolist()

    be = get_backend("jax")
    fused = be.score_matrices(
        sim, tids, resources, p_cpu=p_cpu, p_gpu=p_gpu,
        use_cp=True, affinity="accel_write", x_rows=True,
    )
    X_ref = np.asarray(
        sim.transfer_model.task_input_transfer_rows(
            sim.arrays, tids, [r.mem for r in resources], sim.residency
        )
    )
    S_ref = np.asarray(
        affinity_rows(
            "accel_write", sim.arrays, tids, tasks, resources, sim.residency
        )
    )
    assert (fused["X_np"] == X_ref).all()
    assert (fused["S_np"] == S_ref).all()
    # C = class duration + transfer, same op order
    gpu_col = np.asarray([r.is_accelerator for r in resources])
    base = np.where(gpu_col[None, :], np.asarray(p_gpu)[:, None],
                    np.asarray(p_cpu)[:, None])
    assert (fused["C_np"] == base + X_ref).all()


def test_pallas_transfer_kernel_matches_jnp_fold():
    jnp = jax.numpy
    from repro.kernels.sched_score import (
        transfer_matrix_jnp,
        transfer_matrix_pallas,
    )

    rng = np.random.default_rng(0)
    n_pad, r_pad, n_u = 256, 4, 25
    masks = rng.integers(0, 1 << (n_u + 1), size=(n_pad, r_pad)).astype(
        np.int32
    )
    per_read = rng.random((n_pad, r_pad))
    col_bits = np.asarray([1 << (u + 1) for u in range(n_u)], dtype=np.int32)
    host_col = np.zeros(n_u, dtype=bool)
    host_col[0] = True
    a = transfer_matrix_jnp(
        jnp.asarray(masks), jnp.asarray(per_read),
        jnp.asarray(col_bits), jnp.asarray(host_col),
    )
    b = transfer_matrix_pallas(
        jnp.asarray(masks), jnp.asarray(per_read),
        jnp.asarray(col_bits), jnp.asarray(host_col), interpret=True,
    )
    assert (np.asarray(a) == np.asarray(b)).all()


# ---------------------------------------------------------------------------
# backend selection, fallback, retrace bounds


def test_backend_name_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_SCHED_BACKEND", raising=False)
    assert backend_name() == "numpy"
    assert backend_name("jax") == "jax"
    monkeypatch.setenv("REPRO_SCHED_BACKEND", "jax")
    assert backend_name() == "jax"
    assert backend_name("numpy") == "numpy"
    with pytest.raises(ValueError):
        backend_name("cuda")


def test_numpy_backend_is_none():
    assert get_backend("numpy") is None


def test_min_wide_env(monkeypatch):
    monkeypatch.delenv("REPRO_SCHED_JAX_MIN", raising=False)
    assert jax_min_wide() == 32
    monkeypatch.setenv("REPRO_SCHED_JAX_MIN", "4")
    assert jax_min_wide() == 4
    # malformed values now fail loudly at SchedConfig.from_env() instead
    # of silently falling back to the default deep inside the backend
    monkeypatch.setenv("REPRO_SCHED_JAX_MIN", "junk")
    with pytest.raises(ValueError, match="REPRO_SCHED_JAX_MIN"):
        jax_min_wide()


def test_missing_jax_falls_back_with_warning(monkeypatch):
    """A jax backend that cannot be built raises — every resolution, and
    through a strategy that asked for it — instead of degrading to the
    numpy path behind the caller's back."""
    import repro.core.backend as backend_mod

    class _Broken:
        def __init__(self, config=None):
            raise ImportError("no module named jax (simulated)")

    _reset_backend_cache()
    monkeypatch.setattr(backend_mod, "JaxScoringBackend", _Broken)
    try:
        with pytest.raises(ImportError, match="simulated"):
            get_backend("jax")
        # nothing was cached: the second resolution raises again
        with pytest.raises(ImportError, match="simulated"):
            get_backend("jax")
        with pytest.raises(ImportError, match="simulated"):
            run_simulation(
                cholesky_graph(4, 256, with_fns=False), paper_machine(2),
                DADA(alpha=0.5, backend="jax"), seed=0,
            )
        # the numpy path needs no jax backend at all
        assert get_backend("numpy") is None
    finally:
        _reset_backend_cache()


def test_get_backend_jax_is_real(force_jax):
    """On the installed jax the backend builds, and a wide activation is
    scored on the device rather than handed back to numpy."""
    from repro.core.backend import JaxScoringBackend

    be = get_backend("jax")
    assert isinstance(be, JaxScoringBackend)
    assert be.platform == jax.default_backend()
    before = dict(be.counts)
    run_simulation(
        cholesky_graph(5, 256, with_fns=False), paper_machine(3),
        DADA(alpha=0.5, use_cp=True, backend="jax"), seed=0,
    )
    assert be.counts["device"] > before["device"]
    assert be.counts["outside"] == before["outside"]
    assert be.counts["rejected"] == before["rejected"]


def test_backend_does_not_leak_x64(force_jax):
    """The f64 scoring math is scoped per call: building and using the
    backend must not flip the process-wide default dtype of unrelated
    jax code (models/linalg/kernels stay f32)."""
    machine = paper_machine(3)
    run_simulation(
        cholesky_graph(5, 256, with_fns=False), machine,
        DADA(alpha=0.5, use_cp=True, backend="jax"), seed=0,
    )
    assert jax.numpy.asarray([1.0]).dtype == jax.numpy.float32


def test_padded_shapes_bound_retraces(force_jax):
    """Activation widths within one power-of-two bucket share a compiled
    search: the jit caches must stay bounded across activations."""
    be = get_backend("jax")
    n_search_before = len(be._search_fns)
    machine = paper_machine(3)
    run_simulation(
        cholesky_graph(6, 256, with_fns=False), machine,
        DADA(alpha=0.5, use_cp=True, backend="jax"), seed=0,
    )
    # ready widths 1..15 at NT=6 → buckets {8, 16} × (chain, flags) variants
    grown = len(be._search_fns) - n_search_before
    assert grown <= 8, f"unbounded retraces: {grown} new search signatures"


# ---------------------------------------------------------------------------
# program spans and transfer counters (repro.core.obs)


PHASES = ("pack", "upload", "dispatch", "readback")


def test_spans_leave_placements_bit_equal_and_count_transfers(force_jax):
    """With the recorder on, a device-scored DADA+CP schedule places as it
    does with it off; every device-scored activation has one span of each
    phase of both programs, and makes 2 uploads and 2 read-backs: each
    program puts its inputs on the device as one packed buffer, the score
    matrices come back as one packed buffer (C, the row maxima of X and the
    affinity scores), and λ as one value."""
    from repro.core import obs

    graph = cholesky_graph(5, 256, with_fns=False)
    machine = paper_machine(3)
    be = get_backend("jax")

    def run():
        before = dict(be.counts)
        res = run_simulation(graph, machine, DADA(alpha=0.5, use_cp=True, backend="jax"),
                             seed=0)
        return res, {k: be.counts[k] - before[k] for k in be.counts}

    off, n_off = run()
    obs.drain()
    obs.enable(True)
    try:
        on, n_on = run()
    finally:
        obs.enable(False)
    spans = obs.drain()
    assert _fingerprint(on) == _fingerprint(off)
    assert n_on == n_off
    assert n_on["device"] > 0 and n_on["outside"] == n_on["rejected"] == 0
    assert n_on["uploads"] == 2 * n_on["device"]
    assert n_on["readbacks"] == 2 * n_on["device"]

    roots = {s.id for s in spans if s.name == "dada.place"}
    assert all(s.root in roots for s in spans)
    per_root = {}
    for s in spans:
        per_root.setdefault(s.root, []).append(s.name)
    scored = [names for names in per_root.values() if "score.dispatch" in names]
    assert len(scored) == n_on["device"]
    for names in scored:
        for prog in ("score", "search"):
            for phase in PHASES:
                assert names.count(f"{prog}.{phase}") == 1, (prog, phase, names)
        assert "dada.search_host" not in names
        for phase in ("predict", "order", "rebuild"):
            assert names.count(f"dada.{phase}") == 1


def test_programs_have_stable_names(force_jax):
    """The jitted programs lower as modules named after what they do, so a
    device trace can tell them apart."""
    be = get_backend("jax")
    run_simulation(
        cholesky_graph(4, 256, with_fns=False), paper_machine(3),
        HEFT(backend="jax"), seed=0,
    )
    run_simulation(
        cholesky_graph(4, 256, with_fns=False), paper_machine(3),
        DADA(alpha=0.5, use_cp=True, backend="jax"), seed=0,
    )
    names = {f.__name__ for fns in (be._matrix_fns, be._search_fns, be._heft_fns)
             for f in fns.values()}
    assert names == {"dada_score_matrices", "dada_lambda_search", "heft_select"}
    (n_pad, n_res), fn = next(iter(be._heft_fns.items()))
    f64 = be.f64.encode(np.zeros(0)).dtype
    rows = jax.ShapeDtypeStruct((n_pad, n_res), f64)
    with jax.enable_x64(True):
        text = fn.lower(rows, rows, jax.ShapeDtypeStruct((n_pad,), bool),
                        jax.ShapeDtypeStruct((n_res,), f64),
                        jax.ShapeDtypeStruct((), f64)).as_text()
    assert "@jit_heft_select" in text
