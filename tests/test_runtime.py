"""The layered runtime engine: multi-graph streams, capacity-bounded
memories, the memory-pressure signal, and stale-transfer cancellation.

Bit-for-bit equivalence of the unbounded single-graph path is covered by
tests/test_equivalence*.py and tests/test_residency_property.py; this
module tests the new opt-in behaviors.
"""
import pytest

from repro.configs.paper_machine import paper_machine
from repro.core import DataObject, Mode, Simulator, TaskGraph
from repro.linalg.cholesky import cholesky_graph
from repro.linalg.lu import lu_graph
from repro.linalg.qr import qr_graph
from repro.runtime import Engine, predicted_eviction_bytes
from repro.sched import resolve

MB = 1024 * 1024


# ---------------------------------------------------------------------------
# multi-graph streaming


def _submit_four(engine):
    ctxs = []
    for i, gf in enumerate((cholesky_graph, lu_graph, qr_graph, cholesky_graph)):
        at = None if i < 2 else 0.02 * i  # two at t=0, two streamed in later
        ctxs.append(engine.submit(gf(6, 256, with_fns=False), at=at))
    return ctxs


@pytest.mark.parametrize("spec", ["heft", "dada?alpha=0.5&use_cp=1", "ws"])
def test_four_graph_stream_completes_with_per_graph_results(spec):
    eng = Engine(paper_machine(4), resolve(spec), seed=0)
    ctxs = _submit_four(eng)
    results = eng.run()
    assert len(results) == 4
    for ctx, res in zip(ctxs, results):
        assert sorted(iv.tid for iv in res.intervals) == list(
            range(ctx.n_tasks)
        )
        assert res.makespan > 0
        # the graph cannot have finished before it arrived
        assert ctx.finish >= ctx.submit_at
    # streamed graphs really started after their arrival events
    assert all(
        iv.start >= ctx.submit_at - 1e-12
        for ctx in ctxs[2:]
        for iv in ctx.intervals
    )


def test_stream_workers_never_double_booked_across_tenants():
    eng = Engine(paper_machine(3), resolve("heft"), seed=1)
    _submit_four(eng)
    eng.run()
    per_worker = {}
    for iv in eng.intervals:  # engine-global timeline, all tenants
        per_worker.setdefault(iv.rid, []).append((iv.start, iv.end))
    for rid, ivs in per_worker.items():
        ivs.sort()
        for (s1, e1), (s2, e2) in zip(ivs, ivs[1:]):
            assert e1 <= s2 + 1e-9, f"worker {rid} overlaps across graphs"


def test_stream_is_deterministic():
    def fingerprint():
        eng = Engine(paper_machine(4), resolve("dada?alpha=0.5"), seed=3)
        _submit_four(eng)
        return [
            (r.makespan, tuple((iv.tid, iv.rid, iv.start) for iv in r.intervals))
            for r in eng.run()
        ]

    assert fingerprint() == fingerprint()


def test_submit_after_run_start_uses_arrival_event():
    eng = Engine(paper_machine(2), resolve("heft"), seed=0)
    first = eng.submit(cholesky_graph(6, 256, with_fns=False))
    late = eng.submit(lu_graph(5, 256, with_fns=False), at=0.01)
    results = eng.run()
    assert late.submit_at == 0.01
    assert results[1].makespan > 0
    assert min(iv.start for iv in late.intervals) >= 0.01
    assert first.finish > 0


# ---------------------------------------------------------------------------
# submission error paths + serving-mode arrivals under faults


def test_double_submission_rejected():
    eng = Engine(paper_machine(2), resolve("heft"), seed=0)
    g = cholesky_graph(4, 256, with_fns=False)
    eng.submit(g)
    with pytest.raises(ValueError, match="already submitted"):
        eng.submit(g)
    # a fresh graph of the same shape is a different tenant: fine
    eng.submit(cholesky_graph(4, 256, with_fns=False))
    assert len(eng.run()) == 2


def test_mid_run_submit_during_fault_drain():
    """A tenant arriving while a GPU is draining must be admitted, placed
    only on live workers, and completed once the GPU reattaches."""
    detach_t, attach_t = 0.005, 0.08

    def run():
        eng = Engine(
            paper_machine(2), resolve("heft"), seed=0,
            rescore="incremental",
        )
        first = eng.submit(cholesky_graph(8, 256, with_fns=False))
        gpu = eng.machine.gpus[0].rid
        eng.inject("detach", gpu, at=detach_t, mode="drain")
        eng.inject("attach", gpu, at=attach_t)
        # arrives mid-run, inside the dead window
        late = eng.submit(lu_graph(5, 256, with_fns=False), at=0.01)
        eng.run()
        return eng, first, late, gpu

    eng, first, late, gpu = run()
    assert first.n_done == first.n_tasks
    assert late.n_done == late.n_tasks
    assert eng.metrics.n_arrivals == 2
    assert late.submit_at == 0.01
    assert min(iv.start for iv in late.intervals) >= 0.01
    # drain semantics: the task running at detach finishes, but nothing
    # new starts on the dead rid until the attach event
    for iv in eng.intervals:
        if iv.rid == gpu:
            assert not (
                detach_t + 1e-12 < iv.start < attach_t - 1e-12
            ), f"task {iv.tid} started on drained rid {gpu} at {iv.start}"
    # and the whole interleaving is deterministic
    fp = lambda e: [
        (iv.tid, iv.rid, iv.start, iv.end) for iv in e.intervals
    ]
    assert fp(eng) == fp(run()[0])


# ---------------------------------------------------------------------------
# stale-transfer cancellation (REPRO_SCHED_CANCEL_STALE)


def _stale_landing_sim(cancel: bool):
    """A copy of ``d`` is in flight to GPU memory 1 while a task on GPU 0
    overwrites ``d``: with cancellation off the old bytes still land as a
    "valid" copy (the historical modeling artifact); with it on they are
    dropped."""
    g = TaskGraph()
    d = DataObject("d", 50 * MB)  # ~6 ms in flight: lands well after the write
    e = DataObject("e", 1000)
    g.add_task("w", [(e, Mode.R), (d, Mode.W)], flops=1e6)

    class PinGpu0:
        name = "pin0"
        allow_steal = False
        owner_lifo = False

        def init(self, sim):
            self.gpu = sim.machine.gpus[0].rid

        def place(self, sim, ready, src):
            for t in ready:
                sim.push(t, self.gpu)

    sim = Simulator(
        g, paper_machine(2), PinGpu0(), seed=0, noise=0.0,
        cancel_stale=cancel,
    )
    # start the doomed transfer: host copy of d -> memory 1
    sim.request_transfer("d", 50 * MB, 1)
    sim.run()
    return sim


def test_stale_transfer_lands_by_default():
    sim = _stale_landing_sim(cancel=False)
    # the artifact, preserved for bit-for-bit equivalence: stale copy valid
    assert sim.residency.is_resident("d", 1)


def test_cancel_stale_drops_overwritten_inflight_copy():
    sim = _stale_landing_sim(cancel=True)
    assert not sim.residency.is_resident("d", 1)
    # the rewritten copy on GPU 0's memory is the only valid one
    assert sim.residency.locations("d") == {0}


def test_cancel_stale_config_flag(monkeypatch):
    from repro.sched import current_config

    monkeypatch.setenv("REPRO_SCHED_CANCEL_STALE", "1")
    assert current_config().cancel_stale is True
    g = TaskGraph()
    g.add_task("k", [(DataObject("x", 10), Mode.W)], flops=1.0)
    sim = Simulator(g, paper_machine(1), resolve("heft"), seed=0)
    assert sim._cancel_stale is True


def test_equivalence_unaffected_by_cancel_flag_without_races():
    """On a run with no mid-flight overwrites both modes are identical."""
    g1 = cholesky_graph(6, 256, with_fns=False)
    g2 = cholesky_graph(6, 256, with_fns=False)
    m = paper_machine(3)
    a = Simulator(g1, m, resolve("heft"), seed=5, cancel_stale=False).run()
    b = Simulator(g2, m, resolve("heft"), seed=5, cancel_stale=True).run()
    assert [(iv.tid, iv.rid, iv.start, iv.end) for iv in a.intervals] == [
        (iv.tid, iv.rid, iv.start, iv.end) for iv in b.intervals
    ]
    assert a.total_bytes == b.total_bytes


# ---------------------------------------------------------------------------
# capacity configuration and the pressure signal


def test_capacity_too_small_for_one_task_rejected():
    g = TaskGraph()
    g.add_task("big", [(DataObject("x", 100 * MB), Mode.RW)], flops=1e9)
    with pytest.raises(ValueError, match="working set"):
        Simulator(g, paper_machine(1), resolve("heft"), mem_capacity=MB)


def test_unknown_eviction_policy_rejected():
    g = cholesky_graph(4, 256, with_fns=False)
    with pytest.raises(ValueError, match="eviction"):
        Simulator(
            g, paper_machine(1), resolve("heft"),
            mem_capacity=64 * MB, eviction="random",
        )


def test_capacity_env_knobs(monkeypatch):
    from repro.sched import current_config

    monkeypatch.setenv("REPRO_SCHED_MEM_CAPACITY", str(64 * MB))
    monkeypatch.setenv("REPRO_SCHED_EVICTION", "affinity")
    cfg = current_config()
    assert cfg.mem_capacity == 64 * MB
    assert cfg.eviction == "affinity"
    sim = Simulator(
        cholesky_graph(4, 256, with_fns=False), paper_machine(2),
        resolve("heft"), seed=0,
    )
    assert sim.memory.bounded and sim.memory.capacity == 64 * MB
    assert sim.memory.policy == "affinity"
    monkeypatch.setenv("REPRO_SCHED_EVICTION", "banana")
    with pytest.raises(ValueError, match="REPRO_SCHED_EVICTION"):
        current_config()


def test_pressure_matrix_none_when_unbounded():
    from repro.sched import ScoreMatrixPolicy

    sim = Simulator(
        cholesky_graph(4, 256, with_fns=False), paper_machine(2),
        resolve("locality"), seed=0,
    )
    ready = sim.graph.roots()
    assert ScoreMatrixPolicy.pressure_matrix(sim.strategy, sim, ready) is None


def test_pressure_rows_positive_on_crowded_memory():
    sim = Simulator(
        cholesky_graph(8, 512, with_fns=False), paper_machine(2),
        resolve("locality"), seed=0, mem_capacity=8 * MB,
    )
    # fill GPU memory 0 to capacity with tiles the probed tasks don't read
    for name in sim.arrays.data_names[-4:]:  # 4 x 2 MB tiles
        sim.residency.add_copy(name, 0)
    tids = [t.tid for t in sim.graph.tasks[:5]]
    mems = [r.mem for r in sim.machine.resources]
    rows = sim.memory.pressure_rows(
        sim.arrays, tids, mems, sim.residency, sim.transfer_model
    )
    gpu0_col = mems.index(0)
    host_col = mems.index(-1)
    assert (rows[:, host_col] == 0.0).all()  # host is unbounded
    # tasks whose inputs are not on mem 0 would overflow it: positive cost
    assert rows[:, gpu0_col].max() > 0.0
    # and the emptier memory 1 is strictly cheaper for some task
    gpu1_col = mems.index(1)
    assert (rows[:, gpu1_col] <= rows[:, gpu0_col]).all()


def test_pressure_changes_placements_under_capacity():
    """With the signal wired into HEFT's transfer matrix, a capacity-
    bounded run must not place exactly like the unbounded one on a
    pressure-heavy workload (and both must still complete)."""
    def run(cap):
        sim = Simulator(
            cholesky_graph(12, 512, with_fns=False), paper_machine(4),
            resolve("heft"), seed=0, noise=0.0, mem_capacity=cap,
        )
        res = sim.run()
        return [(iv.tid, iv.rid) for iv in res.intervals], res

    unbounded, _ = run(0)
    bounded, res = run(24 * MB)
    assert sorted(t for t, _ in bounded) == sorted(t for t, _ in unbounded)
    assert bounded != unbounded


def test_predicted_eviction_bytes_formula():
    import numpy as np

    out = predicted_eviction_bytes(
        np.array([0.0, 50.0, 120.0]), np.array([30.0, 80.0, 10.0]), 100.0
    )
    assert out.tolist() == [0.0, 30.0, 10.0]


def test_expert_replanning_prices_eviction_cost():
    """The dist bridge shares the eviction-cost formula: a nearly-full
    group repels incoming experts unless they were already there."""
    from repro.dist.sched_bridge import plan_expert_placement

    # e2/e3 are new experts (prev -1): without memory pricing the score
    # tie sends e2 to group 0; with group 0's HBM full the eviction cost
    # steers it to the empty group 1 instead
    mass = [5.0, 5.0, 4.0, 4.0]
    prev = [0, 1, -1, -1]
    kw = dict(prev_assignment=prev, alpha=0.1)
    free = plan_expert_placement(mass, 2, **kw)
    priced = plan_expert_placement(
        mass, 2, **kw,
        expert_bytes=10.0, group_hbm_bytes=15.0,
        group_resident_bytes=[15.0, 5.0],  # group 0 full, group 1 roomy
    )
    assert free.assignment[2] == 0
    assert priced.assignment[2] == 1
    # capacity stays exact (2 slots per group) under pricing
    assert sorted(priced.assignment.tolist()) == [0, 0, 1, 1]
    # previously-placed experts keep their homes (staying is free)
    assert priced.assignment[0] == 0 and priced.assignment[1] == 1


# ---------------------------------------------------------------------------
# jax scoring backend: pressure fold keeps decisions identical to numpy


def _wide_wave(graph):
    depth = [0] * len(graph)
    for t in graph.tasks:
        preds = graph.pred[t.tid]
        depth[t.tid] = (max(depth[p] for p in preds) + 1) if preds else 0
    counts = {}
    for d in depth:
        counts[d] = counts.get(d, 0) + 1
    best = max(counts, key=lambda d: (counts[d], -d))
    return [t for t in graph.tasks if depth[t.tid] == best]


@pytest.mark.parametrize("spec", ["dada?alpha=0.5&use_cp=1", "heft"])
def test_jax_backend_pressure_fold_matches_numpy(spec):
    pytest.importorskip("jax")
    graph = cholesky_graph(10, 256, with_fns=False)
    wave = _wide_wave(graph)
    assert len(wave) >= 32  # wide enough for the jax path to engage
    placements = {}
    for backend in ("numpy", "jax"):
        strat = resolve(spec, backend=backend)
        sim = Simulator(
            graph, paper_machine(4), strat, seed=0,
            mem_capacity=4 * MB, eviction="affinity",
        )
        for k, name in enumerate(sim.arrays.data_names):
            if k % 3 == 0:
                sim.residency.write(name, k % 4)
        placed = {}
        sim.push = lambda task, rid, _p=placed: _p.__setitem__(task.tid, rid)
        strat.place(sim, wave, None)
        placements[backend] = placed
    assert placements["jax"] == placements["numpy"]
