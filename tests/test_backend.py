"""The jax placement-scoring backend must be a pure speed refactor:
decisions, λ trajectories and score values bit-identical to the numpy
path, a hard failure when the jax backend cannot be built, bounded jit
retraces via padded shapes, and a Pallas transfer kernel that matches the
XLA fold."""
import functools
from types import SimpleNamespace

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
# the one-dispatch program: scores, affinity order and λ search


def _decisions(strategy, graph, machine, seed):
    """A schedule's fingerprint, and the accepted λ and the loads of every
    activation in order."""
    trail = []
    place = strategy.place

    def recording(sim, ready, src):
        place(sim, ready, src)
        trail.append((strategy.last_lambda, tuple(sorted(strategy.last_loads.items()))))

    strategy.place = recording
    res = run_simulation(graph, machine, strategy, seed=seed)
    return _fingerprint(res), trail


def _machine(name):
    if name == "dgx_a100":
        from repro.configs.dgx_a100 import dgx_a100

        return dgx_a100()
    return paper_machine(8)


# (machine, NT, alpha, +CP, area_bound): NT=32 gives the 8-31 wide
# activations of the schedule cells, NT=20 those of 8-19
ONE_DISPATCH_CASES = [
    ("paper_machine", 32, 0.5, True, False),
    ("dgx_a100", 32, 0.5, True, False),
    ("paper_machine", 20, 0.0, False, False),
    ("paper_machine", 20, 1.0, True, False),
    ("paper_machine", 20, 0.5, False, False),
    ("paper_machine", 20, 0.5, True, True),
    ("dgx_a100", 20, 0.0, True, False),
    ("dgx_a100", 20, 1.0, False, True),
]


@pytest.mark.parametrize("machine,nt,alpha,use_cp,area_bound", ONE_DISPATCH_CASES)
def test_one_dispatch_matches_numpy(machine, nt, alpha, use_cp, area_bound):
    """Every activation from 8 tasks wide runs the one program; the
    placements, and each activation's accepted λ and loads, equal the
    numpy path's bit for bit, with one upload and one read-back each."""
    from repro.sched.config import SchedConfig

    cfg = SchedConfig(backend="jax", jax_min=8)
    mach = _machine(machine)
    tile = 1024 if machine == "dgx_a100" else 512
    be = get_backend("jax", cfg)
    kw = dict(alpha=alpha, use_cp=use_cp, area_bound=area_bound, config=cfg)
    before = dict(be.counts)
    got = _decisions(DADA(backend="jax", **kw), cholesky_graph(nt, tile, with_fns=False),
                     mach, seed=4)
    n = {k: be.counts[k] - before[k] for k in be.counts}
    want = _decisions(DADA(backend="numpy", **kw), cholesky_graph(nt, tile, with_fns=False),
                      mach, seed=4)
    assert got == want
    assert n["device"] > 0 and n["outside"] == n["rejected"] == 0
    assert n["fused"] == n["uploads"] == n["readbacks"] == n["device"]


def _wide_wave(graph):
    """The widest same-depth task set: the trailing-update wave."""
    depth = [0] * len(graph)
    for t in graph.tasks:
        depth[t.tid] = max((depth[p] + 1 for p in graph.pred[t.tid]), default=0)
    widest = max(set(depth), key=depth.count)
    return [t for t in graph.tasks if depth[t.tid] == widest]


def test_one_dispatch_matches_numpy_on_a_wide_wave():
    """The ≥256-wide wave of Cholesky NT=32 on the 32-resource machine
    (``chip_smoke.py`` phase (a)), with residency seeded so that transfers
    and affinities are not trivial: one activation, placed alike."""
    from repro.sched.config import SchedConfig

    graph = cholesky_graph(32, 512, with_fns=False)
    wave = _wide_wave(graph)
    assert len(wave) >= 256
    machine = scaled_machine()
    placed = []
    for backend in ("jax", "numpy"):
        strat = DADA(alpha=0.5, use_cp=True, backend=backend,
                     config=SchedConfig(backend=backend, jax_min=8))
        sim = Simulator(graph, machine, strat, seed=0)
        for k, name in enumerate(sim.arrays.data_names):
            if k % 3 == 0:
                sim.residency.write(name, k % 24)
        pushes = []
        sim.push = lambda task, rid: pushes.append((task.tid, rid))
        strat.place(sim, wave, None)
        placed.append((pushes, strat.last_lambda, strat.last_loads, list(sim.load_ts)))
    assert placed[0] == placed[1]


def _sequential_affinity(loads0, by_score, budget, cap):
    """``try_build``'s affinity phase as written: entries in by-score order."""
    loads = list(loads0)
    taken, bad = [], False
    for _, rid, c in by_score:
        take = loads[rid] <= budget
        taken.append(take)
        if take:
            v = loads[rid] + c
            bad |= v > cap
            loads[rid] = v
    return loads, taken, bad


def _host_chains(by_score, n_res):
    """The chains as ``dada_lambda_search`` packs them."""
    rids = np.asarray([e[1] for e in by_score], np.int64)
    perm = np.argsort(rids, kind="stable")
    srid = rids[perm]
    pos = np.arange(len(rids)) - np.searchsorted(srid, srid, side="left")
    chain_pad = int(pos.max()) + 1
    cost = np.zeros((chain_pad, n_res))
    valid = np.zeros((chain_pad, n_res), bool)
    cost[pos, srid] = np.asarray([e[2] for e in by_score])[perm]
    valid[pos, srid] = True
    slot = np.empty(len(rids), np.int64)
    slot[perm] = pos * n_res + srid
    return cost, valid, slot


@pytest.mark.parametrize("arith", ["native", "soft"])
@pytest.mark.parametrize("case", range(4))
def test_prefix_affinity_verdict_equals_sequential_scan(arith, case):
    """The λ search's affinity phase in prefix form (the chains' loads
    folded once, then one compare and gather per probe) against the
    sequential scan over the by-score list: the same loads bit for bit,
    the same takes and the same overflow verdict. Costs are quarters, so
    loads land exactly on the budget; some are 0."""
    from repro.core import f64
    from repro.core.backend import affinity_prefix, chain_loads

    F = f64.NATIVE if arith == "native" else f64.SOFT
    rng = np.random.default_rng(case)
    n_res, m = 6, 24
    budget = 1.0
    cap = (2.0, 1.25, 1.5, 3.0)[case]
    loads0 = rng.choice([0.0, 0.25, 0.5, 1.0, 1.25], size=n_res)
    loads0[0] = budget
    by_score = [(tid, int(rng.integers(0, n_res)), float(rng.choice([0.0, 0.25, 0.5, 0.75])))
                for tid in rng.permutation(m)]
    want_loads, want_taken, want_bad = _sequential_affinity(loads0, by_score, budget, cap)
    cost, valid, slot = _host_chains(by_score, n_res)
    with jax.enable_x64(True):
        cum = chain_loads(F, jax.numpy.asarray(F.encode(loads0)),
                          jax.numpy.asarray(F.encode(cost)), cost.shape[0])
        loads, bad, takes = affinity_prefix(F, cum, jax.numpy.asarray(valid),
                                            F.const(budget), F.const(cap))
        loads, bad, takes = F.decode(loads), bool(bad), np.asarray(takes)
    assert loads.tolist() == want_loads
    assert takes.reshape(-1)[slot].tolist() == want_taken
    assert bad == want_bad
    assert any(want_taken) and not all(want_taken)


@pytest.mark.parametrize("arith", ["native", "soft"])
def test_device_affinity_order_equals_host_rule(arith):
    """The device's best resource per row (rid-ascending, ``s > best +
    1e-12``, ties within the tolerance included) and its (−score, tid)
    order equal DADA's host rule; padded rows and all-zero rows have no
    preference."""
    from repro.core import f64
    from repro.core.backend import affinity_order

    F = f64.NATIVE if arith == "native" else f64.SOFT
    rng = np.random.default_rng(3)
    n, n_pad, n_res = 13, 16, 5
    base = rng.choice([0.0, 1.0, 2.0], size=(n_pad, n_res))
    # steps below and above the tolerance decide between near-equal scores
    S = base + rng.choice([0.0, 0.6e-12, 1.2e-12, 2.5e-12], size=(n_pad, n_res))
    S[0] = [1.0, 1.0 + 0.6e-12, 0.0, 0.0, 0.0]  # within the tolerance: rid 0
    S[1] = [0.0, 2.0, 2.0 + 0.5e-12, 2.0 + 1.1e-12, 0.0]  # past it: rid 3
    S[2] = 0.0
    S[3] = [1.0, 1.0, 1.0, 0.0, 0.0]  # ties with row 0 too: tid decides
    S[n:] = rng.random((n_pad - n, n_res))  # padding: never a preference
    C = rng.random((n_pad, n_res))
    tids = rng.permutation(100)[:n_pad]
    pref = []
    for i in range(n):
        best, best_rid = 0.0, -1
        for rid in range(n_res):
            if S[i, rid] > best + 1e-12:
                best, best_rid = S[i, rid], rid
        if best_rid >= 0:
            pref.append((-best, int(tids[i]), i, best_rid))
    pref.sort()
    with jax.enable_x64(True):
        jnp = jax.numpy
        ord_row, ord_rid, n_pref, _ = affinity_order(
            F, jnp.asarray(F.encode(S)), jnp.asarray(F.encode(C)),
            jnp.asarray(tids, dtype=jnp.int64), jnp.arange(n_pad) < n,
            jnp.arange(n_res, dtype=jnp.int32))
        m = int(n_pref)
    assert m == len(pref) < n
    assert np.asarray(ord_row)[:m].tolist() == [e[2] for e in pref]
    assert np.asarray(ord_rid)[:m].tolist() == [e[3] for e in pref]


# ---------------------------------------------------------------------------
# the λ search's balance passes: one loop over the rows the grid's live
# probes own, against DADA's Python search written out


_TINY = 1e-12


def _host_probe(a, lam):
    """``try_build(lam)`` as ``dada.place`` runs it, without its early
    exits: whether λ is feasible, and the rows the balance phase visits if
    the probe has not failed before it (the dedicated rows and the
    flexible positions of ``flex_order``; on one class, every row left)."""
    C, p_cpu, p_gpu = a["C"], a["p_cpu"], a["p_gpu"]
    cpus, gpus = a["cpu_rids"], a["gpu_rids"]
    cap = (2.0 + a["alpha"]) * lam + _TINY
    bad = a["max_off"] > cap
    if a["area_bound"]:
        bad |= a["area"] > lam * len(C[0]) - a["off_total"] + _TINY
    loads = list(a["offsets"])
    assigned = set()
    budget = a["alpha"] * lam + _TINY
    for row, rid, c in a["by_score"]:
        if loads[rid] <= budget:
            assigned.add(row)
            loads[rid] = loads[rid] + c
            bad |= loads[rid] > cap
    rem = [i for i in range(len(C)) if i not in assigned]
    bad |= any((not cpus or p_cpu[i] > lam) and (not gpus or p_gpu[i] > lam) for i in rem)

    def eft(i, pool):
        best_v, best = float("inf"), pool[0]
        for rid in pool:
            v = loads[rid] + C[i][rid]
            if v < best_v:
                best_v, best = v, rid
        loads[best] = best_v
        return best_v > cap

    if not (cpus and gpus):
        ded, flex = rem, []
    else:
        ded = [i for i in rem if p_cpu[i] > lam or p_gpu[i] > lam]
        flex = [k for k, i in enumerate(a["flex_order"]) if i in rem and i not in ded]
    owned = (set(), set()) if bad else (set(ded), set(flex))
    for i in ded:
        bad |= eft(i, (gpus if p_cpu[i] > lam else cpus) if cpus and gpus else cpus or gpus)
    for k in flex:
        i = a["flex_order"][k]
        g = min(gpus, key=lambda r: loads[r])  # the first minimum
        if loads[g] <= lam + _TINY:
            loads[g] = loads[g] + C[i][g]
            bad |= loads[g] > cap
        else:
            bad |= eft(i, cpus)
    return not bad, owned


def _host_search(a, depth):
    """DADA's bisection on λ, a λ-loop iteration of the device at a time:
    the final λ, and for each iteration the union of the rows its grid's
    live probes own (the dedicated and flexible passes' lengths)."""
    lower, upper, it = 0.0, a["upper0"], 0

    def searching():
        return upper - lower > a["eps_rel"] * upper and it < a["max_iters"]

    grids = []
    while searching():
        # every probe the next `depth` bisection steps can make
        probes, spans = [], [(lower, upper)]
        for _ in range(depth):
            mids = [(lo + hi) / 2.0 for lo, hi in spans]
            probes += mids
            spans = [s for (lo, hi), m in zip(spans, mids) for s in ((lo, m), (m, hi))]
        ded, flex = set(), set()
        for lam in probes:
            d, f = _host_probe(a, lam)[1]
            ded |= d
            flex |= f
        grids.append((len(ded), len(flex)))
        for _ in range(depth):
            if not searching():
                break
            lam = (upper + lower) / 2.0
            if _host_probe(a, lam)[0]:
                upper = lam
            else:
                lower = lam
            it += 1
    return upper, grids


def _activation(case):
    """A synthetic activation drawn from a seed per case. Durations and
    transfers are small dyadic numbers, so loads tie and land on the caps;
    ``all_bad`` holds a task larger than λ everywhere and an upper bound
    just above it, so every probe of the first grid fails before its
    balance phase."""
    rng = np.random.default_rng(sum(map(ord, case)))
    n = {"n9": 9, "n17": 17}.get(case, 13)
    n_cpu, n_gpu = (6, 0) if case == "one_class" else (4, 2)
    accel = np.asarray([False] * n_cpu + [True] * n_gpu)
    p_cpu = rng.choice([1.0, 2.0, 3.0, 4.0, 6.0, 8.0], n)
    p_gpu = p_cpu / rng.choice([0.25, 0.5, 1.0, 4.0, 16.0], n)
    X = rng.choice([0.0, 0.25, 0.5], (n, len(accel)))
    C = np.where(accel[None, :], p_gpu[:, None], p_cpu[:, None]) + X
    offsets = rng.choice([0.0, 0.5, 1.0], len(accel))
    upper0 = float(np.maximum(p_cpu, p_gpu).sum() + offsets.max() + X.max(axis=1).sum()) + _TINY
    if case == "all_bad":
        p_cpu[0] = p_gpu[0] = 64.0
        upper0 = 64.0 * 32 / 31.5
    alpha = 0.0 if case == "alpha0" else 0.5
    rows = rng.permutation(n)[:0 if alpha == 0.0 else n // 2]
    by_score = [(int(i), int(rng.integers(len(accel))), 0.0) for i in rows]
    by_score = [(i, rid, float(C[i, rid])) for i, rid, _ in by_score]
    return dict(
        C=C.tolist(), p_cpu=p_cpu.tolist(), p_gpu=p_gpu.tolist(), offsets=offsets.tolist(),
        cpu_rids=[j for j in range(len(accel)) if not accel[j]],
        gpu_rids=[j for j in range(len(accel)) if accel[j]],
        flex_order=rng.permutation(n).tolist(), by_score=by_score, alpha=alpha,
        area_bound=case == "n17", area=float(np.minimum(p_cpu, p_gpu).sum()),
        off_total=float(offsets.sum()), max_off=float(offsets.max()),
        eps_rel=1e-3, max_iters=40, upper0=upper0, accel=accel.tolist())


@functools.lru_cache(maxsize=None)
def _search_backend(depth, arith):
    from repro.core import f64
    from repro.core.backend import JaxScoringBackend
    from repro.sched.config import SchedConfig

    be = JaxScoringBackend(SchedConfig(backend="jax", lambda_depth=depth))
    be.f64 = f64.NATIVE if arith == "native" else f64.SOFT
    return be


@pytest.mark.parametrize("case", ["mixed", "all_bad", "alpha0", "one_class", "n9", "n17"])
@pytest.mark.parametrize("arith", ["native", "soft"])
@pytest.mark.parametrize("depth", [1, 5])
def test_compacted_balance_search_equals_host_loop(depth, arith, case):
    """The λ search, its balance passes looping over the rows the grid's
    live probes own, settles on the Python loop's λ bit for bit, and counts
    as its loop steps the union of those rows, grid by grid, against
    ``2 · n_pad`` a grid (``n_pad`` on one class) for passes over every
    padded row."""
    from repro.core.backend import _bucket, _pad_rows

    a = _activation(case)
    want, grids = _host_search(a, depth)
    n, n_res = len(a["C"]), len(a["accel"])
    both = bool(a["cpu_rids"] and a["gpu_rids"])
    if case == "all_bad":
        assert grids[0] == (0, 0)
    elif both:
        assert any(d for d, _ in grids) and any(f for _, f in grids)
    be = _search_backend(depth, arith)
    with jax.enable_x64(True):
        C_dev = jax.numpy.asarray(be.f64.encode(_pad_rows(a["C"], _bucket(n))))
    before = dict(be.counts)
    got = be.dada_lambda_search(
        n=n, n_res=n_res, offsets=a["offsets"], C_dev=C_dev, p_cpu=a["p_cpu"],
        p_gpu=a["p_gpu"], by_score=[(1.0, i, rid, c) for i, rid, c in a["by_score"]],
        tid_index={i: i for i in range(n)}, flex_order=a["flex_order"],
        resources=[SimpleNamespace(is_accelerator=x) for x in a["accel"]],
        have_both=both, no_cpus=not a["cpu_rids"], no_gpus=not a["gpu_rids"],
        alpha=a["alpha"], area_bound=a["area_bound"], area=a["area"],
        off_total=a["off_total"], max_off=a["max_off"], eps_rel=a["eps_rel"],
        max_iters=a["max_iters"], upper0=a["upper0"])
    assert got == want
    assert be.counts["balance_steps"] - before["balance_steps"] == sum(map(sum, grids))
    assert (be.counts["balance_steps_padded"] - before["balance_steps_padded"]
            == (2 if both else 1) * _bucket(n) * len(grids))


def test_balance_counters_on_one_schedule():
    """Every 17-to-31-wide activation of a DADA+CP Cholesky schedule on the
    paper's machine, through the one program at the chip's depth 5: its λ
    is the Python loop's, ``balance_steps`` is the union of the rows its
    grids' live probes own, ``balance_steps_padded`` is ``2 · n_pad`` a
    grid, and both come back in its one read-back."""
    from repro.core.backend import _bucket
    from repro.sched.config import SchedConfig

    cfg = SchedConfig(backend="jax", jax_min=17, lambda_depth=5)
    be = get_backend("jax", cfg)
    score = be.score_matrices
    seen = []

    def recording(sim, tids, resources, **kw):
        before = dict(be.counts)
        out = score(sim, tids, resources, **kw)
        seen.append((tids, resources, kw, out,
                     {k: be.counts[k] - before[k] for k in be.counts}))
        return out

    be.score_matrices = recording
    try:
        run_simulation(cholesky_graph(20, 512, with_fns=False), paper_machine(8),
                       DADA(alpha=0.5, use_cp=True, backend="jax", config=cfg), seed=4)
    finally:
        del be.score_matrices
    with_prefs = 0
    for tids, resources, kw, out, n in seen:
        s, C = kw["search"], out["C_np"]
        accel = [r.is_accelerator for r in resources]
        a = dict(
            C=C.tolist(), p_cpu=list(kw["p_cpu"]), p_gpu=list(kw["p_gpu"]),
            offsets=list(s["offsets"]), cpu_rids=[j for j, x in enumerate(accel) if not x],
            gpu_rids=[j for j, x in enumerate(accel) if x], flex_order=list(s["flex_order"]),
            by_score=[(int(i), int(r), float(C[i, r]))
                      for i, r in zip(out["order_rows"], out["order_rids"])],
            alpha=s["alpha"], area_bound=s["area_bound"], area=s["area"],
            off_total=s["off_total"], max_off=s["max_off"], eps_rel=s["eps_rel"],
            max_iters=s["max_iters"], upper0=out["upper0"])
        lam, grids = _host_search(a, 5)
        steps = sum(map(sum, grids))
        assert out["lam"] == lam
        assert n["balance_steps"] == steps
        assert n["balance_steps_padded"] == 2 * _bucket(len(tids)) * len(grids)
        assert n["uploads"] == n["readbacks"] == n["fused"] == n["device"] == 1
        with_prefs += bool(a["by_score"]) and steps > 0
    assert len(seen) >= 4 and with_prefs
    assert all(17 <= len(tids) <= 31 for tids, *_ in seen)


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
    program: the jit caches must stay bounded across activations."""
    be = get_backend("jax")
    n_before = len(be._matrix_fns)
    machine = paper_machine(3)
    run_simulation(
        cholesky_graph(6, 256, with_fns=False), machine,
        DADA(alpha=0.5, use_cp=True, backend="jax"), seed=0,
    )
    # ready widths 1..15 at NT=6 → buckets {8, 16} × (read, write) CSR
    # width variants; the affinity chains are no longer part of the key
    grown = len(be._matrix_fns) - n_before
    assert grown <= 8, f"unbounded retraces: {grown} new program signatures"


# ---------------------------------------------------------------------------
# program spans and transfer counters (repro.core.obs)


PHASES = ("pack", "upload", "dispatch", "readback")


def test_spans_leave_placements_bit_equal_and_count_transfers(force_jax):
    """With the recorder on, a device-scored DADA+CP schedule places as it
    does with it off; every device-scored activation runs one program, with
    one span of each of its phases, and makes 1 upload and 1 read-back: its
    inputs go to the device as one packed buffer, and C, the affinity order
    and λ come back as one."""
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
    assert n_on["uploads"] == n_on["readbacks"] == n_on["fused"] == n_on["device"]

    roots = {s.id for s in spans if s.name == "dada.place"}
    assert all(s.root in roots for s in spans)
    per_root = {}
    for s in spans:
        per_root.setdefault(s.root, []).append(s.name)
    scored = [names for names in per_root.values() if "score.dispatch" in names]
    assert len(scored) == n_on["device"]
    for names in scored:
        for phase in PHASES:
            assert names.count(f"score.{phase}") == 1, (phase, names)
        assert not any(name.startswith("search.") for name in names), names
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
    assert names == {"dada_score_matrices", "dada_score_and_search", "heft_select"}
    (n_pad, n_res), fn = next(iter(be._heft_fns.items()))
    f64 = be.f64.encode(np.zeros(0)).dtype
    rows = jax.ShapeDtypeStruct((n_pad, n_res), f64)
    with jax.enable_x64(True):
        text = fn.lower(rows, rows, jax.ShapeDtypeStruct((n_pad,), bool),
                        jax.ShapeDtypeStruct((n_res,), f64),
                        jax.ShapeDtypeStruct((), f64)).as_text()
    assert "@jit_heft_select" in text
