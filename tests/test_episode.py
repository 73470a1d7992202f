"""Surrogate episode engine (``REPRO_SCHED_EXACT=0``) vs the exact oracle.

Correctness contract of :mod:`repro.core.episode` is *ranking fidelity*,
not bit-equality: on paper-size traces the surrogate must order the
strategies (makespan and transferred bytes) the way the exact engine
does, for every pair the oracle separates by a clear margin. On top of
that, the padded/batched episode must be provably insensitive to its own
padding: batch-axis permutations, batch padding (``pad_to``) and step
padding (``extra_steps``) are bit-level no-ops.
"""
import dataclasses
from functools import partial

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from _hypothesis_compat import given, settings, st

from repro.configs.paper_machine import paper_machine
from repro.core import cached_graph, run_batch, run_simulation
from repro.core import episode as ep
from repro.linalg.cholesky import cholesky_graph
from repro.linalg.lu import lu_graph
from repro.linalg.qr import qr_graph
from repro.sched import resolve
from repro.sched.config import SchedConfig

CFG = SchedConfig(backend="jax")

SPECS = ("heft", "ws", "dada?alpha=0", "dada?alpha=0.5&use_cp=1")
N_SEEDS = 20
SEEDS = tuple(1234 + i for i in range(N_SEEDS))
NOISE = 0.03
# a pair of strategies counts as "separated" when the oracle's means
# differ by more than this fraction — closer pairs are near-ties
# (cf. C4: HEFT vs dual on QR) whose order sits inside the surrogate's
# documented ~±10% relative error and is not part of the contract
MARGIN = 0.10

KERNELS = {
    "cholesky": cholesky_graph,
    "lu": lu_graph,
    "qr": qr_graph,
}


def _graph(kernel: str, nt: int):
    return cached_graph(partial(KERNELS[kernel], nt, 256, with_fns=False))


def _oracle_means(graph, machine):
    """Mean (makespan, total_bytes) per spec through the exact engine."""
    out = {}
    for spec in SPECS:
        mks, gbs = [], []
        for seed in SEEDS:
            r = run_simulation(
                graph, machine, resolve(spec), seed=seed, noise=NOISE
            )
            mks.append(r.makespan)
            gbs.append(r.total_bytes)
        out[spec] = (float(np.mean(mks)), float(np.mean(gbs)))
    return out


def _surrogate_means(graph, machine):
    items = [
        {"graph": graph, "machine": machine, "strategy": spec,
         "seed": seed, "noise": NOISE}
        for spec in SPECS
        for seed in SEEDS
    ]
    results = run_batch(items, config=CFG)
    out = {}
    for k, spec in enumerate(SPECS):
        rs = results[k * N_SEEDS : (k + 1) * N_SEEDS]
        assert all(r.strategy == spec for r in rs)
        out[spec] = (
            float(np.mean([r.makespan for r in rs])),
            float(np.mean([r.total_bytes for r in rs])),
        )
    return out


def _assert_separated_pairs_ordered_alike(
    oracle, surrogate, axis, label, specs=SPECS
):
    """Every pair the oracle clearly separates, the surrogate orders the
    same way; oracle near-ties impose nothing."""
    bad = ep.ranking_mismatches(oracle, surrogate, axis, specs, MARGIN)
    assert not bad, f"{label}: " + "; ".join(bad)


@pytest.mark.parametrize("kernel", sorted(KERNELS))
@pytest.mark.parametrize("nt", [8, 16])
def test_ranking_fidelity(kernel, nt):
    """Strategy orderings (makespan and bytes) survive the surrogate on
    paper-size traces, at a transfer-light and a transfer-heavy machine
    shape, across 20 seeds."""
    graph = _graph(kernel, nt)
    # both machine shapes at the cheap size; the paper shape runs at the
    # transfer-heavy 8-GPU box only (the oracle side is 20 Python sims
    # per strategy, and the 2-GPU orderings are already pinned at NT=8)
    for n_gpus in (2, 8) if nt == 8 else (8,):
        machine = paper_machine(n_gpus)
        oracle = _oracle_means(graph, machine)
        surrogate = _surrogate_means(graph, machine)
        tag = f"{kernel} nt={nt} gpus={n_gpus}"
        _assert_separated_pairs_ordered_alike(
            oracle, surrogate, 0, f"{tag} makespan"
        )
        # bytes ordering is asserted over the affinity family only: blind
        # work stealing's transfer volume in the oracle comes from
        # randomized victim churn, which a deterministic surrogate cannot
        # (and need not) reproduce — the contract for ws is its makespan
        # spread, checked above and below
        _assert_separated_pairs_ordered_alike(
            oracle, surrogate, 1, f"{tag} bytes",
            specs=tuple(s for s in SPECS if s != "ws"),
        )
        # blind work stealing is the paper's known-bad baseline: the
        # surrogate must reproduce it as the clear makespan loser
        worst = max(SPECS, key=lambda s: surrogate[s][0])
        assert worst == "ws", f"{tag}: surrogate worst is {worst}, not ws"


# ---------------------------------------------------------------------------
# invariance properties: padding and batch order are bit-level no-ops


def _small_setup():
    graph = _graph("cholesky", 4)
    machine = paper_machine(2)
    plan = ep.build_plan(graph, machine, n_u=3)
    isg, val, mc, lg = ep.machine_axes(machine, plan.n_res)
    rows = [
        ("heft", 1), ("ws", 2), ("dada?alpha=0", 3),
        ("dada?alpha=0.5&use_cp=1", 4), ("dada?alpha=1", 5),
    ]
    B = len(rows)
    params = [ep.surrogate_params(s) for s, _ in rows]
    batch = ep.EpisodeBatch(
        is_gpu=np.stack([isg] * B),
        valid_res=np.stack([val] * B),
        mem_col=np.stack([mc] * B),
        link_grp=np.stack([lg] * B),
        alpha=np.array([p[0] for p in params]),
        use_cp=np.array([p[1] for p in params]),
        ws_pref=np.array([p[2] for p in params], dtype=bool),
        noise=np.stack(
            [ep.noise_factors(sd, NOISE, plan.n, plan.n_pad) for _, sd in rows]
        ),
        cap=np.full(B, np.inf),
    )
    return plan, batch


def _take(batch, idx):
    return dataclasses.replace(
        batch,
        **{
            f.name: getattr(batch, f.name)[idx]
            for f in dataclasses.fields(batch)
        },
    )


@pytest.fixture(scope="module")
def small_episode():
    plan, batch = _small_setup()
    base = ep.run_episodes(plan, batch, config=CFG)
    return plan, batch, base


@given(pad_to=st.sampled_from([8, 16, 24]), extra=st.sampled_from([0, 7]))
@settings(max_examples=12, deadline=None)
def test_padding_invariance(small_episode, pad_to, extra):
    """Batch padding and step padding never change any configuration's
    result — padded rows and padded steps are provable no-ops."""
    plan, batch, base = small_episode
    out = ep.run_episodes(
        plan, batch, config=CFG, pad_to=pad_to, extra_steps=extra
    )
    for key in ("makespan", "total_bytes", "n_placed"):
        np.testing.assert_array_equal(out[key], base[key], err_msg=key)


@given(perm=st.permutations(list(range(5))))
@settings(max_examples=12, deadline=None)
def test_batch_permutation_invariance(small_episode, perm):
    """Row order on the batch axis is irrelevant: configurations don't
    interact."""
    plan, batch, base = small_episode
    idx = np.array(perm)
    out = ep.run_episodes(plan, _take(batch, idx), config=CFG)
    for key in ("makespan", "total_bytes", "n_placed"):
        np.testing.assert_array_equal(out[key], base[key][idx], err_msg=key)


def test_every_task_placed(small_episode):
    plan, _, base = small_episode
    assert (base["n_placed"] == plan.n).all()


# ---------------------------------------------------------------------------
# engine plumbing


def test_run_batch_preserves_input_order():
    graph = _graph("cholesky", 4)
    m2, m4 = paper_machine(2), paper_machine(4)
    # interleave machines and strategies: grouping must not leak into
    # result order
    items = [
        {"graph": graph, "machine": m, "strategy": s, "seed": sd,
         "noise": NOISE}
        for sd in (1, 2)
        for m in (m2, m4)
        for s in ("heft", "dada?alpha=0.5")
    ]
    fwd = run_batch(items, config=CFG)
    rev = run_batch(list(reversed(items)), config=CFG)
    for a, b in zip(fwd, reversed(rev)):
        assert a.strategy == b.strategy and a.seed == b.seed
        assert a.makespan == b.makespan
        assert a.total_bytes == b.total_bytes


def test_run_batch_spans_share_one_root_across_worker_threads():
    """With the span recorder on, every span of one run_batch call, those of
    the chunks dispatched on worker threads included, has the call's
    ``batch.run`` as its root, and results do not change."""
    from repro.core import obs

    graph = _graph("cholesky", 4)
    items = [{"graph": graph, "machine": paper_machine(2), "strategy": "heft",
              "seed": sd, "noise": NOISE} for sd in range(40)]  # three chunks
    off = run_batch(items, config=CFG)
    obs.drain()
    obs.enable(True)
    try:
        on = run_batch(items, config=CFG)
    finally:
        obs.enable(False)
    spans = obs.drain()
    assert [r.makespan for r in on] == [r.makespan for r in off]
    (root,) = [s for s in spans if s.name == "batch.run"]
    assert all(s.root == root.id for s in spans)
    names = [s.name for s in spans]
    for phase in ("upload", "dispatch", "readback"):
        assert names.count("episode." + phase) == 3
    assert names.count("episode.pack") == 6  # configuration arrays, then the plan's


def test_pallas_route_matches_jnp():
    """REPRO_SCHED_PALLAS=1 routes the episode's transfer rows through the
    Pallas CSR kernel (interpret mode on CPU) with identical results."""
    plan, batch = _small_setup()
    off = ep.run_episodes(
        plan, batch, config=dataclasses.replace(CFG, pallas="0")
    )
    on = ep.run_episodes(
        plan, batch, config=dataclasses.replace(CFG, pallas="1")
    )
    np.testing.assert_allclose(on["makespan"], off["makespan"], rtol=1e-6)
    np.testing.assert_array_equal(on["n_placed"], off["n_placed"])
    np.testing.assert_allclose(
        on["total_bytes"], off["total_bytes"], rtol=1e-6
    )


def test_capacity_axis_adds_traffic():
    """A tight device-memory cap can only add transferred bytes (eviction
    write-backs and re-fetches), never remove them."""
    graph = _graph("cholesky", 8)
    machine = paper_machine(2)
    items = [
        {"graph": graph, "machine": machine, "strategy": "dada?alpha=0.5",
         "seed": 7, "noise": NOISE, "capacity": cap}
        for cap in (0, 8 * 1024 * 1024)
    ]
    unbounded, bounded = run_batch(items, config=CFG)
    assert bounded.total_bytes >= unbounded.total_bytes
    assert np.isfinite(bounded.makespan)


def test_surrogate_params_rejects_unmapped_policies():
    with pytest.raises(ValueError, match="surrogate"):
        ep.surrogate_params("random")


def test_exact_knob_validation():
    """REPRO_SCHED_EXACT=0 demands the jax backend; malformed surrogate
    knobs fail loudly."""
    with pytest.raises(ValueError, match="REPRO_SCHED_BACKEND"):
        SchedConfig(backend="numpy", exact=False)
    with pytest.raises(ValueError, match="REPRO_SCHED_BATCH"):
        SchedConfig.from_env({"REPRO_SCHED_BATCH": "0"})
    with pytest.raises(ValueError, match="REPRO_SCHED_EXACT"):
        SchedConfig.from_env({"REPRO_SCHED_EXACT": "maybe"})
    cfg = SchedConfig.from_env(
        {"REPRO_SCHED_EXACT": "0", "REPRO_SCHED_BACKEND": "jax",
         "REPRO_SCHED_BATCH": "64"}
    )
    assert cfg.exact is False and cfg.batch == 64
