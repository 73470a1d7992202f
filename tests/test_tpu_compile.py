"""Compile the scheduler's device path for a described TPU v5e chip.

Nothing runs: each program is lowered and compiled by the TPU compiler for
one chip of a ``v5e:2x2`` topology that is described, not attached, at the
widths ``chip_smoke.py`` uses. A kernel that interpret mode accepts but
the chip's compiler refuses fails here, at no chip time. The topology is
described inside a fixture (never at import), and the persistent
compilation cache is off around the compiles: a program compiled for a
described chip cannot be read back from it.
"""
import os
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_machine import paper_machine, scaled_machine
from repro.core import DADA, HEFT, Simulator
from repro.core import episode as ep
from repro.core import f64
from repro.core.backend import DEFAULT_JAX_MIN, JaxScoringBackend
from repro.linalg import cholesky
from repro.linalg.cholesky import cholesky_graph
from repro.sched.config import SchedConfig

# phase (a) of chip_smoke.py: Cholesky NT=32 at tile 512 on 32 resources
NT, TILE = 32, 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, args, sharding):
    sds = [jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype, sharding=sharding)
           for a in args]
    return jax.jit(fn).lower(*sds).compile()


def _recording(build, calls):
    """Wrap a jit factory so the function it makes records its arguments."""

    def wrapped(key):
        fn = build(key)

        def rec(*args):
            calls.append((key, fn, args))
            return fn(*args)

        return rec

    return wrapped


def _wide_wave(graph):
    """The widest same-depth task set: the trailing-update wave."""
    depth = [0] * len(graph)
    for t in graph.tasks:
        preds = graph.pred[t.tid]
        depth[t.tid] = max((depth[p] + 1 for p in preds), default=0)
    widest = max(set(depth), key=depth.count)
    return [t for t in graph.tasks if depth[t.tid] == widest]


def _recorded_backend(calls, jax_min=DEFAULT_JAX_MIN):
    """A depth-5 backend in the chip's integer-exact f64 whose jit factories
    record into ``calls`` (the fused programs apart from the score-matrix
    programs, which share one cache)."""
    be = JaxScoringBackend(SchedConfig(backend="jax", lambda_depth=5, jax_min=jax_min))
    be.f64 = f64.for_platform("tpu")  # the chip has no IEEE f64
    for kind, attr in (("matrix", "_build_matrix_fn"), ("fused", "_build_fused_fn"),
                       ("search", "_build_search_fn"), ("heft", "_build_heft_fn")):
        if kind in calls:
            setattr(be, attr, _recording(getattr(be, attr), calls[kind]))
    return be


def _place_wave(be, strat, graph, machine, wave, n_mems):
    """One activation of ``wave``, with residency seeded over ``n_mems``
    memories; returns the cost matrix the DADA program left on the device."""
    strat._backend, strat._backend_resolved = be, True
    sim = Simulator(graph, machine, strat, seed=0)
    for k, name in enumerate(sim.arrays.data_names):
        if k % 3 == 0:
            sim.residency.write(name, k % n_mems)
    sim.push = lambda task, rid: None
    kept = []
    score = be.score_matrices

    def keeping(*args, **kw):
        out = score(*args, **kw)
        kept.append(out["C_dev"])
        return out

    be.score_matrices = keeping
    try:
        strat.place(sim, wave, None)
    finally:
        del be.score_matrices
    return kept[0] if kept else None


def _standalone_search(be, resources, C_dev):
    """The standalone λ search, as the benchmark's set-up warms it: one
    affinity chain as long as the rows."""
    n_pad, n_res = C_dev.shape
    gpu = next(j for j, r in enumerate(resources) if r.is_accelerator)
    be.dada_lambda_search(
        n=n_pad, n_res=n_res, offsets=[0.0] * n_res, C_dev=C_dev,
        p_cpu=[1.0] * n_pad, p_gpu=[1.0] * n_pad,
        by_score=[(1.0, i, gpu, 1.0) for i in range(n_pad)],
        tid_index={i: i for i in range(n_pad)}, flex_order=list(range(n_pad)),
        resources=resources, have_both=True, no_cpus=False, no_gpus=False,
        alpha=0.5, area_bound=False, area=0.0, off_total=0.0, max_off=0.0,
        eps_rel=0.01, max_iters=30, upper0=2.0 * n_pad)


@pytest.fixture(scope="module")
def backend_calls():
    """One wide activation of DADA+cp and of HEFT on the CPU, with the
    backend's jitted functions and their real arguments recorded, and the
    standalone λ search over the DADA program's cost matrix."""
    graph = cholesky_graph(NT, TILE, with_fns=False)
    machine = scaled_machine()
    wave = _wide_wave(graph)
    calls = {"matrix": [], "fused": [], "search": [], "heft": []}
    be = _recorded_backend(calls)
    C_dev = _place_wave(be, DADA(alpha=0.5, use_cp=True, backend="jax"), graph,
                        machine, wave, 24)
    _place_wave(be, HEFT(backend="jax"), graph, machine, wave, 24)
    _standalone_search(be, machine.resources, C_dev)
    assert len(wave) >= 256 and all(calls.values()), (len(wave), calls.keys())
    return calls


@pytest.fixture(scope="module")
def peer_calls():
    """One 31-wide activation of DADA+cp and of HEFT on the DGX A100
    deployment (128 resources, NVSwitch peers): the score programs with
    the fabric's fold at 128 columns, and the standalone λ search, recorded
    as for ``backend_calls``."""
    from repro.configs.dgx_a100 import dgx_a100

    graph = cholesky_graph(NT, 1024, itemsize=8, with_fns=False)
    wave = _wide_wave(graph)[:NT - 1]
    machine = dgx_a100()
    calls = {"matrix": [], "fused": [], "search": []}
    be = _recorded_backend(calls, jax_min=8)
    C_dev = _place_wave(be, DADA(alpha=0.5, use_cp=True, backend="jax"), graph,
                        machine, wave, 8)
    _place_wave(be, HEFT(backend="jax"), graph, machine, wave, 8)
    _standalone_search(be, machine.resources, C_dev)
    assert all(calls.values())
    assert calls["fused"][0][0][11], "the DADA program took no fabric"
    assert calls["matrix"][0][0][-1], "the score program took no fabric"
    return calls


@pytest.mark.parametrize("kind", ["matrix", "search", "heft", "peer_matrix", "peer_search",
                                  "fused", "peer_fused"])
def test_backend_functions_compile(one_chip, request, kind):
    """The score-matrix, one-dispatch DADA (scores, affinity order and λ
    search), standalone λ-search (depth 5, the TPU default) and HEFT EFT
    programs in the chip's integer-exact f64, at a phase-(a) width; the
    score, one-dispatch and search programs of the DGX A100 deployment at
    128 columns."""
    if kind.startswith("peer_"):
        calls = request.getfixturevalue("peer_calls")[kind[5:]]
    else:
        calls = request.getfixturevalue("backend_calls")[kind]
    for key, fn, args in calls:
        with jax.enable_x64(True):
            compiled = _compile(fn, args, one_chip)
        assert compiled.as_text()


def test_transfer_folds_compile(one_chip):
    """The Pallas transfer kernel natively in f32 (the dtype the surrogate
    feeds it), and the backend's XLA fold over the int64 bit patterns of
    its f64 times (what the backend passes on a TPU), at phase-(a) widths."""
    from repro.kernels.sched_score import (
        transfer_matrix_from_full,
        transfer_matrix_pallas,
    )

    n_pad, r_pad, n_u = 512, 4, 25
    compiled = _compile(
        transfer_matrix_pallas,
        [np.zeros((n_pad, r_pad), np.int32), np.zeros((n_pad, r_pad), np.float32),
         np.zeros(n_u, np.int32), np.zeros(n_u, bool)],
        one_chip,
    )
    assert "tpu_custom_call" in compiled.as_text()
    with jax.enable_x64(True):
        compiled = _compile(
            partial(transfer_matrix_from_full, add=f64.for_platform("tpu").add),
            [np.zeros((n_pad, r_pad), np.int64)] * 2
            + [np.zeros(n_u, np.int64), np.zeros(n_u, bool)],
            one_chip,
        )
    assert "tpu_custom_call" not in compiled.as_text()


def test_surrogate_episode_compiles(one_chip, monkeypatch):
    """One surrogate episode of phase (b) — Cholesky NT=16 on
    paper_machine(8), a 16-wide batch — through the native Pallas route."""
    from repro.core import run_batch

    calls = []
    monkeypatch.setattr(ep, "_EPISODE_CACHE", {})
    monkeypatch.setattr(ep, "_build_episode_fn",
                        _recording(ep._build_episode_fn, calls))
    graph = partial(cholesky_graph, 16, TILE, with_fns=False)
    run_batch(
        [{"graph": graph, "machine": paper_machine(8), "strategy": s, "seed": i}
         for s in ("heft", "dada?alpha=0.5&use_cp=1", "ws") for i in range(5)],
        config=SchedConfig(backend="jax", exact=False),
    )
    key, _, args = calls[0]
    native = key[:10] + (True, False) + key[12:]  # use_pallas, not interpret
    compiled = _compile(ep._build_episode_fn(native), args, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("body,n_in", [
    (cholesky._potrf, 1), (cholesky._trsm, 2), (cholesky._syrk, 2),
    (cholesky._gemm, 3),
])
def test_cholesky_tile_bodies_compile(one_chip, body, n_in):
    compiled = _compile(
        body, [np.zeros((TILE, TILE), np.float32)] * n_in, one_chip
    )
    assert compiled.as_text()
