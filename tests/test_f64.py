"""Integer-exact IEEE f64 (``repro.core.f64.SOFT``) against numpy.

A TPU has no IEEE f64, so there the jax scoring backend computes on the
int64 bit patterns of its values. Every operation must return numpy's bits:
random operands across the whole exponent range (subnormals, overflow to
infinity), exact cancellation, rounding ties and infinities — and whole
simulations scored through the soft arithmetic must place exactly as the
numpy path does.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from repro.configs.paper_machine import paper_machine, scaled_machine
from repro.core import DADA, HEFT, run_simulation
from repro.core import backend as backend_mod
from repro.core import f64
from repro.linalg.cholesky import cholesky_graph
from repro.linalg.qr import qr_graph

SOFT = f64.SOFT


def _operands(seed: int, n: int = 60000):
    rng = np.random.default_rng(seed)
    mant = rng.random(n) + 0.5
    a = np.ldexp(mant, rng.integers(-60, 60, n))
    kind = rng.integers(0, 5, n)
    a = np.where(kind == 0, np.ldexp(mant, rng.integers(-1074, 1024, n)), a)
    a = np.where(kind == 1, np.ldexp(rng.integers(0, 1 << 52, n).astype(float), -1074), a)
    a = np.where(kind == 2, 0.0, a)
    a = np.where(np.isfinite(a), a, 1.0)
    a = np.where(rng.random(n) < 0.4, -a, a)
    b = np.roll(a, 1)
    # exact and near cancellation, wide exponent gaps, rounding ties
    k = n // 6
    b[:k] = -a[:k] * (1 + rng.integers(-3, 4, k) * 2.0 ** -52)
    with np.errstate(over="ignore"):
        b[k:2 * k] = a[k:2 * k] * 2.0 ** rng.integers(-60, 60, k)
    b[2 * k:3 * k] = np.ldexp(1.0, rng.integers(-60, 60, k)) * 2.0 ** -53 * np.sign(a[2 * k:3 * k])
    b[3 * k:3 * k + 100] = np.inf
    b[3 * k + 100:3 * k + 200] = -np.inf
    return a, b


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("op", ["add", "sub", "mul"])
def test_soft_arithmetic_matches_numpy_bits(seed, op):
    a, b = _operands(seed)
    with np.errstate(all="ignore"):
        want = {"add": a + b, "sub": a - b, "mul": a * b}[op]
    with jax.enable_x64(True):
        got = SOFT.decode(jax.jit(getattr(SOFT, op))(
            jnp.asarray(SOFT.encode(a)), jnp.asarray(SOFT.encode(b))))
    ok = (got.view(np.int64) == want.view(np.int64)) | np.isnan(want)
    bad = np.nonzero(~ok)[0][:3]
    assert ok.all(), [(a[i], b[i], got[i], want[i]) for i in bad]


def test_soft_order_matches_numpy():
    a, b = _operands(2)
    with jax.enable_x64(True):
        A, B = jnp.asarray(SOFT.encode(a)), jnp.asarray(SOFT.encode(b))
        assert (np.asarray(SOFT.lt(A, B)) == (a < b)).all()
        assert (np.asarray(SOFT.le(A, B)) == (a <= b)).all()
        M = A.reshape(600, 100)
        assert (SOFT.decode(SOFT.min(M, axis=1)) == a.reshape(600, 100).min(axis=1)).all()
        assert (SOFT.decode(SOFT.max(M, axis=1)) == a.reshape(600, 100).max(axis=1)).all()


def test_platform_picks_the_arithmetic():
    assert f64.for_platform("tpu") is f64.SOFT
    assert f64.for_platform("cpu") is f64.NATIVE
    assert f64.for_platform("gpu") is f64.NATIVE


@pytest.fixture
def soft_backend(monkeypatch):
    """The jax backend as a TPU builds it, on the CPU."""
    monkeypatch.setenv("REPRO_SCHED_JAX_MIN", "1")
    monkeypatch.setattr(backend_mod, "f64_for_platform", lambda p: f64.SOFT)
    backend_mod._reset_backend_cache()
    yield backend_mod.get_backend("jax")
    backend_mod._reset_backend_cache()


def _fingerprint(res):
    return (
        res.makespan, res.total_bytes, res.n_transfers,
        tuple((iv.tid, iv.rid, iv.start, iv.end) for iv in res.intervals),
    )


@pytest.mark.parametrize("graph,machine", [
    (lambda: cholesky_graph(6, 256, with_fns=False), lambda: paper_machine(3)),
    (lambda: qr_graph(5, 256, with_fns=False), lambda: paper_machine(8)),
    (lambda: cholesky_graph(8, 512, with_fns=False), lambda: scaled_machine(n_gpus=12, n_cpus=4)),
])
@pytest.mark.parametrize("strat", [
    lambda b: HEFT(backend=b),
    lambda b: DADA(alpha=0.5, use_cp=True, backend=b),
    lambda b: DADA(alpha=0.5, area_bound=True, backend=b),
])
def test_soft_backend_places_like_numpy(soft_backend, graph, machine, strat):
    assert soft_backend.f64 is f64.SOFT
    a = run_simulation(graph(), machine(), strat("numpy"), seed=3)
    b = run_simulation(graph(), machine(), strat("jax"), seed=3)
    assert _fingerprint(a) == _fingerprint(b)
    assert soft_backend.counts["device"] > 0
    assert soft_backend.counts["outside"] == soft_backend.counts["rejected"] == 0


# every f64 bit pattern the scoring programs can be handed: signed zeros,
# infinities, the subnormal range's ends, the normal range's ends
_EDGES = np.array([
    0.0, -0.0, np.inf, -np.inf, 5e-324, -5e-324, 2.225073858507201e-308,
    np.finfo(np.float64).tiny, np.finfo(np.float64).max, -np.finfo(np.float64).max,
    1.0, 0.1, -1e-300, 1.5e300,
])
_I32 = np.iinfo(np.int32)


@pytest.mark.parametrize("arith", [f64.NATIVE, f64.SOFT], ids=["native", "soft"])
def test_packed_boundary_round_trips_every_bit(arith):
    """One packed buffer in, typed fields in the program, one packed buffer
    out: every f64 bit pattern, flag and index survives unchanged."""
    layout = backend_mod.Packed([
        ("edges", _EDGES.shape, "f64"), ("grid", (2, 7), "f64"), ("lam", (), "f64"),
        ("flags", (4,), "bool"), ("on", (), "bool"),
        ("idx", (4,), "i32"), ("iters", (), "i32"), ("masks", (3,), "i64"),
    ])
    values = dict(
        edges=_EDGES, grid=_EDGES.reshape(2, 7)[::-1], lam=-0.0,
        flags=np.array([True, False, False, True]), on=True,
        idx=np.array([_I32.min, -1, 0, _I32.max]), iters=_I32.max,
        masks=np.array([np.iinfo(np.int64).min, 1 << 31, np.iinfo(np.int64).max]),
    )
    buf = layout.pack(values)
    assert buf.dtype == np.int64 and buf.shape == (layout.size,) == (42,)
    with jax.enable_x64(True):
        @jax.jit
        def through(packed):
            typed = layout.unpack(packed, arith)
            return typed, layout.join(typed, arith)

        typed, joined = through(jax.device_put(buf))
        native = arith is f64.NATIVE
        assert typed["edges"].dtype == (jnp.float64 if native else jnp.int64)
        assert typed["lam"].shape == () and typed["on"].dtype == jnp.bool_
        assert typed["idx"].dtype == jnp.int32 and typed["masks"].dtype == jnp.int64
    # inside the program, each field holds its value ...
    for name in ("edges", "grid", "lam"):
        got = np.asarray(arith.decode(typed[name]))
        want = np.asarray(values[name], dtype=np.float64)
        assert (got.view(np.int64) == want.view(np.int64)).all(), name
    for name in ("flags", "on", "idx", "iters", "masks"):
        assert (np.asarray(typed[name]) == values[name]).all(), name
    # ... and the buffer the program writes is the one the host packed
    assert (np.asarray(joined) == buf).all()
    back = layout.split(joined)
    assert back["edges"].dtype == np.float64 and back["idx"].dtype == np.int32
    assert back["flags"].dtype == bool
    for name, want in values.items():
        want = np.asarray(want)
        got = back[name]
        if want.dtype == np.float64:
            got, want = got.view(np.int64), want.view(np.int64)
        assert got.shape == want.shape and (got == want).all(), name


def test_packed_refuses_a_misshapen_field():
    layout = backend_mod.Packed([("p", (4,), "f64"), ("n", (), "i32")])
    with pytest.raises(ValueError, match="'p'"):
        layout.pack({"p": np.zeros(3), "n": 1})
    with pytest.raises(ValueError, match="'n'"):
        layout.pack({"p": np.zeros(4), "n": [1]})
