"""Pluggable placement-scoring backends (``REPRO_SCHED_BACKEND=numpy|jax``).

The scheduling strategies (``dada.py``, ``heft.py``) are written against the
numpy/scalar scoring path; this module adds an optional JAX backend that
accelerates the two placement hot spots on wide activations:

  * **fused score matrices** — the (ready × resources) duration / transfer /
    affinity matrices come out of one jitted call over padded CSR slices
    (reads and writes are padded to static shapes so retraces stay bounded),
    with the CSR-incidence → transfer-time reduction through the shared
    in-order hop fold of ``repro.kernels.sched_score`` (XLA: the
    scores are f64, and the Pallas kernel, which has no f64 lowering on
    TPU, serves the f32 surrogate episodes instead);
  * **batched λ-probe search** — DADA's binary search on the makespan guess
    λ runs as **one jitted dispatch** (a ``lax.while_loop``, no Python
    loop): each iteration computes the 2^d−1 midpoints reachable within
    the next ``d`` bisection steps (a speculative midpoint tree), evaluates
    the whole λ grid in one vmapped sweep of the feasibility verdict, and
    walks the tree with the verdicts. The λ trajectory (every probe value,
    every accept/reject and the final accepted λ) is bit-identical to the
    Python binary-search loop. On CPU the default depth is 1 (the tree
    degenerates to plain bisection — speculative probes cost real time on
    a single core); on gpu/tpu it is 5, where the 31-probe vmap rides the
    accelerator for free;
  * **one program per DADA activation** — ``dada_score_and_search``
    scores the matrices, orders DADA's affinity preferences (best resource
    per row, the (−score, tid) sort, the per-resource chains), sums the λ
    upper bound and runs the λ search in one dispatch, taking one packed
    int64 buffer and returning one (:class:`Packed`): a TPU pays a fixed
    latency per transfer and per dispatch, whatever its size.

Bit-for-bit contract: the backend only ever computes *score values* (which
are IEEE-f64 op-for-op identical to the numpy path), DADA's *affinity
order* (by the host's tolerance rule and sort key) and *feasibility
verdicts*; the placement for the accepted λ is always rebuilt by the
strategy's own Python ``try_build``, so decisions — including tie-breaks —
cannot drift. ``tests/test_backend.py`` enforces both levels. A TPU has no
IEEE f64 (XLA emulates it with pairs of f32, which changes values in their
last bits), so there the programs compute on the int64 bit patterns of
their f64 values with integer-exact IEEE arithmetic (``repro.core.f64``);
``tests/test_f64.py`` holds that arithmetic to numpy's bits and
``chip_smoke.py`` holds the chip's placements to numpy's.

The feasibility verdict reproduces ``try_build``'s boolean without its
early exits (overflow flags are sticky, loads accumulate through the same
op sequence), which admits structural speedups that keep bit-equal
results:

  * the **affinity phase decomposes into per-resource chains**: each
    by-score entry only reads/writes its own resource's load, and a
    resource takes a prefix of its chain, so the chains' loads are folded
    once per search and each probe only compares and gathers
    (:func:`affinity_prefix`); the per-task assignment flags come back
    through one gather;
  * the flexible phase runs on **split CPU/GPU load lanes** (the paper's
    Algorithm 2 only ever takes a min over one class at a time), with
    first-occurrence ``argmin`` preserving the scalar tie-break;
  * the balance passes (dedicated rows, then flexible ones) **loop only
    over the rows a live probe owns**: a probe that failed before its
    balance phase owns none, and most rows went to the affinity phase.
    Each pass is one loop, of data-dependent length, over the union of
    the grid's live probes' rows in pass order; a probe applies a step only
    on a row of its own, so it sees its own rows in its own order. A
    ``lax.cond`` cannot skip this work: under ``vmap`` a batched
    predicate turns it into a select that runs both branches, and the
    dedicated pass has rows in most λ-loop iterations. ``counts`` keeps
    the steps run and the ``2 · n_pad`` per iteration that passes over
    every padded row would run (``balance_steps``,
    ``balance_steps_padded``).

The backend is selected per strategy instance (``DADA(backend="jax")``),
falling back to the scheduling configuration (``repro.sched.SchedConfig``,
itself parsed once from ``REPRO_SCHED_BACKEND`` et al. with validation)
and defaulting to numpy. JAX is imported lazily; a jax backend that
cannot be built raises instead of degrading to numpy.

Knobs (all parsed/validated by ``SchedConfig.from_env``; this module never
reads ``os.environ`` directly):
  REPRO_SCHED_BACKEND       numpy (default) | jax
  REPRO_SCHED_JAX_MIN       ready-set width from which the jax path engages
                            (default 32; set 1 to force it everywhere)
  REPRO_SCHED_LAMBDA_DEPTH  speculative bisection depth d (default: 1 on
                            cpu, 5 on gpu/tpu; 1-8)
"""
from __future__ import annotations

import functools
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import obs
from .f64 import for_platform as f64_for_platform
from .machine import HOST_MEM

DEFAULT_JAX_MIN = 32


def _resolve_config(config=None):
    """The active ``SchedConfig`` (lazy import: repro.sched.policies
    imports this module back for the strategy classes)."""
    if config is not None:
        return config
    from repro.sched.config import current_config

    return current_config()

_TINY = 1e-12  # must match dada._TINY

_BACKENDS = ("numpy", "jax")


def backend_name(explicit: Optional[str] = None, config=None) -> str:
    """Resolve the backend name: explicit arg > SchedConfig > ``numpy``."""
    if explicit is None:
        return _resolve_config(config).backend
    name = explicit.lower()
    if name not in _BACKENDS:
        raise ValueError(
            f"unknown scheduling backend {name!r} (choose from {_BACKENDS})"
        )
    return name


def jax_min_wide(config=None) -> int:
    """Ready-set width from which the jax path engages (config-tunable)."""
    return _resolve_config(config).jax_min


# built backends keyed by the config fields the backend actually consumes
# (lambda_depth, jax_min) — the typical process uses one config and hence
# one instance (its jit caches are the expensive part), but an explicit
# per-strategy SchedConfig must not silently inherit the first caller's
# settings
_JAX_BACKENDS: Dict[tuple, "JaxScoringBackend"] = {}


def get_backend(explicit: Optional[str] = None, config=None):
    """Return the scoring backend: ``None`` for numpy, else the jax backend.

    A jax backend that cannot be built raises: a run that asked for the
    device path never degrades to numpy behind the caller's back.
    """
    config = _resolve_config(config)
    if backend_name(explicit, config) == "numpy":
        return None
    key = (config.lambda_depth, config.jax_min)
    be = _JAX_BACKENDS.get(key)
    if be is None:
        be = _JAX_BACKENDS[key] = JaxScoringBackend(config)
    return be


def _reset_backend_cache() -> None:
    """Test hook: forget built backends."""
    _JAX_BACKENDS.clear()


_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX read it at import and
    it is left alone; otherwise the cache lives in one fixed, git-ignored
    directory of the checkout (a moving path would never hit).
    """
    import jax

    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", str(_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)


def accelerator_initialised() -> bool:
    """True once this process has initialised a non-CPU JAX backend, and so
    holds the chip (checked without initialising a backend itself)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    # private: no public call answers this without initialising a backend
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized() and jax.default_backend() != "cpu"


def _pad_rows(x, n_pad: int) -> np.ndarray:
    """The f64 rows of ``x``, then zero rows up to ``n_pad``."""
    x = np.asarray(x, dtype=np.float64)
    out = np.zeros((n_pad,) + x.shape[1:])
    out[:len(x)] = x
    return out


def _bucket(n: int, lo: int = 8) -> int:
    """Next power-of-two ≥ n (≥ lo): bounds distinct jit signatures."""
    b = lo
    while b < n:
        b *= 2
    return b


class ScoringBackendMixin:
    """Lazy, cached scoring-backend resolution shared by the strategy
    classes (DADA, HEFT): one place defines the fallback semantics.

    ``config`` is the typed :class:`repro.sched.SchedConfig`; when None
    the process-wide environment-derived config applies at resolution
    time (not at construction, so strategies stay picklable and cheap)."""

    def _init_backend(self, backend: Optional[str], config=None) -> None:
        self.backend_name = backend
        self.config = config
        self._backend = None
        self._backend_resolved = False

    def _scoring_backend(self):
        if not self._backend_resolved:
            self._backend = get_backend(self.backend_name, self.config)
            self._backend_resolved = True
        return self._backend


def _x64_scoped(method):
    """Run a backend method under a temporarily-enabled x64 context so the
    f64 scoring math never leaks into the process-wide jax config."""
    @functools.wraps(method)
    def wrapper(self, *args, **kwargs):
        with self._x64():
            return method(self, *args, **kwargs)

    return wrapper


class Packed:
    """The layout of one int64 buffer that carries a program's per-call
    values across the host–device boundary in one transfer: a TPU pays a
    fixed latency per transfer (about 0.35 ms on a v5e), whatever its size.

    ``fields`` are ``(name, shape, kind)``, ``kind`` one of ``"f64"`` (the
    value's bit pattern), ``"i64"``, ``"i32"`` and ``"bool"`` (0 or 1).
    Offsets follow from the shapes alone, so a program whose key fixes the
    shapes slices its buffer at offsets known when it is traced. ``pack``
    and ``split`` run on the host, ``unpack`` and ``join`` inside the
    program, in the f64 arithmetic ``F`` (``repro.core.f64``). Every value
    crosses bit for bit.
    """

    def __init__(self, fields: Sequence[Tuple[str, tuple, str]]) -> None:
        self.fields = []
        off = 0
        for name, shape, kind in fields:
            size = int(np.prod(shape, dtype=np.int64))
            self.fields.append((name, tuple(shape), kind, off, size))
            off += size
        self.size = off

    def pack(self, values: Dict[str, object]) -> np.ndarray:
        """``values`` (each of its field's shape) in one host buffer."""
        buf = np.empty(self.size, dtype=np.int64)
        as_f64 = buf.view(np.float64)
        for name, shape, kind, off, size in self.fields:
            v = np.asarray(values[name], dtype=np.float64 if kind == "f64" else None)
            if v.shape != shape:
                raise ValueError(f"packed field {name!r}: shape {v.shape}, not {shape}")
            (as_f64 if kind == "f64" else buf)[off:off + size] = v.reshape(-1)
        return buf

    def unpack(self, buf, F) -> Dict[str, object]:
        """The fields of the device buffer ``buf``, typed as ``F`` computes."""
        import jax.numpy as jnp

        out = {}
        for name, shape, kind, off, size in self.fields:
            x = buf[off:off + size].reshape(shape)
            if kind == "f64":
                x = F.from_bits(x)
            elif kind == "i32":
                x = x.astype(jnp.int32)
            elif kind == "bool":
                x = x != 0
            out[name] = x
        return out

    def join(self, values: Dict[str, object], F):
        """``values`` (device arrays, as ``F`` computes) in one device buffer."""
        import jax.numpy as jnp

        parts = []
        for name, shape, kind, _, _ in self.fields:
            x = values[name]
            x = F.to_bits(x) if kind == "f64" else x.astype(jnp.int64)
            parts.append(x.reshape(-1))
        return jnp.concatenate(parts)

    def split(self, buf) -> Dict[str, np.ndarray]:
        """The fields of a buffer read back to the host (f64 as ``float64``)."""
        buf = np.asarray(buf, dtype=np.int64)
        as_f64 = buf.view(np.float64)
        out = {}
        for name, shape, kind, off, size in self.fields:
            x = (as_f64 if kind == "f64" else buf)[off:off + size].reshape(shape)
            if kind == "i32":
                x = x.astype(np.int32)
            elif kind == "bool":
                x = x != 0
            out[name] = x
        return out


@functools.lru_cache(maxsize=None)
def _score_layout(key) -> Tuple[Packed, Packed]:
    """The score program's packed inputs and outputs, from its key."""
    (n_pad, r_pad, w_pad, _, n_res,
     want_x, x_rows, want_s, want_c, _, want_bias, peer) = key
    ins, outs = [], []
    if want_x:
        ins += [("read_masks", (n_pad, r_pad), "i64"), ("per_read", (n_pad, r_pad), "f64")]
        if peer:
            ins.append(("per_read_peer", (n_pad, r_pad), "f64"))
        if x_rows:
            outs.append(("X", (n_pad, n_res), "f64"))
    if want_bias:
        ins.append(("x_bias", (n_pad, n_res), "f64"))
    if want_s:
        ins += [("write_masks", (n_pad, w_pad), "i64"),
                ("write_weights", (n_pad, w_pad), "f64")]
        outs.append(("S", (n_pad, n_res), "f64"))
    if want_c:
        ins += [("p_cpu", (n_pad,), "f64"), ("p_gpu", (n_pad,), "f64")]
        outs.insert(0, ("C", (n_pad, n_res), "f64"))
    return Packed(ins), Packed(outs)


# the λ search's scalars, in the order of its packed inputs
_SEARCH_SCALARS = (
    ("no_cpus", "bool"), ("no_gpus", "bool"), ("alpha", "f64"), ("two_alpha", "f64"),
    ("area", "f64"), ("off_total", "f64"), ("max_off", "f64"), ("n_res_f", "f64"),
    ("eps_rel", "f64"), ("max_iters", "i32"),
)


def _search_fields(n_pad: int, n_res: int, n_cpu: int, n_gpu: int) -> list:
    """The λ-independent inputs of the λ search that the host packs, less
    the per-class durations and the affinity chains."""
    return ([("loads0", (n_res,), "f64"), ("valid", (n_pad,), "bool"),
             ("flex_ord", (n_pad,), "i32"), ("cpu_idx", (n_cpu,), "i32"),
             ("gpu_idx", (n_gpu,), "i32")]
            + [(name, (), kind) for name, kind in _SEARCH_SCALARS])


def _search_vals(n: int, n_pad: int, resources, s: Dict[str, object]) -> dict:
    """The values of ``_search_fields`` from the search's inputs ``s`` (the
    ``search`` of :meth:`JaxScoringBackend.score_matrices`)."""
    accel = [r.is_accelerator for r in resources]
    # padded flex_order entries point at row 0; the search masks them with
    # the position-validity of `valid` (True exactly for k < n)
    ford = np.zeros(n_pad, dtype=np.int64)
    ford[:n] = s["flex_order"]
    alpha = s["alpha"]
    return dict(
        loads0=s["offsets"], valid=np.arange(n_pad) < n, flex_ord=ford,
        cpu_idx=[j for j, a in enumerate(accel) if not a],
        gpu_idx=[j for j, a in enumerate(accel) if a],
        no_cpus=s["no_cpus"], no_gpus=s["no_gpus"], alpha=alpha,
        two_alpha=2.0 + alpha, area=s["area"], off_total=s["off_total"],
        max_off=s["max_off"], n_res_f=float(len(resources)),
        eps_rel=s["eps_rel"], max_iters=s["max_iters"],
    )


@functools.lru_cache(maxsize=None)
def _search_layout(key) -> Packed:
    """The λ search's packed inputs, from its key: the affinity chains are
    padded to the rows, and their length is a value, not a shape."""
    n_pad, n_res, n_cpu, n_gpu = key[:4]
    fields = [("p_cpu", (n_pad,), "f64"), ("p_gpu", (n_pad,), "f64"),
              ("chain_cost", (n_pad, n_res), "f64"),
              ("chain_valid", (n_pad, n_res), "bool"),
              ("task_slot", (n_pad,), "i32"), ("chain_len", (), "i32")]
    fields += _search_fields(n_pad, n_res, n_cpu, n_gpu) + [("upper0", (), "f64")]
    return Packed(fields)


# what a λ search returns (``lambda_search``), read back with it: the final
# λ, and the loop steps its balance passes ran and the padded scans' steps
_SEARCH_OUTS = Packed([("lam", (), "f64"), ("balance_steps", (), "i32"),
                       ("balance_steps_padded", (), "i32")])
_SEARCH_NAMES = tuple(f[0] for f in _SEARCH_OUTS.fields)


@functools.lru_cache(maxsize=None)
def _fused_layout(key) -> Tuple[Packed, Packed]:
    """The one-dispatch DADA program's packed inputs and outputs, from its
    key: the score program's key, then the search's machine flags."""
    n_pad, n_res, want_s = key[0], key[4], key[7]
    n_cpu, n_gpu = key[12:14]
    ins, _ = _score_layout(key[:12])
    fields = [f[:3] for f in ins.fields] + _search_fields(n_pad, n_res, n_cpu, n_gpu)
    # the host's part of the λ upper bound: sum(max(p_cpu, p_gpu)) + max_off
    fields.append(("upper_host", (), "f64"))
    outs = [("C", (n_pad, n_res), "f64")]
    if want_s:
        fields.append(("tids", (n_pad,), "i64"))
        outs += [("ord_row", (n_pad,), "i32"), ("ord_rid", (n_pad,), "i32"),
                 ("n_pref", (), "i32")]
    outs += [("upper0", (), "f64")] + [f[:3] for f in _SEARCH_OUTS.fields]
    return Packed(fields), Packed(outs)


def call_program(prog: str, fn, args, reads, counts: Optional[dict] = None):
    """Run the jitted ``fn`` as three traced phases: ``<prog>.upload`` puts
    the host values of ``args`` on the device, ``<prog>.dispatch`` calls the
    program, ``<prog>.readback`` copies ``reads`` of its outputs back.

    ``args`` are ``(to_device, value)`` pairs in ``fn``'s argument order,
    ``to_device`` None for a value already on the device; ``reads`` are
    ``(output, decode)`` pairs, ``output`` the index into the program's
    output tuple (None for a lone output). Returns the raw outputs and the
    decoded ones, in the order of ``reads``. ``counts``, where given, gains
    the ``uploads`` and ``readbacks`` made.
    """
    with obs.span(prog + ".upload"):
        dev = [x if up is None else up(x) for up, x in args]
    with obs.span(prog + ".dispatch"):
        out = fn(*dev)
    with obs.span(prog + ".readback"):
        host = [dec(out if i is None else out[i]) for i, dec in reads]
    if counts is not None:
        counts["uploads"] += sum(up is not None for up, _ in args)
        counts["readbacks"] += len(reads)
    return out, host


# ---------------------------------------------------------------------------
# DADA's order and λ search as traced functions: each program that runs them
# (``dada_lambda_search``, ``dada_score_and_search``) traces these, in the
# f64 arithmetic ``F`` (``repro.core.f64``)


def chain_loads(F, loads0, chain_cost, length):
    """Each resource's load before each entry of its affinity chain: row
    ``k`` is ``loads0`` plus the chain's first ``k`` costs, added in chain
    order; row ``k + 1`` is the load after entry ``k`` if it is taken.
    ``length`` is the longest chain; rows past it stay 0, and no verdict
    reads them. Nothing here depends on λ, so a search folds it once."""
    import jax
    import jax.numpy as jnp

    cum = jnp.zeros((chain_cost.shape[0] + 1,) + loads0.shape, loads0.dtype)

    def body(k, cum):
        return cum.at[k + 1].set(F.add(cum[k], chain_cost[k]))

    return jax.lax.fori_loop(0, length, body, cum.at[0].set(loads0))


def affinity_prefix(F, cum, chain_valid, budget, cap):
    """DADA's local affinity phase at one guess λ, from the chains' loads
    ``cum`` (:func:`chain_loads`): the loads after the phase, whether a
    taken entry overflows ``cap``, and which entries are taken.

    An entry is taken iff its resource's load before it is ≤ ``budget``;
    a skipped entry leaves the load as it was, so every later entry of its
    chain is skipped too, and a resource's takes are a prefix of its chain.
    Each probe then only compares and gathers: the verdict is the
    sequential chain scan's, bit for bit."""
    import jax
    import jax.numpy as jnp

    ok = chain_valid & F.le(cum[:-1], budget)
    takes = jax.lax.cummin(ok.astype(jnp.int32), axis=0) == 1
    bad = jnp.any(takes & F.lt(cap, cum[1:]))
    n_taken = jnp.sum(takes, axis=0, dtype=jnp.int32)
    loads = jnp.take_along_axis(cum, n_taken[None, :], axis=0)[0]
    return loads, bad, takes


def affinity_order(F, S, C, tids, valid, cols):
    """DADA's affinity preferences, ordered as ``dada.place`` orders them
    on the host: each row's best resource by the rid-ascending
    ``s > best + _TINY`` scan over ``cols`` (ascending; a column left out
    must hold no positive score), the rows that have one sorted by
    (−score, tid), and each resource's chain of them in that order.

    Returns ``(ord_row, ord_rid, n_pref, chains)``: the row and resource of
    each entry in order (the first ``n_pref`` have a preference), and the
    chains as :func:`lambda_search` takes them."""
    import jax
    import jax.numpy as jnp

    lax = jax.lax
    n_pad, n_res = C.shape
    TINY = F.const(_TINY)

    def scan_col(carry, x):
        best, best_rid = carry
        col, rid = x
        upd = F.lt(F.add(best, TINY), col)
        return (jnp.where(upd, col, best), jnp.where(upd, rid, best_rid)), None

    (best, best_rid), _ = lax.scan(
        scan_col, (jnp.zeros_like(S[:, 0]), jnp.full((n_pad,), -1, jnp.int32)),
        (S[:, cols].T, cols),
    )
    sel = valid & (best_rid >= 0)
    rows = jnp.arange(n_pad, dtype=jnp.int32)
    # tids are unique, so the order of the entries with a preference is
    # total; those without one sort last, in any order
    _, _, _, ord_row, ord_rid = lax.sort(
        (jnp.where(sel, 0, 1), -F.key(best), tids, rows, best_rid), num_keys=3
    )
    n_pref = jnp.sum(sel, dtype=jnp.int32)
    entry = rows < n_pref
    rid = jnp.maximum(ord_rid, 0)
    # an entry's position in its resource's chain: the earlier entries of
    # that resource; entries without a preference get n_pad, which every
    # scatter below drops
    same = (rid[:, None] == jnp.arange(n_res)[None, :]) & entry[:, None]
    pos = jnp.take_along_axis(jnp.cumsum(same, axis=0, dtype=jnp.int32),
                              rid[:, None], axis=1)[:, 0] - 1
    pos = jnp.where(entry, pos, n_pad)
    chain_cost = jnp.zeros_like(C).at[pos, rid].set(C[ord_row, rid], mode="drop")
    chain_valid = jnp.zeros(C.shape, bool).at[pos, rid].set(True, mode="drop")
    # each row's cell of the chains, or the cell past them (never taken)
    task_slot = jnp.zeros((n_pad,), jnp.int32).at[ord_row].set(
        jnp.where(entry, pos * n_res + rid, n_pad * n_res))
    length = jnp.max(jnp.where(entry, pos + 1, 0))
    return ord_row, ord_rid, n_pref, (chain_cost, chain_valid, task_slot, length)


def _compact(mask):
    """The indices at which ``mask`` holds, in order, at the front of an
    array as long as ``mask`` (0 past them), and how many there are."""
    import jax.numpy as jnp

    n = mask.shape[0]
    at = jnp.where(mask, jnp.cumsum(mask, dtype=jnp.int32) - 1, n)
    idx = jnp.zeros((n,), jnp.int32).at[at].set(jnp.arange(n, dtype=jnp.int32),
                                                 mode="drop")
    return idx, jnp.sum(mask, dtype=jnp.int32)


def lambda_search(F, depth, have_both, area_bound, C, v, chains=None):
    """DADA's binary search on λ as one traced loop: the final ``upper``,
    bit for bit the value the Python loop in ``dada.place`` settles on.

    ``C`` is the padded cost matrix; ``v`` holds the search's inputs by
    their packed names (``_search_fields``, ``p_cpu``, ``p_gpu`` and
    ``upper0``); ``chains`` is ``(chain_cost, chain_valid, task_slot,
    length)`` (:func:`affinity_order`), or None without an affinity phase.

    Returns ``(upper, steps, padded)``: with the final λ, the loop steps
    the balance passes ran over the whole search, and the steps passes over
    every padded row would have run: per λ-loop iteration ``n_pad`` for the
    dedicated and ``n_pad`` for the flexible pass, or ``n_pad`` for the one
    pass of a single-class machine.
    """
    import jax
    import jax.numpy as jnp

    lax = jax.lax
    add, sub, mul, lt, le = F.add, F.sub, F.mul, F.lt, F.le
    n_pad, n_res = C.shape
    K = 2 ** depth - 1
    # the probes of one λ-loop iteration: vmapped over the grid, or, with
    # one probe, unbatched, so that gathers and updates stay scalar-indexed
    # (cheap on CPU) instead of turning into batched scatters
    grid = jax.vmap if K > 1 else (lambda fn, in_axes=0: fn)
    (loads0, p_cpu, p_gpu, valid, flex_ord, cpu_idx, gpu_idx, no_cpus, no_gpus,
     alpha, two_alpha, area, off_total, max_off, n_res_f, eps_rel, max_iters,
     upper0) = map(v.get, (
         "loads0", "p_cpu", "p_gpu", "valid", "flex_ord", "cpu_idx", "gpu_idx",
         "no_cpus", "no_gpus", "alpha", "two_alpha", "area", "off_total",
         "max_off", "n_res_f", "eps_rel", "max_iters", "upper0"))
    TINY, INF, HALF = F.const(_TINY), F.const(float("inf")), F.const(0.5)
    # probe-invariant values, computed once per search
    if chains is not None:
        chain_cost, chain_valid, task_slot, length = chains
        cum = chain_loads(F, loads0, chain_cost, length)
    if have_both:
        Cf_g = C[:, gpu_idx][flex_ord]
        Cf_c = C[:, cpu_idx][flex_ord]
        gpu_mask = jnp.zeros((n_res,), bool).at[gpu_idx].set(True)
        cpu_mask = ~gpu_mask
        lanes = jnp.arange(n_res)

    def before_balance(lam):
        """One probe up to its balance phase: the cap, whether the probe
        has failed already, the loads after the affinity phase, and the
        rows left to the balance phase. With both classes those are the
        dedicated rows, whether each goes to the GPUs, and the flexible
        positions of ``flex_ord``; on one class, every row left."""
        cap = add(mul(two_alpha, lam), TINY)
        bad = lt(cap, max_off)
        if area_bound:
            bad = bad | lt(add(sub(mul(lam, n_res_f), off_total), TINY), area)
        loads = loads0

        if chains is not None:
            budget = add(mul(alpha, lam), TINY)
            loads, over, takes = affinity_prefix(F, cum, chain_valid, budget, cap)
            bad = bad | over
            flat = jnp.append(takes.reshape(-1), False)
            assigned = flat[task_slot]
        else:
            assigned = jnp.zeros((n_pad,), dtype=bool)

        rem = valid & ~assigned
        big_cpu = no_cpus | lt(lam, p_cpu)
        big_gpu = no_gpus | lt(lam, p_gpu)
        bad = bad | jnp.any(rem & big_cpu & big_gpu)
        if not have_both:
            return cap, bad, loads, (rem,)
        flex = rem & le(p_cpu, lam) & le(p_gpu, lam)
        # `valid` is a position mask (True exactly for k < n), so it also
        # masks padded flex positions
        return cap, bad, loads, (rem & ~flex, lt(lam, p_cpu), flex[flex_ord] & valid)

    # one balance step of one probe: `on` says whether the row is the
    # probe's own; a step on a row it does not own changes nothing
    def dstep(loads, bad, cap, on, to_gpu, crow):
        pool = jnp.where(to_gpu, gpu_mask, cpu_mask)
        vm = jnp.where(pool, add(loads, crow), INF)
        # first-occurrence argmin keeps the scalar tie-break
        j = jnp.argmin(F.key(vm))
        bv = vm[j]
        bad = bad | (on & lt(cap, bv))
        loads = jnp.where((lanes == j) & on, bv, loads)
        return loads, bad

    # flexible phase on split class lanes: Algorithm 2 only ever takes the
    # min over one class at a time
    def fstep(loads_g, loads_c, bad, cap, gpu_budget, on, crow_g, crow_c):
        g = jnp.argmin(F.key(loads_g))
        gl = loads_g[g]
        use_gpu = on & le(gl, gpu_budget)
        vg = add(gl, crow_g[g])
        bad = bad | (use_gpu & lt(cap, vg))
        loads_g = loads_g.at[g].set(jnp.where(use_gpu, vg, gl))
        vm = add(loads_c, crow_c)
        j = jnp.argmin(F.key(vm))
        bv = vm[j]
        use_eft = on & ~use_gpu
        bad = bad | (use_eft & lt(cap, bv))
        loads_c = loads_c.at[j].set(jnp.where(use_eft, bv, loads_c[j]))
        return loads_g, loads_c, bad

    # single-class machine: the EFT pool is every resource, in index order
    def sstep(loads, bad, cap, on, crow):
        vm = add(loads, crow)
        j = jnp.argmin(F.key(vm))
        bv = vm[j]
        bad = bad | (on & lt(cap, bv))
        loads = loads.at[j].set(jnp.where(on, bv, loads[j]))
        return loads, bad

    def balance_pass(step, carry, consts, on, per_row, rows):
        """``step`` over the rows any probe owns (``on``, probe × row), in
        order, for every probe at once: one loop over a shared list as long
        as the union of the probes' rows, each step reading one unbatched
        row of ``rows``. Each probe applies the same operations to the same
        rows in the same order as a pass over all rows would, since a step
        it does not own leaves it as it was. Returns the carry and the
        number of steps run."""
        idx, count = _compact(jnp.any(on.reshape(-1, n_pad), axis=0))
        batched = grid(step, in_axes=(0,) * (len(carry) + len(consts) + 1 + len(per_row))
                       + (None,) * len(rows))

        def body(i, carry):
            k = idx[i]
            return batched(*carry, *consts, on[..., k], *(x[..., k] for x in per_row),
                           *(x[k] for x in rows))

        return lax.fori_loop(0, count, body, tuple(carry)), count

    def verdicts(lam):
        """Feasibility of the guesses ``lam`` (the grid, or one probe) —
        the exact booleans dada's ``try_build(lam) is not None`` yields
        (early-exit order differs, the verdicts cannot: overflow flags are
        sticky and loads accumulate through the same op sequence) — and
        the balance steps run. A probe that failed before its balance
        phase owns no row of it."""
        cap, bad, loads, left = grid(before_balance)(lam)
        live = (~bad)[..., None]
        if have_both:
            ded, to_gpu, flex_o = left
            (loads, bad), n_ded = balance_pass(dstep, (loads, bad), (cap,),
                                               ded & live, (to_gpu,), (C,))
            (_, _, bad), n_flex = balance_pass(
                fstep, (loads[..., gpu_idx], loads[..., cpu_idx], bad),
                (cap, add(lam, TINY)), flex_o & live, (), (Cf_g, Cf_c))
            return ~bad, n_ded + n_flex
        (rem,) = left
        (_, bad), n = balance_pass(sstep, (loads, bad), (cap,), rem & live, (), (C,))
        return ~bad, n

    padded_per_grid = (2 if have_both else 1) * n_pad

    def searching(lower, upper, it):
        return lt(mul(eps_rel, upper), sub(upper, lower)) & (it < max_iters)

    def cond(state):
        return searching(*state[:3])

    def body(state):
        lower, upper, it, steps, padded = state
        # speculative midpoint tree (heap layout): node k covers an
        # interval; its midpoint is the probe the bisection would make on
        # reaching it. Depth-d tree = the next d probes for every possible
        # verdict path — all evaluated in one sweep of the λ grid.
        lo = [None] * K
        hi = [None] * K
        mid = [None] * K
        lo[0], hi[0] = lower, upper
        for k in range(K):
            # (lo + hi) / 2: halving is exact, so * 0.5 is the same
            mid[k] = mul(add(lo[k], hi[k]), HALF)
            if 2 * k + 2 < K:
                lo[2 * k + 1], hi[2 * k + 1] = lo[k], mid[k]
                lo[2 * k + 2], hi[2 * k + 2] = mid[k], hi[k]
        mids = jnp.stack(mid)
        feas, n = verdicts(mids if K > 1 else mids[0])
        feas = jnp.reshape(feas, (K,))
        # walk ≤ depth bisection steps, re-checking the stopping rule
        # before each (exactly like the Python while loop)
        idx = jnp.int32(0)
        for _ in range(depth):
            go = searching(lower, upper, it)
            safe = jnp.minimum(idx, K - 1)
            f = feas[safe]
            lam = mids[safe]
            lower = jnp.where(go & ~f, lam, lower)
            upper = jnp.where(go & f, lam, upper)
            it = it + go.astype(jnp.int32)
            idx = jnp.where(go, 2 * idx + jnp.where(f, 1, 2), idx)
        return lower, upper, it, steps + n, padded + padded_per_grid

    zero = jnp.int32(0)
    _, upper, _, steps, padded = lax.while_loop(
        cond, body, (F.const(0.0), upper0, zero, zero, zero))
    return upper, steps, padded


def _row_fold(F, x):
    """``((0 + x[0]) + x[1]) + …``: the sum in row order, as the host adds."""
    import jax

    acc, _ = jax.lax.scan(lambda acc, v: (F.add(acc, v), None), F.const(0.0), x)
    return acc


class JaxScoringBackend:
    """JAX implementation of the placement-scoring hot paths.

    All public methods take/return host-side numpy/python data (plus opaque
    device handles threaded between the matrices call and the λ search);
    device placement, padding to static shapes and jit-cache management are
    internal. Methods return ``None`` when an activation or machine shape
    is outside the supported envelope (caller falls back to numpy).
    """

    name = "jax"

    # envelope of the fused path: residency masks are int64 with bit
    # mem+1 per memory; wider machines take the numpy path (counted)
    _MAX_UNIQ_MEMS = 30

    def __init__(self, config=None) -> None:
        import jax  # lazy: numpy-only environments never pay this
        import jax.numpy as jnp

        config = _resolve_config(config)
        enable_compile_cache()

        # x64 is scoped per backend call (see _x64), never flipped
        # process-wide: the repo's other jax stacks (models, linalg tiles,
        # Pallas kernels) must keep their f32 defaults regardless of
        # whether a scheduling strategy was instantiated first
        self.jax = jax
        self.jnp = jnp
        self._x64 = lambda: jax.enable_x64(True)
        platform = jax.default_backend()
        self.platform = platform
        default_depth = 1 if platform == "cpu" else 5
        self.depth = (
            config.lambda_depth if config.lambda_depth is not None else default_depth
        )
        self._min_wide = config.jax_min
        # IEEE f64 in hardware, or integer-exact f64 where the platform has
        # none (TPU): either way the device's bits are numpy's
        self.f64 = f64_for_platform(platform)
        # activations scored on the device vs returned to numpy because
        # they fall outside the supported envelope (``outside``) or because
        # the device's λ was not feasible on the host (``rejected``); the
        # host->device and device->host transfers the programs made
        # (``uploads``, ``readbacks``: one packed buffer each way for each
        # DADA program call; the per-machine arrays, uploaded once, are not
        # counted); task x resource score cells computed on the device
        # (``cells_device``) and, by the strategies, on the host
        # (``cells_host``); activations whose order and λ search ran in the
        # one program that scored them (``fused``); the loop steps the λ
        # searches' balance passes ran over the rows their live probes own
        # (``balance_steps``), and the steps passes over every padded row
        # would have run (``balance_steps_padded``), both read back with λ
        self.counts = {"device": 0, "outside": 0, "rejected": 0,
                       "uploads": 0, "readbacks": 0,
                       "cells_device": 0, "cells_host": 0, "fused": 0,
                       "balance_steps": 0, "balance_steps_padded": 0}
        self._matrix_fns: Dict[tuple, object] = {}
        self._search_fns: Dict[tuple, object] = {}
        self._heft_fns: Dict[tuple, object] = {}
        self._machine_cache: Dict[tuple, dict] = {}

    # ------------------------------------------------------------------
    @property
    def min_wide(self) -> int:
        # frozen at construction from the resolved SchedConfig: per-call
        # environment scans have no place on the activation hot path, and
        # an explicitly threaded config's jax_min must win (get_backend
        # keys its cache on it)
        return self._min_wide

    # ------------------------------------------------------------------
    @_x64_scoped
    def _machine_arrays(self, resources, transfer_model) -> Optional[dict]:
        """Activation-invariant per-machine device arrays (cached)."""
        mems = tuple(r.mem for r in resources)
        accel = tuple(r.is_accelerator for r in resources)
        tm = transfer_model
        key = (mems, accel, tm.latency, tm.bandwidth,
               tm.peer_mems, tm.peer_latency, tm.peer_bandwidth)
        m = self._machine_cache.get(key)
        if m is not None:
            return m
        uniq, col_of, _ = transfer_model.mem_plan(mems)
        if len(uniq) > self._MAX_UNIQ_MEMS:
            return None
        jnp = self.jnp
        m = dict(
            uniq=tuple(uniq),
            col_of=jnp.asarray(col_of, dtype=jnp.int32),
            # full-mask residency tests shift by mem+1 per unique memory
            mem_shift=jnp.asarray(
                [u + 1 for u in uniq], dtype=jnp.int64
            ),
            host_col=jnp.asarray([mem == HOST_MEM for mem in uniq], dtype=bool),
            accel_res=jnp.asarray(accel, dtype=bool),
            latency=tm.latency,
            bandwidth=tm.bandwidth,
            # per unique memory, the residency bits one fabric hop away
            # (None on a machine without a fabric)
            peer_bits=jnp.asarray(
                [tm.peer_reach(u) for u in uniq], dtype=jnp.int64
            ) if tm.peer_mems else None,
            peer_latency=tm.peer_latency,
            peer_bandwidth=tm.peer_bandwidth,
        )
        self._machine_cache[key] = m
        return m

    @staticmethod
    def _pad_csr(
        indptr: np.ndarray, values: Sequence[np.ndarray], n_pad: int, r_pad: int
    ) -> List[np.ndarray]:
        """Scatter gathered CSR rows into dense (n_pad × r_pad) blocks."""
        n = len(indptr) - 1
        counts = indptr[1:] - indptr[:-1]
        rows = np.repeat(np.arange(n, dtype=np.int64), counts)
        cols = np.arange(int(indptr[-1]), dtype=np.int64) - np.repeat(
            indptr[:-1], counts
        )
        out = []
        for v in values:
            dense = np.zeros((n_pad, r_pad), dtype=v.dtype)
            dense[rows, cols] = v
            out.append(dense)
        return out

    # ------------------------------------------------------------------
    @_x64_scoped
    def score_matrices(
        self,
        sim,
        tids: Sequence[int],
        resources,
        *,
        p_cpu: Optional[Sequence[float]] = None,
        p_gpu: Optional[Sequence[float]] = None,
        use_cp: bool = False,
        affinity: Optional[str] = None,
        x_rows: bool = False,
        x_bias: Optional[np.ndarray] = None,
        search: Optional[dict] = None,
    ) -> Optional[dict]:
        """Fused (ready × resources) scoring matrices.

        ``x_bias`` (optional, capacity-bounded memories): an additive
        (n × resources) penalty — predicted eviction seconds — folded
        into the transfer matrix on device before ``C`` / ``X`` / the
        per-row maxima are derived, so jax scores stay bit-equal to the
        numpy path's ``x + bias`` fold.

        Returns ``{"C": list rows|None, "C_np": array|None, "C_dev":
        device array|None, "X_np": array|None, "S_np": array|None}``: cost
        ``C`` (duration + predicted transfer) when per-class durations are
        supplied, transfer times ``X`` when ``use_cp`` and ``x_rows``
        (HEFT), affinity scores ``S`` when ``affinity`` names a
        resident-weighted score. Every entry is
        bit-equal to the numpy path (same IEEE op order); ``C_dev`` is the
        padded cost matrix, left on the device. ``None`` means unsupported
        (caller takes the numpy path).

        ``search`` (DADA, with the per-class durations): the λ-independent
        inputs of DADA's λ search, by name: ``offsets``, ``flex_order``,
        ``have_both``, ``no_cpus``, ``no_gpus``, ``alpha``, ``area_bound``,
        ``area``, ``off_total``, ``max_off``, ``eps_rel``, ``max_iters`` and
        ``upper_host``, the host's part of the upper bound
        (``sum(max(p_cpu, p_gpu)) + max_off``). One program, ``dada_score_and_search``, then also orders the
        affinity preferences and runs the whole λ search, in one dispatch
        and one read-back; the result holds ``C``, ``C_np`` and ``C_dev``,
        the affinity order (``order_rows``, ``order_rids``: the row and the
        resource of each preference, best first), the search's final λ
        (``lam``) and its upper bound at the start (``upper0``). An
        affinity with no device form (``missing_bytes``) is then outside
        the envelope: the order needs its scores on the device.
        """
        from .affinity import affinity_csr_source

        with obs.span("score.pack"):
            mach = self._machine_arrays(resources, sim.transfer_model)
            if mach is None:
                self.counts["outside"] += 1
                return None
            arr = sim.arrays
            residency = sim.residency
            n = len(tids)
            n_pad = _bucket(n)
            tids_arr = np.asarray(tids, dtype=np.int64)
            uniq = mach["uniq"]

            want_x = use_cp
            peer = want_x and mach["peer_bits"] is not None
            aff_src = affinity_csr_source(affinity, arr) if affinity else None
            want_s = aff_src is not None
            if not (want_x or want_s or p_cpu is not None) or (
                    search is not None and affinity and not want_s):
                self.counts["outside"] += 1
                return None
            want_bias = want_x and x_bias is not None
            vals = {}
            if want_bias:
                vals["x_bias"] = _pad_rows(x_bias, n_pad)

            r_pad = w_pad = 0
            accel_only = False
            if want_x:
                r_indptr, r_ids, r_sizes = arr.gather_csr(
                    tids_arr, arr.read_indptr, arr.read_ids, arr.read_sizes
                )
                r_pad = _bucket(int((r_indptr[1:] - r_indptr[:-1]).max(initial=1)), lo=1)
                r_masks = residency.mask_of_ids(r_ids)
                read_masks, read_sizes = self._pad_csr(
                    r_indptr, [r_masks, r_sizes], n_pad, r_pad
                )
                vals["read_masks"] = read_masks
                # per-read one-hop times on the host, as the numpy path has them
                vals["per_read"] = np.where(
                    read_sizes <= 0.0, 0.0,
                    mach["latency"] + read_sizes / mach["bandwidth"],
                )
                if peer:
                    vals["per_read_peer"] = np.where(
                        read_sizes <= 0.0, 0.0,
                        mach["peer_latency"] + read_sizes / mach["peer_bandwidth"],
                    )

            if want_s:
                w_indptr_full, w_ids_full, w_weights_full, accel_only = aff_src
                w_indptr, w_ids, w_weights = arr.gather_csr(
                    tids_arr, w_indptr_full, w_ids_full, w_weights_full
                )
                w_pad = _bucket(int((w_indptr[1:] - w_indptr[:-1]).max(initial=1)), lo=1)
                w_masks = residency.mask_of_ids(w_ids)
                vals["write_masks"], vals["write_weights"] = self._pad_csr(
                    w_indptr, [w_masks, w_weights.astype(np.float64)], n_pad, w_pad
                )

            want_c = p_cpu is not None
            if want_c:
                vals["p_cpu"], vals["p_gpu"] = _pad_rows(p_cpu, n_pad), _pad_rows(p_gpu, n_pad)

            key = (n_pad, r_pad, w_pad, len(uniq), len(resources),
                   want_x, bool(x_rows), want_s, want_c, accel_only, want_bias, peer)
            if search is None:
                ins, outs = _score_layout(key)
                build = self._build_matrix_fn
            else:
                assert want_c and not x_rows
                vals.update(_search_vals(n, n_pad, resources, search))
                vals["upper_host"] = search["upper_host"]
                if want_s:
                    vals["tids"] = np.zeros(n_pad, dtype=np.int64)
                    vals["tids"][:n] = tids_arr
                key += (len(vals["cpu_idx"]), len(vals["gpu_idx"]),
                        bool(search["have_both"]), bool(search["area_bound"]), self.depth)
                ins, outs = _fused_layout(key)
                build = self._build_fused_fn
            fn = self._matrix_fns.get(key)
            if fn is None:
                fn = self._matrix_fns[key] = build(key)
            args = [
                (self.jax.device_put, ins.pack(vals)),
                (None, mach["mem_shift"]), (None, mach["host_col"]),
                (None, mach["col_of"]), (None, mach["accel_res"]),
            ]
            if peer:
                args.append((None, mach["peer_bits"]))

        def dec(x):
            return {k: v[:n] if v.ndim else v for k, v in outs.split(x).items()}

        raw, (host,) = call_program("score", fn, args, [(1, dec)], self.counts)
        self.counts["device"] += 1
        self.counts["cells_device"] += n * len(resources)
        out = dict(C=None, C_np=host.get("C"), C_dev=None, X_np=host.get("X"),
                   S_np=host.get("S"))
        if want_c:
            out["C_dev"] = raw[0]
            out["C"] = out["C_np"].tolist()
        if search is not None:
            self.counts["fused"] += 1
            self._count_balance(host)
            m = int(host["n_pref"]) if want_s else 0
            none = np.zeros(0, np.int32)
            out.update(order_rows=host.get("ord_row", none)[:m],
                       order_rids=host.get("ord_rid", none)[:m],
                       lam=float(host["lam"]), upper0=float(host["upper0"]))
        return out

    def _score_body(self, key):
        """The score program's computation, as a function of its unpacked
        inputs and the machine's arrays: ``(C, X, X_max, S)``, each None
        where the key does not ask for it."""
        (n_pad, r_pad, w_pad, n_u, n_res,
         want_x, x_rows, want_s, want_c, accel_only, want_bias, peer) = key
        jax, jnp = self.jax, self.jnp
        F = self.f64

        def scores(v, mem_shift, host_col, col_of, accel_res, peer_bits):
            (read_masks, per_read, per_read_peer, x_bias, write_masks,
             write_weights, p_cpu, p_gpu) = map(v.get, (
                 "read_masks", "per_read", "per_read_peer", "x_bias",
                 "write_masks", "write_weights", "p_cpu", "p_gpu"))
            X_res = None
            X_max = None
            if want_x:
                # in-order fold over the read axis: bit-equal to the
                # reduceat fold of the numpy matrix path (hops come
                # straight off the full residency masks; the formula
                # lives once, in repro.kernels.sched_score)
                from repro.kernels.sched_score import transfer_matrix_from_full

                X_u = transfer_matrix_from_full(
                    read_masks, per_read, mem_shift, host_col, add=F.add,
                    peer_bits=peer_bits, per_read_peer=per_read_peer,
                )
                X_res = X_u[:, col_of]
                if want_bias:
                    # memory-pressure penalty: the same host-computed
                    # addend the numpy path folds, applied before C and
                    # the per-row maxima derive from X
                    X_res = F.add(X_res, x_bias)
                if not x_rows:
                    # max is order-independent: equals max(row) on host
                    X_max = F.max(X_res, axis=1)
            S_res = None
            if want_s:
                def wbody(r, acc):
                    m = write_masks[:, r][:, None]
                    resident = ((m >> mem_shift[None, :]) & 1) != 0
                    w = write_weights[:, r][:, None]
                    return F.add(acc, jnp.where(resident, w, 0))

                S_u = jax.lax.fori_loop(
                    0, w_pad, wbody,
                    jnp.zeros((n_pad, n_u), dtype=write_weights.dtype),
                )
                S_res = S_u[:, col_of]
                if accel_only:
                    S_res = jnp.where(accel_res[None, :], S_res, 0)
            C = None
            if want_c:
                base = jnp.where(
                    accel_res[None, :], p_gpu[:, None], p_cpu[:, None]
                )
                C = F.add(base, X_res) if want_x else jnp.broadcast_to(
                    base, (n_pad, n_res)
                )
            return C, X_res, X_max, S_res

        return scores

    def _build_matrix_fn(self, key):
        F = self.f64
        ins, outs = _score_layout(key)
        scores = self._score_body(key)

        def dada_score_matrices(packed, mem_shift, host_col, col_of, accel_res,
                                peer_bits=None):
            """``(C, bits)``: the cost matrix, left on the device, and one
            buffer of the outputs the host reads."""
            C, X, _, S = scores(ins.unpack(packed, F), mem_shift, host_col,
                                col_of, accel_res, peer_bits)
            return C, outs.join(dict(C=C, X=X, S=S), F)

        return self.jax.jit(dada_score_matrices)

    def _build_fused_fn(self, key):
        n_res, want_x, want_s, accel_only = key[4], key[5], key[7], key[9]
        have_both, area_bound, depth = key[14:]
        jnp = self.jnp
        F = self.f64
        ins, outs = _fused_layout(key)
        scores = self._score_body(key[:12])

        def score_and_search(packed, mem_shift, host_col, col_of, accel_res,
                             peer_bits=None):
            """``(C, bits)``: the cost matrix, left on the device, and one
            buffer of what the host reads: ``C``, the affinity order, the
            final λ and the search's upper bound at the start."""
            v = ins.unpack(packed, F)
            C, _, X_max, S = scores(v, mem_shift, host_col, col_of, accel_res,
                                    peer_bits)
            valid = v["valid"]
            # the upper bound in the host's fold order: its own part, then
            # the row maxima of X in row order, then _TINY
            upper = v["upper_host"]
            if want_x:
                upper = F.add(upper, _row_fold(F, jnp.where(valid, X_max, F.const(0.0))))
            v["upper0"] = F.add(upper, F.const(_TINY))
            out = dict(C=C, upper0=v["upper0"])
            chains = None
            if want_s:
                # with accel_only, the other columns of S are 0: never a best
                cols = v["gpu_idx"] if accel_only else jnp.arange(n_res, dtype=jnp.int32)
                out["ord_row"], out["ord_rid"], out["n_pref"], chains = affinity_order(
                    F, S, C, v["tids"], valid, cols)
            out.update(zip(_SEARCH_NAMES, lambda_search(
                F, depth, have_both, area_bound, C, v, chains)))
            return C, outs.join(out, F)

        # the program's name in a device trace (jit_dada_score_and_search)
        score_and_search.__name__ = "dada_score_and_search"
        return self.jax.jit(score_and_search)

    # ------------------------------------------------------------------
    # DADA λ-probe search
    # ------------------------------------------------------------------
    @_x64_scoped
    def dada_lambda_search(
        self,
        *,
        n: int,
        n_res: int,
        offsets: Sequence[float],
        C_dev,
        p_cpu: Sequence[float],
        p_gpu: Sequence[float],
        by_score: Sequence[Tuple[float, int, int, float]],
        tid_index: Dict[int, int],
        flex_order,
        resources,
        have_both: bool,
        no_cpus: bool,
        no_gpus: bool,
        alpha: float,
        area_bound: bool,
        area: float,
        off_total: float,
        max_off: float,
        eps_rel: float,
        max_iters: int,
        upper0: float,
    ) -> float:
        """Run DADA's binary search on λ entirely on the backend, as a
        program of its own over the order the host made (``by_score``,
        ``flex_order``). DADA itself runs the search inside
        ``dada_score_and_search`` (:meth:`score_matrices` with ``search``).

        Returns the final ``upper`` — identical (bit-for-bit) to the value
        the Python loop in ``dada.place`` would settle on, because every
        probe value and every feasibility verdict is reproduced exactly.
        The caller then rebuilds the placement at that λ with its own
        ``try_build``. ``C_dev`` is the device-resident padded cost matrix
        from :meth:`score_matrices` (same ``_bucket(n)`` padding).
        """
        with obs.span("search.pack"):
            n_pad = _bucket(n)
            assert C_dev.shape == (n_pad, n_res), (C_dev.shape, n_pad, n_res)

            vals = _search_vals(n, n_pad, resources, dict(
                offsets=offsets, flex_order=flex_order, no_cpus=no_cpus,
                no_gpus=no_gpus, alpha=alpha, area=area, off_total=off_total,
                max_off=max_off, eps_rel=eps_rel, max_iters=max_iters))
            vals.update(p_cpu=_pad_rows(p_cpu, n_pad), p_gpu=_pad_rows(p_gpu, n_pad),
                        upper0=upper0)

            # Affinity phase → per-resource chains: entry k of by_score only
            # reads/writes loads[rid_k], so entries of different resources are
            # independent; within one resource the by-score order is preserved
            # by the stable sort. The search folds the chains' loads once and
            # each probe takes a prefix of every chain (affinity_prefix); each
            # task reads its own take-flag back through one gather (task_slot
            # points at the task's (chain position, rid) cell; the cell past
            # the chains, never taken, absorbs tasks without a preference).
            chain_cost = np.zeros((n_pad, n_res), dtype=np.float64)
            chain_valid = np.zeros((n_pad, n_res), dtype=bool)
            task_slot = np.full(n_pad, n_pad * n_res, dtype=np.int64)
            m = len(by_score)
            chain_len = 0
            if m:
                rids = np.fromiter((e[2] for e in by_score), np.int64, m)
                costs = np.fromiter((e[3] for e in by_score), np.float64, m)
                tis = np.fromiter(
                    (tid_index[e[1]] for e in by_score), np.int64, m
                )
                perm = np.argsort(rids, kind="stable")
                srid = rids[perm]
                first = np.searchsorted(srid, srid, side="left")
                pos = np.arange(m, dtype=np.int64) - first
                chain_len = int(pos.max()) + 1
                chain_cost[pos, srid] = costs[perm]
                chain_valid[pos, srid] = True
                task_slot[tis[perm]] = pos * n_res + srid
            vals.update(chain_cost=chain_cost, chain_valid=chain_valid,
                        task_slot=task_slot, chain_len=chain_len)

            key = (n_pad, n_res, len(vals["cpu_idx"]), len(vals["gpu_idx"]),
                   bool(have_both), bool(area_bound), self.depth)
            fn = self._search_fns.get(key)
            if fn is None:
                fn = self._build_search_fn(key)
                self._search_fns[key] = fn
            args = [(self.jax.device_put, _search_layout(key).pack(vals)), (None, C_dev)]
        _, (out,) = call_program(
            "search", fn, args, [(None, _SEARCH_OUTS.split)], self.counts)
        self._count_balance(out)
        return float(out["lam"])

    def _count_balance(self, out: Dict[str, np.ndarray]) -> None:
        """Add a λ search's balance counters, read back with its λ."""
        for k in _SEARCH_NAMES[1:]:
            self.counts[k] += int(out[k])

    def _build_search_fn(self, key):
        have_both, area_bound, depth = key[4:]
        F = self.f64
        layout = _search_layout(key)

        def search(packed, C):
            v = layout.unpack(packed, F)
            chains = (v["chain_cost"], v["chain_valid"], v["task_slot"], v["chain_len"])
            out = lambda_search(F, depth, have_both, area_bound, C, v, chains)
            return _SEARCH_OUTS.join(dict(zip(_SEARCH_NAMES, out)), F)

        # the program's name in a device trace (jit_dada_lambda_search); the
        # def is named apart from the method, which the lint would take for it
        search.__name__ = "dada_lambda_search"
        return self.jax.jit(search)

    # ------------------------------------------------------------------
    # HEFT earliest-finish-time selection
    # ------------------------------------------------------------------
    @_x64_scoped
    def heft_select(
        self,
        D_ord: np.ndarray,
        X_ord: np.ndarray,
        load_ts: Sequence[float],
        now: float,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Sequential EFT worker selection over tasks in priority order.

        ``D_ord``/``X_ord`` are (n × n_res) duration / transfer rows already
        gathered in priority order. Returns (chosen rid, eft) per task —
        the same values (1e-15 strict-improvement tie-break included) the
        scalar loop in ``heft.place`` computes.
        """
        with obs.span("heft.pack"):
            jnp = self.jnp
            F = self.f64
            n, n_res = D_ord.shape
            n_pad = _bucket(n)
            D = np.zeros((n_pad, n_res), dtype=np.float64)
            X = np.zeros((n_pad, n_res), dtype=np.float64)
            valid = np.zeros(n_pad, dtype=bool)
            D[:n] = D_ord
            X[:n] = X_ord
            valid[:n] = True
            key = (n_pad, n_res)
            fn = self._heft_fns.get(key)
            if fn is None:
                fn = self._build_heft_fn(key)
                self._heft_fns[key] = fn
            up = jnp.asarray
            args = [(up, F.encode(D)), (up, F.encode(X)), (up, valid),
                    (up, F.encode(load_ts)), (F.const, now)]
        _, (rids, efts) = call_program(
            "heft", fn, args,
            [(0, lambda x: np.asarray(x)[:n]), (1, lambda x: F.decode(x)[:n])],
            self.counts)
        return rids, efts

    def _build_heft_fn(self, key):
        n_pad, n_res = key
        jax, jnp = self.jax, self.jnp
        F = self.f64
        add, lt = F.add, F.lt

        def select(D, X, valid, load_ts, now):
            INF, EPS = F.const(float("inf")), F.const(1e-15)

            def step(lts, x):
                drow, xrow, on = x
                start = jnp.where(lt(lts, now), now, lts)
                eft = add(add(start, xrow), drow)
                # the 1e-15 strict-improvement rule is a left fold over the
                # resource lanes; n_res is small and static, so unroll it
                # into scalar selects (no fori machinery per task)
                if n_res <= 64 and F.unroll > 1:
                    bv = INF
                    bj = jnp.int32(0)
                    for r in range(n_res):
                        e = eft[r]
                        upd = lt(e, F.sub(bv, EPS))
                        bv = jnp.where(upd, e, bv)
                        bj = jnp.where(upd, jnp.int32(r), bj)
                else:
                    def rstep(r, st):
                        bv, bj = st
                        e = eft[r]
                        upd = lt(e, F.sub(bv, EPS))
                        return (
                            jnp.where(upd, e, bv),
                            jnp.where(upd, r, bj),
                        )

                    bv, bj = jax.lax.fori_loop(
                        0, n_res, rstep, (INF, jnp.int32(0))
                    )
                lts = lts.at[bj].set(jnp.where(on, bv, lts[bj]))
                return lts, (bj, bv)

            _, (rids, efts) = jax.lax.scan(
                step, load_ts, (D, X, valid), unroll=F.unroll
            )
            return rids, efts

        select.__name__ = "heft_select"  # as search.__name__ above
        return jax.jit(select)
