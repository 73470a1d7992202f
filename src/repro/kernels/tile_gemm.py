"""Pallas TPU kernel: tiled GEMM update ``C <- C + alpha * A @ op(B)``.

This is the compute hot spot of every PLASMA tile kernel the paper schedules
(gemm / syrk / ssssm / tsmqr are all GEMM-shaped updates).

TPU mapping (DESIGN.md §2 hardware adaptation):
  * grid = (M/bm, N/bn, K/bk), K innermost ("arbitrary") so the fp32
    accumulator lives in VMEM scratch across K steps while A/B blocks
    stream HBM -> VMEM;
  * block shapes default to 128x128 (MXU-aligned; 8x128 lane/sublane tiles);
  * ``preferred_element_type=float32`` keeps MXU accumulation in fp32 even
    for bf16 inputs.

VMEM budget at defaults: (bm*bk + bk*bn + 2*bm*bn) * 4B = 256 KiB << 16 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _gemm_kernel(c_in_ref, a_ref, b_ref, c_out_ref, acc_ref, *, alpha, trans_b, n_k):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = c_in_ref[...].astype(jnp.float32)

    a = a_ref[...]
    b = b_ref[...]
    if trans_b:
        b = b.T
    acc_ref[...] += alpha * jax.lax.dot(
        a, b, preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _done():
        c_out_ref[...] = acc_ref[...].astype(c_out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("alpha", "trans_b", "bm", "bn", "bk", "interpret"),
)
def gemm_update(
    c: jax.Array,
    a: jax.Array,
    b: jax.Array,
    *,
    alpha: float = -1.0,
    trans_b: bool = False,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """``C + alpha * A @ B`` (or ``A @ B.T`` when ``trans_b``)."""
    m, k_dim = a.shape
    if trans_b:
        n, kb = b.shape
    else:
        kb, n = b.shape
    assert kb == k_dim, (a.shape, b.shape)
    assert c.shape == (m, n), (c.shape, m, n)
    bm = min(bm, m)
    bn = min(bn, n)
    bk = min(bk, k_dim)
    assert m % bm == 0 and n % bn == 0 and k_dim % bk == 0, (
        "shapes must tile evenly",
        (m, n, k_dim),
        (bm, bn, bk),
    )
    n_k = k_dim // bk
    grid = (m // bm, n // bn, n_k)
    b_spec = (
        pl.BlockSpec((bn, bk), lambda i, j, k: (j, k))
        if trans_b
        else pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))
    )
    return pl.pallas_call(
        functools.partial(
            _gemm_kernel, alpha=alpha, trans_b=trans_b, n_k=n_k
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),  # C in
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),  # A
            b_spec,  # B
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), c.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(c, a, b)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret")
)
def matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Plain ``A @ B`` through the same kernel (C = 0, alpha = +1)."""
    m, _ = a.shape
    n = b.shape[1]
    c0 = jnp.zeros((m, n), dtype=a.dtype)
    return gemm_update(
        c0, a, b, alpha=1.0, trans_b=False, bm=bm, bn=bn, bk=bk, interpret=interpret
    )
