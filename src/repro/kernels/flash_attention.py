"""Pallas TPU kernel: causal flash attention with GQA (prefill hot spot).

Online-softmax blocked attention (Rabe-Staats/FlashAttention scheme) adapted
to the TPU memory hierarchy: K/V blocks stream HBM -> VMEM along the
innermost ("arbitrary") grid axis while the running max / normalizer /
accumulator live in VMEM scratch. Q/K/V use (block_q x head_dim) /
(block_k x head_dim) tiles — multiples of (8, 128) for lane/sublane layout.

GQA is handled in the BlockSpec index maps: query head h reads KV head
``h // group`` — no materialized repeat (saves HBM bandwidth, the point of
GQA).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


_NEG = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *, scale, causal, n_k, bq, bk, offset
):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)  # (bq, d)
    k = k_ref[0].astype(jnp.float32)  # (bk, d)
    v = v_ref[0].astype(jnp.float32)  # (bk, d)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bk)

    if causal:
        i = pl.program_id(1)
        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        logits = jnp.where(kpos <= qpos + offset, logits, _NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(j == n_k - 1)
    def _done():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "bq", "bk", "interpret")
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """q: (hq, sq, d); k, v: (hk, sk, d) with hq % hk == 0. Returns (hq, sq, d)."""
    hq, sq, d = q.shape
    hk, sk, _ = k.shape
    assert hq % hk == 0, (hq, hk)
    group = hq // hk
    if scale is None:
        scale = float(1.0 / (d**0.5))
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0, ((sq, sk), (bq, bk))
    n_k = sk // bk
    grid = (hq, sq // bq, n_k)
    return pl.pallas_call(
        functools.partial(
            _flash_kernel,
            scale=scale,
            causal=causal,
            n_k=n_k,
            bq=bq,
            bk=bk,
            offset=sk - sq,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j, g=group: (h // g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j, g=group: (h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
