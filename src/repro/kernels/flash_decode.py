"""Pallas TPU kernel: single-token GQA decode attention (flash-decode).

The serving hot spot: one query token per sequence against a long KV cache.
Memory-bound by the cache read, so the kernel streams K/V blocks
HBM -> VMEM along the innermost grid axis with an online-softmax
accumulator in VMEM scratch — one pass over the cache, no (S,) logits
round-trip to HBM.

Layout: q (B, Hq, hd); cache k/v (B, S, Hkv, hd) — the serving cache layout
(seq-major, matching serve/decode.py). Query heads of one KV group are
processed together as the sublane dim of an (group x hd) MXU tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


_NEG = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, len_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, scale, n_blk, bk):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)       # (group, hd)
    k = k_ref[0, :, 0].astype(jnp.float32)    # (bk, hd)
    v = v_ref[0, :, 0].astype(jnp.float32)    # (bk, hd)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                  # (group, bk)
    # mask positions beyond the live cache length
    pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(pos < len_ref[0], logits, _NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(j == n_blk - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bk", "interpret"))
def flash_decode(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    length,
    *,
    scale: float | None = None,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, Hq, hd); k, v: (B, S, Hkv, hd); length: live cache length.

    Returns (B, Hq, hd). Hq % Hkv == 0; positions >= length are masked.
    """
    B, Hq, hd = q.shape
    _, S, Hkv, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    if scale is None:
        scale = float(1.0 / (hd**0.5))
    bk = min(bk, S)
    assert S % bk == 0, (S, bk)
    n_blk = S // bk
    qg = q.reshape(B, Hkv, group, hd)
    lengths = jnp.full((B, 1), length, jnp.int32)
    grid = (B, Hkv, n_blk)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, n_blk=n_blk, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, group, hd), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, bk, 1, hd), lambda b, h, j: (b, j, h, 0)),
            pl.BlockSpec((1, bk, 1, hd), lambda b, h, j: (b, j, h, 0)),
            pl.BlockSpec((1, 1), lambda b, h, j: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, hd), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, hd), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qg, k, v, lengths)
    return out.reshape(B, Hq, hd)
