"""Plain reference for the tile Cholesky: the test matrix and the error figures.

The matrix is made on the device from the seed: ``A = G Gᵀ / n + I / 10``
with ``G`` standard normal, formed at full f32 precision and symmetrised
exactly (a blocked product need not round (i, j) and (j, i) alike). Its
eigenvalues lie in about [0.1, 4.1], so it is well conditioned, and its
trailing updates are as large as its entries: the precision of the tile
products shows in the backward error (with ``+ n I`` the diagonal swamps
them, and three bf16 passes read almost as full f32).

A factor ``L`` is held to two figures, both computed at full f32 precision:

- ``residual``: ``max|L Lᵀ - A| / max|A|``;
- ``componentwise``: ``max |L Lᵀ - A| / (|L| |Lᵀ|)``, the backward error that
  f32 Cholesky keeps near ``sqrt(n) u`` (u = 2⁻²⁴) and that a matrix product
  in fewer bf16 passes breaks.

``jnp.linalg.cholesky`` of the same matrix at full f32 precision is the
reference factor; its figures, and the forward gap
``max|L - L_ref| / max|L_ref|``, are logged beside the program's.
Nothing of the program is imported.
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

COMPARED = ("componentwise", "residual")
SHIFT = 0.1


def spd_matrix(n: int, seed: int, dtype: str = "float32"):
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    return _spd(key, n, jnp.dtype(dtype))


@partial(jax.jit, static_argnums=(1, 2))
def _spd(key, n, dtype):
    g = jax.random.normal(key, (n, n), dtype=dtype)
    with jax.default_matmul_precision("highest"):
        s = g @ g.T / n
    return (s + s.T) / 2 + SHIFT * jnp.eye(n, dtype=dtype)


@jax.jit
def _stats(L, A):
    with jax.default_matmul_precision("highest"):
        R = L @ L.T - A
        LL = jnp.abs(L) @ jnp.abs(L).T
    return {"residual": jnp.max(jnp.abs(R)) / jnp.max(jnp.abs(A)),
            "componentwise": jnp.max(jnp.abs(R) / LL)}


def stats(L, A) -> Dict[str, float]:
    return {k: float(v) for k, v in _stats(L, A).items()}


def reference_factor(A, precision: str = "highest"):
    with jax.default_matmul_precision(precision):
        return jnp.linalg.cholesky(A)


def bf16x3(a, b):
    """``a @ b`` in three bf16 passes (hi·hi + hi·lo + lo·hi), accumulated in
    f32, written out: the control's product where the platform has no bf16
    passes of its own (the CPU). The split rounds with ``reduce_precision``,
    which the compiler may not drop as it may drop a round trip through
    bf16. (On a v5e it reads about 2.4 times below ``xla_high``.)"""
    def split(x):
        hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
        return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)

    ah, al = split(a)
    bh, bl = split(b)

    def dot(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


def xla_high(a, b):
    """``a @ b`` at ``precision="high"``: three bf16 passes on a TPU (and
    plain f32 on a CPU, which has no bf16 passes)."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGH)


@partial(jax.jit, static_argnums=(1, 2))
def control_factor(A, block: int, product=xla_high):
    """The control: a right-looking blocked Cholesky whose trailing updates
    are computed by ``product``, in the nearest precision below full f32;
    panel factorisations and solves stay at full f32 precision."""
    n = A.shape[0]
    L = jnp.zeros_like(A)
    S = A
    with jax.default_matmul_precision("highest"):
        for k in range(0, n, block):
            e = k + block
            lkk = jnp.linalg.cholesky(S[k:e, k:e])
            L = L.at[k:e, k:e].set(lkk)
            if e < n:
                panel = jax.scipy.linalg.solve_triangular(
                    lkk, S[e:, k:e].T, lower=True).T
                L = L.at[e:, k:e].set(panel)
                S = S.at[e:, e:].add(-product(panel, panel.T))
    return L


def readings(L, A) -> Dict[str, float]:
    """The program's figures, the reference factor's, and the forward gap."""
    L = jnp.tril(L)
    out = stats(L, A)
    L_ref = reference_factor(A)
    ref = stats(L_ref, A)
    out["forward"] = float(jnp.max(jnp.abs(L - L_ref)) / jnp.max(jnp.abs(L_ref)))
    out.update({f"reference_{k}": v for k, v in ref.items()})
    return out
