"""Tile LU (PLASMA DGETRF task shape) as a data-flow task graph.

Task kinds / flop counts (tile size b):
  getrf  2/3 b^3    gessm  b^3     tstrf  b^3     ssssm  2 b^3
Total ~ 2 n^3 / 3.

Execution note (DESIGN.md §2): PLASMA's DGETRF uses *incremental pivoting*
inside TSTRF/SSSSM; TPU-friendly execution here uses the no-pivot
right-looking block LU, which has the *same task/dependency shape* (what the
scheduler sees) and is numerically safe on the diagonally-dominant test
matrices used by the tests. The simulator costs remain the PLASMA ones.
"""
from __future__ import annotations

from repro.core.dag import Mode, TaskGraph

from .tiles import f32_precise, make_tile_objects


@f32_precise
def _getrf(a_kk):
    """No-pivot in-tile LU: returns packed L\\U (unit lower not stored)."""
    import jax
    import jax.numpy as jnp

    def body(k, a):
        col = a[:, k] / a[k, k]
        col = jnp.where(jnp.arange(a.shape[0]) > k, col, a[:, k])
        a = a.at[:, k].set(col)
        update = jnp.outer(
            jnp.where(jnp.arange(a.shape[0]) > k, a[:, k], 0.0),
            jnp.where(jnp.arange(a.shape[1]) > k, a[k, :], 0.0),
        )
        return a - update

    n = a_kk.shape[0]
    return (jax.lax.fori_loop(0, n, body, a_kk),)


def _split_lu(packed):
    import jax.numpy as jnp

    l = jnp.tril(packed, -1) + jnp.eye(packed.shape[0], dtype=packed.dtype)
    u = jnp.triu(packed)
    return l, u


@f32_precise
def _gessm(a_kk, a_kj):
    import jax

    l, _ = _split_lu(a_kk)
    return (jax.scipy.linalg.solve_triangular(l, a_kj, lower=True, unit_diagonal=True),)


@f32_precise
def _tstrf(a_kk, a_ik):
    import jax

    _, u = _split_lu(a_kk)
    # A[i,k] <- A[i,k] U^{-1}
    x = jax.scipy.linalg.solve_triangular(u.T, a_ik.T, lower=True)
    return (x.T,)


@f32_precise
def _ssssm(a_ik, a_kj, a_ij):
    return (a_ij - a_ik @ a_kj,)


def lu_graph(
    n_tiles: int, tile: int = 512, itemsize: int = 8, with_fns: bool = True
) -> TaskGraph:
    g = TaskGraph()
    A = make_tile_objects("A", n_tiles, tile, itemsize)
    b3 = float(tile) ** 3
    fns = with_fns
    for k in range(n_tiles):
        g.add_task(
            "getrf",
            [(A[(k, k)], Mode.RW)],
            flops=2.0 * b3 / 3.0,
            fn=_getrf if fns else None,
            tag=("getrf", k),
        )
        for j in range(k + 1, n_tiles):
            g.add_task(
                "gessm",
                [(A[(k, k)], Mode.R), (A[(k, j)], Mode.RW)],
                flops=b3,
                fn=_gessm if fns else None,
                tag=("gessm", k, j),
            )
        for i in range(k + 1, n_tiles):
            g.add_task(
                "tstrf",
                [(A[(k, k)], Mode.R), (A[(i, k)], Mode.RW)],
                flops=b3,
                fn=_tstrf if fns else None,
                tag=("tstrf", i, k),
            )
            for j in range(k + 1, n_tiles):
                g.add_task(
                    "ssssm",
                    [
                        (A[(i, k)], Mode.R),
                        (A[(k, j)], Mode.R),
                        (A[(i, j)], Mode.RW),
                    ],
                    flops=2.0 * b3,
                    fn=_ssssm if fns else None,
                    tag=("ssssm", i, j, k),
                )
    return g


def reference_flops(n: int) -> float:
    return 2.0 * n**3 / 3.0
