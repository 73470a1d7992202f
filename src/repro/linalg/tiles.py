"""Tiled-matrix helpers (PLASMA-style square tiles)."""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import numpy as np

from repro.core.dag import DataObject


def tile_name(label: str, i: int, j: int) -> str:
    return f"{label}[{i},{j}]"


def make_tile_objects(
    label: str, n_tiles: int, tile: int, itemsize: int = 8
) -> Dict[Tuple[int, int], DataObject]:
    """DataObjects for an n_tiles x n_tiles tiled matrix."""
    objs = {}
    for i in range(n_tiles):
        for j in range(n_tiles):
            objs[(i, j)] = DataObject(
                name=tile_name(label, i, j),
                size_bytes=tile * tile * itemsize,
                meta=(label, i, j),
            )
    return objs


def split_tiles(a, tile: int) -> Dict[str, "jnp.ndarray"]:
    """Split a square matrix into named tiles A[i,j]."""
    n = a.shape[0]
    assert a.shape == (n, n) and n % tile == 0
    nt = n // tile
    out = {}
    for i in range(nt):
        for j in range(nt):
            out[tile_name("A", i, j)] = a[
                i * tile : (i + 1) * tile, j * tile : (j + 1) * tile
            ]
    return out


def join_tiles(tiles: Dict[str, "jnp.ndarray"], nt: int, tile: int) -> "jnp.ndarray":
    import jax.numpy as jnp

    rows = []
    for i in range(nt):
        rows.append(
            jnp.concatenate([tiles[tile_name("A", i, j)] for j in range(nt)], axis=1)
        )
    return jnp.concatenate(rows, axis=0)


def f32_precise(body):
    """Run a tile body with its matmuls at full f32 precision.

    The TPU's default f32 dot is one bf16 pass, which the f32 rounding-error
    bounds of the factorisations do not cover; on the CPU, f32 dots are f32
    already and this changes nothing.
    """

    @functools.wraps(body)
    def wrapper(*args):
        import jax

        with jax.default_matmul_precision("highest"):
            return body(*args)

    return wrapper


def _normals(n: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((n, n), dtype=np.float32)


def random_spd(n: int, seed: int = 0, dtype=None) -> "jnp.ndarray":
    """Symmetric positive-definite test matrix ``G Gᵀ/n + n I``.

    ``G`` is drawn in bulk on the host; the product is formed on the
    device at full f32 precision (an N=16384 matrix is 1 GiB in f32) and
    symmetrised exactly — a blocked product need not round (i, j) and
    (j, i) alike, and the factorisations read one triangle only.
    """
    import jax
    import jax.numpy as jnp

    g = jnp.asarray(_normals(n, seed), dtype=dtype or jnp.float32)
    return jax.jit(_spd_of, static_argnums=1)(g, n)


@f32_precise
def _spd_of(g, n):
    import jax.numpy as jnp

    s = g @ g.T / n
    return (s + s.T) / 2 + n * jnp.eye(n, dtype=g.dtype)


def random_dd(n: int, seed: int = 0, dtype=None) -> "jnp.ndarray":
    """Diagonally-dominant matrix (safe for no-pivot LU)."""
    import jax.numpy as jnp

    a = _normals(n, seed)
    a = a + np.eye(n, dtype=a.dtype) * (np.abs(a).sum(axis=1).max() + n)
    return jnp.asarray(a, dtype=dtype or jnp.float32)


def random_dense(n: int, seed: int = 0, dtype=None) -> "jnp.ndarray":
    import jax.numpy as jnp

    return jnp.asarray(_normals(n, seed), dtype=dtype or jnp.float32)
