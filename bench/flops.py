"""Operations and bytes of one tile task, from its kind and the tile size.

Flops are the standard dense counts (b the tile size): Cholesky's potrf
b³/3, trsm b³, syrk b³, gemm 2b³ (n³/3 for the whole factorisation), and
LU without pivoting's getrf 2b³/3, gessm b³, tstrf b³, ssssm 2b³. Bytes are
the least a task moves to and from device memory: each b×b tile it reads
once and each it writes once.
"""
from __future__ import annotations

# kind: (flops / b³, tiles read, tiles written)
TILE_TASKS = {
    "potrf": (1.0 / 3.0, 1, 1),
    "trsm": (1.0, 2, 1),
    "syrk": (1.0, 2, 1),
    "gemm": (2.0, 3, 1),
    "getrf": (2.0 / 3.0, 1, 1),
    "gessm": (1.0, 2, 1),
    "tstrf": (1.0, 2, 1),
    "ssssm": (2.0, 3, 1),
}


def tile_flops(kind: str, b: int) -> float:
    return TILE_TASKS[kind][0] * float(b) ** 3


def tile_bytes(kind: str, b: int, itemsize: int) -> float:
    _, reads, writes = TILE_TASKS[kind]
    return float((reads + writes) * b * b * itemsize)


def least_seconds(kind: str, b: int, itemsize: int, peak_flops: float,
                  peak_bytes_per_s: float) -> tuple:
    """(least time, the bound that sets it): the larger of flops over the
    compute peak and bytes over the memory bandwidth."""
    tc = tile_flops(kind, b) / peak_flops
    tm = tile_bytes(kind, b, itemsize) / peak_bytes_per_s
    return (tc, "compute") if tc >= tm else (tm, "memory")
