"""Pallas kernel: CSR read-incidence → predicted transfer-time reduction.

This is the scoring hot spot of the ``use_cp`` scheduling strategies: for
every (ready task i, memory space u) pair, sum the per-read transfer times
of the reads that are *not* resident at u —

    X[i, u] = Σ_r  hops(mask[i, r], u) * per_read[i, r]

where ``mask`` holds compact residency codes (bit 0 = a host copy exists,
bit u+1 = a valid copy at unique memory u) and ``hops`` is the paper-era
PCIe path length: 0 if resident (or the data exists nowhere yet), 1 for
host→device / anything→host, 2 for device→host→device. The surrogate
episodes (the Pallas kernel) model only that path; the jax scheduling
backend's fold also prices a copy over a machine's peer fabric.

Layout mirrors ``tile_gemm``: the grid tiles the task axis, each program
reduces its (bt × r_pad) read block into a (bt × n_u) output block. The
reduction is an **in-order fori fold over the read axis**, so every output
entry is bit-equal to the scalar reference in ``repro.core._reference``
(padded reads carry mask 0 → hops 0 → exact +0.0). ``transfer_matrix_jnp``
is the XLA form of the identical fold and the reference the Pallas kernel
is tested against (interpret mode on CPU).

Dtypes: the kernel lowers for the TPU in f32 only (Mosaic has no f64), which
is what the surrogate episodes of ``repro.core.episode`` feed it. The f64
scores of the jax scheduling backend go through the XLA fold
(``transfer_matrix_from_full``) on every platform.
"""
from __future__ import annotations

import functools
import operator
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _hop_fold(masks, per_read, resident_of, host_col, n_u, add=operator.add,
              peer=None):
    """Shared in-order read fold: the single home of the hop formula.

    ``resident_of(r)`` returns the (n_pad, n_u) residency booleans of read
    column r; everything else (host short-circuit, 2-hop device→device,
    nowhere-yet data) is identical for the compact- and full-mask callers,
    so the bit-for-bit-critical arithmetic lives exactly once. A read
    costs 0, 1 or 2 one-hop times, and ``p + p`` is ``2 p`` exactly, so
    the fold needs nothing but ``add`` (which may be an integer-exact f64
    add over bit patterns, see ``repro.core.f64``). ``peer``, on a machine
    with a fabric, is ``(peer_of, per_read_peer)``: where ``peer_of(r)``
    holds, a fabric peer of column u has read r and the copy costs one
    fabric hop, ``per_read_peer`` (``TransferModel.route`` prefers it).
    """
    on_host = (masks & 1) != 0
    nowhere = masks == 0
    n_pad = masks.shape[0]

    def body(r, acc):
        skip = resident_of(r) | nowhere[:, r][:, None]
        one_hop = host_col[None, :] | on_host[:, r][:, None]
        p = per_read[:, r][:, None]
        cost = jnp.where(one_hop, p, add(p, p))
        if peer is not None:
            peer_of, q = peer
            cost = jnp.where(peer_of(r), q[:, r][:, None], cost)
        return add(acc, jnp.where(skip, 0, cost))

    return jax.lax.fori_loop(
        0, masks.shape[1], body, jnp.zeros((n_pad, n_u), dtype=per_read.dtype)
    )


def transfer_matrix_jnp(
    masks: jax.Array,  # (n_pad, r_pad) int32 compact residency codes
    per_read: jax.Array,  # (n_pad, r_pad) per-read transfer times
    col_bits: jax.Array,  # (n_u,) int32, bit u+1 set
    host_col: jax.Array,  # (n_u,) bool, True where unique mem u is the host
) -> jax.Array:
    """XLA reference over compact codes: (n_pad × n_u) transfer times."""
    return _hop_fold(
        masks, per_read,
        lambda r: (masks[:, r][:, None] & col_bits[None, :]) != 0,
        host_col, col_bits.shape[0],
    )


def transfer_matrix_from_full(
    masks: jax.Array,  # (n_pad, r_pad) int64 full residency masks
    per_read: jax.Array,  # (n_pad, r_pad) per-read transfer times
    mem_shift: jax.Array,  # (n_u,) int64, mem+1 shift per unique memory
    host_col: jax.Array,  # (n_u,) bool, True where unique mem u is the host
    add=operator.add,
    peer_bits: Optional[jax.Array] = None,  # (n_u,) int64 fabric reach of u
    per_read_peer: Optional[jax.Array] = None,  # (n_pad, r_pad) fabric-hop times
) -> jax.Array:
    """Same fold straight off the full int64 residency masks — the
    transfer fold of the jax scheduling backend (no compact remap). On a
    machine with a fabric, ``peer_bits[u]`` are the residency bits one
    fabric hop from unique memory u (0 for the host)."""
    peer = None
    if peer_bits is not None:
        peer = (lambda r: (masks[:, r][:, None] & peer_bits[None, :]) != 0,
                per_read_peer)
    return _hop_fold(
        masks, per_read,
        lambda r: ((masks[:, r][:, None] >> mem_shift[None, :]) & 1) != 0,
        host_col, mem_shift.shape[0], add, peer,
    )


def _xfer_kernel(masks_ref, pr_ref, bits_ref, host_ref, out_ref, *, r_pad):
    masks = masks_ref[...]  # (bt, r_pad)
    pr = pr_ref[...]
    bits = bits_ref[...]  # (1, n_u)
    hostc = host_ref[...] != 0  # (1, n_u)
    bt, n_u = out_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, masks.shape, 1)

    def body(r, acc):
        # column r through an iota mask: Mosaic has no dynamic_slice, and
        # a one-hot sum returns the selected entry exactly
        sel = lane == r
        m = jnp.sum(jnp.where(sel, masks, 0), axis=1, keepdims=True)  # (bt, 1)
        prr = jnp.sum(jnp.where(sel, pr, 0.0), axis=1, keepdims=True)
        skip = ((m & bits) != 0) | (m == 0)  # (bt, n_u)
        hops = jnp.where(
            skip, 0.0, jnp.where(hostc | ((m & 1) != 0), 1.0, 2.0)
        ).astype(pr.dtype)
        return acc + hops * prr

    out_ref[...] = jax.lax.fori_loop(
        0, r_pad, body, jnp.zeros((bt, n_u), dtype=pr.dtype)
    )


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def transfer_matrix_pallas(
    masks: jax.Array,
    per_read: jax.Array,
    col_bits: jax.Array,
    host_col: jax.Array,
    *,
    bt: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Pallas version of :func:`transfer_matrix_jnp` (same fold order).

    ``bt`` tiles the task axis; reads and memory columns stay whole per
    program (r_pad and n_u are small — a handful of reads per task, ≤ ~32
    memory spaces). ``interpret=True`` runs on CPU for testing, where any
    float dtype works.
    """
    n_pad, r_pad = masks.shape
    n_u = col_bits.shape[0]
    if not interpret and per_read.dtype != jnp.float32:
        raise TypeError(
            f"transfer_matrix_pallas lowers for f32 only, got {per_read.dtype}"
        )
    bt = min(bt, n_pad)
    assert n_pad % bt == 0, (n_pad, bt)
    grid = (n_pad // bt,)
    bits2 = col_bits.reshape(1, n_u)
    host2 = host_col.astype(jnp.int32).reshape(1, n_u)
    return pl.pallas_call(
        functools.partial(_xfer_kernel, r_pad=r_pad),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, r_pad), lambda i: (i, 0)),  # masks
            pl.BlockSpec((bt, r_pad), lambda i: (i, 0)),  # per-read times
            pl.BlockSpec((1, n_u), lambda i: (0, 0)),  # column bits
            pl.BlockSpec((1, n_u), lambda i: (0, 0)),  # host-column flags
        ],
        out_specs=pl.BlockSpec((bt, n_u), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, n_u), per_read.dtype),
        interpret=interpret,
    )(masks, per_read, bits2, host2)
