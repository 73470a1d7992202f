#!/usr/bin/env python3
"""Smoke test of the scheduler's device path on one TPU, end to end.

Phases, at sizes a user of the paper's method would run:

  (a) exact engine, jax scoring — tile Cholesky NT=32 (N=16384 at tile
      512) on the 32-resource ``scaled_machine``; ``dada?alpha=0.5&use_cp=1``
      and ``heft``, each with ``backend="jax"`` and ``backend="numpy"``.
      Counts the activations scored on the device and those that fell back
      to numpy; the placements (run fingerprints) must equal numpy's.
  (b) surrogate episodes — ``run_batch`` on Cholesky/LU/QR NT=16 x
      ``paper_machine(8)`` x {heft, dada+cp, ws} x 20 seeds through the
      native Pallas transfer kernel; the strategy ranking must match the
      exact engine's (``repro.core.episode.ranking_mismatches``).
  (c) scheduled tile work — the DADA schedule of (a) replayed by
      ``execute_schedule`` on an N=16384 f32 SPD matrix; the residual must
      meet the f32 bounds below, and so must ``jnp.linalg.cholesky`` of the
      same matrix, which the result must agree with.

Every phase prints its wall-clock time (set-up, compilation included: these
are single cold runs, not measurements). The last line of standard output
is one JSON object naming the device. Without a TPU the script exits
non-zero and prints no result; it has no CPU mode.

Run from the repository root:  python chip_smoke.py
"""
from __future__ import annotations

import json
import math
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.paper_machine import paper_machine, scaled_machine  # noqa: E402
from repro.core import cached_graph, get_backend, run_batch, run_simulation  # noqa: E402
from repro.core.episode import ranking_mismatches  # noqa: E402
from repro.linalg import tiles as T  # noqa: E402
from repro.linalg.cholesky import cholesky_graph  # noqa: E402
from repro.linalg.execute import execute_schedule  # noqa: E402
from repro.linalg.lu import lu_graph  # noqa: E402
from repro.linalg.qr import qr_graph  # noqa: E402
from repro.sched import SchedConfig, resolve  # noqa: E402

U32 = 2.0 ** -24  # f32 unit roundoff
EXACT_SPECS = ("dada?alpha=0.5&use_cp=1", "heft")
SURROGATE_SPECS = ("heft", "dada?alpha=0.5&use_cp=1", "ws")
KERNELS = {"cholesky": cholesky_graph, "lu": lu_graph, "qr": qr_graph}


class SmokeFailure(AssertionError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def fingerprint(res) -> tuple:
    """Everything a placement decides: makespan, traffic, per-task intervals."""
    return (
        res.makespan, res.total_bytes, res.n_transfers, res.n_steals,
        tuple(sorted(res.busy.items())),
        tuple((iv.tid, iv.rid, iv.start, iv.end) for iv in res.intervals),
    )


# ---------------------------------------------------------------------------
# (a) exact engine, jax scoring vs numpy


def phase_exact(nt: int, tile: int, machine, seed: int = 0, jax_min: int = 8) -> dict:
    """Run each spec on both backends; return the jax results by spec.

    The exact engine activates as tasks become ready, so at NT=32 no
    activation reaches the default jax width of 32 (the widest, the first
    trsm wave, is 31): ``jax_min`` lowers it, as the large equivalence
    tests do.
    """
    graph = cholesky_graph(nt, tile, with_fns=False)
    config = SchedConfig(jax_min=jax_min)
    be = get_backend("jax", config)
    log(f"[a] Cholesky NT={nt} (N={nt * tile}, tile {tile}), {len(graph)} tasks, "
        f"{len(machine.resources)} resources; jax backend on {be.platform}")
    out = {}
    for spec in EXACT_SPECS:
        before = dict(be.counts)
        res_jax = run_simulation(
            graph, machine, resolve(spec, backend="jax", config=config), seed=seed)
        n = {k: be.counts[k] - before[k] for k in be.counts}
        res_np = run_simulation(
            graph, machine, resolve(spec, backend="numpy", config=config), seed=seed)
        same = fingerprint(res_jax) == fingerprint(res_np)
        log(f"[a] {spec}: activations of width >= {jax_min} scored on the device "
            f"{n['device']}, "
            f"fell back to numpy {n['outside'] + n['rejected']} "
            f"(outside the envelope {n['outside']}, device λ rejected {n['rejected']}); "
            f"placements equal numpy: {same}; makespan {res_jax.makespan!r} s, "
            f"{res_jax.gbytes!r} GB moved")
        check(n["device"] > 0, f"{spec}: no activation was scored on the device")
        check(same, f"{spec}: jax placements differ from numpy")
        out[spec] = res_jax
    return out


# ---------------------------------------------------------------------------
# (b) surrogate episodes vs the exact engine's ranking


def phase_surrogate(nt: int, tile: int, n_gpus: int, n_seeds: int,
                    noise: float = 0.03) -> dict:
    machine = paper_machine(n_gpus)
    seeds = [1234 + i for i in range(n_seeds)]
    items = [
        {"graph": partial(KERNELS[k], nt, tile, with_fns=False),
         "machine": machine, "strategy": spec, "seed": s, "noise": noise}
        for k in KERNELS for spec in SURROGATE_SPECS for s in seeds
    ]
    t0 = time.perf_counter()
    results = run_batch(items)
    log(f"[b] run_batch: {len(items)} configurations "
        f"({len(KERNELS)} kernels x {len(SURROGATE_SPECS)} strategies x "
        f"{n_seeds} seeds, NT={nt}, paper_machine({n_gpus})) in "
        f"{time.perf_counter() - t0!r} s (set-up, compile included)")
    out = {}
    k = 0
    for kernel in KERNELS:
        graph = cached_graph(partial(KERNELS[kernel], nt, tile, with_fns=False))
        surrogate, oracle = {}, {}
        for spec in SURROGATE_SPECS:
            rs = results[k: k + n_seeds]
            k += n_seeds
            surrogate[spec] = (float(np.mean([r.makespan for r in rs])),
                               float(np.mean([r.total_bytes for r in rs])))
            exact = [run_simulation(graph, machine, resolve(spec), seed=s, noise=noise)
                     for s in seeds]
            oracle[spec] = (float(np.mean([r.makespan for r in exact])),
                            float(np.mean([r.total_bytes for r in exact])))
        bad = ranking_mismatches(oracle, surrogate, 0, SURROGATE_SPECS)
        # ws's bytes come from randomized stealing in the oracle: its contract
        # is the makespan (as in tests/test_episode.py)
        bad += ranking_mismatches(oracle, surrogate, 1,
                                  tuple(s for s in SURROGATE_SPECS if s != "ws"))
        worst = max(SURROGATE_SPECS, key=lambda s: surrogate[s][0])
        rank = sorted(SURROGATE_SPECS, key=lambda s: surrogate[s][0])
        log(f"[b] {kernel}: surrogate makespan ranking {rank}; "
            f"mean makespan surrogate/exact "
            + ", ".join(f"{s} {surrogate[s][0]!r}/{oracle[s][0]!r}" for s in SURROGATE_SPECS))
        check(not bad, f"{kernel}: " + "; ".join(bad))
        check(worst == "ws", f"{kernel}: surrogate worst is {worst}, not ws")
        out[kernel] = (surrogate, oracle)
    return out


# ---------------------------------------------------------------------------
# (c) the DADA schedule's tile work on the device


@jax.jit
def _stats(L, A):
    """Residual figures of ``L Lᵀ = A``, all matmuls at full f32 precision."""
    with jax.default_matmul_precision("highest"):
        R = L @ L.T - A
        LL = jnp.abs(L) @ jnp.abs(L).T
    return dict(
        res_max=jnp.max(jnp.abs(R)) / jnp.max(jnp.abs(A)),
        res_fro=jnp.linalg.norm(R) / jnp.linalg.norm(A),
        componentwise=jnp.max(jnp.abs(R) / LL),
    )


def phase_tiles(nt: int, tile: int, dada_result, seed: int = 0) -> dict:
    """Execute the schedule and hold the factor to stated f32 bounds.

    With u = 2^-24 and n the order, f32 Cholesky satisfies
    |LLᵀ - A| <= γ_{n+1} |L||Lᵀ| with γ_k = k u / (1 - k u) (Higham,
    Accuracy and Stability, Thm 10.3); since (|L||Lᵀ|)_ij <= sqrt(a_ii a_jj),
    max|LLᵀ - A| / max|A| <= γ_{n+1}, plus up to 2 γ_{n+1} for the check's
    own rounding: bound 3 γ_{n+1}. That worst case grows with n; the
    probabilistic bound of Higham & Mary (SISC 2019), λ sqrt(n+1) u with
    λ = 8 (failure probability below 1e-9 at these n), also holds the
    componentwise ratio and is what a single bf16 matmul pass would break.
    The reference factor must meet both, and by first-order perturbation
    the two factors agree to 2 κ · 3 γ_{n+1}, κ bounding cond₂(A)
    (Gershgorin).
    """
    n = nt * tile
    t0 = time.perf_counter()
    A = T.random_spd(n, seed=seed).block_until_ready()
    t_gen = time.perf_counter() - t0
    graph = cholesky_graph(nt, tile)
    t0 = time.perf_counter()
    store = execute_schedule(graph, T.split_tiles(A, tile), dada_result)
    L = jnp.tril(T.join_tiles(store, nt, tile)).block_until_ready()
    del store
    t_exec = time.perf_counter() - t0
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        L_ref = jnp.linalg.cholesky(A).block_until_ready()
    t_ref = time.perf_counter() - t0
    got = {k: float(v) for k, v in _stats(L, A).items()}
    ref = {k: float(v) for k, v in _stats(L_ref, A).items()}
    absA = jnp.abs(A)
    off = jnp.sum(absA, axis=1) - jnp.diagonal(absA)
    lam_hi = float(jnp.max(jnp.diagonal(A) + off))
    lam_lo = float(jnp.min(jnp.diagonal(A) - off))
    kappa = lam_hi / lam_lo if lam_lo > 0 else math.inf
    fwd = float(jnp.max(jnp.abs(L - L_ref)) / jnp.max(jnp.abs(L_ref)))
    gamma = (n + 1) * U32 / (1 - (n + 1) * U32)
    worst, prob = 3 * gamma, 8 * math.sqrt(n + 1) * U32
    log(f"[c] N={n} f32 SPD matrix made in {t_gen!r} s; {len(graph)} tile tasks "
        f"executed in schedule order in {t_exec!r} s; jnp.linalg.cholesky in "
        f"{t_ref!r} s (set-up, compile included)")
    for name, st in (("schedule", got), ("jnp.linalg.cholesky", ref)):
        log(f"[c] {name}: max|LLᵀ-A|/max|A| = {st['res_max']!r} (bound 3γ = {worst!r}); "
            f"‖LLᵀ-A‖_F/‖A‖_F = {st['res_fro']!r}; "
            f"max |LLᵀ-A|/(|L||Lᵀ|) = {st['componentwise']!r} "
            f"(probabilistic bound {prob!r})")
        check(st["res_max"] <= worst, f"{name}: residual {st['res_max']} > {worst}")
        check(st["componentwise"] <= prob,
              f"{name}: componentwise residual {st['componentwise']} > {prob}")
    log(f"[c] schedule vs jnp.linalg.cholesky: max|L-L_ref|/max|L_ref| = {fwd!r} "
        f"(bound 2κ·3γ = {2 * kappa * worst!r}, κ <= {kappa!r})")
    check(fwd <= 2 * kappa * worst, f"factor differs from jnp.linalg.cholesky: {fwd}")
    return dict(schedule=got, reference=ref, forward=fwd)


# ---------------------------------------------------------------------------


def main() -> int:
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is {dev.platform!r}); "
              "this smoke test runs on a TPU only", file=sys.stderr)
        return 2
    log(f"device: platform={dev.platform} device_kind={dev.device_kind} "
        f"count={len(devices)}")

    nt, tile = 32, 512
    t0 = time.perf_counter()
    exact = phase_exact(nt, tile, scaled_machine(), seed=0)
    log(f"[a] wall-clock {time.perf_counter() - t0!r} s (set-up, compile included)")
    t0 = time.perf_counter()
    phase_surrogate(16, tile, n_gpus=8, n_seeds=20)
    log(f"[b] wall-clock {time.perf_counter() - t0!r} s (set-up, compile included)")
    t0 = time.perf_counter()
    phase_tiles(nt, tile, exact[EXACT_SPECS[0]], seed=0)
    log(f"[c] wall-clock {time.perf_counter() - t0!r} s (set-up, compile included)")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
