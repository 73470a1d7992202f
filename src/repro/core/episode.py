"""Batched surrogate episodes: whole list-scheduling runs as one jax dispatch.

The exact engine (:mod:`repro.runtime.engine`) is a Python event loop —
the verification oracle, bit-for-bit pinned to the reference simulator.
This module is the opt-in approximation behind ``REPRO_SCHED_EXACT=0``
(:class:`repro.sched.SchedConfig`): it compiles a *whole* greedy
list-scheduling placement episode — ready-set maintenance over the padded
CSR incidence, fused per-resource score rows, argmin assignment, EFT/clock
advance and residency bitmask updates — into a single ``lax.scan`` over
task steps with fixed-shape padded state, and batches it over a leading
axis of configurations (seeds × α/cp parameters × machine shapes ×
capacities). Scatter updates inside the step are ``jax.vmap``-ed over the
batch axis; the transfer-cost rows are computed batch-wide through the
shared hop fold of :mod:`repro.kernels.sched_score` (the Pallas kernel
when ``REPRO_SCHED_PALLAS`` selects it, interpret mode on CPU), so every
step's residency→transfer math lives exactly once in the codebase.

What the surrogate relaxes (and why rankings still transfer):

* **Tie-breaking** — deterministic index-order argmin/argmax instead of
  the oracle's per-strategy tie rules; list order is a static upward-rank
  priority instead of event-driven activation order.
* **Online calibration** — scores use the static ``flops/rate`` estimate
  (the oracle's history model converges to the same mean under the seeded
  multiplicative noise, which the surrogate applies to the *executed*
  durations from the identical ``default_rng(seed)`` stream).
* **Transfer overlap** — a placement pays its transfer time serially
  before executing instead of overlapping with prefetch. Link contention
  *is* modeled to first order: transfers serialize FIFO on the
  destination resource's PCIe switch group (a per-group free clock, the
  oracle's ``link_free``), which is what makes affinity pay off at high
  GPU counts; the source leg of a two-hop move does not occupy the
  source's group. Strategies pay the same relaxation, so *orderings*
  (DADA vs HEFT makespan and transferred bytes) survive; absolute
  makespans carry a reported relative error (see
  ``tests/test_episode.py``).
* **Eviction** — capacity pressure uses a bounded per-step LRU pass
  (at most ``_K_EVICT`` victims per placement) instead of the exact
  reservation protocol.

Correctness is therefore *ranking fidelity*, asserted against the oracle
in CI, not bit-equality.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import obs
from repro.core.backend import _bucket, call_program, enable_compile_cache
from repro.core.dag import TaskGraph
from repro.core.machine import HOST_MEM, MachineModel

# indegree sentinel for padded task rows: never ready
_NEVER = np.int32(1 << 30)
# LRU eviction budget per placement step (capacity-bounded batches only)
_K_EVICT = 8


# ---------------------------------------------------------------------------
# host-side plan: one graph × one machine template, shared by a whole batch


@dataclass
class EpisodePlan:
    """Padded device-ready arrays for one (graph, machine-template) pair.

    Shared across every configuration in a batch: configurations vary the
    resource composition (``is_gpu``/``mem_col``), the strategy parameters
    and the seeds — not the incidence structure.
    """

    n: int
    n_pad: int
    r_pad: int
    w_pad: int
    s_pad: int
    n_data: int
    n_u: int
    n_res: int
    read_ids: np.ndarray  # (n_pad, r_pad) int32, padded entries -> n_data
    read_t: np.ndarray  # (n_pad, r_pad) f64 per-read one-hop seconds
    read_sz: np.ndarray  # (n_pad, r_pad) f64 bytes
    write_ids: np.ndarray  # (n_pad, w_pad) int32, padded entries -> n_data
    write_sz: np.ndarray  # (n_pad, w_pad) f64 bytes
    succ_ids: np.ndarray  # (n_pad, s_pad) int32, padded entries -> n_pad
    indeg0: np.ndarray  # (n_pad + 1,) int32 (+1: dummy scatter slot)
    prio: np.ndarray  # (n_pad,) f64 upward rank (higher = earlier)
    dur_cpu: np.ndarray  # (n_pad,) f64 static exec times (1e-7 floor)
    dur_gpu: np.ndarray
    sizes: np.ndarray  # (n_data + 1,) f64 bytes (dummy slot 0)
    col_bits: np.ndarray  # (n_u,) int32: bit 0 host, bit 1+g device g
    host_col: np.ndarray  # (n_u,) bool
    bandwidth: float
    latency: float
    total_flops: float


def _pad2(rows: List[List[Tuple[int, float]]], n_pad: int, width: int, fill_id: int):
    # pad slot j carries the *distinct* dummy id fill_id + j: indices stay
    # unique within a row, so every scatter in the compiled episode can
    # promise unique_indices (XLA CPU scatters are scalar loops otherwise)
    # and rely on mode="drop" to discard the out-of-bounds dummies
    ids = np.tile(fill_id + np.arange(width, dtype=np.int32), (n_pad, 1))
    val = np.zeros((n_pad, width), dtype=np.float64)
    for t, row in enumerate(rows):
        for j, (i, v) in enumerate(row):
            ids[t, j] = i
            val[t, j] = v
    return ids, val


def _refuse_fabric(machine: MachineModel) -> None:
    if machine.fabric is not None:
        raise ValueError(
            "the surrogate episode engine stages every device->device copy "
            "through the host; a machine with a peer fabric runs on the "
            "exact engine (repro.core.run_simulation)"
        )


def build_plan(
    graph: TaskGraph, machine: MachineModel, n_u: Optional[int] = None
) -> EpisodePlan:
    """Build (and memoize on ``arrays().cache``) the padded episode plan.

    ``machine`` is a *template*: it supplies the CPU/GPU resource classes
    and the link model. ``n_u`` is the unique-memory column count the
    batch needs (1 + the largest device-memory id across the batch);
    defaults to this machine's own layout.
    """
    _refuse_fabric(machine)
    arr = graph.arrays()
    cpu_cls = next((r.cls for r in machine.resources if not r.is_accelerator), None)
    gpu_cls = next((r.cls for r in machine.resources if r.is_accelerator), None)
    if cpu_cls is None:
        cpu_cls = gpu_cls
    if gpu_cls is None:
        gpu_cls = cpu_cls
    max_mem = max((r.mem for r in machine.resources if r.is_accelerator), default=-1)
    if n_u is None:
        n_u = max_mem + 2
    key = (
        "episode_plan", n_u, len(machine.resources),
        cpu_cls.name, gpu_cls.name,
        machine.link.bandwidth, machine.link.latency,
    )
    plan = arr.cache.get(key)
    if plan is not None:
        return plan

    n = arr.n_tasks
    # multiples of 128 (not pow2): the scan walks (B, n_pad) state every
    # step, so a 1496-task trace padded to 2048 would pay 37% dead traffic
    n_pad = max(128, -(-n // 128) * 128)
    n_data = len(arr.data_sizes)
    lat, bw = machine.link.latency, machine.link.bandwidth

    reads = [
        [(did, 0.0 if sz <= 0 else lat + sz / bw) for did, _, sz in row]
        for row in arr.task_reads
    ]
    r_pad = _bucket(max((len(r) for r in reads), default=1), lo=2)
    read_ids, read_t = _pad2(reads, n_pad, r_pad, n_data)
    _, read_sz = _pad2(
        [[(did, float(sz)) for did, _, sz in row] for row in arr.task_reads],
        n_pad, r_pad, n_data,
    )
    writes = [[(did, float(sz)) for did, _, sz in row] for row in arr.task_writes]
    w_pad = _bucket(max((len(w) for w in writes), default=1), lo=2)
    write_ids, write_sz = _pad2(writes, n_pad, w_pad, n_data)

    succ = [graph.succ[t.tid] for t in graph.tasks]
    s_pad = _bucket(max((len(s) for s in succ), default=1), lo=2)
    succ_ids = np.tile(n_pad + np.arange(s_pad, dtype=np.int32), (n_pad, 1))
    for t, ss in enumerate(succ):
        succ_ids[t, : len(ss)] = ss

    indeg0 = np.full(n_pad + 1, _NEVER, dtype=np.int32)
    indeg0[:n] = [len(graph.pred[t.tid]) for t in graph.tasks]

    # static exec-time vectors, identical to ClassPredictor's bootstrap
    def _static(cls) -> np.ndarray:
        rates = np.array([cls.rate(k) for k in arr.kinds], dtype=np.float64)
        est = arr.flops / rates[arr.kind_codes]
        est = np.where(arr.flops <= 0.0, 1e-7, est)
        out = np.zeros(n_pad, dtype=np.float64)
        out[:n] = est
        return out

    dur_cpu = _static(cpu_cls)
    dur_gpu = _static(gpu_cls)

    # upward rank over machine-average durations + produced-data transfer
    # time: a static critical-path-aware list priority (arxiv 1711.06433's
    # generic list-scheduling formulation)
    avg = (dur_cpu[:n] + dur_gpu[:n]) / 2.0
    comm = np.array(
        [
            max((lat + sz / bw for _, _, sz in row if sz > 0), default=0.0)
            for row in arr.task_writes
        ]
    )
    prio = np.zeros(n_pad, dtype=np.float64)
    for tid in reversed(graph.topo_order()):
        down = max((prio[s] for s in graph.succ[tid]), default=0.0)
        prio[tid] = avg[tid] + comm[tid] + down

    sizes = np.zeros(n_data + 1, dtype=np.float64)
    sizes[:n_data] = arr.data_sizes

    col_bits = np.array([1 << u for u in range(n_u)], dtype=np.int32)
    host_col = np.zeros(n_u, dtype=bool)
    host_col[0] = True

    plan = EpisodePlan(
        n=n, n_pad=n_pad, r_pad=r_pad, w_pad=w_pad, s_pad=s_pad,
        n_data=n_data, n_u=n_u, n_res=len(machine.resources),
        read_ids=read_ids, read_t=read_t, read_sz=read_sz,
        write_ids=write_ids, write_sz=write_sz, succ_ids=succ_ids,
        indeg0=indeg0, prio=prio, dur_cpu=dur_cpu, dur_gpu=dur_gpu,
        sizes=sizes, col_bits=col_bits, host_col=host_col,
        bandwidth=bw, latency=lat, total_flops=graph.total_flops(),
    )
    arr.cache[key] = plan
    return plan


# ---------------------------------------------------------------------------
# per-configuration batch axes


@dataclass
class EpisodeBatch:
    """Stacked per-configuration inputs (leading axis = batch)."""

    is_gpu: np.ndarray  # (B, R) bool
    valid_res: np.ndarray  # (B, R) bool
    mem_col: np.ndarray  # (B, R) int32 unique-memory column per resource
    link_grp: np.ndarray  # (B, R) int32 link group per resource (< R)
    alpha: np.ndarray  # (B,) f64 affinity weight
    use_cp: np.ndarray  # (B,) f64 0/1: transfer prediction in the score
    ws_pref: np.ndarray  # (B,) bool: parent-worker (LIFO) preference
    noise: np.ndarray  # (B, n_pad) f64 multiplicative duration factors
    cap: np.ndarray  # (B,) f64 device-memory bytes (+inf = unbounded)

    def __len__(self) -> int:
        return len(self.alpha)


def surrogate_params(spec: str) -> Tuple[float, float, bool]:
    """Map a policy spec to surrogate (alpha, use_cp, ws_pref) axes.

    Only list-scheduling strategies have a surrogate form: ``heft`` is
    EFT with transfer prediction, ``dada``/``dual`` add the α-weighted
    write-affinity bonus, ``ws`` is blind EFT with a parent-worker (LIFO
    locality) preference. Randomized policies have no mapping — the
    exact engine remains their only path.
    """
    from repro.sched.registry import parse_spec

    name, raw = parse_spec(spec)
    truthy = ("1", "true", "yes", "on")
    if name == "heft":
        return 0.0, 1.0, False
    if name == "ws":
        return 0.0, 0.0, True
    if name in ("dada", "dual"):
        alpha = 0.0 if name == "dual" else 0.5
        if "alpha" in raw:
            alpha = float(raw["alpha"])
        use_cp = 1.0 if str(raw.get("use_cp", "0")).lower() in truthy else 0.0
        return alpha, use_cp, False
    raise ValueError(
        f"strategy {spec!r} has no surrogate episode mapping "
        "(supported: heft, ws, dada, dual); run it on the exact engine"
    )


def noise_factors(seed: int, noise: float, n: int, n_pad: int) -> np.ndarray:
    """The oracle's per-task duration factors, from the identical stream
    (``Engine.submit`` draws one batched normal in tid order)."""
    out = np.ones(n_pad, dtype=np.float64)
    if noise > 0 and n > 0:
        out[:n] = np.exp(np.random.default_rng(seed).normal(0.0, noise, size=n))
    return out


def machine_axes(
    machine: MachineModel, n_res: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """(is_gpu, valid, mem_col, link_grp) rows for one machine, padded to
    ``n_res``.

    ``link_grp`` densely renumbers the machine's PCIe switch groups and
    gives every CPU its own group — transfers into a resource serialize
    FIFO against others on the same group (the oracle's ``link_free``),
    and host-side pulls don't contend with each other. Group ids stay
    below the resource count, so the episode's link clock is (B, R).
    """
    _refuse_fabric(machine)
    is_gpu = np.zeros(n_res, dtype=bool)
    valid = np.zeros(n_res, dtype=bool)
    mem_col = np.zeros(n_res, dtype=np.int32)
    link_grp = np.zeros(n_res, dtype=np.int32)
    groups: Dict[int, int] = {}
    for r in machine.resources:
        if r.is_accelerator and r.link is not None:
            groups.setdefault(r.link, len(groups))
    n_sw = len(groups)
    for r in machine.resources:
        is_gpu[r.rid] = r.is_accelerator
        valid[r.rid] = True
        mem_col[r.rid] = 0 if r.mem == HOST_MEM else r.mem + 1
        if r.is_accelerator and r.link is not None:
            link_grp[r.rid] = groups[r.link]
        else:
            n_sw += 1
            link_grp[r.rid] = min(n_sw - 1, n_res - 1)
    return is_gpu, valid, mem_col, link_grp


# ---------------------------------------------------------------------------
# the compiled episode: lax.scan over steps, batch axis across configs

_EPISODE_CACHE: Dict[tuple, object] = {}


def _pallas_mode(mode: str, platform: str) -> str:
    """Transfer-row route from ``REPRO_SCHED_PALLAS`` and the platform:
    ``native`` on gpu/tpu (auto or forced), ``interpret`` only when forced
    on cpu, else ``off`` (the XLA fold). A TPU never interprets."""
    if mode in ("0", "off", "false"):
        return "off"
    if platform == "cpu":
        return "interpret" if mode == "1" else "off"
    return "native"


def _build_episode_fn(shape_key: tuple):
    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    from repro.kernels.sched_score import (
        transfer_matrix_jnp,
        transfer_matrix_pallas,
    )

    (B, n_pad, r_pad, w_pad, s_pad, R, n_u, nd1, n_steps,
     use_cap, use_pallas, interpret, emit) = shape_key

    def xfer_rows(masks, per_read, col_bits, host_col):
        if use_pallas:
            bt = min(128, B)
            return transfer_matrix_pallas(
                masks, per_read, col_bits, host_col, bt=bt, interpret=interpret
            )
        return transfer_matrix_jnp(masks, per_read, col_bits, host_col)

    # batch-axis scatters, vmapped over configurations. Indices are unique
    # within a row by construction (distinct out-of-range dummies for pads
    # and masked-off steps), so XLA gets the unique_indices promise and
    # drop semantics — without them CPU scatters fall back to a guarded
    # scalar loop that dominates the whole scan
    _HINTS = dict(mode="drop", unique_indices=True)
    scat_set = jax.vmap(lambda a, i, v: a.at[i].set(v, **_HINTS))
    scat_add = jax.vmap(lambda a, i, v: a.at[i].add(v, **_HINTS))
    scat_max = jax.vmap(lambda a, i, v: a.at[i].max(v, **_HINTS))
    row_of = jax.vmap(lambda a, i: a[i])  # a: (B, X, Y), i: (B,) -> (B, Y)
    scat_row_set = jax.vmap(lambda a, u, i, v: a.at[u, i].set(v, **_HINTS))

    def pick(mat, idx):  # (B, X), (B,) -> (B,)
        return jnp.take_along_axis(mat, idx[:, None], axis=1)[:, 0]

    def gather_rows(mat, idx):  # clamped: pad ids sit past the last slot
        return jnp.take_along_axis(
            mat, jnp.minimum(idx, mat.shape[1] - 1), axis=1
        )

    def surrogate_episode(
        read_ids, read_t, read_sz, write_ids, write_sz, succ_ids,
        indeg0, prio, dur_cpu, dur_gpu, sizes, col_bits, host_col,
        is_gpu, valid_res, mem_col, link_grp, alpha, use_cp, ws_pref,
        noise, cap, bandwidth,
    ):
        rr = jnp.arange(R, dtype=jnp.int32)
        iota_n = jnp.arange(n_pad, dtype=jnp.int32)
        iota_nd = jnp.arange(nd1, dtype=jnp.int32)

        def step(carry, k):
            (load, tcount, pready, ready_t, indeg, res_mask, touch, resbytes,
             writer, link_free, total_b, mk, npl) = carry
            tb_in = total_b  # for the emitted per-step eviction bytes

            # pready carries the ready set directly: prio where ready,
            # -inf otherwise. max + first-match iota-min instead of argmax:
            # XLA's CPU argmax lowers to a scalar index-tracking loop (~4x
            # slower than these two vectorized reduces), and the max value
            # doubles as the activity test
            best = jnp.max(pready, axis=1)
            t = jnp.min(
                jnp.where(pready == best[:, None], iota_n, n_pad - 1), axis=1
            ).astype(jnp.int32)
            act = best > -jnp.inf  # padded steps: no-op

            rids = read_ids[t]  # (B, r_pad)
            prt = read_t[t]
            rsz = read_sz[t]
            wids = write_ids[t]  # (B, w_pad)
            wsz = write_sz[t]
            masks = gather_rows(res_mask, rids)
            wmasks = gather_rows(res_mask, wids)

            # fused score row pieces -------------------------------------
            X = xfer_rows(masks, prt, col_bits, host_col)  # (B, n_u) s
            aff = (
                ((wmasks[:, :, None] & col_bits[None, None, :]) != 0)
                * wsz[:, :, None]
            ).sum(axis=1) / bandwidth
            aff = jnp.where(host_col[None, :], 0.0, aff)  # accel_write

            est = pick(ready_t, t)
            dur_r = jnp.where(is_gpu, dur_gpu[t][:, None], dur_cpu[t][:, None])
            X_r = jnp.take_along_axis(X, mem_col, axis=1)
            aff_r = jnp.take_along_axis(aff, mem_col, axis=1)
            base = jnp.maximum(est[:, None], load)
            score = base + use_cp[:, None] * X_r + dur_r
            score = score - alpha[:, None] * aff_r
            score = jnp.where(valid_res, score, jnp.inf)
            r_sel = jnp.argmin(score, axis=1).astype(jnp.int32)

            # work-stealing surrogate: blind stealing spreads tasks by
            # *count*, not time — CPUs absorb the same share as GPUs —
            # with xkaapi's LIFO rule keeping a child on its parent's
            # worker unless that worker is clearly backlogged
            tscore = jnp.where(valid_res, tcount.astype(jnp.float32), jnp.inf)
            ws_sel = jnp.argmin(tscore, axis=1).astype(jnp.int32)
            pref = pick(writer, rids[:, 0])
            pref_c = jnp.clip(pref, 0, R - 1)
            pref_ok = (
                (pref >= 0)
                & pick(valid_res, pref_c)
                & (pick(tscore, pref_c) <= jnp.min(tscore, axis=1) + 1.0)
            )
            ws_sel = jnp.where(pref_ok, pref_c, ws_sel)
            r_sel = jnp.where(ws_pref, ws_sel, r_sel)

            u = pick(mem_col, r_sel)
            dst_bit = col_bits[u]  # (B,)
            dst_host = host_col[u]

            # ground-truth advance: per-read hops to the chosen memory
            resident = (masks & dst_bit[:, None]) != 0
            nowhere = masks == 0
            on_host = (masks & 1) != 0
            hops = jnp.where(
                resident | nowhere,
                0.0,
                jnp.where(dst_host[:, None] | on_host, 1.0, 2.0),
            )
            xfer_t = (hops * prt).sum(axis=1)
            xfer_b = (hops * rsz).sum(axis=1)

            dur_sel = pick(dur_r, r_sel) * pick(noise, t)
            # transfers serialize FIFO on the destination's link group
            # (the oracle's link_free clock): contention on shared PCIe
            # switches is what makes affinity pay off at high GPU counts
            grp = pick(link_grp, r_sel)
            has_x = xfer_t > 0.0
            start = jnp.maximum(est, pick(load, r_sel))
            start = jnp.maximum(
                start, jnp.where(has_x, pick(link_free, grp), 0.0)
            )
            fin = start + xfer_t + dur_sel
            grp_eff = jnp.where(act & has_x, grp, R)  # OOB: dropped
            link_free = scat_set(
                link_free, grp_eff[:, None], (start + xfer_t)[:, None]
            )

            # clock / ready-set updates ----------------------------------
            sel_hot = (rr[None, :] == r_sel[:, None]) & act[:, None]
            load = jnp.where(sel_hot, fin[:, None], load)
            tcount = tcount + sel_hot.astype(jnp.int32)
            npl = npl + act.astype(jnp.int32)
            # retire the chosen task (scatter -inf), decrement successor
            # indegrees, and light up successors that just became ready;
            # dummy successor slots and inactive steps index past the
            # state's edge and are dropped by the scatter mode
            pready = scat_set(
                pready, jnp.where(act, t, n_pad)[:, None],
                jnp.full((B, 1), -jnp.inf, pready.dtype),
            )
            succs = succ_ids[t] + jnp.where(act, 0, n_pad + s_pad)[:, None]
            indeg = scat_add(indeg, succs, jnp.full_like(succs, -1))
            now_ready = gather_rows(indeg, succs) == 0
            pready = scat_max(
                pready, succs,
                jnp.where(now_ready, prio[jnp.minimum(succs, n_pad - 1)], -jnp.inf),
            )
            ready_t = scat_max(
                ready_t, succs, jnp.broadcast_to(fin[:, None], succs.shape)
            )
            mk = jnp.maximum(mk, jnp.where(act, fin, 0.0))
            total_b = total_b + jnp.where(act, xfer_b, 0.0)

            # residency updates: reads land copies, writes invalidate ----
            new_rmask = (
                masks
                | jnp.where(hops > 0, dst_bit[:, None], 0)
                | (hops == 2).astype(jnp.int32)
            )
            rids_eff = rids + jnp.where(act, 0, nd1)[:, None]
            res_mask = scat_set(res_mask, rids_eff, new_rmask)
            wids_eff = wids + jnp.where(act, 0, nd1)[:, None]
            res_mask = scat_set(
                res_mask, wids_eff, jnp.broadcast_to(dst_bit[:, None], wids.shape)
            )
            res_mask = res_mask.at[:, nd1 - 1].set(1)  # dummy slot stays host
            writer = scat_set(
                writer, wids_eff, jnp.broadcast_to(r_sel[:, None], wids.shape)
            )
            writer = writer.at[:, nd1 - 1].set(-1)

            if use_cap:
                onehot_u = (jnp.arange(n_u)[None, :] == u[:, None])
                rd_new = (jnp.where(hops > 0, rsz, 0.0)).sum(axis=1)
                host_new = (jnp.where(hops == 2, rsz, 0.0)).sum(axis=1)
                w_res = (wmasks[:, :, None] & col_bits[None, None, :]) != 0
                w_drop = jnp.where(w_res, wsz[:, :, None], 0.0).sum(axis=1)
                w_tot = wsz.sum(axis=1)
                delta = (
                    onehot_u * (rd_new + w_tot)[:, None]
                    - w_drop
                    + host_col[None, :] * host_new[:, None]
                )
                resbytes = resbytes + jnp.where(act[:, None], delta, 0.0)
                touch = scat_row_set(touch, u, rids_eff, jnp.full_like(rids, k))
                touch = scat_row_set(touch, u, wids_eff, jnp.full_like(wids, k))

                def evict(_, st):
                    res_mask, resbytes, total_b = st
                    need = act & ~dst_host & (pick(resbytes, u) > cap)
                    res_at = (res_mask & dst_bit[:, None]) != 0
                    touch_u = row_of(touch, u)  # (B, nd1)
                    cand = res_at & (touch_u < k) & (sizes[None, :] > 0)
                    key = jnp.where(cand, touch_u, _NEVER)
                    km = jnp.min(key, axis=1)
                    v = jnp.min(
                        jnp.where(key == km[:, None], iota_nd, nd1 - 1), axis=1
                    ).astype(jnp.int32)
                    can = need & (km < _NEVER)
                    vsz = jnp.where(can, sizes[v], 0.0)
                    vmask = pick(res_mask, v)
                    dirty = vmask == dst_bit  # sole device copy: write back
                    total_b = total_b + jnp.where(can & dirty, vsz, 0.0)
                    newm = jnp.where(
                        can, (vmask | dirty.astype(jnp.int32)) & ~dst_bit, vmask
                    )
                    v_eff = jnp.where(can, v, nd1)  # dropped unless evicting
                    res_mask = scat_set(
                        res_mask, v_eff[:, None], newm[:, None]
                    )
                    resbytes = resbytes - onehot_u * vsz[:, None]
                    return res_mask, resbytes, total_b

                res_mask, resbytes, total_b = jax.lax.fori_loop(
                    0, _K_EVICT, evict, (res_mask, resbytes, total_b)
                )

            # schedule emission (audit schema for repro.verify): the
            # chosen task/resource and its timeline per step. Off by
            # default — emit changes the compiled shape, so it is part of
            # the cache key and costs nothing when disabled.
            if emit:
                evict_b = total_b - tb_in - jnp.where(act, xfer_b, 0.0)
                ys = (t, r_sel, act, start, xfer_t, fin,
                      jnp.where(act, xfer_b, 0.0), evict_b)
            else:
                ys = None
            return (
                (load, tcount, pready, ready_t, indeg, res_mask, touch,
                 resbytes, writer, link_free, total_b, mk, npl),
                ys,
            )

        f32 = jnp.float32
        carry0 = (
            jnp.zeros((B, R), f32),
            jnp.zeros((B, R), jnp.int32),
            jnp.broadcast_to(
                jnp.where(indeg0[None, :n_pad] == 0, prio[None, :], -jnp.inf),
                (B, n_pad),
            ).astype(f32),
            jnp.zeros((B, n_pad + 1), f32),
            jnp.broadcast_to(indeg0[None, :], (B, n_pad + 1)).astype(jnp.int32),
            jnp.ones((B, nd1), jnp.int32),  # everything starts on host
            jnp.full((B, n_u if use_cap else 1, nd1 if use_cap else 1), -1, jnp.int32),
            jnp.zeros((B, n_u), f32),
            jnp.full((B, nd1), -1, jnp.int32),
            jnp.zeros((B, R), f32),  # per-link-group free clock
            jnp.zeros((B,), f32),
            jnp.zeros((B,), f32),
            jnp.zeros((B,), jnp.int32),
        )
        carry, ys = jax.lax.scan(
            step, carry0, jnp.arange(n_steps, dtype=jnp.int32)
        )
        total_b, mk, npl = carry[-3], carry[-2], carry[-1]
        if emit:
            return mk, total_b, npl, ys
        return mk, total_b, npl

    return jax.jit(surrogate_episode)


def run_episodes(
    plan: EpisodePlan,
    batch: EpisodeBatch,
    *,
    config=None,
    extra_steps: int = 0,
    pad_to: Optional[int] = None,
    emit_schedule: bool = False,
) -> Dict[str, np.ndarray]:
    """Run every configuration of ``batch`` through one compiled episode.

    Returns ``makespan`` / ``total_bytes`` / ``n_placed`` arrays aligned
    with the batch. ``extra_steps`` and ``pad_to`` (batch-axis padding)
    exist for the padding-invariance property suite: padded steps and
    padded batch rows are provably no-ops.

    ``emit_schedule`` additionally returns a ``"schedule"`` dict of
    (B, n_steps) arrays — per-step chosen task/resource and timeline in
    the audit schema (see :func:`episode_audit_logs`). It is part of the
    compile-cache key, so the default path's compiled episode is
    unchanged.
    """
    import jax
    import jax.numpy as jnp

    with obs.span("episode.pack"):
        if config is None:
            from repro.sched.config import current_config

            config = current_config()

        B = len(batch)
        B_pad = pad_to if pad_to is not None else _bucket(B, lo=8)
        if B_pad < B:
            raise ValueError(f"pad_to={B_pad} smaller than batch ({B})")
        use_cap = bool(np.isfinite(batch.cap).any())
        mode = _pallas_mode(config.pallas, jax.default_backend())
        use_pallas, interpret = mode != "off", mode == "interpret"
        n_steps = plan.n + int(extra_steps)

        def padb(a: np.ndarray, fill=0) -> np.ndarray:
            if B_pad == B:
                return a
            pad = np.full((B_pad - B,) + a.shape[1:], fill, dtype=a.dtype)
            return np.concatenate([a, pad], axis=0)

        shape_key = (
            B_pad, plan.n_pad, plan.r_pad, plan.w_pad, plan.s_pad,
            plan.n_res, plan.n_u, plan.n_data + 1, n_steps,
            use_cap, use_pallas, interpret, bool(emit_schedule),
        )
        fn = _EPISODE_CACHE.get(shape_key)
        if fn is None:
            fn = _EPISODE_CACHE[shape_key] = _build_episode_fn(shape_key)

        # the surrogate runs in f32: it reports *rankings* and relative error,
        # and halving the scan's state traffic is most of its speed advantage
        up = jnp.asarray
        f32 = partial(jnp.asarray, dtype=np.float32)
        args = [
            (up, plan.read_ids), (f32, plan.read_t),
            (f32, plan.read_sz), (up, plan.write_ids),
            (f32, plan.write_sz), (up, plan.succ_ids),
            (up, plan.indeg0), (f32, plan.prio),
            (f32, plan.dur_cpu),
            (f32, plan.dur_gpu),
            (f32, plan.sizes), (up, plan.col_bits),
            (up, plan.host_col),
            # padded batch rows: no valid resources -> every step inactive
            (up, padb(batch.is_gpu)),
            (up, padb(batch.valid_res)),
            (up, padb(batch.mem_col)),
            (up, padb(batch.link_grp)),
            (f32, padb(batch.alpha)),
            (f32, padb(batch.use_cp)),
            (up, padb(batch.ws_pref)),
            (f32, padb(batch.noise, fill=1)),
            (f32, padb(batch.cap, fill=np.inf)),
            (f32, plan.bandwidth),
        ]

        def first_b(x):
            return np.asarray(x)[:B]

        def first_b_f64(x):
            return first_b(x).astype(np.float64)

        # outputs (makespan, total bytes, tasks placed[, schedule])
        reads = [(0, first_b_f64), (1, first_b_f64), (2, first_b)]
        if emit_schedule:
            # scan stacks along the step axis: (n_steps, B) -> (B, n_steps)
            names = ("tid", "rid", "act", "start", "xfer_t", "fin", "xfer_b",
                     "evict_b")
            reads.append((3, lambda cols: {
                name: np.asarray(col)[:, :B].T for name, col in zip(names, cols)}))
    _, host = call_program("episode", fn, args, reads)
    return dict(zip(("makespan", "total_bytes", "n_placed", "schedule"), host))


def ranking_mismatches(
    oracle: Dict[str, Tuple[float, ...]],
    surrogate: Dict[str, Tuple[float, ...]],
    axis: int,
    specs: Sequence[str],
    margin: float = 0.10,
) -> List[str]:
    """The surrogate's ranking contract against the exact engine.

    ``oracle`` / ``surrogate`` map a policy spec to its mean metrics
    (makespan, total bytes, ...). Every pair the oracle separates by more
    than ``margin`` (relative) must be ordered alike on ``axis``; closer
    pairs are near-ties inside the surrogate's documented relative error
    and impose nothing. Returns one message per misordered pair.
    """
    out = []
    for i, a in enumerate(specs):
        for b in specs[i + 1:]:
            oa, ob = oracle[a][axis], oracle[b][axis]
            if abs(oa - ob) <= margin * max(abs(oa), abs(ob)):
                continue
            sa, sb = surrogate[a][axis], surrogate[b][axis]
            if (oa < ob) != (sa < sb):
                out.append(
                    f"oracle orders {a} vs {b} as {oa:.4g} vs {ob:.4g} "
                    f"but surrogate says {sa:.4g} vs {sb:.4g}"
                )
    return out


def episode_audit_logs(graph, batch: EpisodeBatch, out: Dict[str, np.ndarray]):
    """Convert an ``emit_schedule`` run into per-configuration audit logs.

    Each batch row becomes one ``repro.verify.audit.AuditLog`` with
    ``engine="surrogate"``: per-step placements as exec records (start
    after the step's transfer time, end at the step's finish), demand
    transfers and capacity write-backs as hop records, and the episode's
    claimed makespan/total-bytes as the result footer — the same schema
    the exact engine emits, so ``repro.verify.verify_audit`` re-checks
    surrogate schedules with no engine-specific code.
    """
    from repro.verify.audit import AuditLog, graph_accesses

    sched = out["schedule"]
    accesses = graph_accesses(graph)
    n = len(accesses)
    n_res = batch.mem_col.shape[1]
    logs = []
    for b in range(len(batch)):
        log = AuditLog(engine="surrogate")
        log.machine = {
            "host_mem": 0,
            "resources": [
                {
                    "rid": r,
                    "mem": int(batch.mem_col[b, r]),
                    "valid": bool(batch.valid_res[b, r]),
                    "link": int(batch.link_grp[b, r]),
                }
                for r in range(n_res)
            ],
        }
        log.graphs[0] = {"submit_at": 0.0, "tasks": accesses}
        for k in range(sched["tid"].shape[1]):
            if not sched["act"][b, k]:
                continue
            tid = int(sched["tid"][b, k])
            if tid >= n:
                continue  # padded step ids never activate; defensive
            rid = int(sched["rid"][b, k])
            start = float(sched["start"][b, k])
            xt = float(sched["xfer_t"][b, k])
            xb = float(sched["xfer_b"][b, k])
            eb = float(sched["evict_b"][b, k])
            fin = float(sched["fin"][b, k])
            log.log_exec(0, tid, rid, int(batch.mem_col[b, rid]), start + xt, fin)
            grp = int(batch.link_grp[b, rid])
            if xb > 0:
                log.log_hop("copy", int(round(xb)), grp, start, start + xt)
            if eb > 0:
                log.log_hop("writeback", int(round(eb)), grp, start, fin)
        log.result = {
            "total_bytes": float(out["total_bytes"][b]),
            "n_transfers": None,
            "makespan": float(out["makespan"][b]),
            "per_graph": {
                0: {"finish": float(out["makespan"][b]), "submit_at": 0.0}
            },
        }
        logs.append(log)
    return logs
