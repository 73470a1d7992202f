"""Plain reference of the surrogate episode: greedy list scheduling, step by step.

The same semantics as the batched ``lax.scan`` of the surrogate sweep, written
as a straightforward loop over placement steps, batched only over
configurations with numpy. Nothing of the program is imported: the task
graph and the machine are read as plain data (task reads, writes,
successors, flops, kinds; resources, memories, link groups, class rates).

One step of one configuration:

- the ready task of highest upward rank is placed (lowest task id on a tie);
- its score on resource r is ``max(ready time, load[r]) + use_cp * transfer
  time to r's memory + duration on r's class - alpha * write affinity``,
  where the transfer time sums 0, 1 or 2 one-hop times per read (0 if the
  data is resident there or nowhere yet, 1 if a copy is on the host or the
  target is the host, 2 device to device) and the write affinity is the
  bytes of the task's writes resident in that device memory over the link
  bandwidth; the lowest score wins (lowest resource id on a tie);
- work stealing (``ws_pref``) instead takes the resource that ran fewest
  tasks, or the writer of the task's first read unless it ran more than
  one task beyond the fewest;
- the task starts at ``max(ready time, load[r])``, after the link group of
  r is free when it moves data, runs its duration times its noise factor,
  and its successors' ready times rise to its finish;
- reads land copies in the chosen memory (and on the host for a two-hop
  move), writes leave the chosen memory as the only copy.

Arithmetic runs in ``dtype`` throughout, with the inputs rounded to it once.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


class GraphData:
    """The task graph as plain arrays, built from its tasks and edges."""

    def __init__(self, graph) -> None:
        tasks = graph.tasks
        self.n = len(tasks)
        ids: Dict[str, int] = {}
        self.reads: List[List[tuple]] = []
        self.writes: List[List[tuple]] = []
        for t in tasks:
            rd, wr = [], []
            for acc in t.accesses:
                d = acc.data
                did = ids.setdefault(d.name, len(ids))
                mode = acc.mode.value
                if "r" in mode:
                    rd.append((did, float(d.size_bytes)))
                if "w" in mode:
                    wr.append((did, float(d.size_bytes)))
            self.reads.append(rd)
            self.writes.append(wr)
        self.n_data = len(ids)
        self.succ = [list(graph.succ[t.tid]) for t in tasks]
        self.indeg = np.array([len(graph.pred[t.tid]) for t in tasks], dtype=np.int64)
        self.flops = np.array([t.flops for t in tasks], dtype=np.float64)
        self.kinds = [t.kind for t in tasks]


class MachineData:
    """Resources as plain arrays: class, memory column and link group."""

    def __init__(self, machine) -> None:
        res = machine.resources
        self.R = len(res)
        self.is_gpu = np.array([r.mem >= 0 for r in res])
        self.mem_col = np.array([0 if r.mem < 0 else r.mem + 1 for r in res])
        self.n_u = max((r.mem for r in res if r.mem >= 0), default=-1) + 2
        # accelerators share their switch's group; every CPU has its own
        groups: Dict[int, int] = {}
        for r in res:
            if r.mem >= 0 and r.link is not None:
                groups.setdefault(r.link, len(groups))
        n_sw = len(groups)
        grp = []
        for r in res:
            if r.mem >= 0 and r.link is not None:
                grp.append(groups[r.link])
            else:
                n_sw += 1
                grp.append(min(n_sw - 1, self.R - 1))
        self.link_grp = np.array(grp)
        cpu = next((r.cls for r in res if r.mem < 0), None)
        gpu = next((r.cls for r in res if r.mem >= 0), None)
        self.cpu_cls = cpu or gpu
        self.gpu_cls = gpu or cpu
        self.bandwidth = float(machine.link.bandwidth)
        self.latency = float(machine.link.latency)


def _static_times(g: GraphData, cls) -> np.ndarray:
    out = np.empty(g.n)
    for i in range(g.n):
        f = g.flops[i]
        out[i] = 1e-7 if f <= 0.0 else f / cls.rate(g.kinds[i])
    return out


def upward_rank(g: GraphData, m: MachineData) -> np.ndarray:
    """Mean duration plus the longest produced-data transfer plus the
    highest successor rank, in float64."""
    dc, dg = _static_times(g, m.cpu_cls), _static_times(g, m.gpu_cls)
    lat, bw = m.latency, m.bandwidth
    prio = np.zeros(g.n)
    indeg = g.indeg.copy()
    order, stack = [], [i for i in range(g.n) if indeg[i] == 0]
    while stack:
        i = stack.pop()
        order.append(i)
        for s in g.succ[i]:
            indeg[s] -= 1
            if indeg[s] == 0:
                stack.append(s)
    for i in reversed(order):
        avg = (dc[i] + dg[i]) / 2.0
        comm = max((lat + sz / bw for _, sz in g.writes[i] if sz > 0), default=0.0)
        down = max((prio[s] for s in g.succ[i]), default=0.0)
        prio[i] = avg + comm + down
    return prio


def noise_factors(seed: int, noise: float, n: int) -> np.ndarray:
    """Per-task multiplicative duration noise, ``exp(N(0, noise))`` drawn in
    task order from ``default_rng(seed)``."""
    if noise <= 0:
        return np.ones(n)
    return np.exp(np.random.default_rng(seed).normal(0.0, noise, size=n))


def run_reference(g: GraphData, m: MachineData, alpha: Sequence[float],
                  use_cp: Sequence[float], ws_pref: Sequence[bool],
                  noise: np.ndarray, dtype=np.float32) -> Dict[str, np.ndarray]:
    """Makespan and bytes moved of B configurations, in ``dtype`` arithmetic.

    ``noise`` is (B, n) in float64, as drawn; everything is rounded to
    ``dtype`` once on the way in.
    """
    dt = np.dtype(dtype)
    B, R, n, nd = len(alpha), m.R, g.n, g.n_data

    def c(x):
        return np.asarray(x, dtype=np.float64).astype(dt)

    lat, bw = m.latency, m.bandwidth
    read_t = [[c(0.0 if sz <= 0 else lat + sz / bw) for _, sz in rd] for rd in g.reads]
    read_sz = [[c(sz) for _, sz in rd] for rd in g.reads]
    dur_c = c(_static_times(g, m.cpu_cls))
    dur_g = c(_static_times(g, m.gpu_cls))
    prio = c(upward_rank(g, m))
    noise = c(noise)
    bw_d = c(bw)
    alpha_d, cp_d = c(alpha), c(use_cp)
    ws = np.asarray(ws_pref, dtype=bool)
    is_gpu, mem_col, link_grp = m.is_gpu, m.mem_col, m.link_grp
    n_u = m.n_u
    col_bits = np.array([1 << u for u in range(n_u)], dtype=np.int64)
    host_col = np.arange(n_u) == 0
    zero, ninf, inf = c(0.0), c(-np.inf), c(np.inf)

    bi = np.arange(B)
    load = np.zeros((B, R), dt)
    tcount = np.zeros((B, R), np.int64)
    pready = np.where(g.indeg == 0, prio, ninf)[None, :].repeat(B, 0).astype(dt)
    ready_t = np.zeros((B, n), dt)
    indeg = np.broadcast_to(g.indeg, (B, n)).copy()
    res_mask = np.ones((B, nd), np.int64)  # everything starts on the host
    writer = np.full((B, nd), -1, np.int64)
    link_free = np.zeros((B, R), dt)
    total_b = np.zeros(B, dt)
    mk = np.zeros(B, dt)

    for _ in range(n):
        best = pready.max(axis=1)
        t = np.argmax(pready == best[:, None], axis=1)  # lowest id on a tie
        X = np.zeros((B, n_u), dt)
        aff = np.zeros((B, n_u), dt)
        for b in range(B):
            tb = t[b]
            for (did, _), p in zip(g.reads[tb], read_t[tb]):
                mask = res_mask[b, did]
                if mask == 0:
                    continue
                for u in range(n_u):
                    if mask & col_bits[u]:
                        continue
                    one = host_col[u] or (mask & 1)
                    X[b, u] = X[b, u] + (p if one else p + p)
            for did, sz in g.writes[tb]:
                mask = res_mask[b, did]
                for u in range(1, n_u):
                    if mask & col_bits[u]:
                        aff[b, u] = aff[b, u] + c(sz)
        aff = aff / bw_d
        aff[:, 0] = zero

        est = ready_t[bi, t]
        dur_r = np.where(is_gpu[None, :], dur_g[t][:, None], dur_c[t][:, None])
        X_r = X[:, mem_col]
        aff_r = aff[:, mem_col]
        base = np.maximum(est[:, None], load)
        score = base + cp_d[:, None] * X_r + dur_r
        score = score - alpha_d[:, None] * aff_r
        r_sel = np.argmin(score, axis=1)

        tscore = tcount.astype(dt)
        ws_sel = np.argmin(tscore, axis=1)
        for b in np.nonzero(ws)[0]:
            rd = g.reads[t[b]]
            pref = writer[b, rd[0][0]] if rd else -1
            if pref >= 0 and tscore[b, pref] <= tscore[b].min() + c(1.0):
                ws_sel[b] = pref
        r_sel = np.where(ws, ws_sel, r_sel)

        for b in range(B):
            tb, r = t[b], r_sel[b]
            u = mem_col[r]
            dst_bit = col_bits[u]
            dst_host = host_col[u]
            xfer_t, xfer_b = zero, zero
            landed = []
            for (did, _), p, sz in zip(g.reads[tb], read_t[tb], read_sz[tb]):
                mask = res_mask[b, did]
                if mask & dst_bit or mask == 0:
                    hops = 0
                elif dst_host or mask & 1:
                    hops = 1
                else:
                    hops = 2
                xfer_t = xfer_t + c(hops) * p
                xfer_b = xfer_b + c(hops) * sz
                new = mask
                if hops:
                    new |= dst_bit
                if hops == 2:
                    new |= 1
                landed.append((did, new))
            dur = (dur_g[tb] if is_gpu[r] else dur_c[tb]) * noise[b, tb]
            grp = link_grp[r]
            start = max(est[b], load[b, r])
            if xfer_t > zero:
                start = max(start, link_free[b, grp])
            start_x = start + xfer_t
            fin = start_x + dur
            if xfer_t > zero:
                link_free[b, grp] = start_x
            load[b, r] = fin
            tcount[b, r] += 1
            pready[b, tb] = ninf
            for s in g.succ[tb]:
                indeg[b, s] -= 1
                if indeg[b, s] == 0:
                    pready[b, s] = max(pready[b, s], prio[s])
                ready_t[b, s] = max(ready_t[b, s], fin)
            mk[b] = max(mk[b], fin)
            total_b[b] = total_b[b] + xfer_b
            for did, new in landed:
                res_mask[b, did] = new
            for did, _ in g.writes[tb]:
                res_mask[b, did] = dst_bit
                writer[b, did] = r
    return {"makespan": mk.astype(np.float64), "total_bytes": total_b.astype(np.float64)}
