"""Plain reference of one DADA activation with transfers priced by route.

The algorithm is ``dada_ref``'s (paper §3.2, Algorithm 2), and only the
transfer prediction (``+CP``) differs: a copy takes the route a machine
with a peer fabric (NVLink/NVSwitch, ICI) gives it. A read that is
resident, or exists nowhere yet, costs nothing; one that a memory on the
destination's fabric holds costs one fabric hop (``peer_latency`` +
bytes / ``peer_bandwidth``), preferred to any other source; otherwise one
host-link hop if the host holds it or the destination is the host, and two
(device → host → device) if not. Without a fabric every price is
``dada_ref``'s, in the same operations, so on the paper's machine the two
agree bit for bit.

Nothing of the program is imported. ``dtype`` sets the arithmetic:
float64 is the precision the scheduler states; float32 is the control.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench.refs.dada_ref import TINY, Activation, History, ReadyTask, _resident

__all__ = ["Activation", "History", "ReadyTask", "place"]


def place(act: Activation, history: History, classes: Tuple[str, str],
          mems: Sequence[int], alpha: float, use_cp: bool,
          latency: float, bandwidth: float, peer_latency: float = 0.0,
          peer_bandwidth: float = 0.0, peer_mems: Sequence[int] = (),
          eps_rel: float = 0.01, max_iters: int = 30,
          dtype=np.float64) -> Tuple[Dict[int, int], List[float]]:
    """Return ``({tid: rid}, new stamps)`` for one activation.

    ``history`` predicts durations (advanced to the activation by the
    caller); ``classes`` names the CPU and the accelerator class.
    ``mems[rid]`` is resource rid's memory: -1 for the host (a CPU), an
    accelerator's memory id otherwise. Resource ids are list positions.
    ``peer_mems`` are the accelerator memories joined by a peer fabric of
    ``peer_latency`` and ``peer_bandwidth`` (none: every device copy goes
    through the host).
    """
    f = np.dtype(dtype).type
    tiny = f(TINY)
    zero = f(0.0)
    lat, bw = f(latency), f(bandwidth)
    peer_lat, peer_bw = f(peer_latency), f(peer_bandwidth)
    fabric = set(peer_mems)
    rids = list(range(len(mems)))
    cpus = [r for r in rids if mems[r] < 0]
    gpus = [r for r in rids if mems[r] >= 0]
    now = f(act.now)
    ready = act.tasks
    cpu_cls, gpu_cls = classes
    p_cpu = {t.tid: f(history.predict(t.kind, t.flops, cpu_cls)) for t in ready}
    p_gpu = {t.tid: f(history.predict(t.kind, t.flops, gpu_cls)) for t in ready}

    def one_hop(nbytes: float):
        return zero if nbytes <= 0 else lat + f(nbytes) / bw

    def peer_hop(nbytes: float):
        return zero if nbytes <= 0 else peer_lat + f(nbytes) / peer_bw

    def from_peer(mask: int, dst: int) -> bool:
        """A memory on dst's fabric holds a copy."""
        return dst in fabric and any(_resident(mask, m) for m in fabric)

    xfer: Dict[Tuple[int, int], object] = {}
    for t in ready:
        for r in rids:
            total = zero
            if use_cp:
                dst = mems[r]
                for size, mask in t.reads:
                    if mask == 0 or _resident(mask, dst):
                        continue
                    if from_peer(mask, dst):
                        total = total + peer_hop(size)  # one fabric hop
                        continue
                    hops = 1 if (dst < 0 or mask & 1) else 2
                    total = total + f(hops) * one_hop(size)
            xfer[t.tid, r] = total

    def cost(t: ReadyTask, r: int):
        p = p_gpu[t.tid] if mems[r] >= 0 else p_cpu[t.tid]
        return p + xfer[t.tid, r]

    offsets = {}
    for r in rids:
        d = f(act.stamps_before[r]) - now
        offsets[r] = d if d > 0 else zero

    pref: Dict[int, Tuple[object, int]] = {}
    if alpha > 0.0:
        for t in ready:
            best, best_r = zero, -1
            for r in rids:
                if mems[r] < 0:
                    continue  # host-resident data gives no locality
                s = zero
                for size, mask in t.writes:
                    if _resident(mask, mems[r]):
                        s = s + f(size)
                if s > best + tiny:
                    best, best_r = s, r
            if best_r >= 0:
                pref[t.tid] = (best, best_r)
    by_score = sorted(((sc, tid, r) for tid, (sc, r) in pref.items()),
                      key=lambda x: (-x[0], x[1]))
    by_tid = {t.tid: t for t in ready}
    a = f(alpha)

    def try_build(lam):
        loads = dict(offsets)
        assign: Dict[int, int] = {}
        if alpha > 0.0:
            for _, tid, r in by_score:
                if loads[r] <= a * lam + tiny:
                    assign[tid] = r
                    loads[r] = loads[r] + cost(by_tid[tid], r)
        rem = [t for t in ready if t.tid not in assign]
        for t in rem:
            if (not cpus or p_cpu[t.tid] > lam) and (not gpus or p_gpu[t.tid] > lam):
                return None

        def eft(t, pool):
            r = min(pool, key=lambda q: (loads[q] + cost(t, q), q))
            assign[t.tid] = r
            loads[r] = loads[r] + cost(t, r)

        flex = []
        for t in rem:
            if cpus and gpus:
                if p_cpu[t.tid] > lam:
                    eft(t, gpus)
                elif p_gpu[t.tid] > lam:
                    eft(t, cpus)
                else:
                    flex.append(t)
            else:
                eft(t, cpus or gpus)
        flex.sort(key=lambda t: (-(p_cpu[t.tid] / max(p_gpu[t.tid], tiny)), t.tid))
        for t in flex:
            g = min(gpus, key=lambda q: (loads[q], q)) if gpus else None
            if g is not None and loads[g] <= lam + tiny:
                assign[t.tid] = g
                loads[g] = loads[g] + cost(t, g)
            else:
                eft(t, cpus or gpus)
        bound = (f(2.0) + a) * lam
        if all(v <= bound + tiny for v in loads.values()):
            return assign, loads
        return None

    max_off = max(offsets.values(), default=zero)
    worst = zero
    if use_cp:
        for t in ready:
            worst = worst + max(xfer[t.tid, r] for r in rids)
    # the work bound is Python's sum() of floats, which rounds like a
    # compensated sum (Python 3.12 on), not like a loop of additions: the
    # last bit of the bound sets the probes of the search
    upper = f(sum(float(max(p_cpu[t.tid], p_gpu[t.tid])) for t in ready))
    upper = upper + max_off + worst + tiny
    lower = zero
    kept = None
    it = 0
    eps = f(eps_rel)
    while upper - lower > eps * upper and it < max_iters:
        lam = (upper + lower) / f(2.0)
        built = try_build(lam)
        if built is not None:
            upper, kept = lam, built
        else:
            lower = lam
        it += 1
    if kept is None:
        kept = try_build(upper)
        if kept is None:
            raise ArithmeticError("λ = upper bound was not feasible")
    assign, loads = kept
    return assign, [float(now + loads[r]) for r in rids]
