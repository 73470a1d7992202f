"""Plain reference of one DADA activation (paper §3.2, Algorithm 2).

Given what an activation sees — the clock, every resource's predicted
completion stamp, and for each ready task its kind, its flops and the
residency of the data it reads and writes — compute the placement and the
new stamps the way the paper's algorithm states it: a binary search on the makespan guess λ; for each
guess an affinity phase up to α·λ, a dual-approximation balance phase and
the acceptance test ``load <= (2 + α)·λ``. Transfer prediction (``+CP``) is
the asymptotic-bandwidth model, one hop host↔device and two device↔device;
affinity is the bytes a task writes that are resident on an accelerator.

Durations are predicted as the paper's §2.3 states, by :class:`History`:
the mean of the durations observed so far for the task's kind on the
resource class, and before any observation the task's flops over the
class's rate. The observations are the engine's measured task durations,
in the order they were made; the mean is kept as a running mean
(``mean += (d - mean) / n``), the order of operations that fixes its last
bit.

Nothing of the program is imported. ``dtype`` sets the arithmetic:
float64 is the precision the scheduler states; float32 is the control.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

TINY = 1e-12


@dataclass
class ReadyTask:
    tid: int
    kind: str
    flops: float
    reads: List[Tuple[float, int]]  # (bytes, residency mask) in access order
    writes: List[Tuple[float, int]]


@dataclass
class Activation:
    """One activation as the scheduler saw it, and the stamps it left."""

    now: float
    stamps_before: List[float]
    tasks: List[ReadyTask]
    stamps_after: List[float]
    n_observed: int  # observations made before the activation


class History:
    """History-based duration prediction for one schedule.

    ``rates[cls] = (rate by kind, default rate)`` in flop/s, as the
    configuration's machine states them; ``observed`` holds the schedule's
    ``(kind, class, seconds)`` observations in order. :meth:`at` advances
    to the first ``n`` of them, so activations are taken in the order they
    were made.
    """

    def __init__(self, rates: Dict[str, Tuple[Dict[str, float], float]],
                 observed: Sequence[Tuple[str, str, float]]) -> None:
        self.rates = rates
        self.observed = observed
        self.n = 0
        self.means: Dict[Tuple[str, str], Tuple[int, float]] = {}

    def at(self, n: int) -> "History":
        if n < self.n:
            raise ValueError("activations must be replayed in the order they were made")
        for kind, cls, d in self.observed[self.n:n]:
            k, mean = self.means.get((kind, cls), (0, 0.0))
            k += 1
            mean += (d - mean) / k
            self.means[kind, cls] = (k, mean)
        self.n = n
        return self

    def predict(self, kind: str, flops: float, cls: str) -> float:
        seen = self.means.get((kind, cls))
        if seen is not None:
            return seen[1]
        if flops <= 0.0:
            return 1e-7  # bookkeeping tasks are cheap but not free
        by_kind, default = self.rates[cls]
        return flops / by_kind.get(kind, default)


def _resident(mask: int, mem: int) -> bool:
    return bool(mask & (1 << (mem + 1)))


def place(act: Activation, history: History, classes: Tuple[str, str],
          mems: Sequence[int], alpha: float, use_cp: bool,
          latency: float, bandwidth: float, eps_rel: float = 0.01,
          max_iters: int = 30, dtype=np.float64) -> Tuple[Dict[int, int], List[float]]:
    """Return ``({tid: rid}, new stamps)`` for one activation.

    ``history`` predicts durations (advanced to the activation by the
    caller); ``classes`` names the CPU and the accelerator class.
    ``mems[rid]`` is resource rid's memory: -1 for the host (a CPU), an
    accelerator's memory id otherwise. Resource ids are list positions.
    """
    f = np.dtype(dtype).type
    tiny = f(TINY)
    zero = f(0.0)
    lat, bw = f(latency), f(bandwidth)
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

    xfer: Dict[Tuple[int, int], object] = {}
    for t in ready:
        for r in rids:
            total = zero
            if use_cp:
                dst = mems[r]
                for size, mask in t.reads:
                    if mask == 0 or _resident(mask, dst):
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
