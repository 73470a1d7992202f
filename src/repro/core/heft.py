"""HEFT — Heterogeneous Earliest Finish Time, XKaapi variant (paper §3.1).

Both phases run inside ``activate`` (Algorithm 1):
  * task prioritizing: ready tasks sorted by decreasing GPU speedup
    ``S_i = p_i^CPU / p_i^GPU`` (the paper replaces upward-rank with this),
  * worker selection: each task goes to the worker with the earliest
    predicted finish time, *always* including predicted transfer time
    ("HEFT strategy always computes the earliest finish time of a task
    taking into account the time to transfer data", §4.1).

Array-native: per-class predicted durations come from the cached vector
predictor (class durations are invariant within an activation, so they are
hoisted out of the EFT loop entirely) and the (ready × resources) transfer
estimates come from the CSR read incidence + residency bitmasks — batched
numpy for wide activations, a scalar pass over the same arrays for narrow
ones (``activate`` usually wakes 1-3 tasks, where per-call numpy setup
would dominate). The per-task EFT selection keeps the strict-improvement
scan of the scalar reference, so placements (including tie-breaks within
1e-15) are bit-identical to ``repro.core._reference.ReferenceHEFT``.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from .backend import ScoringBackendMixin
from .dag import Task
from .simulator import Simulator, Strategy

_WIDE = 32  # ready-set size from which the batched numpy path wins


class HEFT(ScoringBackendMixin, Strategy):
    name = "heft"
    allow_steal = False
    owner_lifo = False

    def __init__(self, backend: Optional[str] = None, config=None) -> None:
        """``backend``: placement-scoring backend (``numpy``/``jax``);
        default follows the scheduling configuration (``config`` or the
        environment-derived ``repro.sched.SchedConfig``). The jax backend
        computes the transfer matrix in one fused dispatch and runs the
        sequential EFT selection as a jitted scan on wide activations —
        placements (including the 1e-15 strict-improvement tie-break) are
        bit-identical to the scalar loop."""
        self._init_backend(backend, config)

    def place(self, sim: Simulator, ready: List[Task], src: Optional[int]) -> None:
        machine = sim.machine
        resources = machine.resources
        cpus = machine.cpus
        gpus = machine.gpus
        cpu_cls = cpus[0].cls if cpus else gpus[0].cls
        gpu_cls = gpus[0].cls if gpus else cpu_cls

        n = len(ready)
        tids = [t.tid for t in ready]

        # --- per-class predicted durations (activation-invariant) --------
        if n >= _WIDE:
            tids_arr = np.asarray(tids, dtype=np.int64)
            p_cpu = sim.predictor(cpu_cls).times(tids_arr).tolist()
            p_gpu = sim.predictor(gpu_cls).times(tids_arr).tolist()
        else:
            p_cpu = sim.predictor(cpu_cls).times_list(tids)
            p_gpu = sim.predictor(gpu_cls).times_list(tids)

        # --- task prioritizing: decreasing speedup -----------------------
        speed = [pc / pg if pg > 0 else 1.0 for pc, pg in zip(p_cpu, p_gpu)]
        order = sorted(range(n), key=lambda i: (-speed[i], tids[i]))

        # per-resource duration columns (only two classes exist in the
        # paper machine, so this is two lookups, not a per-resource model
        # call)
        cls_times = {cpu_cls.name: p_cpu, gpu_cls.name: p_gpu}
        cols = []
        for r in resources:
            col = cls_times.get(r.cls.name)
            if col is None:
                col = sim.predictor(r.cls).times_list(tids)
                cls_times[r.cls.name] = col
            cols.append(col)

        # memory-pressure penalty (capacity-bounded memories, plus the
        # +inf mask over detached resources): predicted eviction seconds
        # folded into the transfer matrix, on the numpy and jax scoring
        # paths alike
        from repro.runtime.memory import fold_pressure, pressure_rows_for

        P = pressure_rows_for(sim, tids, resources)

        # under active faults the scalar path runs (dead columns carry
        # +inf, which the fused backend's kernels do not model — and a
        # pending preemption notice adds a time-varying finite penalty
        # the kernels do not model either); with no resource detached or
        # noticed the fused path is untouched, preserving cross-backend
        # equivalence
        faults = getattr(sim, "faults", None)
        any_dead = faults is not None and (
            faults.any_dead or bool(faults.noticed)
        )

        # accelerated path (wide activations, jax backend): fused transfer
        # matrix + jitted sequential EFT scan, bit-identical placements
        be = self._scoring_backend()
        if be is not None and n >= be.min_wide and not any_dead:
            fused = be.score_matrices(
                sim, tids, resources, use_cp=True, x_rows=True, x_bias=P
            )
            if fused is not None:
                load_ts = sim.load_ts
                colsT = np.asarray(cols, dtype=np.float64).T  # (n, n_res)
                X_np = fused["X_np"]
                rids, efts = be.heft_select(
                    colsT[order], X_np[order], load_ts, sim.now
                )
                for k, i in enumerate(order):
                    rid = int(rids[k])
                    load_ts[rid] = float(efts[k])
                    sim.push(ready[i], rid)
                return
        if be is not None:
            be.counts["cells_host"] += n * len(resources)

        X = fold_pressure(
            sim.transfer_model.task_input_transfer_rows(
                sim.arrays, tids, [r.mem for r in resources], sim.residency
            ),
            P,
        )

        # --- worker selection: earliest finish time ----------------------
        load_ts = sim.load_ts
        now = sim.now
        n_res = len(resources)
        first_rid = resources[0].rid
        inf = float("inf")
        for i in order:
            xrow = X[i]
            best_eft = inf
            best_rid = first_rid
            for rid in range(n_res):
                lt = load_ts[rid]
                start = now if now > lt else lt
                eft = start + xrow[rid] + cols[rid][i]
                if eft < best_eft - 1e-15:
                    best_eft = eft
                    best_rid = rid
            load_ts[best_rid] = best_eft
            sim.push(ready[i], best_rid)
