"""DADA — Distributed Affinity Dual Approximation (paper §3.2, Algorithm 2).

Binary search on a makespan guess ``λ``; for each guess:

  * **local affinity phase** — ready tasks are placed on their max-affinity
    processor (affinity = bytes the task writes that are resident there),
    loading each processor up to *overreaching* ``α·λ``;
  * **global balance phase** — a ρ=2 dual approximation on the rest: tasks
    that only fit one class are dedicated; flexible tasks go to GPUs by
    decreasing speedup until the GPU loads overreach ``λ``; the remainder
    goes to CPUs with an earliest-finish-time rule;
  * the guess is accepted iff every processor's load fits ``(2+α)·λ``.

``α = 0`` disables the affinity phase: DADA(0) is the plain dual
approximation. ``use_cp=True`` (the paper's "+CP") adds communication
prediction (asymptotic-bandwidth model) to every load/finish-time estimate.

Array-native: everything λ-independent is batched once per activation —
per-class duration vectors from the cached vector predictor, the
(ready × resources) transfer matrix from the CSR read incidence +
residency bitmasks, the affinity score matrix, the speedup sort keys and
the full cost matrix ``C = p + xfer``. Each λ-probe of ``try_build`` then
runs over plain float rows with no model calls at all, which is what makes
the ~30-probe binary search cheap. Decisions (including tie-breaks) are
bit-identical to ``repro.core._reference.ReferenceDADA``.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from . import obs
from .affinity import AFFINITY_FUNCTIONS, AffinityFn, affinity_rows
from .backend import ScoringBackendMixin
from .dag import Task
from .simulator import Simulator, Strategy

_TINY = 1e-12
_WIDE = 32  # ready-set size from which the batched numpy path wins


class DADA(ScoringBackendMixin, Strategy):
    allow_steal = False
    owner_lifo = False

    def __init__(
        self,
        alpha: float = 0.5,
        use_cp: bool = False,
        affinity: str = "accel_write",
        eps_rel: float = 0.01,
        max_iters: int = 30,
        area_bound: bool = False,
        recover: bool = False,
        backend: Optional[str] = None,
        config=None,
    ) -> None:
        """``area_bound``: also reject a guess λ when the total work area
        exceeds λ x (number of resources) — a valid no-schedule certificate
        that keeps λ (and hence the affinity budget α·λ) near the true
        optimum instead of descending to OPT/(2+α). Off by default (the
        paper's Algorithm 2 rejects only on the big-task criterion); the
        expert-placement bridge turns it on.

        ``recover``: notice-aware placement (``resolve("dada?recover=1")``).
        A preemption-noticed resource (detach announced, not yet fired —
        see ``repro.runtime.faults``) has its cost column charged the
        remaining notice window and is skipped by the affinity phase, so
        new work and fresh affinity steer off a condemned device *before*
        it dies instead of being requeued off it afterwards. Off by
        default; with no pending notice the recover path is untouched, so
        ``recover=True`` is bit-identical to ``recover=False`` outside
        notice windows.

        ``backend``: placement-scoring backend (``numpy``/``jax``); default
        follows the scheduling configuration (``config`` or the
        environment-derived ``repro.sched.SchedConfig``). The jax backend
        batches the score matrices and the λ-probe search on wide
        activations; placements are bit-identical either way (see
        ``repro.core.backend``)."""
        if not 0.0 <= alpha <= 1.0:
            raise ValueError("alpha must be within [0, 1]")
        self.alpha = alpha
        self.use_cp = use_cp
        self.affinity_name = affinity
        self.affinity_fn: AffinityFn = AFFINITY_FUNCTIONS[affinity]
        self.eps_rel = eps_rel
        self.max_iters = max_iters
        self.area_bound = area_bound
        self.recover = recover
        self._init_backend(backend, config)
        cp = "+cp" if use_cp else ""
        rec = "+rec" if recover else ""
        self.name = f"dada({alpha:g}){cp}{rec}"

    # ------------------------------------------------------------------
    def place(self, sim: Simulator, ready: List[Task], src: Optional[int]) -> None:
        with obs.span("dada.place"):
            self._place(sim, ready, src)

    def _place(self, sim: Simulator, ready: List[Task], src: Optional[int]) -> None:
        machine = sim.machine
        resources = machine.resources
        cpus = machine.cpus
        gpus = machine.gpus
        cpu_cls = cpus[0].cls if cpus else gpus[0].cls
        gpu_cls = gpus[0].cls if gpus else cpu_cls
        n_res = len(resources)
        n = len(ready)
        tids = [t.tid for t in ready]

        with obs.span("dada.predict"):
            # --- λ-independent precomputation (batched for wide activations,
            # --- scalar over the same arrays for narrow ones) ----------------
            if n >= _WIDE:
                tids_arr = np.asarray(tids, dtype=np.int64)
                p_cpu = sim.predictor(cpu_cls).times(tids_arr).tolist()
                p_gpu = sim.predictor(gpu_cls).times(tids_arr).tolist()
            else:
                p_cpu = sim.predictor(cpu_cls).times_list(tids)
                p_gpu = sim.predictor(gpu_cls).times_list(tids)

            # memory-pressure penalty under +CP (capacity-bounded memories):
            # predicted eviction seconds folded into the transfer matrix on
            # the numpy and jax scoring paths alike. fault_mask=False: DADA
            # handles detached resources by filtering its placement pools
            # below — an +inf fold would blow up `upper` (the λ search's
            # feasibility anchor) and every probe's load updates
            from repro.runtime.memory import fold_pressure, pressure_rows_for

            P = (
                pressure_rows_for(sim, tids, resources, fault_mask=False)
                if self.use_cp
                else None
            )

            # detached resources (repro.runtime.faults): excluded from every
            # placement pool and load update; with no resource detached the
            # sets below are unchanged and the fused path stays available
            faults = getattr(sim, "faults", None)
            dead = (
                faults.dead_rids
                if faults is not None and faults.any_dead
                else frozenset()
            )

            # notice-aware recovery (recover=True only): a condemned column
            # pays the remaining notice window, by resource position — the
            # same finite decaying signal pressure_rows_for feeds score-matrix
            # policies, folded into C below so every phase of the λ search
            # steers off a dying device. Empty whenever no notice is pending,
            # keeping recover=True bit-identical outside notice windows.
            noticed_pen: Dict[int, float] = {}
            if self.recover and faults is not None and faults.noticed:
                for j, r in enumerate(resources):
                    pending = faults.noticed.get(r.rid)
                    if pending is not None:
                        p = pending[1] - sim.now
                        if p > 0.0:
                            noticed_pen[j] = p

        with obs.span("dada.order"):
            offsets = [
                lt - sim.now if lt - sim.now > 0.0 else 0.0
                for lt in (sim.load_ts[r.rid] for r in resources)
            ]
            if dead:
                # dead resources receive no load and contribute no backlog
                # (their stale load_ts must not gate the λ feasibility test)
                for j, r in enumerate(resources):
                    if r.rid in dead:
                        offsets[j] = 0.0

            # speedup sort keys for the flexible phase (λ-independent)
            skey = [-(pc / max(pg, _TINY)) for pc, pg in zip(p_cpu, p_gpu)]

            cpu_rids = [r.rid for r in cpus if r.rid not in dead]
            gpu_rids = [r.rid for r in gpus if r.rid not in dead]
            any_rids = cpu_rids or gpu_rids
            if not any_rids:
                raise RuntimeError("DADA: every resource is detached")
            have_both = bool(cpu_rids and gpu_rids)
            no_cpus = not cpu_rids
            no_gpus = not gpu_rids

            area = off_total = 0.0
            if self.area_bound:
                area = sum(min(pc, pg) for pc, pg in zip(p_cpu, p_gpu))
                off_total = sum(offsets)

            all_idx = list(range(n))
            # global flex order (λ-independent): per-probe flex sets are subsets
            # of ready, so filtering this order equals sorting each subset.
            # (skey, tid) keys are unique per task (tids are unique), so the
            # wide-activation lexsort yields the identical permutation.
            if n >= _WIDE:
                flex_order = np.lexsort(
                    (np.asarray(tids, dtype=np.int64), np.asarray(skey))
                ).tolist()
            else:
                flex_order = sorted(all_idx, key=lambda i: (skey[i], tids[i]))
            alpha = self.alpha
            two_alpha = 2.0 + alpha
            area_bound = self.area_bound
            max_off = max(offsets, default=0.0)
            n_res_alive = n_res - len(dead)
            # the λ search's upper bound, less the transfer terms (X's row
            # maxima) and _TINY, added in this order wherever it is summed
            upper_host = sum(max(pc, pg) for pc, pg in zip(p_cpu, p_gpu)) + max_off

            # wide activations, jax backend: one program scores C (and X's
            # row maxima and the affinity scores), orders the affinity
            # preferences and runs the λ search, bit-equal to the host path
            # below (skipped under active faults or pending notices — the
            # backend kernels do not model liveness)
            be = self._scoring_backend()
            fused = None
            if be is not None and n >= be.min_wide and not dead and not noticed_pen:
                fused = be.score_matrices(
                    sim, tids, resources,
                    p_cpu=p_cpu, p_gpu=p_gpu,
                    use_cp=self.use_cp,
                    affinity=self.affinity_name if alpha > 0.0 else None,
                    x_bias=P,
                    search=dict(
                        offsets=offsets, flex_order=flex_order, have_both=have_both,
                        no_cpus=no_cpus, no_gpus=no_gpus, alpha=alpha,
                        area_bound=area_bound, area=area, off_total=off_total,
                        max_off=max_off, eps_rel=self.eps_rel,
                        max_iters=self.max_iters, upper_host=upper_host,
                    ),
                )
            if be is not None and fused is None:
                be.counts["cells_host"] += n * n_res

            if fused is not None:
                C_rows = fused["C"]
            else:
                X = None
                if self.use_cp:
                    X = fold_pressure(
                        sim.transfer_model.task_input_transfer_rows(
                            sim.arrays, tids, [r.mem for r in resources],
                            sim.residency,
                        ),
                        P,
                    )
                # cost matrix C[i][rid] = duration-on-class + predicted transfer
                gpu_pos = [j for j, r in enumerate(resources) if r.is_accelerator]
                C_rows = []
                if X is None:
                    for pc, pg in zip(p_cpu, p_gpu):
                        row = [pc] * n_res
                        for j in gpu_pos:
                            row[j] = pg
                        C_rows.append(row)
                else:
                    for pc, pg, xrow in zip(p_cpu, p_gpu, X):
                        row = [pc + x for x in xrow]
                        for j in gpu_pos:
                            row[j] = pg + xrow[j]
                        C_rows.append(row)
                if noticed_pen:
                    # condemned columns pay the remaining notice window
                    for row in C_rows:
                        for j, p in noticed_pen.items():
                            row[j] += p

                # affinity preferences per task, with the placement cost
                # prefetched: (tid, rid, cost) by (-score, tid)
                pref: List[Tuple[float, int, int, float]] = []
                if alpha > 0.0:
                    S_rows = affinity_rows(
                        self.affinity_name, sim.arrays, tids, ready, resources,
                        sim.residency,
                    )
                    for i, row in enumerate(S_rows):
                        if not any(row):
                            continue  # all-zero (C-level falsy) row: no preference
                        best_score, best_rid = 0.0, -1
                        for rid in range(n_res):
                            if rid in dead:
                                continue  # affinity to a vanished memory is void
                            if rid in noticed_pen:
                                # affinity to a condemned memory is a trap:
                                # the data is leaving with the device
                                continue
                            s = row[rid]
                            if s > best_score + _TINY:
                                best_score, best_rid = s, rid
                        if best_rid >= 0:
                            pref.append(
                                (best_score, tids[i], best_rid, C_rows[i][best_rid])
                            )
                by_score = [
                    (tid, rid, c)
                    for _, tid, rid, c in sorted(pref, key=lambda x: (-x[0], x[1]))
                ]

                # upper bound of the binary search on λ
                worst_xfer = 0.0
                if X is not None:
                    for xrow in X:
                        worst_xfer += max(xrow)
                upper = upper_host + worst_xfer + _TINY
                if noticed_pen:
                    # the notice penalties inflate C, so the feasibility anchor
                    # must cover them too (λ=upper stays provably feasible)
                    upper += n * max(noticed_pen.values())

            # ------------------------------------------------------------------
            def try_build(lam: float) -> Optional[Tuple[Dict[int, int], List[float]]]:
                # try_build is pure (touches only its locals), so the acceptance
                # test `all(load <= (2+α)λ)` is folded into every load update:
                # loads only grow, hence the first overflow already decides the
                # probe — same verdict as building fully, minus the wasted work.
                cap = two_alpha * lam + _TINY
                if max_off > cap:
                    return None
                if area_bound:
                    capacity = lam * n_res_alive - off_total
                    if area > capacity + _TINY:
                        return None  # certificate: no λ-schedule exists
                loads = offsets.copy()
                assign: Dict[int, int] = {}

                # ---- local affinity phase (line 5-7) -------------------------
                if by_score:
                    budget = alpha * lam + _TINY
                    for tid, rid, c in by_score:
                        if loads[rid] <= budget:
                            assign[tid] = rid
                            v = loads[rid] + c
                            if v > cap:
                                return None
                            loads[rid] = v

                # ---- global balance phase (line 8-9) -------------------------
                if assign:
                    rem = [i for i in all_idx if tids[i] not in assign]
                else:
                    rem = all_idx
                for i in rem:  # reject if a task is larger than λ everywhere
                    big_cpu = no_cpus or p_cpu[i] > lam
                    big_gpu = no_gpus or p_gpu[i] > lam
                    if big_cpu and big_gpu:
                        return None

                flex = None
                if have_both:
                    flex = bytearray(n)
                    for i in rem:
                        if p_cpu[i] > lam:
                            pool_rids = gpu_rids  # dedicated to GPUs
                        elif p_gpu[i] > lam:
                            pool_rids = cpu_rids  # dedicated to CPUs
                        else:
                            flex[i] = 1
                            continue
                        # earliest finish time; first minimum wins (== min by
                        # (finish, rid): pool rids are ascending)
                        crow = C_rows[i]
                        best_v = float("inf")
                        best_rid = pool_rids[0]
                        for rid in pool_rids:
                            v = loads[rid] + crow[rid]
                            if v < best_v:
                                best_v = v
                                best_rid = rid
                        if best_v > cap:
                            return None
                        assign[tids[i]] = best_rid
                        loads[best_rid] = best_v
                else:
                    for i in rem:
                        crow = C_rows[i]
                        best_v = float("inf")
                        best_rid = any_rids[0]
                        for rid in any_rids:
                            v = loads[rid] + crow[rid]
                            if v < best_v:
                                best_v = v
                                best_rid = rid
                        if best_v > cap:
                            return None
                        assign[tids[i]] = best_rid
                        loads[best_rid] = best_v

                # flexible tasks: largest speedup first, to GPUs up to
                # overreaching λ, the rest to CPUs (earliest finish time)
                if flex is not None:
                    gpu_budget = lam + _TINY
                    for i in flex_order:
                        if not flex[i]:
                            continue
                        if gpu_rids:
                            g = gpu_rids[0]
                            gl = loads[g]
                            for rid in gpu_rids[1:]:
                                if loads[rid] < gl:
                                    gl = loads[rid]
                                    g = rid
                            if gl <= gpu_budget:
                                v = gl + C_rows[i][g]
                                if v > cap:
                                    return None
                                assign[tids[i]] = g
                                loads[g] = v
                                continue
                        crow = C_rows[i]
                        best_v = float("inf")
                        best_rid = any_rids[0]
                        for rid in any_rids:
                            v = loads[rid] + crow[rid]
                            if v < best_v:
                                best_v = v
                                best_rid = rid
                        if best_v > cap:
                            return None
                        assign[tids[i]] = best_rid
                        loads[best_rid] = best_v

                # acceptance (line 10) already enforced incrementally above
                return assign, loads

        # the placement at the final λ; where the search runs on the host
        # (narrow activations, or a rejected device λ) dada.search_host
        # nests inside this span
        with obs.span("dada.rebuild"):
            # binary search on λ (the classical dual-approximation loop)
            lower = 0.0
            kept: Optional[Tuple[Dict[int, int], List[float]]] = None
            searched = False
            if fused is not None:
                # the device's λ is bit-identical to the Python loop's final
                # upper, and the placement is rebuilt by try_build, over the
                # device's affinity order, so decisions (tie-breaks
                # included) cannot drift
                rows, rids = fused["order_rows"], fused["order_rids"]
                by_score = list(zip(
                    np.asarray(tids, dtype=np.int64)[rows].tolist(), rids.tolist(),
                    fused["C_np"][rows, rids].tolist(),
                ))
                upper = fused["upper0"]
                built = try_build(fused["lam"])
                if built is not None:
                    upper = fused["lam"]
                    kept = built
                    searched = True
                else:
                    # defensive — a divergent verdict would leave an
                    # infeasible λ; counted, then the Python search below
                    be.counts["rejected"] += 1
            if not searched:
                with obs.span("dada.search_host"):
                    it = 0
                    while upper - lower > self.eps_rel * upper and it < self.max_iters:
                        lam = (upper + lower) / 2.0
                        built = try_build(lam)
                        if built is not None:
                            upper = lam
                            kept = built
                        else:
                            lower = lam
                        it += 1
                    if kept is None:
                        kept = try_build(upper)
                        assert kept is not None, "λ=upper must always be feasible"

            assign, loads = kept
            # expose the accepted guess for tests / introspection
            self.last_lambda = upper
            self.last_loads = {r.rid: loads[j] for j, r in enumerate(resources)}
            for t in ready:
                rid = assign[t.tid]
                sim.push(t, rid)
            for j, r in enumerate(resources):
                sim.load_ts[r.rid] = sim.now + loads[j]


class DualApprox(DADA):
    """Plain ρ=2 dual approximation — DADA with the affinity phase off."""

    def __init__(self, use_cp: bool = False, **kw) -> None:
        super().__init__(alpha=0.0, use_cp=use_cp, **kw)
        self.name = "dual" + ("+cp" if use_cp else "")
