"""Traffic kind ``schedule``: whole schedules on the exact engine, back to back.

Each schedule is the configuration's task graph placed on its machine by the
traffic's policy through ``repro.core.run_simulation``: every activation goes
through ``strategy.place``, where the scoring programs run on the device for
activations at least ``jax_min`` wide. The benchmark wraps the strategy
object to time every activation (the host clock around ``place`` includes
reading the device's results back, which ``place`` waits for).

Set-up does not depend on ``--seed``: it places one schedule of the
traffic's ``warm_seed``, which compiles (or loads from the persistent
cache) the scoring programs that schedule uses, then runs DADA's λ search
once at every shape an activation up to the configuration's
``widest_ready`` can give it (rows bucketed to powers of two from
``jax_min``; per-resource affinity chains of no entry, or of a power of two
up to the rows). The window then places one schedule after another, each
with a fresh seed drawn from ``--seed`` (its noise draw), and closes at the
end of the first schedule that ends after ``--seconds``: it holds whole
schedules.

Correctness, after the window: a sample of the window's activations drawn
from ``--seed`` is replayed through the plain reference of one DADA
activation (``refs/dada_ref.py``), which predicts durations itself from the
task's flops, the machine's class rates and the durations the engine had
observed before the activation; its placement and stamps must equal the
program's bit for bit. Every schedule the window ran must be a valid one
(each task once, after its predecessors, one task at a time per resource).
"""
from __future__ import annotations

import time
from functools import partial
from typing import Dict, List

import numpy as np

from bench import harness as H
from bench.refs import dada_ref

COMPARED = ("decisions_differing", "invalid_schedules")


def _bucket(n: int, lo: int = 1) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


class Timed:
    """The strategy as the engine sees it, with every activation timed.

    Every schedule's duration observations are logged in order. While the
    window is open, each ``place`` is timed and counted, and sampled
    activations are captured for the reference: what the activation saw,
    and the stamps it left.
    """

    def __init__(self, inner, backend, check_share: float, seed: int) -> None:
        self.inner = inner
        self.counts = backend.counts if backend is not None else {"device": 0}
        self.allow_steal = inner.allow_steal
        self.owner_lifo = inner.owner_lifo
        self.name = inner.name
        self.win = None
        self.spans = None
        self.check_share = check_share
        self.rng = np.random.default_rng([seed, 1])
        self.latencies: List[float] = []
        self.placed = 0
        self.device = 0
        self.captures: List[tuple] = []
        self.observed: List[list] = []  # per window schedule

    def init(self, sim) -> None:
        log: list = []
        if self.win is not None and not self.win.closed:
            self.observed.append(log)
        observe = sim.model.observe

        def logged(task, cls, duration):
            log.append((task.kind, cls.name, duration))
            observe(task, cls, duration)

        sim.model.observe = logged
        self.log = log
        self.inner.init(sim)

    def place(self, sim, ready, src) -> None:
        win = self.win
        if win is None or win.closed:
            return self.inner.place(sim, ready, src)
        snap = None
        if self.rng.random() < self.check_share:
            snap = capture(sim, ready, len(self.log))
        d0 = self.counts["device"]
        with self.spans.span("place"):
            t0 = time.perf_counter()
            self.inner.place(sim, ready, src)
            dt = time.perf_counter() - t0
        self.device += self.counts["device"] - d0
        self.latencies.append(dt)
        self.placed += len(ready)
        if snap is not None:
            snap.stamps_after = list(sim.load_ts)
            self.captures.append((len(self.observed) - 1, snap))


def capture(sim, ready, n_observed: int) -> dada_ref.Activation:
    """What one activation sees: clock, stamps, tasks, residency."""
    res = sim.residency
    tasks = [dada_ref.ReadyTask(
        tid=t.tid, kind=t.kind, flops=t.flops,
        reads=[(d.size_bytes, res.mask(d.name)) for d in t.reads],
        writes=[(d.size_bytes, res.mask(d.name)) for d in t.writes]) for t in ready]
    return dada_ref.Activation(now=sim.now, stamps_before=list(sim.load_ts),
                               tasks=tasks, stamps_after=[], n_observed=n_observed)


class State:
    pass


def setup(cell: H.Cell, seed: int, devs) -> State:
    from repro.core import get_backend, run_simulation
    from repro.sched import SchedConfig, resolve

    cfg, tr = cell.config, cell.traffic
    st = State()
    st.graph = H.build_graph(cfg)
    st.machine = H.build_machine(cfg)
    st.sched = SchedConfig(backend=tr["backend"], **cfg.get("sched", {}))
    st.policy = tr["policy"]
    st.noise = float(tr["noise"])
    st.check_share = float(tr["check_share"])
    st.limits = cell.limits
    st.backend = get_backend(tr["backend"], st.sched)
    st.inner = resolve(H.policy_spec(tr["policy"]), backend=tr["backend"], config=st.sched)
    st.run = partial(run_simulation, st.graph, st.machine, noise=st.noise, config=st.sched)
    t0 = time.perf_counter()
    kept = warm_schedule(st, int(tr["warm_seed"]))
    t1 = time.perf_counter()
    n = warm_search(st, kept, int(cfg["widest_ready"]))
    H.log(f"warm-up: schedule of {len(st.graph)} tasks in {t1 - t0!r} s; "
          f"lambda search at {n} shapes in {time.perf_counter() - t1!r} s")
    reseed(st, seed)
    return st


def reseed(st: State, seed: int) -> None:
    """Fresh per-seed state: the window's schedule seeds and its sample."""
    st.seed = seed
    st.seeds = np.random.default_rng([seed, 4])
    st.timed = Timed(st.inner, st.backend, st.check_share, seed)


def warm_schedule(st: State, warm_seed: int) -> Dict[int, object]:
    """One schedule of ``warm_seed``; returns a cost matrix on the device
    for each row bucket it scored, as the λ search receives them."""
    be = st.backend
    kept: Dict[int, object] = {}
    score = be.score_matrices

    def keeping(*args, **kw):
        out = score(*args, **kw)
        if out is not None and out["C_dev"] is not None:
            kept[out["C_dev"].shape[0]] = out["C_dev"]
        return out

    be.score_matrices = keeping
    try:
        st.run(Timed(st.inner, be, 0.0, 0), seed=warm_seed)
    finally:
        del be.score_matrices
    return kept


def warm_search(st: State, kept: Dict[int, object], widest: int) -> int:
    """DADA's λ search once at every (rows, affinity chain) bucket that an
    activation of ``jax_min`` to ``widest`` tasks can reach."""
    be, inner, machine = st.backend, st.inner, st.machine
    resources = machine.resources
    n_res = len(resources)
    gpu = next(j for j, r in enumerate(resources) if r.is_accelerator)
    rows = sorted({_bucket(w, 8) for w in range(st.sched.jax_min, widest + 1)})
    n = 0
    for n_pad in rows:
        if n_pad not in kept:
            raise H.BenchError(f"the warm-up schedule scored no activation of {n_pad} rows")
        chains = [0] + [1 << k for k in range(n_pad.bit_length()) if 1 << k <= n_pad]
        for chain in chains:
            be.dada_lambda_search(
                n=n_pad, n_res=n_res, offsets=[0.0] * n_res, C_dev=kept[n_pad],
                p_cpu=[1.0] * n_pad, p_gpu=[1.0] * n_pad,
                by_score=[(1.0, i, gpu, 1.0) for i in range(chain)],
                tid_index={i: i for i in range(n_pad)}, flex_order=list(range(n_pad)),
                resources=resources, have_both=bool(machine.cpus and machine.gpus),
                no_cpus=not machine.cpus, no_gpus=not machine.gpus,
                alpha=inner.alpha, area_bound=inner.area_bound, area=0.0, off_total=0.0,
                max_off=0.0, eps_rel=inner.eps_rel, max_iters=inner.max_iters,
                upper0=2.0 * n_pad)
            n += 1
    return n


def window(st: State, win: H.Window, spans: H.Spans) -> Dict:
    timed = st.timed
    timed.win, timed.spans = win, spans
    st.results = []
    win.open()
    while not win.expired():
        seed = int(st.seeds.integers(0, 2**31 - 1))
        with spans.span("schedule"):
            st.results.append(st.run(timed, seed=seed))
    win.close()
    n_act = len(timed.latencies)
    return {
        "e2e": {"sched_tasks_per_s": timed.placed / win.length,
                "decision_p95_ms": H.percentile([1e3 * x for x in timed.latencies], 95)},
        "attempted": n_act,
        "counters": {"activations": n_act, "tasks_placed": timed.placed,
                     "device_scored": timed.device, "schedules": len(st.results)},
    }


def invalid_schedule(res, graph) -> str:
    """Why the schedule is not valid, or '' if it is."""
    ivs = res.intervals
    start = {iv.tid: iv.start for iv in ivs}
    end = {iv.tid: iv.end for iv in ivs}
    if len(ivs) != len(graph) or len(start) != len(graph):
        return f"{len(ivs)} intervals for {len(graph)} tasks"
    for iv in ivs:
        for p in graph.pred[iv.tid]:
            if end[p] > iv.start:
                return f"task {iv.tid} starts {iv.start!r} before predecessor {p} ends {end[p]!r}"
    by_res: Dict[int, list] = {}
    for iv in ivs:
        by_res.setdefault(iv.rid, []).append((iv.start, iv.end))
    for rid, spans in by_res.items():
        spans.sort()
        for (_, e), (s, _) in zip(spans, spans[1:]):
            if s < e:
                return f"resource {rid} runs two tasks at once at {s!r}"
    return ""


def _class_rates(machine) -> Dict[str, tuple]:
    return {r.cls.name: (dict(r.cls.rates), r.cls.default_rate) for r in machine.resources}


def readings(st: State, dtype=np.float64, against=None) -> Dict[str, float]:
    """The numbers compared: sampled activations whose placement or stamps
    differ from the reference computed in ``dtype`` (the program's, or with
    ``against`` the reference's in that dtype), and invalid schedules."""
    machine = st.machine
    mems = [r.mem for r in machine.resources]
    classes = ((machine.cpus or machine.gpus)[0].cls.name,
               (machine.gpus or machine.cpus)[0].cls.name)
    rates = _class_rates(machine)
    p = st.policy
    link = machine.link
    ref = partial(dada_ref.place, classes=classes, mems=mems,
                  alpha=float(p.get("alpha", 0.5)), use_cp=bool(int(p.get("use_cp", 0))),
                  latency=link.latency, bandwidth=link.bandwidth,
                  eps_rel=float(p.get("eps_rel", 0.01)), max_iters=int(p.get("max_iters", 30)))
    timed = st.timed
    histories = [dada_ref.History(rates, log) for log in timed.observed]
    assigned = [{iv.tid: iv.rid for iv in res.intervals} for res in st.results]
    differ = moved = 0
    for k, act in timed.captures:
        history = histories[k].at(act.n_observed)
        assign, stamps = ref(act, history, dtype=dtype)
        if against is None:
            got = {t.tid: assigned[k].get(t.tid) for t in act.tasks}
            got_stamps = act.stamps_after
        else:
            got, got_stamps = ref(act, history, dtype=against)
        moved += got != assign
        differ += got != assign or got_stamps != stamps
    bad = [why for why in (invalid_schedule(r, st.graph) for r in st.results) if why]
    for why in bad[:3]:
        H.log(f"invalid schedule: {why}")
    return {"decisions_differing": float(differ), "invalid_schedules": float(len(bad)),
            "placements_differing": float(moved), "sampled": float(len(timed.captures))}


def control_readings(st: State) -> Dict[str, float]:
    """The control: the reference in float32 in the program's place, held
    against the reference in the float64 the scheduler states."""
    return readings(st, dtype=np.float32, against=np.float64)


def check(st: State, record: Dict) -> List[H.Check]:
    t0 = time.perf_counter()
    r = readings(st)
    H.log(f"reference: {int(r['sampled'])} sampled activations replayed in "
          f"{time.perf_counter() - t0!r} s; {int(r['placements_differing'])} placed differently")
    record["failed"] = int(r["decisions_differing"])
    return H.checks(r, st.limits, COMPARED)
