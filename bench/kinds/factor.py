"""Traffic kind ``factor``: a DADA schedule's tile work executed on the device, again and again.

Set-up makes the symmetric positive-definite matrix on the device from the
seed, splits it into tiles, and computes the schedule of the
configuration's tile Cholesky once with the traffic's policy (numpy scoring:
the scheduler's device path is not what this traffic measures). The
schedule comes from the traffic's ``schedule_seed``, not from ``--seed``:
every run then replays the same tasks in the same order, and only the
matrix changes with the seed (a schedule drawn per seed moved the rate by
some 3% from seed to seed on the chip). The window
then replays the schedule with ``repro.linalg.execute_schedule`` from the
pristine tiles, factorisation after factorisation, and closes once the
first factorisation that ends after ``--seconds`` has been waited for: it
holds whole factorisations. Each tile body is counted as it is dispatched.

Correctness, after the window: the factor of the last factorisation is
held to the f32 backward-error figures of ``refs/cholesky_ref.py`` and
compared with ``jnp.linalg.cholesky`` of the same matrix at full f32
precision.
"""
from __future__ import annotations

import time
from typing import Dict, List

from bench import flops as F
from bench import harness as H
from bench.refs import cholesky_ref

COMPARED = cholesky_ref.COMPARED


class State:
    pass


class Counted:
    """A tile body that counts itself."""

    def __init__(self, st: State, kind: str, fn) -> None:
        self.st, self.kind, self.fn = st, kind, fn

    def __call__(self, *args):
        st = self.st
        st.done[self.kind] = st.done.get(self.kind, 0) + 1
        with st.spans.span("tile_body"):
            return self.fn(*args)


def setup(cell: H.Cell, seed: int, devs) -> State:
    import jax

    from repro.core import run_simulation
    from repro.sched import SchedConfig, resolve

    cfg, tr = cell.config, cell.traffic
    st = State()
    st.limits = cell.limits
    st.nt, st.tile = cfg["n_tiles"], cfg["tile"]
    st.n = st.nt * st.tile
    st.dtype = cfg["tile_dtype"]
    st.graph = H.build_graph(cfg, with_fns=True)
    machine = H.build_machine(cfg)
    sched = SchedConfig(backend="numpy", **cfg.get("sched", {}))
    t0 = time.perf_counter()
    st.result = run_simulation(st.graph, machine,
                               resolve(H.policy_spec(tr["policy"]), backend="numpy",
                                       config=sched),
                               seed=int(tr["schedule_seed"]), noise=float(tr["noise"]),
                               config=sched)
    t1 = time.perf_counter()
    reseed(st, seed)
    t2 = time.perf_counter()
    st.spans, st.done = H.Spans(), {}
    for t in st.graph.tasks:
        t.fn = Counted(st, t.kind, t.fn)
    # one body of each kind on pristine tiles compiles every eager op
    first = {}
    for t in st.graph.tasks:
        first.setdefault(t.kind, t)
    jax.block_until_ready([t.fn(*[st.tiles[a.data.name] for a in t.accesses if a.mode.reads])
                           for t in first.values()])
    H.log(f"set-up: schedule {t1 - t0!r} s, matrix and tiles {t2 - t1!r} s, "
          f"warm-up {time.perf_counter() - t2!r} s")
    return st


def reseed(st: State, seed: int) -> None:
    """The seed's matrix, on the device, and its pristine tiles."""
    import jax

    from repro.linalg import tiles as T

    st.seed = seed
    st.A = cholesky_ref.spd_matrix(st.n, seed, st.dtype)
    st.tiles = T.split_tiles(st.A, st.tile)
    jax.block_until_ready(st.tiles)


def window(st: State, win: H.Window, spans: H.Spans) -> Dict:
    import jax

    from repro.linalg.execute import execute_schedule

    st.spans = spans
    st.factorisations = 0
    win.open()
    st.done = {}
    while not win.expired():
        with spans.span("execute_schedule"):
            store = execute_schedule(st.graph, st.tiles, st.result)
            jax.block_until_ready(store)
        st.factorisations += 1
    win.close()
    st.store = store
    flops = sum(n * F.tile_flops(k, st.tile) for k, n in st.done.items())
    tasks = sum(st.done.values())
    return {"e2e": {"factor_tflop_s": flops / win.length / 1e12},
            "attempted": tasks,
            "counters": {"tasks_by_kind": dict(st.done), "tile": st.tile,
                         "itemsize": st.A.dtype.itemsize,
                         "factorisations": st.factorisations}}


def check(st: State, record: Dict) -> List[H.Check]:
    from repro.linalg import tiles as T

    t0 = time.perf_counter()
    L = T.join_tiles(st.store, st.nt, st.tile)
    del st.store, st.tiles
    r = cholesky_ref.readings(L, st.A)
    H.log(f"reference: {r} in {time.perf_counter() - t0!r} s")
    checks = H.checks(r, st.limits, COMPARED)
    record["failed"] = sum(not c.ok for c in checks)
    return checks


def control_readings(st: State, product=cholesky_ref.xla_high) -> Dict[str, float]:
    """The control: a blocked Cholesky of the same matrix whose trailing
    products run at ``precision="high"`` (three bf16 passes), one step below
    the tile bodies' full f32, in the program's place."""
    return cholesky_ref.stats(cholesky_ref.control_factor(st.A, max(st.tile, st.n // 8),
                                                          product), st.A)
