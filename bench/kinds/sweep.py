"""Traffic kind ``sweep``: policy sweeps through the batched surrogate, back to back.

A user sweeping the paper's figures asks ``repro.core.run_batch`` for
every (kernel × policy × seed) configuration of a machine; each call here
is one kernel's configurations (``policies`` × ``seeds_per_call`` fresh
seeds), which ``run_batch`` runs as chunks of the compiled episode scan
(the Pallas transfer fold on the TPU). Calls cycle over the kernels, and
the window closes at the end of the first round of all kernels that ends
after ``--seconds``: it holds whole rounds.
Seeds come from ``--seed``; they change the noise, never a shape, so
set-up's one call per kernel compiles (or loads) every program.

Correctness, after the window: a sample of the window's configurations
drawn from the seed (``check_per_kernel`` per kernel) is run through the
plain reference of the episode (``refs/episode_ref.py``) in float32, the
precision the surrogate states; the widest relative gap of makespan and of
bytes moved is compared.
"""
from __future__ import annotations

import time
from functools import partial
from typing import Dict, List

import numpy as np

from bench import harness as H
from bench.refs import episode_ref as E

COMPARED = ("makespan_gap", "bytes_gap")


class State:
    pass


def setup(cell: H.Cell, seed: int, devs) -> State:
    from repro.core import cached_graph, run_batch

    cfg, tr = cell.config, cell.traffic
    st = State()
    st.limits = cell.limits
    st.machine = H.build_machine(cfg)
    # partials of the builder itself, with hashable arguments, which
    # run_batch's graph cache keys on
    st.factories = {
        k: partial(H.load_callable(path), cfg["n_tiles"], cfg["tile"],
                   itemsize=cfg["itemsize"], with_fns=False)
        for k, path in cfg["graphs"].items()}
    st.graphs = {k: cached_graph(f) for k, f in st.factories.items()}
    st.policies = tr["policies"]
    st.noise = float(tr["noise"])
    st.n_seeds = int(tr["seeds_per_call"])
    st.check_per_kernel = int(tr["check_per_kernel"])
    st.run_batch = run_batch
    reseed(st, seed)
    t0 = time.perf_counter()
    for k in st.factories:
        run_batch(items(st, k, next_seeds(st)))
    H.log(f"warm-up: one call per kernel in {time.perf_counter() - t0!r} s")
    return st


def reseed(st: State, seed: int) -> None:
    st.seed = seed
    st.rng = np.random.default_rng([seed, 2])


def next_seeds(st: State) -> List[int]:
    return [int(s) for s in st.rng.integers(0, 2**31 - 1, st.n_seeds)]


def items(st: State, kernel: str, seeds: List[int]) -> List[dict]:
    return [{"graph": st.factories[kernel], "machine": st.machine,
             "strategy": H.policy_spec(p), "seed": s, "noise": st.noise}
            for p in st.policies for s in seeds]


def window(st: State, win: H.Window, spans: H.Spans) -> Dict:
    st.calls = []
    kernels = list(st.factories)
    win.open()
    while not win.expired():
        for k in kernels:
            seeds = next_seeds(st)
            with spans.span("run_batch"):
                res = st.run_batch(items(st, k, seeds))
            st.calls.append((k, seeds, res))
    win.close()
    n = sum(len(r) for _, _, r in st.calls)
    return {"e2e": {"sweep_configs_per_s": n / win.length}, "attempted": n,
            "counters": {"configs": n, "calls": len(st.calls)}}


def _params(p: Dict):
    """(alpha, use_cp, ws_pref) of a policy, as the surrogate models it."""
    name = p["name"]
    if name == "heft":
        return 0.0, 1.0, False
    if name == "ws":
        return 0.0, 0.0, True
    if name == "dada":
        return float(p.get("alpha", 0.5)), float(int(p.get("use_cp", 0))), False
    raise ValueError(f"no surrogate form for policy {name!r}")


def sample(st: State) -> Dict[str, List[tuple]]:
    """Per kernel, ``check_per_kernel`` configurations of the window drawn
    from the seed: (policy, seed, program makespan, program bytes)."""
    rng = np.random.default_rng([st.seed, 3])
    out: Dict[str, List[tuple]] = {}
    for k in st.factories:
        # results come back in the order of items(): policy-major
        done = [(p, s, r.makespan, r.total_bytes)
                for kk, seeds, res in st.calls if kk == k
                for (p, s), r in zip([(p, s) for p in st.policies for s in seeds], res)]
        if done:
            pick = rng.choice(len(done), size=min(st.check_per_kernel, len(done)),
                              replace=False)
            out[k] = [done[j] for j in sorted(pick)]
    return out


def readings(st: State, dtype=np.float32, against=None) -> Dict[str, float]:
    """Widest relative gap between the program's (or, with ``against``, a
    reference in that dtype's) makespans and bytes and the reference in
    ``dtype``."""
    md = E.MachineData(st.machine)
    gap_mk = gap_b = 0.0
    n = 0
    for k, rows in sample(st).items():
        gd = E.GraphData(st.graphs[k])
        params = [_params(p) for p, *_ in rows]
        noise = np.stack([E.noise_factors(s, st.noise, gd.n) for _, s, _, _ in rows])
        args = ([a for a, _, _ in params], [c for _, c, _ in params],
                [w for _, _, w in params], noise)
        ref = E.run_reference(gd, md, *args, dtype=dtype)
        if against is None:
            mk = np.array([r[2] for r in rows])
            tb = np.array([r[3] for r in rows])
        else:
            other = E.run_reference(gd, md, *args, dtype=against)
            mk, tb = other["makespan"], other["total_bytes"]
        gap_mk = max(gap_mk, float(np.max(np.abs(mk - ref["makespan"]) / ref["makespan"])))
        gap_b = max(gap_b, float(np.max(np.abs(tb - ref["total_bytes"])
                                        / np.maximum(ref["total_bytes"], 1.0))))
        n += len(rows)
    return {"makespan_gap": gap_mk, "bytes_gap": gap_b, "sampled": float(n)}


def check(st: State, record: Dict) -> List[H.Check]:
    t0 = time.perf_counter()
    r = readings(st)
    H.log(f"reference: {int(r['sampled'])} sampled configurations in "
          f"{time.perf_counter() - t0!r} s")
    checks = H.checks(r, st.limits, COMPARED)
    record["failed"] = sum(not c.ok for c in checks)
    return checks


def control_readings(st: State) -> Dict[str, float]:
    """The control: the reference in bfloat16 in the program's place, held
    against the reference in the float32 the surrogate states."""
    import ml_dtypes

    return readings(st, dtype=np.float32, against=ml_dtypes.bfloat16)
