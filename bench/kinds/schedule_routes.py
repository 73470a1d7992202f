"""Traffic kind ``schedule_routes``: kind ``schedule`` on a machine whose
copies take routes, a peer fabric among them.

Set-up, the window and its timing are kind ``schedule``'s (whole schedules
on the exact engine, back to back, every activation timed around
``strategy.place``). What differs:

- the check replays the sampled activations through the reference that
  prices each copy by its route (``refs/dada_route_ref.py``: one fabric hop
  between peers, one host-link hop, or two through the host), with the
  configuration's machine's own fabric;
- the record's counters add, over the window, the score cells (task ×
  resource) the scoring backend computed on the device and on the host
  (``cells_device``, ``cells_host``), and the demand-copy hops and bytes
  of each route the engine made (``hops_host``, ``hops_peer``,
  ``hops_staged``, ``bytes_*``). A program that keeps no such counter
  leaves them out.
"""
from __future__ import annotations

import time
from functools import partial
from typing import Dict, List

import numpy as np

from bench import harness as H
from bench.kinds import schedule
from bench.kinds.schedule import COMPARED, State, reseed, setup  # noqa: F401
from bench.refs import dada_route_ref

CELL_COUNTERS = ("cells_device", "cells_host")


def window(st: State, win: H.Window, spans: H.Spans) -> Dict:
    counts = st.backend.counts
    before = dict(counts)
    record = schedule.window(st, win, spans)
    c = record["counters"]
    for k in CELL_COUNTERS:
        if k in counts:
            c[k] = counts[k] - before[k]
    for res in st.results:
        for k, v in (getattr(res, "routes", None) or {}).items():
            c[k] = c.get(k, 0) + v
    return record


def _fabric(machine) -> dict:
    fab = getattr(machine, "fabric", None)
    if fab is None:
        return {}
    return dict(peer_latency=fab.link.latency, peer_bandwidth=fab.link.bandwidth,
                peer_mems=tuple(fab.mems))


def readings(st: State, dtype=np.float64, against=None) -> Dict[str, float]:
    """As ``schedule.readings``, against the route-aware reference."""
    machine = st.machine
    mems = [r.mem for r in machine.resources]
    classes = ((machine.cpus or machine.gpus)[0].cls.name,
               (machine.gpus or machine.cpus)[0].cls.name)
    p = st.policy
    ref = partial(dada_route_ref.place, classes=classes, mems=mems,
                  alpha=float(p.get("alpha", 0.5)), use_cp=bool(int(p.get("use_cp", 0))),
                  latency=machine.link.latency, bandwidth=machine.link.bandwidth,
                  eps_rel=float(p.get("eps_rel", 0.01)), max_iters=int(p.get("max_iters", 30)),
                  **_fabric(machine))
    timed = st.timed
    rates = schedule._class_rates(machine)
    histories = [dada_route_ref.History(rates, log) for log in timed.observed]
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
    bad = [why for why in (schedule.invalid_schedule(r, st.graph) for r in st.results) if why]
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
