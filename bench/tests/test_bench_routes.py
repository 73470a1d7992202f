"""Kind ``schedule_routes`` end to end at a tiny size on the CPU, on the DGX
A100 deployment: the program agrees with the route-aware reference, the
float32 control does not, planted faults are caught, and the reference
agrees with ``dada_ref`` bit for bit where no fabric is declared."""
from __future__ import annotations

import copy

import numpy as np
import pytest

from bench import harness as H
from bench.kinds import schedule_routes
from bench.refs import dada_ref, dada_route_ref
from bench.tests.tiny import TINY_GRAPH, drive, tiny_cell

CELL = "dgx32_dada_schedule"


def tiny_routes_cell() -> H.Cell:
    cell = copy.deepcopy(H.load_cell(CELL))
    cell.config.update(TINY_GRAPH)
    cell.config["sched"]["jax_min"] = 1  # every activation on the device path
    cell.config["widest_ready"] = TINY_GRAPH["n_tiles"] - 1
    cell.traffic["check_share"] = 1.0
    return cell


@pytest.fixture(scope="module")
def run():
    return drive(tiny_routes_cell())


def test_program_agrees_with_the_route_reference(run):
    st, record, checks = run
    assert checks and all(c.ok for c in checks), [(c.name, c.value, c.limit) for c in checks]
    assert record["attempted"] > 0 and len(st.timed.captures) == record["attempted"]


def test_counters_and_their_readers(run):
    _, record, _ = run
    c = record["counters"]
    assert c["cells_device"] > 0 and c["cells_host"] == 0
    assert c["cells_device"] == 128 * c["tasks_placed"]
    assert c["hops_peer"] > 0 and c["hops_staged"] == 0 and c["bytes_peer"] > 0
    assert H.layer_reader("device_cell_share.dgx")(record) == 100.0
    hops = c["hops_host"] + c["hops_peer"]
    assert H.layer_reader("hops_per_task.dgx")(record) == hops / c["tasks_placed"]


def test_readers_read_nothing_from_a_program_without_the_counters():
    record = {"counters": {"activations": 5, "tasks_placed": 9, "device_scored": 3}}
    assert H.layer_reader("device_cell_share.dgx")(record) is None
    assert H.layer_reader("hops_per_task.dgx")(record) is None


def test_control_is_not_correct(run):
    st, _, _ = run
    held = H.checks(schedule_routes.control_readings(st), st.limits, schedule_routes.COMPARED)
    assert not all(c.ok for c in held), [(c.name, c.value, c.limit) for c in held]


def _fails(plant):
    _, _, checks = drive(tiny_routes_cell(), before_window=plant)
    assert not all(c.ok for c in checks), [(c.name, c.value, c.limit) for c in checks]


def test_fault_answer_altered():
    def plant(st):
        inner = st.timed.inner
        place = inner.place

        def broken(sim, ready, src):
            place(sim, ready, src)
            sim.load_ts[-1] += 1e-4

        inner.place = broken

    _fails(plant)


def test_fault_peer_copies_priced_through_the_host(monkeypatch):
    # a fault in the device's transfer fold: it forgets the fabric and
    # prices every device copy as staged through the host
    def plant(st):
        for m in st.backend._machine_cache.values():
            if m["peer_bits"] is not None:
                monkeypatch.setitem(m, "peer_bits", np.zeros(m["peer_bits"].shape, np.int64))

    _fails(plant)


def test_route_reference_equals_dada_ref_without_a_fabric():
    # the paper machine's captures: the two references agree bit for bit
    st, _, checks = drive(tiny_cell("chol32_dada_schedule"))
    assert all(c.ok for c in checks)
    m = st.machine
    kw = dict(classes=("cpu", "gpu"), mems=[r.mem for r in m.resources], alpha=0.5,
              use_cp=True, latency=m.link.latency, bandwidth=m.link.bandwidth)
    rates = {r.cls.name: (dict(r.cls.rates), r.cls.default_rate) for r in m.resources}
    for dtype in ("float64", "float32"):
        a = [dada_ref.History(rates, log) for log in st.timed.observed]
        b = [dada_route_ref.History(rates, log) for log in st.timed.observed]
        assert st.timed.captures
        for k, act in st.timed.captures:
            ha, hb = a[k].at(act.n_observed), b[k].at(act.n_observed)
            assert dada_ref.place(act, ha, dtype=dtype, **kw) == \
                dada_route_ref.place(act, hb, dtype=dtype, **kw)
