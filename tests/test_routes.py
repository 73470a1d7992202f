"""Routing and pricing of copies, owned by ``TransferModel.route``: one
fabric hop between peers on a machine with a fabric, one host-link hop
from or to the host, and two through the host where no fabric reaches,
exactly as before on the paper machine."""
import numpy as np
import pytest

from repro.configs.dgx_a100 import dgx_a100
from repro.configs.paper_machine import paper_machine
from repro.core import run_simulation
from repro.core.machine import HOST_MEM, LinkModel, make_machine
from repro.core.perfmodel import (
    ROUTE_HOST, ROUTE_NONE, ROUTE_PEER, ROUTE_STAGED, Residency, TransferModel, route,
)
from repro.linalg.cholesky import cholesky_graph
from repro.sched import resolve


HOST, G0, G1, G2 = 1, 1 << 1, 1 << 2, 1 << 3  # residency bits


def _peer_machine(n_gpus=3):
    from repro.configs.paper_machine import CPU_CLASS, GPU_CLASS

    return make_machine(6, n_gpus, CPU_CLASS, GPU_CLASS, pcie_bandwidth=8e9,
                        pcie_latency=15e-6, fabric=LinkModel(80e9, 5e-6))


@pytest.mark.parametrize("mask,dst,want", [
    (0, 0, ROUTE_NONE), (G0, 0, ROUTE_NONE), (HOST, 0, ROUTE_HOST),
    (G1, HOST_MEM, ROUTE_HOST), (G1, 0, ROUTE_PEER), (HOST | G1, 0, ROUTE_PEER),
    (G2, 0, ROUTE_PEER),
])
def test_route_on_a_fabric(mask, dst, want):
    tm = TransferModel.of(_peer_machine())
    assert tm.route(mask, dst) == want


@pytest.mark.parametrize("mask,dst,want", [
    (0, 0, ROUTE_NONE), (HOST, 0, ROUTE_HOST), (G1, HOST_MEM, ROUTE_HOST),
    (G1, 0, ROUTE_STAGED), (HOST | G1, 0, ROUTE_HOST),
])
def test_route_without_a_fabric(mask, dst, want):
    assert TransferModel.of(paper_machine(4)).route(mask, dst) == want
    assert route(mask, dst) == want


def test_prices_by_route():
    tm = TransferModel.of(_peer_machine())
    plain = TransferModel.of(paper_machine(4))
    n = 8 * 1024 * 1024
    t = 15e-6 + n / 8e9
    assert tm.read_time(G1, 0, n) == 5e-6 + n / 80e9
    assert plain.read_time(G1, 0, n) == 2 * t == t + t
    assert plain.read_time(HOST, 0, n) == tm.read_time(HOST, 0, n) == t
    assert tm.read_time(G1, 1, n) == 0.0 == tm.read_time(0, 1, n)


def test_transfer_hops_by_route():
    res = Residency()
    res.write("a", 1)
    assert res.transfer_hops("a", 0) == 2
    assert res.transfer_hops("a", HOST_MEM) == 1 == res.transfer_hops("b", 0) + 1
    with pytest.raises(ValueError):
        res.transfer_hops("a", 99)


@pytest.mark.parametrize("machine", [_peer_machine, dgx_a100, lambda: paper_machine(8)])
def test_narrow_rows_equal_the_matrix(machine):
    # the scalar path of narrow activations and the batched numpy path
    # price every read alike, bit for bit
    m = machine()
    g = cholesky_graph(6, 256, with_fns=False)
    arr = g.arrays()
    tm = TransferModel.of(m)
    res = Residency()
    res.attach(arr)
    rng = np.random.default_rng(0)
    accel = [r.mem for r in m.gpus]
    for name in arr.data_names:
        for mem in rng.choice([HOST_MEM] + accel, size=rng.integers(0, 3)):
            res.add_copy(name, int(mem))
    mems = [r.mem for r in m.resources]
    tids = list(range(len(g)))
    narrow = [tm.task_input_transfer_rows(arr, tids[i:i + 4], mems, res)
              for i in range(0, len(tids), 4)]
    wide = tm.task_input_transfer_rows(arr, tids, mems, res)
    assert [row for rows in narrow for row in rows] == wide
    for tid in tids[:20]:
        task = g.tasks[tid]
        assert [tm.task_input_transfer_time(task, r, res) for r in m.resources] == wide[tid]


def test_engine_makes_one_peer_hop_and_the_paper_machine_two():
    g = cholesky_graph(8, 1024, itemsize=8, with_fns=False)
    spec = "dada?alpha=0.5&use_cp=1"
    peer = run_simulation(g, dgx_a100(), resolve(spec), seed=1).routes
    assert peer["hops_peer"] > 0 and peer["hops_staged"] == 0
    assert peer["bytes_peer"] == peer["hops_peer"] * 1024 * 1024 * 8
    staged = run_simulation(g, paper_machine(8), resolve(spec), seed=1)
    assert staged.routes["hops_peer"] == 0 and staged.routes["hops_staged"] > 0
    r = staged.routes
    assert r["hops_host"] + r["hops_staged"] == staged.n_transfers
    assert r["bytes_host"] + r["bytes_staged"] == staged.total_bytes
