"""The yardstick's arithmetic: tile flops and bytes, the trace reduction."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import flops, trace

FIXTURES = Path(__file__).parent / "fixtures"


@pytest.mark.parametrize("kernel", ["cholesky", "lu"])
def test_tile_flops_and_bytes_match_the_task_graph(kernel):
    from repro.linalg.cholesky import cholesky_graph
    from repro.linalg.lu import lu_graph

    b, itemsize = 64, 4
    graph = {"cholesky": cholesky_graph, "lu": lu_graph}[kernel](4, b, itemsize=itemsize,
                                                                 with_fns=False)
    for t in graph.tasks:
        assert flops.tile_flops(t.kind, b) == pytest.approx(t.flops, rel=1e-12)
        moved = sum(a.data.size_bytes * (a.mode.reads + a.mode.writes) for a in t.accesses)
        assert flops.tile_bytes(t.kind, b, itemsize) == moved


def test_cholesky_total_is_n_cubed_over_three():
    from repro.linalg.cholesky import cholesky_graph

    nt, b = 8, 64
    total = sum(flops.tile_flops(t.kind, b) for t in cholesky_graph(nt, b, with_fns=False).tasks)
    n = nt * b
    assert total == pytest.approx(n**3 / 3, rel=0.02)


def test_least_seconds_names_its_bound():
    t, bound = flops.least_seconds("gemm", 512, 4, 197e12, 819e9)
    assert bound == "memory" and t == pytest.approx(4 * 512 * 512 * 4 / 819e9)
    t, bound = flops.least_seconds("gemm", 512, 4, 1e9, 819e9)
    assert bound == "compute" and t == pytest.approx(2 * 512**3 / 1e9)


def _planes(ops, modules, spans):
    return [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": spans}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules}]},
    ]


def test_reduce_union_ops_and_gaps():
    spans = [("bench:window", 100.0, 1000.0), ("bench:place", 150.0, 200.0),
             ("bench:place", 700.0, 100.0)]
    ops = [("fusion.1", 200.0, 100.0), ("fusion.2", 250.0, 100.0),  # overlap: busy 150
           ("copy.3", 500.0, 100.0), ("fusion.1", 1050.0, 100.0)]  # last clipped to 50
    modules = [("jit_score", 190.0, 170.0), ("jit_score", 1040.0, 200.0)]
    r = trace.reduce(_planes(ops, modules, spans))
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(300e-9)
    assert dict((k, v) for k, v in r["device_ops"]) == pytest.approx(
        {"jit_score/fusion.1": 150e-9, "jit_score/fusion.2": 100e-9, "copy.3": 100e-9})
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    # gaps: [100,200) inside the first place span; [350,500) and [600,1050)
    # outside (midpoints 425 and 825 lie after each place span ends)
    assert gaps == pytest.approx({"place": 100e-9, trace.OUTSIDE: 600e-9})


def test_reduce_cuts_a_window_whose_trace_ran_out():
    # ops stop 3 s into a 10 s window: the trace lost its later events
    spans = [("bench:window", 0.0, 10e9), ("bench:run_batch", 0.0, 2e9),
             ("bench:run_batch", 2e9, 2e9), ("bench:run_batch", 4e9, 2e9)]
    ops = [("while", 0.5e9, 1e9), ("while", 2.5e9, 0.5e9)]
    r = trace.reduce(_planes(ops, [], spans))
    assert r["cut"] and r["window_s"] == pytest.approx(3.0)
    assert r["busy_s"] == pytest.approx(1.5)
    assert r["covered"] == {"run_batch": 1}
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(1.5)
    full = trace.reduce(_planes(ops + [("while", 9.5e9, 0.4e9)], [], spans))
    assert not full["cut"] and full["window_s"] == pytest.approx(10.0)


def test_reduce_needs_a_window_and_a_device():
    assert trace.reduce(_planes([], [], [("bench:place", 0.0, 1.0)])) is None
    assert trace.reduce([{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [("bench:window", 0.0, 1.0)]}]}]) is None


def test_reduce_recorded_tpu_trace():
    planes = json.loads((FIXTURES / "tpu_trace.json").read_text())
    planes = [{"name": p["name"], "lines": [
        {"name": ln["name"], "events": [tuple(e) for e in ln["events"]]}
        for ln in p["lines"]]} for p in planes]
    r = trace.reduce(planes)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"] and r["idle_gaps"]
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(r["window_s"] - r["busy_s"])
