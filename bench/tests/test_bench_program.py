"""The program's spans in the benchmark: the trace reduction that puts idle
gaps down to them, the clock offset they show, and the readers of the
program's per-layer metrics on tiny cells run with the recorder on."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import harness as H
from bench import program, trace
from bench.tests.tiny import tiny_cell

FIXTURES = Path(__file__).parent / "fixtures"


def _planes(ops, modules, threads):
    return [
        {"name": "/host:CPU", "lines": [{"name": f"thread{k}", "events": ev}
                                        for k, ev in enumerate(threads)]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules}]},
    ]


def test_gap_goes_to_innermost_program_span_and_same_names_merge():
    main = [("bench:window", 0.0, 1000.0), ("bench:run_batch", 0.0, 1000.0),
            ("repro:batch.run", 10.0, 980.0),
            ("repro:episode.dispatch", 100.0, 200.0),  # [100, 300)
            ("repro:episode.pack", 500.0, 100.0)]      # [500, 600)
    worker = [("repro:episode.dispatch", 250.0, 200.0)]  # [250, 450): overlaps
    ops = [("while.1", 0.0, 50.0), ("while.1", 650.0, 350.0)]
    planes = _planes(ops, [], [main, worker])
    r = program.reduce(planes)
    # the reduction of bench spans alone is unchanged
    assert {k: r[k] for k in trace.reduce(planes)} == trace.reduce(planes)
    gaps = dict(r["program_gaps"])
    # idle [50, 650): midpoint 350 lies in the merged dispatch [100, 450),
    # shorter than batch.run and run_batch
    assert gaps == pytest.approx({"episode.dispatch": 600e-9})
    assert dict(r["idle_gaps"]) == pytest.approx({"run_batch": 600e-9})
    # without the worker's span the midpoint lies in batch.run only
    r1 = program.reduce(_planes(ops, [], [main]))
    assert dict(r1["program_gaps"]) == pytest.approx({"batch.run": 600e-9})
    assert r["clock_offset_ms"] is None


def test_clock_offset_from_dispatch_and_readback_bounds():
    # the device's clock runs 30 ns behind the host's: each module starts
    # 10 ns (host) after its dispatch span starts and ends 15 ns (host)
    # before its read-back span ends -> offset within [20, 45]
    host, modules = [("bench:window", 0.0, 10000.0)], []
    for k in range(3):
        t = 1000.0 * (k + 1)
        host += [("repro:score.dispatch", t, 50.0), ("repro:score.readback", t + 60.0, 100.0)]
        modules.append(("jit_dada_score_matrices(123)", t + 10.0 - 30.0, 135.0))
    # each module's ops leave the device idle for 20 ns in its middle:
    # [t + 20, t + 40) by the device's clock, [t + 52.5, t + 72.5) by the host's
    ops = [op for _, s, _ in modules
           for op in (("fusion.1", s, 40.0), ("fusion.2", s + 60.0, 75.0))]
    r = program.reduce(_planes(ops, modules, [host]))
    assert r["clock_offset_bounds_ms"] == pytest.approx([20e-6, 45e-6])
    assert r["clock_offset_ms"] == pytest.approx(32.5e-6)
    gaps = dict(r["program_gaps"])
    # shifted, the mid-program gaps lie in the read-back, which waits for them
    assert gaps["score.readback"] == pytest.approx(3 * 20e-9)
    assert "score.dispatch" not in gaps
    # the offset moves no busy time and no window
    assert r["busy_s"] == trace.reduce(_planes(ops, modules, [host]))["busy_s"]


def _fixture_planes():
    planes = json.loads((FIXTURES / "tpu_trace.json").read_text())
    return [{"name": p["name"], "lines": [
        {"name": ln["name"], "events": [tuple(e) for e in ln["events"]]}
        for ln in p["lines"]]} for p in planes]


@pytest.mark.parametrize("metric", [m["name"] for m in json.loads(
    (H.ROOT / "BENCHMARK.json").read_text())["per_layer"]])
def test_accepted_readers_read_the_same_from_the_recorded_trace(metric):
    planes = _fixture_planes()

    def record(reduced):
        return {"window_s": reduced["window_s"], "trace": reduced,
                "spans": {"place": 0.0123, "unit": 0.002},
                "counters": {"activations": 40, "device_scored": 17, "configs": 60,
                             "calls": 3}}

    read = H.layer_reader(metric)
    parent = read(record(trace.reduce(planes)))
    assert parent is not None
    assert read(record(program.reduce(planes))) == parent


def _drive(name: str, seed: int = 4321, seconds: float = 0.3):
    """Set-up, and a window with the program's spans on, as program.py runs it."""
    cell = tiny_cell(name)
    kind = H.kind_module(cell.traffic)
    st = kind.setup(cell, seed, None)
    spans = H.Spans()
    be = getattr(st, "backend", None)
    win = program.ProgramWindow(seconds, spans, counts=be.counts if be is not None else None)
    record = kind.window(st, win, spans)
    record.update(window_s=win.length, spans=dict(spans.totals), program=win.program)
    record["counters"].update(win.transfers)
    return record


@pytest.fixture(scope="module")
def runs():
    return {name: _drive(name) for name in ("chol32_dada_schedule", "paper8_sweep")}


def _read(record, name):
    return H.layer_reader(name)(record)


def test_program_readers_on_tiny_schedule(runs):
    r = runs["chol32_dada_schedule"]
    c = r["counters"]
    assert c["device_scored"] == c["activations"] > 0
    # 28 uploads and 4 read-backs per device-scored DADA+CP activation
    assert _read(r, "transfers_per_activation.sched") == 32.0
    for name in ("score_host_ms_per_activation.sched",
                 "readback_wait_ms_per_activation.sched"):
        assert _read(r, name) > 0
    assert 0 < _read(r, "policy_host_share.sched") < 100
    assert _read(r, "batch_host_ms_per_config.sweep") is None
    p = r["program"]
    assert p["dada.place"]["count"] == c["activations"]
    # the named children cover the activation
    assert p["dada.place"]["self_s"] < p["dada.place"]["total_s"]


def test_program_readers_on_tiny_sweep(runs):
    r = runs["paper8_sweep"]
    assert _read(r, "batch_host_ms_per_config.sweep") > 0
    for name in ("score_host_ms_per_activation.sched", "transfers_per_activation.sched",
                 "policy_host_share.sched"):
        assert _read(r, name) is None
    p = r["program"]
    assert p["batch.run"]["count"] == r["counters"]["calls"]
    assert p["episode.dispatch"]["count"] >= p["batch.run"]["count"]


def test_program_readers_need_the_program_record():
    record = {"window_s": 1.0, "spans": {}, "counters": {
        "activations": 4, "device_scored": 2, "configs": 3, "calls": 1,
        "uploads": 56, "readbacks": 8}}
    for name, _ in program.PROGRAM_METRICS:
        assert _read(record, name) is None
