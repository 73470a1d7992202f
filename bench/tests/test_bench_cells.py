"""Each traffic kind end to end at a tiny size on the CPU: set-up, window and
check pass on the program, and fail on the control and on faults planted in
the timed path."""
from __future__ import annotations

import dataclasses

import pytest

from bench import harness as H
from bench.kinds import factor, schedule
from bench.refs import cholesky_ref
from bench.tests.tiny import drive, tiny_cell

CELLS = ("chol32_dada_schedule", "paper8_sweep", "chol32_factor")


@pytest.fixture(scope="module")
def runs():
    return {name: drive(tiny_cell(name)) for name in CELLS}


@pytest.mark.parametrize("name", CELLS)
def test_program_is_correct(runs, name):
    st, record, checks = runs[name]
    assert checks and all(c.ok for c in checks), [(c.name, c.value, c.limit) for c in checks]
    assert record["attempted"] > 0
    assert all(v > 0 for v in record["e2e"].values())


def test_schedule_scored_on_device_and_sampled(runs):
    st, record, _ = runs["chol32_dada_schedule"]
    c = record["counters"]
    assert c["device_scored"] == c["activations"] > 0
    assert len(st.timed.captures) == c["activations"]


def test_schedule_warm_up_covers_every_window_shape():
    # set-up does not depend on the seed, and windows of other seeds build
    # no scoring program and trace none anew
    cell = tiny_cell("chol32_dada_schedule")
    kind = H.kind_module(cell.traffic)
    st = kind.setup(cell, 7, None)
    be = st.backend

    def programs():
        fns = {**be._matrix_fns, **{("search",) + k: f for k, f in be._search_fns.items()}}
        return {k: f._cache_size() for k, f in fns.items()}

    warm = programs()
    for seed in (11, 2**31 + 5, 3 * 2**32 + 1):
        kind.reseed(st, seed)
        spans = H.Spans()
        kind.window(st, H.Window(0.2, spans), spans)
    assert programs() == warm


@pytest.mark.parametrize("name", ["chol32_dada_schedule", "paper8_sweep"])
def test_control_is_not_correct(runs, name):
    st, _, _ = runs[name]
    kind = H.kind_module(H.load_cell(name).traffic)
    held = H.checks(kind.control_readings(st), st.limits, kind.COMPARED)
    assert not all(c.ok for c in held), [(c.name, c.value, c.limit) for c in held]


def test_factor_control_separates(runs):
    # the CPU has no bf16 passes: the control's three passes are written out
    # (bf16x3), which reads below XLA's own on a TPU and, at this size, below
    # the limit set at N=16384; the test holds the control to three times
    # the program's reading, the separation the limit needs (on the chip
    # bench/control.py holds XLA's control to the limits themselves)
    st, _, checks = runs["chol32_factor"]
    program = {c.name: c.value for c in checks}
    r = factor.control_readings(st, cholesky_ref.bf16x3)
    for k in factor.COMPARED:
        assert r[k] > 3 * program[k], (k, r[k], program[k])


# ---------------------------------------------------------------------------
# faults planted under the timed path


def _fail(name, plant):
    _, _, checks = drive(tiny_cell(name), before_window=plant)
    assert not all(c.ok for c in checks), [(c.name, c.value, c.limit) for c in checks]


def _wrap_place(after):
    def plant(st):
        inner = st.timed.inner
        place = inner.place

        def broken(sim, ready, src):
            before = list(sim.load_ts)
            place(sim, ready, src)
            after(sim, before)

        inner.place = broken
    return plant


def test_schedule_fault_answer_altered():
    def alter(sim, before):
        sim.load_ts[0] += 1e-3

    _fail("chol32_dada_schedule", _wrap_place(alter))


def test_schedule_fault_state_unchanged():
    def unchanged(sim, before):
        sim.load_ts[:] = before

    _fail("chol32_dada_schedule", _wrap_place(unchanged))


def test_schedule_fault_in_the_performance_model(monkeypatch):
    # a fault upstream of scoring: predicted durations one part in 10^6 off
    from repro.core.perfmodel import ClassPredictor

    times, times_list = ClassPredictor.times, ClassPredictor.times_list
    monkeypatch.setattr(ClassPredictor, "times",
                        lambda self, tids: times(self, tids) * (1 + 1e-6))
    monkeypatch.setattr(ClassPredictor, "times_list",
                        lambda self, tids: [x * (1 + 1e-6) for x in times_list(self, tids)])
    _fail("chol32_dada_schedule", lambda st: None)


def test_sweep_fault_answer_altered():
    def plant(st):
        run = st.run_batch

        def broken(items):
            return [dataclasses.replace(r, makespan=r.makespan * 1.01) for r in run(items)]

        st.run_batch = broken

    _fail("paper8_sweep", plant)


def test_sweep_fault_half_the_batch_left_out():
    def plant(st):
        run = st.run_batch

        def broken(items):
            half = run(items[: len(items) // 2])
            return half + half[: len(items) - len(half)]

        st.run_batch = broken

    _fail("paper8_sweep", plant)


def _wrap_kind(kind, body):
    def plant(st):
        for t in st.graph.tasks:
            if t.kind == kind:
                inner = t.fn.fn
                t.fn.fn = lambda *a, _f=inner: body(a, _f)
    return plant


def test_factor_fault_answer_altered():
    _fail("chol32_factor", _wrap_kind("gemm", lambda a, f: (f(*a)[0] + 1e-3,)))


def test_factor_fault_state_unchanged():
    _fail("chol32_factor", _wrap_kind("syrk", lambda a, f: (a[1],)))
