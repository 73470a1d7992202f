"""The program's span recorder: nesting, parents across threads, self time,
and nothing kept (one shared no-op) while it is off."""
import threading
import time

import pytest

from repro.core import obs


@pytest.fixture
def recorder():
    obs.drain()
    obs.enable(True)
    try:
        yield obs
    finally:
        obs.enable(False)
        obs.drain()


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s.name, []).append(s)
    return out


def test_nesting_and_parents_across_two_threads(recorder):
    def work(tag):
        with obs.span("child." + tag):
            with obs.span("leaf." + tag):
                time.sleep(0.002)

    with obs.span("root"):
        worker = threading.Thread(target=obs.carry(work), args=("b",))
        worker.start()
        work("a")
        worker.join(timeout=10)
    assert not worker.is_alive()
    with obs.span("other"):
        pass

    spans = _by_name(obs.drain())
    (root,) = spans["root"]
    assert root.parent is None and root.root == root.id
    for tag in ("a", "b"):
        (child,) = spans["child." + tag]
        (leaf,) = spans["leaf." + tag]
        assert child.parent == root.id and child.root == root.id
        assert leaf.parent == child.id and leaf.root == root.id
        assert root.start_ns <= child.start_ns <= leaf.start_ns
        assert leaf.end_ns <= child.end_ns <= root.end_ns
    (other,) = spans["other"]
    assert other.parent is None and other.root == other.id != root.id


def test_self_time_is_duration_less_union_of_children():
    S = obs.Span
    spans = [
        S(0, "parent", 0, 100, None, 0),
        # two children on two threads overlap on [30, 40): union is 50
        S(1, "child", 10, 40, 0, 0),
        S(2, "child", 30, 60, 0, 0),
        # a grandchild counts against its own parent only
        S(3, "leaf", 12, 20, 1, 0),
        S(4, "lone", 200, 250, None, 4),
    ]
    s = obs.summary(spans)
    assert s["parent"] == {"count": 1, "total_s": pytest.approx(100e-9),
                           "self_s": pytest.approx(50e-9)}
    assert s["child"]["count"] == 2
    assert s["child"]["total_s"] == pytest.approx(60e-9)
    assert s["child"]["self_s"] == pytest.approx(52e-9)
    assert s["leaf"]["self_s"] == pytest.approx(8e-9)
    assert s["lone"]["self_s"] == s["lone"]["total_s"] == pytest.approx(50e-9)


def test_summary_of_recorded_spans(recorder):
    with obs.span("outer"):
        with obs.span("inner"):
            time.sleep(0.003)
        time.sleep(0.003)
    s = obs.summary()
    assert s["outer"]["count"] == s["inner"]["count"] == 1
    assert s["inner"]["self_s"] == pytest.approx(s["inner"]["total_s"])
    assert s["outer"]["self_s"] == pytest.approx(
        s["outer"]["total_s"] - s["inner"]["total_s"], abs=1e-9)
    assert s["outer"]["self_s"] >= 0.002


def test_disabled_recorder_keeps_nothing_and_returns_the_shared_noop():
    obs.enable(False)
    obs.drain()
    a, b = obs.span("x"), obs.span("y")
    assert a is b
    with a:
        with b:
            pass

    def fn():
        return 1

    assert obs.carry(fn) is fn
    assert obs.records() == [] and obs.summary() == {}


def test_drain_empties_the_recorder(recorder):
    with obs.span("once"):
        pass
    assert [s.name for s in obs.drain()] == ["once"]
    assert obs.records() == []
