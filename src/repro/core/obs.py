"""Spans of the program's own layers, kept in memory (off by default).

Off, ``span(name)`` returns one shared no-op context manager: the cost on
the hot path is one global check. On (``enable(True)``), each span records
its name, its start and end on ``time.perf_counter_ns``, the id of the span
that was open around it on its thread (its parent) and the id of the
outermost span of that chain (its root), so all spans of one activation or
one ``run_batch`` call share a root. Each span also enters
``jax.profiler.TraceAnnotation("repro:" + name)``, which puts it on the
profiler's host plane, on the device trace's clock, whenever a profiler
session is running; without one it records nothing.

A span opened on a worker thread has the parent that :func:`carry` handed
it from the thread that submitted the work.

Names used by the program (see ``docs/runtime_architecture.md``):
``dada.place`` with ``dada.predict``, ``dada.order``, ``dada.search_host``
and ``dada.rebuild``; ``<program>.pack/upload/dispatch/readback`` for the
programs ``score``, ``search``, ``heft`` (``core/backend.py``) and
``episode`` (``core/episode.py``); ``batch.run`` with ``batch.plan``.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

PREFIX = "repro:"


class Span(NamedTuple):
    id: int
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[int]
    root: int


class _NoSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoSpan()
_on = False
_annotation = None  # jax.profiler.TraceAnnotation, imported by enable(True)
_records: List[Span] = []
_ids = itertools.count()
_local = threading.local()


def _stack() -> List[Tuple[int, int]]:
    """The open spans of this thread, innermost last, as (id, root)."""
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


class _Span:
    __slots__ = ("name", "id", "parent", "root", "t0", "ann")

    def __init__(self, name: str) -> None:
        self.name = name

    def __enter__(self):
        st = _stack()
        self.id = next(_ids)  # itertools.count is atomic under the GIL
        if st:
            self.parent, self.root = st[-1]
        else:
            self.parent, self.root = None, self.id
        st.append((self.id, self.root))
        self.ann = _annotation(PREFIX + self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        _stack().pop()
        _records.append(Span(self.id, self.name, self.t0, t1, self.parent, self.root))
        return False


def span(name: str):
    """A context manager timing one phase named ``name``."""
    if not _on:
        return _NOOP
    return _Span(name)


def enable(on: bool = True) -> None:
    """Turn recording on or off. Records stay until :func:`drain`."""
    global _on, _annotation
    if on and _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    _on = bool(on)


def carry(fn: Callable) -> Callable:
    """``fn``, to run on another thread as a child of the span open here."""
    if not _on:
        return fn
    st = _stack()
    if not st:
        return fn
    ctx = st[-1]

    def under(*args, **kwargs):
        mine = _stack()
        mine.append(ctx)
        try:
            return fn(*args, **kwargs)
        finally:
            mine.pop()

    return under


def records() -> List[Span]:
    """The closed spans recorded so far, in the order they closed."""
    return list(_records)


def drain() -> List[Span]:
    """The closed spans recorded so far; the recorder keeps none of them."""
    global _records
    out, _records = _records, []
    return out


def _covered(intervals: List[Tuple[int, int]], lo: int, hi: int) -> int:
    """Length of the union of ``intervals`` clipped to [lo, hi]."""
    total, end = 0, lo
    for a, b in sorted(intervals):
        a, b = max(a, end), min(b, hi)
        if b > a:
            total += b - a
            end = b
    return total


def summary(spans: Optional[List[Span]] = None) -> Dict[str, Dict[str, float]]:
    """Per name: ``count``, ``total_s`` and ``self_s``, the duration less
    the part of it that child spans (on any thread) cover."""
    spans = records() if spans is None else spans
    children: Dict[int, List[Tuple[int, int]]] = {}
    for s in spans:
        if s.parent is not None:
            children.setdefault(s.parent, []).append((s.start_ns, s.end_ns))
    out: Dict[str, Dict[str, float]] = {}
    for s in spans:
        d = s.end_ns - s.start_ns
        own = d - _covered(children.get(s.id, []), s.start_ns, s.end_ns)
        e = out.setdefault(s.name, {"count": 0, "total_s": 0.0, "self_s": 0.0})
        e["count"] += 1
        e["total_s"] += d / 1e9
        e["self_s"] += own / 1e9
    return out
