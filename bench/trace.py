"""Reduction of a profiler trace to device busy time, op times and idle gaps.

The profiler writes an ``.xplane.pb``; :func:`load` reads it with JAX's
``ProfileData`` into plain lists, and :func:`reduce` works on those lists
only, so a small recorded trace (``tests/fixtures``) checks the arithmetic.

- Device ops are the events of the ``XLA Ops`` line of each ``/device:TPU:<n>``
  plane, clipped to the window. Busy time is the length of the union of
  their intervals, averaged over the devices; idle is the window less busy.
- An op is named ``<module>/<op>``: the HLO instruction's name (the event's
  text up to `` = ``) after the ``XLA Modules`` event that holds it, less
  the module's fingerprint in parentheses.
- The window and the host's spans are the benchmark's own annotations
  (``bench:<name>``) on the host plane. Each idle gap is put down to the
  innermost span that holds its midpoint, or to ``outside spans``. The
  profiler aligns the host's clock with the device's to about a millisecond
  or two (a v5e trace put each op some 1.3 ms before the span that
  dispatched it), so gaps are put down right at the scale of activations and
  tasks, not of single ops.
- The profiler keeps a bounded number of device events: a window of many
  small ops (the surrogate's scan steps) loses its later part. Where the
  last op ends more than a second and a tenth of the window before the
  window does, the window is cut there, and ``covered`` counts the host
  spans that ended inside the cut window, for readers that divide by work.
"""
from __future__ import annotations

import bisect
from pathlib import Path
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench:"
WINDOW = SPAN_PREFIX + "window"
OUTSIDE = "outside spans"

Event = Tuple[str, float, float]  # (name, start_ns, duration_ns)


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: Path) -> List[dict]:
    """Planes as ``{"name", "lines": [{"name", "events": [Event]}]}``.

    Only the planes the reduction reads are kept: device planes and the
    host plane's lines that carry benchmark spans.
    """
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(str(path)).planes:
        device = plane.name.startswith("/device:")
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            events = [(e.name, float(e.start_ns), float(e.duration_ns))
                      for e in line.events
                      if device or e.name.startswith(SPAN_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _host_spans(planes: List[dict]) -> List[Event]:
    return [ev for p in planes if p["name"].startswith("/host:")
            for ln in p["lines"] for ev in ln["events"]
            if ev[0].startswith(SPAN_PREFIX)]


def _device_planes(planes: List[dict]) -> List[dict]:
    return [p for p in planes if p["name"].startswith("/device:TPU:")
            and p["name"][len("/device:TPU:"):].isdigit()]


def reduce(planes: List[dict], top: int = 10) -> Optional[dict]:
    """Busy/idle seconds, op totals and attributed idle gaps of the window.

    Returns None when the trace holds no window span or no device plane.
    """
    spans = _host_spans(planes)
    windows = [ev for ev in spans if ev[0] == WINDOW]
    devices = _device_planes(planes)
    if not windows or not devices:
        return None
    _, w0, wd = windows[0]
    w1 = w0 + wd
    inner = _by_name(ev for ev in spans if ev[0] != WINDOW)

    last = max((s + d for p in devices for ln in p["lines"] if ln["name"] == "XLA Ops"
                for _, s, d in ln["events"] if w0 < s + d and s < w1), default=None)
    if last is not None and w1 - last > max(1e9, 0.1 * wd):
        w1 = last
    busy_total = 0.0
    op_ns: Dict[str, float] = {}
    gap_ns: Dict[str, float] = {}
    for plane in devices:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        mods = sorted(lines.get("XLA Modules", []), key=lambda ev: ev[1])
        mod_starts = [ev[1] for ev in mods]
        ivs = []
        for name, s, d in lines.get("XLA Ops", []):
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            ivs.append((a, b))
            name = name.split(" = ", 1)[0]
            k = bisect.bisect_right(mod_starts, s) - 1
            if k >= 0 and mods[k][1] + mods[k][2] >= s + d:
                name = f"{mods[k][0].split('(', 1)[0]}/{name}"
            op_ns[name] = op_ns.get(name, 0.0) + (b - a)
        busy = union(ivs)
        busy_total += sum(b - a for a, b in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                who = _innermost(inner, (a + b) / 2)
                gap_ns[who] = gap_ns.get(who, 0.0) + (b - a)
    n_dev = len(devices)

    def top_list(d: Dict[str, float]) -> List[list]:
        items = sorted(d.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v / n_dev / 1e9] for k, v in items]

    covered: Dict[str, int] = {}
    for name, (_, ends) in inner.items():
        covered[name] = bisect.bisect_right(ends, w1)
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_total / n_dev / 1e9,
        "cut": w1 < w0 + wd,
        "covered": covered,
        "device_ops": top_list(op_ns),
        "idle_gaps": top_list(gap_ns),
        "n_devices": n_dev,
    }


def _by_name(spans) -> Dict[str, Tuple[List[float], List[float]]]:
    """Spans of one name never overlap one another: keep each name's
    (starts, ends) sorted for bisection."""
    grouped: Dict[str, List[Tuple[float, float]]] = {}
    for name, s, d in spans:
        grouped.setdefault(name[len(SPAN_PREFIX):], []).append((s, s + d))
    out = {}
    for name, ivs in grouped.items():
        ivs.sort()
        out[name] = ([a for a, _ in ivs], [b for _, b in ivs])
    return out


def _innermost(spans: Dict[str, Tuple[List[float], List[float]]], t: float) -> str:
    """The shortest span that holds ``t``: spans nest, so that is the innermost."""
    best, best_len = OUTSIDE, None
    for name, (starts, ends) in spans.items():
        k = bisect.bisect_right(starts, t) - 1
        if k >= 0 and ends[k] >= t:
            length = ends[k] - starts[k]
            if best_len is None or length < best_len:
                best, best_len = name, length
    return best
