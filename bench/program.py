#!/usr/bin/env python3
"""The program's own spans and transfer counters in a traced run of a cell.

    python3 bench/program.py --workload <cell> --seed <n> --seconds <s>

Runs one cell once as ``run.py --trace 1`` does (same set-up, window,
profiler trace and checks), with the program's span recorder
(``repro.core.obs``) on for the window only, and prints one result line: the
cell's per-layer metrics and the program's (``PROGRAM_METRICS``), the
breakdown of the traced window with its idle gaps put down to the
innermost span, benchmark or program (``program_gaps``), the host–device
clock offset that the program's spans show (``clock_offset_ms``), and the
program's span summary (``program``: count, total and self seconds per
span name).

The library half (``ProgramWindow``, ``load``, ``reduce``) is what
``run.py`` needs to report the same in its own traced runs: the window's
``record["program"]`` and ``uploads``/``readbacks`` counters, and the
reduction of ``repro:`` spans beside the ``bench:`` ones. ``reduce`` leaves
everything ``trace.reduce`` gives as it is, so every reader of the trace
reads the same values from the same trace.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

_ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    # as run.py: import the benchmark as the package ``bench``
    sys.path[:1] = [str(_ROOT), str(_ROOT / "src")]

from bench import harness as H  # noqa: E402
from bench import trace as TR  # noqa: E402
from repro.core import obs  # noqa: E402

PREFIX = obs.PREFIX
# the program's per-layer metrics: (name, unit); each has its reader in layers/
PROGRAM_METRICS = (
    ("score_host_ms_per_activation.sched", "ms"),
    ("readback_wait_ms_per_activation.sched", "ms"),
    ("transfers_per_activation.sched", "transfers"),
    ("policy_host_share.sched", "%"),
    ("batch_host_ms_per_config.sweep", "ms"),
)
# XLA module of each jitted program -> the prefix of its spans
MODULES = {"jit_dada_score_matrices": "score", "jit_dada_lambda_search": "search",
           "jit_heft_select": "heft", "jit_surrogate_episode": "episode"}
TRANSFERS = ("uploads", "readbacks")


class ProgramWindow(H.Window):
    """The window, with the program's spans recorded inside it only, and the
    transfers the scoring backend counted in it (``counts``: the backend's
    counters, or None where the cell has no scoring backend)."""

    def __init__(self, seconds: float, spans: H.Spans, counter=None,
                 counts: Optional[Dict[str, int]] = None) -> None:
        super().__init__(seconds, spans, counter)
        self.counts = counts
        self.program: Dict[str, Dict[str, float]] = {}
        self.transfers: Dict[str, int] = {}

    def open(self) -> None:
        obs.drain()
        self._start = {k: self.counts[k] for k in TRANSFERS} if self.counts else {}
        obs.enable(True)
        super().open()

    def close(self) -> None:
        super().close()
        obs.enable(False)
        self.program = obs.summary(obs.drain())
        self.transfers = {k: self.counts[k] - v for k, v in self._start.items()}


# ---------------------------------------------------------------------------
# the trace


def load(path: Path) -> List[dict]:
    """As ``trace.load``, with the program's ``repro:`` spans kept too."""
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
                      if device or e.name.startswith((TR.SPAN_PREFIX, PREFIX))]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _host_events(planes: List[dict], prefix: str) -> List[TR.Event]:
    return [ev for p in planes if p["name"].startswith("/host:")
            for ln in p["lines"] for ev in ln["events"] if ev[0].startswith(prefix)]


def _merged(events: List[TR.Event], prefix: str) -> Dict[str, Tuple[List[float], List[float]]]:
    """Spans by name less ``prefix``, those of one name that overlap (worker
    threads) merged into one, as (starts, ends) sorted for bisection."""
    grouped: Dict[str, List[Tuple[float, float]]] = {}
    for name, s, d in events:
        grouped.setdefault(name[len(prefix):], []).append((s, s + d))
    return {name: ([a for a, _ in ivs], [b for _, b in ivs])
            for name, ivs in ((n, TR.union(v)) for n, v in grouped.items())}


def clock_offset(planes: List[dict]) -> Tuple[Optional[float], Optional[float], Optional[float]]:
    """Host time less device time of one instant, in ns, as (estimate, lower
    bound, upper bound): a program's first op starts no earlier than its
    dispatch span, and its last op ends no later than its read-back span.

    Each module run on the device is paired with the dispatch span of its
    program that starts nearest to it, and that with the first read-back
    span of the program to end after it. The estimate is the middle of the
    bounds, or, where pairings across worker threads leave them crossed,
    the median of the pairs' middles.
    """
    host = _host_events(planes, PREFIX)
    dispatch: Dict[str, List[float]] = {}
    readback: Dict[str, List[float]] = {}
    for name, s, d in host:
        prog, _, phase = name[len(PREFIX):].partition(".")
        if phase == "dispatch":
            dispatch.setdefault(prog, []).append(s)
        elif phase == "readback":
            readback.setdefault(prog, []).append(s + d)
    for v in (*dispatch.values(), *readback.values()):
        v.sort()
    lows, highs = [], []
    for p in TR._device_planes(planes):
        for ln in p["lines"]:
            if ln["name"] != "XLA Modules":
                continue
            for name, s, d in ln["events"]:
                prog = MODULES.get(name.split("(", 1)[0])
                starts, ends = dispatch.get(prog), readback.get(prog)
                if not starts or not ends:
                    continue
                k = bisect.bisect_left(starts, s)
                near = min((j for j in (k - 1, k) if 0 <= j < len(starts)),
                           key=lambda j: abs(starts[j] - s))
                r = bisect.bisect_right(ends, starts[near])
                if r == len(ends):
                    continue
                lows.append(starts[near] - s)
                highs.append(ends[r] - (s + d))
    if not lows:
        return None, None, None
    lo, hi = max(lows), min(highs)
    if lo <= hi:
        return (lo + hi) / 2, lo, hi
    mids = sorted((a + b) / 2 for a, b in zip(lows, highs))
    return mids[len(mids) // 2], lo, hi


def reduce(planes: List[dict], top: int = 10) -> Optional[dict]:
    """``trace.reduce`` of the planes, unchanged, plus ``program_gaps`` (the
    idle gaps put down to the innermost span, benchmark or program, on the
    host's clock: shifted by the clock offset) and ``clock_offset_ms`` with
    its bounds. The offset is applied to nothing else."""
    r = TR.reduce(planes, top)
    if r is None:
        return None
    offset, lo, hi = clock_offset(planes)
    shift = offset or 0.0
    w0 = next(s for name, s, _ in _host_events(planes, TR.WINDOW) if name == TR.WINDOW)
    w1 = w0 + r["window_s"] * 1e9
    spans = _merged([ev for ev in _host_events(planes, TR.SPAN_PREFIX)
                     if ev[0] != TR.WINDOW], TR.SPAN_PREFIX)
    spans.update(_merged(_host_events(planes, PREFIX), PREFIX))
    gap_ns: Dict[str, float] = {}
    devices = TR._device_planes(planes)
    for plane in devices:
        ivs = [(max(s, w0), min(s + d, w1)) for ln in plane["lines"] if ln["name"] == "XLA Ops"
               for _, s, d in ln["events"] if min(s + d, w1) > max(s, w0)]
        edges = [w0] + [x for iv in TR.union(ivs) for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                who = TR._innermost(spans, (a + b) / 2 + shift)
                gap_ns[who] = gap_ns.get(who, 0.0) + (b - a)
    n_dev = len(devices)
    r["program_gaps"] = [[k, v / n_dev / 1e9] for k, v in
                         sorted(gap_ns.items(), key=lambda kv: -kv[1])[:top]]
    r["clock_offset_ms"] = None if offset is None else offset / 1e6
    r["clock_offset_bounds_ms"] = None if lo is None else [lo / 1e6, hi / 1e6]
    return r


# ---------------------------------------------------------------------------
# the command


def parse(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        p.error("--seed must be >= 0 and --seconds > 0")
    return args


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = H.load_cell(args.workload)
        kind = H.kind_module(cell.traffic)
        H.use_compile_cache()
        devs = H.accelerator(cell.chips)
    except H.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    import jax

    dev = devs[0]
    H.log(f"device: platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devs)}; cell {cell.name}, seed {args.seed}; program spans on")
    state = kind.setup(cell, args.seed, devs)
    spans = H.Spans(tracing=True)
    counter = H.CompileCounter()
    backend = getattr(state, "backend", None)
    win = ProgramWindow(args.seconds, spans, counter,
                        backend.counts if backend is not None else None)
    trace_dir = H.scratch_dir()
    jax.profiler.start_trace(str(trace_dir))
    try:
        record = kind.window(state, win, spans)
    finally:
        jax.profiler.stop_trace()
    H.log(f"set-up {win.t_open - T_START!r} s; window {win.length!r} s; "
          f"{counter.n} compilations in the window")
    record.update(window_s=win.length, spans=dict(spans.totals), program=win.program,
                  compiles_in_window=counter.n, peaks=H.peaks_for(dev.device_kind))
    record["counters"].update(win.transfers)
    reduced = reduce(load(TR.find_xplane(trace_dir)))
    shutil.rmtree(trace_dir, ignore_errors=True)
    if reduced is None:
        raise H.BenchError("the trace holds no window or no device plane")
    record["trace"] = reduced
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
              "memory_peak_bytes": H.memory_peak(devs),
              "busy_s": reduced["busy_s"], "window_s": reduced["window_s"]}
    checks = kind.check(state, record)
    metrics = {}
    for name, unit in [(m["name"], m["unit"]) for m in cell.per_layer] + list(PROGRAM_METRICS):
        v = H.layer_reader(name)(record)
        if v is not None:
            metrics[name] = {"value": v, "unit": unit}
    breakdown = {k: reduced[k] for k in ("device_ops", "idle_gaps", "program_gaps",
                                         "clock_offset_ms", "clock_offset_bounds_ms")}
    breakdown.update(counters=record["counters"], program=record["program"])
    H.emit(all(c.ok for c in checks), record["attempted"], record.get("failed", 0),
           metrics, device, checks, breakdown)
    return 0


if __name__ == "__main__":
    sys.exit(main())
