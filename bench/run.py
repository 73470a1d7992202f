#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (imports, inputs made from the seed, warm-up of every shape the
window uses) is timed as ``setup_s``; then the window measures for
``--seconds`` and the outputs it produced are compared with a plain
reference. ``--trace 1`` runs the same window under the profiler and reports
the cell's per-layer metrics instead of its end-to-end ones. Without a TPU
the run exits non-zero and prints no result: there is no CPU mode.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[1]
# import the benchmark as the package ``bench`` (its directory is not a
# top-level path: ``bench/trace.py`` would shadow the standard library)
sys.path[:1] = [str(_ROOT), str(_ROOT / "src")]

from bench import harness as H  # noqa: E402
from bench import trace as TR  # noqa: E402


def parse(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
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
          f"count={len(devs)}; cell {cell.name}, seed {args.seed}")
    state = kind.setup(cell, args.seed, devs)
    spans = H.Spans(tracing=bool(args.trace))
    counter = H.CompileCounter()
    trace_dir = None
    if args.trace:
        trace_dir = H.scratch_dir()
        jax.profiler.start_trace(str(trace_dir))
    win = H.Window(args.seconds, spans, counter)
    try:
        record = kind.window(state, win, spans)
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    setup_s = win.t_open - T_START
    record.update(window_s=win.length, spans=dict(spans.totals),
                  compiles_in_window=counter.n, peaks=H.peaks_for(dev.device_kind))
    H.log(f"set-up {setup_s!r} s; window {win.length!r} s; "
          f"{counter.n} compilations in the window; spans {spans.totals}")
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
              "memory_peak_bytes": H.memory_peak(devs)}
    breakdown = None
    if trace_dir is not None:
        reduced = TR.reduce(TR.load(TR.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if reduced is None:
            raise H.BenchError("the trace holds no window or no device plane")
        record["trace"] = reduced
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}

    checks = kind.check(state, record)
    metrics = {}
    if args.trace:
        for m in cell.per_layer:
            v = H.layer_reader(m["name"])(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = dict(record["e2e"], setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    H.emit(all(c.ok for c in checks), record["attempted"],
           record.get("failed", 0), metrics, device, checks, breakdown)
    return 0


if __name__ == "__main__":
    sys.exit(main())
