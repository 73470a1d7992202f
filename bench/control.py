#!/usr/bin/env python3
"""Readings from which a cell's limits are set: the program's and the control's.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

In one process: set-up once, then for each seed the seed's inputs, a
window of ``--seconds`` at the cell's own size and load, and the numbers
the run compares, read twice and each held to the cell's limits — once for
the program against the plain reference, and once for the control
(``control_readings`` of the traffic's kind): the reference put in the
program's place and computed in the nearest precision below the one the
configuration states (float32 for the scheduler's float64 scoring,
bfloat16 for the surrogate's float32, ``precision="high"`` (three bf16
passes) for the tiles' full-precision float32 products). The control has
to come out not correct. One JSON line per seed names the device it ran
on. The benchmark's own runs never run the control.
"""
import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
sys.path[:1] = [str(_ROOT), str(_ROOT / "src")]

import argparse  # noqa: E402
import json  # noqa: E402

from bench import harness as H  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args()
    import jax

    H.use_compile_cache()
    cell = H.load_cell(args.workload)
    kind = H.kind_module(cell.traffic)
    dev = jax.devices()[0]
    st = None
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        if st is None:
            st = kind.setup(cell, seed, jax.devices()[:cell.chips])
        else:
            kind.reseed(st, seed)
        spans = H.Spans()
        win = H.Window(args.seconds, spans)
        record = kind.window(st, win, spans)
        record.update(window_s=win.length, spans=dict(spans.totals))
        t1 = time.perf_counter()
        program = kind.check(st, record)
        t2 = time.perf_counter()
        control = kind.control_readings(st)
        held = H.checks(control, cell.limits, kind.COMPARED)
        print(json.dumps({
            "workload": cell.name, "seed": seed, "device": dev.device_kind,
            "platform": dev.platform, "window_s": win.length, "e2e": record["e2e"],
            "counters": record["counters"],
            "program": {c.name: c.value for c in program},
            "program_correct": all(c.ok for c in program),
            "control": control, "control_correct": all(c.ok for c in held),
            "seconds": {"setup_and_window": t1 - t0, "check": t2 - t1,
                        "control": time.perf_counter() - t2}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
