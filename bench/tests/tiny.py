"""Cells cut to a size a CPU test can hold, and a driver that runs one
through set-up, window and check without the harness's look for a chip."""
from __future__ import annotations

import copy
import json

from bench import harness as H

TINY_GRAPH = {"n_tiles": 4, "tile": 64}

# cells whose files are kept under bench/ but whose entry is not in
# BENCHMARK.json until they are measured on the chip: (config, traffic)
SHELVED = {"chol32_factor": ("spotrf_nt32_paper8", "tile_factor")}


def _shelved(name: str) -> H.Cell:
    config, traffic = SHELVED[name]

    def read(path):
        return json.loads((H.BENCH / path).read_text())

    return H.Cell(name=name, chips=1, config=read(f"configs/{config}.json"),
                  traffic=read(f"traffic/{traffic}.json"), end_to_end=[], per_layer=[],
                  limits=read(f"limits/{name}.json"))


def tiny_cell(name: str) -> H.Cell:
    cell = _shelved(name) if name in SHELVED else copy.deepcopy(H.load_cell(name))
    cell.config.update(TINY_GRAPH)
    if cell.traffic["kind"] == "schedule":
        cell.config["sched"]["jax_min"] = 1  # every activation on the device path
        cell.config["widest_ready"] = TINY_GRAPH["n_tiles"] - 1
    tr = cell.traffic
    if tr["kind"] == "schedule":
        tr["check_share"] = 1.0
    if tr["kind"] == "sweep":
        tr.update(seeds_per_call=3, check_per_kernel=3)
    return cell


def drive(cell: H.Cell, seed: int = 12345, seconds: float = 0.3, before_window=None):
    """Set-up, window, check: returns (state, record, checks)."""
    kind = H.kind_module(cell.traffic)
    st = kind.setup(cell, seed, None)
    if before_window is not None:
        before_window(st)
    spans = H.Spans()
    win = H.Window(seconds, spans)
    record = kind.window(st, win, spans)
    record.update(window_s=win.length, spans=dict(spans.totals))
    return st, record, kind.check(st, record)
