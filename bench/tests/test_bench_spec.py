"""``BENCHMARK.json`` keeps to the benchmark's contract, every file it names
exists, and a run without a TPU, or without the program, gives no result."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness as H

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(s: str) -> bool:
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"] and 1 <= SPEC["run_seconds"] <= 51
    cmd = SPEC["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for w in cmd:
        if "/" in w:
            assert not w.startswith("/") and ".." not in w and w.startswith("bench/")
    assert (ROOT / cmd[1]).is_file()
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_entries():
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}
    for section, want in keys.items():
        names = [e["name"] for e in SPEC[section]]
        assert len(names) == len(set(names)), section
        optional = {"workloads"} if section in ("end_to_end", "per_layer") else set()
        for e in SPEC[section]:
            assert set(e) - optional == want, e
            assert NAME.match(e["name"]), e["name"]
            for k in ("why", "layer", "source"):
                if k in e:
                    assert _line(e[k]), (e["name"], k)
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
                assert e["source"] in SOURCES
    for c in SPEC["configs"]:
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        on_file = json.loads((ROOT / c["file"]).read_text())
        assert on_file["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in SPEC["workloads"])


def test_cells():
    cells = SPEC["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    configs = {c["name"] for c in SPEC["configs"]}
    for w in cells:
        assert w["config"] in configs and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert (ROOT / "bench" / "limits" / f"{w['name']}.json").is_file()
        cell = H.load_cell(w["name"])
        H.kind_module(cell.traffic)
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)


def test_metrics():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert H.layer_path(m["name"]).is_file()
        H.layer_reader(m["name"])
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"
    assert all(len(v) == 1 for v in layers.values()), layers


def test_run_budget_fits():
    n = 24  # every later check may hold the full 24 cells
    per_run = SPEC["run_seconds"] + 60
    total = (2 + 14 * n) * per_run + n * 2 * 90 + 1200
    assert total <= 43200


def test_unknown_device_kind_is_an_error():
    with pytest.raises(H.BenchError, match="no peaks"):
        H.peaks_for("TPU v0 imaginary")
    assert H.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12


def _run(cwd: Path, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", SPEC["workloads"][0]["name"],
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    r = _run(ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert r.stdout.strip() == ""


def test_benchmark_alone_no_result(tmp_path):
    (tmp_path / "BENCHMARK.json").write_text((ROOT / "BENCHMARK.json").read_text())
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
