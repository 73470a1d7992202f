"""Shared machinery of the chip benchmark: the spec, the device, the result.

``run.py`` drives one cell once; everything that belongs to one
configuration, traffic mix or per-layer metric lives in files of its own
(``configs/``, ``traffic/``, ``layers/``) that this module finds by the
names in ``BENCHMARK.json``. What a traffic mix asks for is done by the
general generator its ``kind`` names (``kinds/<kind>.py``).
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

from bench.trace import SPAN_PREFIX

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, bad spec, ...)."""


# ---------------------------------------------------------------------------
# the spec


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    limits: Dict[str, float]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec_path: Optional[Path] = None) -> Cell:
    spec = json.loads((spec_path or ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        limits=json.loads((BENCH / "limits" / f"{name}.json").read_text()),
    )


def kind_module(traffic: Dict[str, Any]):
    """The general generator that runs this traffic mix."""
    return importlib.import_module(f"bench.kinds.{traffic['kind']}")


def load_callable(path: str) -> Callable:
    """``"package.module:function"`` as a configuration names a builder."""
    mod, name = path.split(":")
    return getattr(importlib.import_module(mod), name)


def build_graph(cfg: Dict[str, Any], kernel: Optional[str] = None, with_fns: bool = False):
    """The configuration's task graph: ``kernel``'s, or its first kernel's."""
    kernel = kernel or next(iter(cfg["graphs"]))
    return load_callable(cfg["graphs"][kernel])(
        cfg["n_tiles"], cfg["tile"], itemsize=cfg["itemsize"], with_fns=with_fns)


def build_machine(cfg: Dict[str, Any]):
    return load_callable(cfg["machine"])(**cfg["machine_args"])


def policy_spec(p: Dict[str, Any]) -> str:
    """A traffic file's policy entry as a ``repro.sched.resolve`` spec."""
    args = "&".join(f"{k}={v}" for k, v in p.items() if k != "name")
    return f"{p['name']}?{args}" if args else p["name"]


def layer_path(metric: str) -> Path:
    """``layers/<metric>.py``; a metric split by cell (``<name>.<suffix>``)
    with no file of its own is read by ``layers/<name>.py``."""
    path = BENCH / "layers" / f"{metric}.py"
    if not path.is_file():
        path = BENCH / "layers" / f"{metric.split('.', 1)[0]}.py"
    return path


def layer_reader(metric: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    """The ``read(record)`` of the metric's reader."""
    path = layer_path(metric)
    spec = importlib.util.spec_from_file_location(f"bench_layer_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the device


def peaks_for(device_kind: str) -> Dict[str, float]:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise BenchError(
            f"no peaks for device kind {device_kind!r} in bench/peaks.json "
            f"(known: {sorted(table)})")
    return table[device_kind]


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at one fixed path in the checkout
    (the one the program uses), or where ``JAX_COMPILATION_CACHE_DIR`` says."""
    import jax

    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def accelerator(chips: int):
    """The devices of this run: ``chips`` TPUs, or an error."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(
            f"no TPU: JAX's first device is {devs[0].platform!r}; "
            "this benchmark runs on a TPU only")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    peaks_for(devs[0].device_kind)
    return devs[:chips]


def memory_peak(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devs)


class CompileCounter:
    """Counts XLA compilations (backend compiles and cache loads) while on."""

    def __init__(self) -> None:
        import jax.monitoring

        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name: str, *_args, **_kw) -> None:
        if self.on and name == "/jax/core/compile/backend_compile_duration":
            self.n += 1


# ---------------------------------------------------------------------------
# host spans (kept in memory; mirrored into the profiler's trace when on)


@dataclass
class Spans:
    tracing: bool = False
    totals: Dict[str, float] = field(default_factory=dict)

    def span(self, name: str):
        return _Span(self, name)


class _Span:
    __slots__ = ("spans", "name", "t0", "ann")

    def __init__(self, spans: Spans, name: str) -> None:
        self.spans, self.name, self.ann = spans, name, None

    def __enter__(self):
        if self.spans.tracing:
            import jax.profiler

            self.ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        if self.ann is not None:
            self.ann.__exit__(*exc)
        tot = self.spans.totals
        tot[self.name] = tot.get(self.name, 0.0) + dt
        return False


class Window:
    """The measured window: opened by the generator once set-up is done,
    closed by it at the end of the first whole unit of its work (a
    schedule, a round of calls, a factorisation) that ends after
    ``seconds``."""

    def __init__(self, seconds: float, spans: Spans,
                 counter: Optional[CompileCounter] = None) -> None:
        self.seconds = seconds
        self.spans = spans
        self.counter = counter
        self.t_open = self.t_close = None
        self._span = None

    def open(self) -> None:
        self._span = self.spans.span("window")
        self._span.__enter__()
        if self.counter is not None:
            self.counter.on = True
        self.t_open = time.perf_counter()
        self.deadline = self.t_open + self.seconds

    def expired(self) -> bool:
        return time.perf_counter() >= self.deadline

    def close(self) -> None:
        self.t_close = time.perf_counter()
        if self.counter is not None:
            self.counter.on = False
        self._span.__exit__(None, None, None)

    @property
    def closed(self) -> bool:
        return self.t_close is not None

    @property
    def length(self) -> float:
        return self.t_close - self.t_open


# ---------------------------------------------------------------------------
# the result


@dataclass
class Check:
    """One number compared, with its limit (``value <= limit`` passes)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def checks(readings: Dict[str, float], limits: Dict[str, float],
           names: List[str]) -> List[Check]:
    """The numbers ``names`` of ``readings``, each held to its limit."""
    return [Check(k, readings[k], limits[k]) for k in names]


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile by linear interpolation (numpy's default)."""
    import numpy as np

    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def emit(correct: bool, attempted: int, failed: int, metrics: Dict[str, Dict],
         device: Dict[str, Any], checks: List[Check],
         breakdown: Optional[Dict[str, list]] = None) -> None:
    """The checks as the last lines on stderr, the result as stdout's last line."""
    for c in checks:
        print(f"check {c.name} = {c.value!r} (limit {c.limit!r}): "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr, flush=True)
    out: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
        "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    sys.stdout.flush()
    print(json.dumps(out), flush=True)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def scratch_dir() -> Path:
    """A fresh directory for this run's trace, under TMPDIR."""
    import tempfile

    return Path(tempfile.mkdtemp(prefix="bench-trace-", dir=os.environ.get("TMPDIR")))
