"""Typed scheduling configuration — the single source of truth for every
``REPRO_SCHED_*`` knob.

Before this module the knobs were parsed ad hoc at several call sites
(``backend.py`` read four env vars with silent fallbacks): a typo like
``REPRO_SCHED_LAMBDA_DEPTH=banana`` silently became the platform default
deep inside the jax backend. ``SchedConfig.from_env()`` parses the whole
environment once, validates every value, and rejects unknown
``REPRO_SCHED_*`` variables with one clear error, so misconfiguration
fails at the edge instead of deep in a hot path. The benchmark scripts
parse their own knobs (``benchmarks/common.py``).

The frozen dataclass is then threaded explicitly through the scheduling
stack (``repro.core.backend`` / ``dada`` / ``heft`` / ``Simulator``) —
``os.environ`` is only ever read here.

``current_config()`` memoizes the parse against a snapshot of the relevant
environment entries, so hot paths pay a dict scan, not a re-parse, while
tests that monkeypatch the environment still see fresh values.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, fields
from typing import Mapping, Optional, Tuple

SCHED_PREFIX = "REPRO_SCHED_"

from repro.runtime.load import ADMISSION_MODES, ARRIVAL_PROCESSES
from repro.runtime.memory import EVICTION_POLICIES
from repro.runtime.rescore import RESCORE_MODES
from repro.runtime.traces import FAULT_MODES

BACKENDS = ("numpy", "jax")
PALLAS_MODES = ("auto", "1", "0", "off", "false")

# env var -> (field name, parser); parsers raise ValueError with the
# offending variable named, so the error reads as configuration feedback
_MISSING = object()


def _err(var: str, value: str, expected: str) -> ValueError:
    return ValueError(
        f"invalid scheduling configuration: {var}={value!r} ({expected})"
    )


def _parse_int(var: str, value: str, lo: Optional[int] = None) -> int:
    try:
        n = int(value)
    except ValueError:
        raise _err(var, value, "expected an integer") from None
    if lo is not None and n < lo:
        raise _err(var, value, f"expected an integer >= {lo}")
    return n


def _parse_float(var: str, value: str) -> float:
    try:
        return float(value)
    except ValueError:
        raise _err(var, value, "expected a number") from None


def _parse_flag(var: str, value: str) -> bool:
    if value in ("", "0"):
        return False
    if value == "1":
        return True
    raise _err(var, value, "expected 0 or 1")


def _parse_rate(var: str, value: str) -> float:
    rate = _parse_float(var, value)
    if rate < 0:
        raise _err(var, value, "expected a rate >= 0")
    return rate


def _parse_trace_path(var: str, value: str) -> Optional[str]:
    if not value:
        return None  # empty = unset (no trace replay)
    if not os.path.isfile(value):
        raise _err(var, value, "expected a path to an existing JSONL trace file")
    return value


@dataclass(frozen=True)
class SchedConfig:
    """Every scheduling knob (``REPRO_SCHED_*``), parsed and validated once.

    - ``backend``: placement-scoring backend, ``numpy`` (default) or
      ``jax``; see ``repro.core.backend``.
    - ``jax_min``: ready-set width from which the jax path engages.
    - ``lambda_depth``: speculative λ-bisection depth (``None`` = platform
      default: 1 on cpu, 5 on gpu/tpu), clamped to [1, 8].
    - ``pallas``: Pallas transfer-kernel mode of the surrogate episodes
      (``auto``/``1``/``0``; see ``repro.core.episode``).
    - ``mem_capacity``: device-memory capacity in bytes (0 = unbounded,
      the default; see ``repro.runtime.memory``).
    - ``eviction``: victim-selection policy under capacity pressure,
      ``lru`` (default) or ``affinity`` (fewest pending readers first).
    - ``cancel_stale``: drop in-flight copies of data overwritten
      mid-flight instead of landing them as "valid" (off by default to
      preserve bit-for-bit equivalence with the reference simulator).
    - ``churn``: seeded random detach/attach rate in events per simulated
      second (0 = no churn, the default; see ``repro.runtime.faults``).
    - ``fault_mode``: recovery mode for detaches, ``drain`` (default) or
      ``kill`` (kill-and-requeue).
    - ``fault_trace``: path to a JSONL preemption trace replayed into
      every engine (``repro.runtime.traces``); must exist at parse time.
    - ``notice_s``: advance-warning window for detach events in simulated
      seconds (0 = no notice, the default). With a notice, the engine
      stops starting new work on the dying resource, proactively
      replicates sole-copy data to host, and policies see a finite
      decaying pressure penalty instead of a surprise death.
    - ``link_flake``: seeded per-hop transfer failure probability in
      [0, 1] (0 = reliable links, the default; see
      ``repro.runtime.transfers``).
    - ``retry_max``: failed-hop retry budget before the transfer times
      out and is re-sourced from another live copy or host.
    - ``backoff_s``: base delay for the capped exponential retry backoff
      (delay doubles per attempt, capped at 64×).
    - ``exact``: simulation engine selector. ``True`` (default) runs the
      exact Python event loop — the verification oracle. ``0`` opts into
      the batched surrogate episode engine (``repro.core.episode``),
      which requires the jax backend; ranking fidelity, not bit
      equality (see docs/runtime_architecture.md).
    - ``arrival``: open-loop arrival process for the serving load layer,
      ``poisson`` (default), ``bursty`` or ``diurnal``; consumed by
      ``repro.runtime.load.make_arrivals`` and the serving benchmark.
    - ``admission``: admission control at graph arrival, ``none``
      (default), ``reject`` (turn away tenants whose predicted working
      set exceeds free aggregate capacity) or ``defer`` (retry the
      arrival after ``admit_defer_s``); requires serving mode.
    - ``rescore``: serving-pool rescoring mode, ``off`` (default: the
      classic per-activation ``strategy.place`` loop, bit-for-bit
      identical to pre-serving engines), ``full`` (shared ready pool,
      every row rebuilt every round — the naive baseline) or
      ``incremental`` (dirty-row rescoring keyed on residency bitmasks
      and fault/pressure epochs; see ``repro.runtime.rescore``).
    - ``admit_defer_s``: simulated delay before a deferred arrival
      retries admission (> 0, or a deferred tenant would respin at the
      same instant forever).
    - ``audit``: record a structured schedule audit log on every engine
      (``repro.verify``): placements, transfer hops, landing decisions,
      evictions and fault windows, consumed by the independent schedule
      verifier. Off by default — audit-off runs are bit-for-bit
      identical to pre-audit behavior (see docs/verification.md).
    - ``batch``: per-dispatch batch-size cap for the surrogate engine
      (``api.run_batch`` splits larger sweeps into chunks of this many
      configurations).
    """

    backend: str = "numpy"
    jax_min: int = 32
    lambda_depth: Optional[int] = None
    pallas: str = "auto"
    mem_capacity: int = 0
    eviction: str = "lru"
    cancel_stale: bool = False
    churn: float = 0.0
    fault_mode: str = "drain"
    fault_trace: Optional[str] = None
    notice_s: float = 0.0
    link_flake: float = 0.0
    retry_max: int = 3
    backoff_s: float = 1e-4
    exact: bool = True
    arrival: str = "poisson"
    admission: str = "none"
    rescore: str = "off"
    admit_defer_s: float = 0.005
    audit: bool = False
    batch: int = 256

    def __post_init__(self) -> None:
        if self.backend not in BACKENDS:
            raise _err(
                "REPRO_SCHED_BACKEND", self.backend,
                f"choose from {BACKENDS}",
            )
        if self.pallas not in PALLAS_MODES:
            raise _err(
                "REPRO_SCHED_PALLAS", self.pallas,
                f"choose from {PALLAS_MODES}",
            )
        if self.eviction not in EVICTION_POLICIES:
            raise _err(
                "REPRO_SCHED_EVICTION", self.eviction,
                f"choose from {EVICTION_POLICIES}",
            )
        if self.churn < 0:
            raise _err(
                "REPRO_SCHED_CHURN", str(self.churn),
                "expected a rate >= 0",
            )
        if self.fault_mode not in FAULT_MODES:
            raise _err(
                "REPRO_SCHED_FAULT_MODE", self.fault_mode,
                f"choose from {FAULT_MODES}",
            )
        if self.notice_s < 0:
            raise _err(
                "REPRO_SCHED_NOTICE_S", str(self.notice_s),
                "expected a number >= 0",
            )
        if not (0.0 <= self.link_flake <= 1.0):
            raise _err(
                "REPRO_SCHED_LINK_FLAKE", str(self.link_flake),
                "expected a probability in [0, 1]",
            )
        if self.retry_max < 0:
            raise _err(
                "REPRO_SCHED_RETRY_MAX", str(self.retry_max),
                "expected an integer >= 0",
            )
        if self.backoff_s < 0:
            raise _err(
                "REPRO_SCHED_BACKOFF_S", str(self.backoff_s),
                "expected a number >= 0",
            )
        if self.arrival not in ARRIVAL_PROCESSES:
            raise _err(
                "REPRO_SCHED_ARRIVAL", self.arrival,
                f"choose from {ARRIVAL_PROCESSES}",
            )
        if self.admission not in ADMISSION_MODES:
            raise _err(
                "REPRO_SCHED_ADMISSION", self.admission,
                f"choose from {ADMISSION_MODES}",
            )
        if self.rescore not in RESCORE_MODES:
            raise _err(
                "REPRO_SCHED_RESCORE", self.rescore,
                f"choose from {RESCORE_MODES}",
            )
        if not (self.admit_defer_s > 0):
            raise _err(
                "REPRO_SCHED_ADMIT_DEFER_S", str(self.admit_defer_s),
                "expected a number > 0",
            )
        if not self.exact and self.backend != "jax":
            # the surrogate episode engine is a jax program; a silent
            # fall-back to the exact path would invert the knob's meaning
            raise ValueError(
                "invalid scheduling configuration: REPRO_SCHED_EXACT=0 "
                "(the batched surrogate engine) requires "
                "REPRO_SCHED_BACKEND=jax, got "
                f"REPRO_SCHED_BACKEND={self.backend!r}"
            )
        if self.lambda_depth is not None:
            object.__setattr__(
                self, "lambda_depth", max(1, min(int(self.lambda_depth), 8))
            )

    # ------------------------------------------------------------------
    @classmethod
    def from_env(cls, env: Optional[Mapping[str, str]] = None) -> "SchedConfig":
        """Parse (and validate) the environment into a ``SchedConfig``.

        Raises ``ValueError`` naming the offending variable for malformed
        values *and* for unknown ``REPRO_SCHED_*`` variables — a typoed
        knob must not silently do nothing.
        """
        if env is None:
            env = os.environ
        kw = {}
        unknown = []
        for var, raw in env.items():
            if not var.startswith(SCHED_PREFIX):
                continue
            spec = _ENV_SCHEMA.get(var)
            if spec is None:
                unknown.append(var)
                continue
            field_name, parse = spec
            kw[field_name] = parse(var, raw)
        if unknown:
            known = ", ".join(sorted(_ENV_SCHEMA))
            raise ValueError(
                "unknown scheduling configuration variable(s): "
                f"{', '.join(sorted(unknown))} (known: {known})"
            )
        return cls(**kw)

    def env_items(self) -> Tuple[Tuple[str, str], ...]:
        """The env-var form of every non-default field (for subprocesses)."""
        defaults = SchedConfig()
        out = []
        for f in fields(self):
            v = getattr(self, f.name)
            if v == getattr(defaults, f.name):
                continue
            var = _FIELD_TO_ENV[f.name]
            if isinstance(v, bool):
                s = "1" if v else "0"
            else:
                s = str(v)
            out.append((var, s))
        return tuple(out)


_ENV_SCHEMA = {
    "REPRO_SCHED_BACKEND": ("backend", lambda var, v: v.lower()),
    "REPRO_SCHED_JAX_MIN": ("jax_min", lambda var, v: _parse_int(var, v, lo=1)),
    "REPRO_SCHED_LAMBDA_DEPTH": (
        "lambda_depth", lambda var, v: _parse_int(var, v)),
    "REPRO_SCHED_PALLAS": ("pallas", lambda var, v: v.lower()),
    "REPRO_SCHED_MEM_CAPACITY": (
        "mem_capacity", lambda var, v: _parse_int(var, v, lo=0)),
    "REPRO_SCHED_EVICTION": ("eviction", lambda var, v: v.lower()),
    "REPRO_SCHED_CANCEL_STALE": ("cancel_stale", _parse_flag),
    "REPRO_SCHED_CHURN": ("churn", _parse_rate),
    "REPRO_SCHED_FAULT_MODE": ("fault_mode", lambda var, v: v.lower()),
    "REPRO_SCHED_FAULT_TRACE": ("fault_trace", _parse_trace_path),
    "REPRO_SCHED_NOTICE_S": ("notice_s", _parse_rate),
    "REPRO_SCHED_LINK_FLAKE": ("link_flake", _parse_rate),
    "REPRO_SCHED_RETRY_MAX": (
        "retry_max", lambda var, v: _parse_int(var, v, lo=0)),
    "REPRO_SCHED_BACKOFF_S": ("backoff_s", _parse_rate),
    "REPRO_SCHED_EXACT": ("exact", _parse_flag),
    "REPRO_SCHED_ARRIVAL": ("arrival", lambda var, v: v.lower()),
    "REPRO_SCHED_ADMISSION": ("admission", lambda var, v: v.lower()),
    "REPRO_SCHED_RESCORE": ("rescore", lambda var, v: v.lower()),
    "REPRO_SCHED_ADMIT_DEFER_S": ("admit_defer_s", _parse_rate),
    "REPRO_SCHED_AUDIT": ("audit", _parse_flag),
    "REPRO_SCHED_BATCH": ("batch", lambda var, v: _parse_int(var, v, lo=1)),
}

_FIELD_TO_ENV = {field: var for var, (field, _) in _ENV_SCHEMA.items()}

KNOWN_ENV_VARS: Tuple[str, ...] = tuple(sorted(_ENV_SCHEMA))


# ---------------------------------------------------------------------------
# memoized accessor: one parse per environment state

_CACHE: Optional[Tuple[Tuple[Tuple[str, str], ...], SchedConfig]] = None


def _env_snapshot() -> Tuple[Tuple[str, str], ...]:
    return tuple(
        sorted((k, v) for k, v in os.environ.items() if k.startswith(SCHED_PREFIX))
    )


def current_config() -> SchedConfig:
    """The process-wide ``SchedConfig`` derived from the environment.

    Re-parses only when a relevant environment entry changed (tests
    monkeypatching ``REPRO_*`` see fresh values immediately); otherwise
    returns the memoized instance, so call sites can treat this as cheap.
    """
    global _CACHE
    snap = _env_snapshot()
    if _CACHE is not None and _CACHE[0] == snap:
        return _CACHE[1]
    cfg = SchedConfig.from_env()
    _CACHE = (snap, cfg)
    return cfg


def _reset_config_cache() -> None:
    """Test hook: forget the memoized environment parse."""
    global _CACHE
    _CACHE = None
