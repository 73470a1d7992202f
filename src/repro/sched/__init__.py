"""``repro.sched`` — the first-class scheduling policy API.

The paper's central mechanism — placement decided from per-(task ×
resource) completion-time and data-transfer scores — is the public
extension surface here:

  * :class:`Policy` / :class:`ScoreMatrixPolicy` — the protocol and the
    generic score-matrix placement driver (``docs/writing_a_policy.md``
    has a worked example);
  * :func:`register` / :func:`resolve` — the policy registry.
    ``resolve("dada?alpha=0.5&use_cp=1")`` replaces the old
    ``make_strategy`` if/elif ladder (which survives as a deprecated
    shim with bit-identical results);
  * :class:`SchedConfig` — every ``REPRO_SCHED_*`` knob parsed and
    validated in one place (:meth:`SchedConfig.from_env`),
    then threaded explicitly through the scheduling stack;
  * :func:`assign_from_scores` — the pure scores → assignment kernel,
    shared with ``repro.dist.sched_bridge``'s expert placement.

Built-in policies: ``heft``, ``dada``, ``dual``, ``ws`` (bit-for-bit equal
to ``repro.core._reference``), plus ``random``, ``locality``, and the
serving-tenant policies ``priority`` / ``wfq`` (weighted-fair queueing;
see ``repro.runtime.load``).
"""
from .config import KNOWN_ENV_VARS, SchedConfig, current_config
from .policy import Policy, ScoreMatrixPolicy, assign_from_scores
from .registry import (
    get_factory,
    parse_spec,
    register,
    registered,
    resolve,
    unregister,
)
from .policies import LocalityPolicy, PriorityPolicy, RandomPolicy, WFQPolicy

__all__ = [
    "KNOWN_ENV_VARS",
    "LocalityPolicy",
    "Policy",
    "PriorityPolicy",
    "RandomPolicy",
    "WFQPolicy",
    "SchedConfig",
    "ScoreMatrixPolicy",
    "assign_from_scores",
    "current_config",
    "get_factory",
    "parse_spec",
    "register",
    "registered",
    "resolve",
    "unregister",
]
