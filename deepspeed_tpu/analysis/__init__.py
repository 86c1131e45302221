"""Graft Auditor — static analysis over the stack's compiled programs.

Two halves (README "Static analysis & program audit"):

- **Compiled-program auditor** (:mod:`hlo`, :mod:`checks`, :mod:`audit`):
  a structured parser over scheduled HLO / StableHLO text producing typed
  :class:`~deepspeed_tpu.analysis.hlo.Collective` / ``Donation`` /
  ``AsyncPair`` records per jit, plus checker passes that prove the
  invariants the stack claims — collective wire-byte budgets against the
  ``comm/budget`` analytic plan, input-output aliasing (donation) of the
  hot jits' KV/param buffers, TP sharding rules (head granularity, scale
  placement), async start/done overlap, and a compilation-cache recompile
  sentinel.  The former scheduled-HLO regex tests ride on these records.
- **Source-level lint** (:mod:`astlint`): AST passes over ``deepspeed_tpu``
  forbidding host syncs in the tick/step hot paths, new process-global
  mutable state, and raw ``lax`` collectives outside ``comm/``.

Graft Race (README "Concurrency model & race analysis") extends the same
prove-don't-regex stance to the HOST-side concurrency seam:

- **Lock-discipline lint** (:mod:`racelint`): infers which locks guard
  which attributes from the code's own ``with self._lock:`` patterns, then
  flags unguarded shared-state writes, lock-order cycles, blocking calls
  under a lock, and engine/jit access from non-owner threads.
- **Deterministic interleaving harness** (:mod:`schedviz`): a seeded
  cooperative scheduler (CHESS-style bounded preemption) that replays the
  hot concurrent serving scenarios — namespace claim vs snapshot,
  submit/tick/cancel, shed vs watchdog, worker-kill vs route — as pure
  functions of their seed.

Entry points: the pytest gates in ``tests/test_analysis.py`` /
``tests/test_racelint.py`` (tier-1).
"""
from .astlint import LintViolation, lint_package, lint_source
from .racelint import (
    RaceViolation,
    lint_race_package,
    lint_race_source,
    stale_race_baseline,
    unbaselined,
)
from .schedviz import Schedule, checkpoint, explore
from .audit import audit_serve_engine, audit_train_step, serve_jit_specs
from .checks import (
    CheckResult,
    RecompileSentinel,
    Violation,
    check_collective_budget,
    check_donation,
    check_overlap,
    check_payload_dtypes,
    check_tp_param_sharding,
)
from .hlo import (
    AsyncPair,
    Collective,
    Donation,
    ProgramFacts,
    parse_scheduled_hlo,
    program_facts,
    stablehlo_collectives,
)

__all__ = [
    "AsyncPair",
    "audit_serve_engine",
    "audit_train_step",
    "serve_jit_specs",
    "CheckResult",
    "Collective",
    "Donation",
    "LintViolation",
    "ProgramFacts",
    "RecompileSentinel",
    "Violation",
    "check_collective_budget",
    "check_donation",
    "check_overlap",
    "check_payload_dtypes",
    "check_tp_param_sharding",
    "RaceViolation",
    "Schedule",
    "checkpoint",
    "explore",
    "lint_package",
    "lint_race_package",
    "lint_race_source",
    "lint_source",
    "parse_scheduled_hlo",
    "program_facts",
    "stale_race_baseline",
    "stablehlo_collectives",
    "unbaselined",
]
