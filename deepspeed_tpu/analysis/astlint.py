"""Source-level lint: AST passes over ``deepspeed_tpu``.

Four rules, each guarding an invariant the runtime cannot check for
itself:

- **host-sync-in-hot-path** — ``jax.block_until_ready`` / ``device_get`` /
  ``.item()`` / ``float(<expr>)`` inside the serving tick/step hot paths
  force a device round trip per call; one stray sync stretched decode
  ticks from ~14 ms to 20-70 ms historically.  Scoped to the functions in
  :data:`HOT_PATHS` (``"*"`` = every function in the file; traced model
  code can never legally host-sync).
- **process-global-mutable-state** — a ``global`` rebind is how the
  ``set_fused_serving`` class of bug enters (one engine's flip silently
  reconfigures every later engine in the process).  Existing globals are
  grandfathered in :data:`GLOBAL_BASELINE`; the set may only shrink.
- **raw-lax-collective** — ``lax.psum`` & friends outside ``comm/`` bypass
  the qcomm transport layer, so the ``fmt='none'`` A/B lever stops being
  universal.  Pre-qcomm training-side modules are grandfathered in
  :data:`LAX_COLLECTIVE_BASELINE`; serving-side code must route through
  ``comm.qcomm``.
- **controller-import** — the online-adaptation controller
  (``autotuning/controller.py``) runs on its own thread and MAY host-sync
  (it is deliberately NOT in :data:`HOT_PATHS`); importing it from a
  tick-path module (any file listed in HOT_PATHS) inverts that layering —
  the serve loop must stay runnable with the controller package absent,
  and coupling would invite tick code calling into a host-syncing,
  lock-taking component.  The controller reaches the engine through the
  scheduler's ``apply_knobs`` surface, never the other way around.

A trailing ``# lint: allow(<rule>)`` comment on the offending line
suppresses that line (for the rare measured-and-documented exception).
The tier-1 gate (``tests/test_analysis.py``) runs :func:`lint_package`
over the repo and fails on any violation.
"""
from __future__ import annotations

import ast
import fnmatch
import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Set, Tuple

PKG_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# functions whose bodies may never host-sync (file -> names, "*" = all).
# Keys are repo-relative paths under deepspeed_tpu/.
HOT_PATHS: Dict[str, Set[str]] = {
    # engine tick/step loop: one deliberate np.asarray fetch per tick is the
    # design; any OTHER sync primitive here is a regression
    "inference/engine_v2.py": {
        "_run_packed_prefill", "prefill_entries", "_decode_tick",
        "_spec_tick", "step", "step_n", "_tables_device",
        "_sampling_device", "_account_comm", "_set_block_table",
        # megastep decode (PR 16): the burst core's ONE np.asarray fetch
        # is the whole design — any other sync inside it would re-pay the
        # host round trip the burst exists to amortize
        "_decode_burst",
        # one ahead (PR 43): the split bodies under ``_run_packed_prefill`` /
        # ``_decode_tick``.  The dispatch halves may not sync at all when
        # split; the ONE designed fetch of a program is ``_fetched``'s
        "pack_dispatch", "pack_collect", "decode_dispatch", "decode_collect",
        "prefill_dispatch", "_packs_of", "_fetched",
        # ... and what a tick's step shares with a pack that carries it (PR 54)
        "_fill_step", "_emit_step",
        # the KV-handoff seam (PR 12): np.asarray is the designed host
        # copy; any OTHER sync primitive mid-migration stalls the tick
        "extract_kv_blocks", "inject_kv_blocks",
    },
    # the serve loop's per-tick driver, plus the whole intake surface: it
    # now runs under the scheduler's intake lock (PR 13), so a host sync
    # there stalls every submitter AND the tick phases behind the lock —
    # the blocking-under-lock class racelint flags, caught at the source
    "inference/scheduler.py": {
        "tick", "try_submit", "_try_submit_locked", "adopt_prefilled",
        "_adopt_prefilled_locked", "cancel", "detach", "_release",
        "_release_locked", "_admit_phase", "_try_admit_locked",
        "_expire_phase", "_preempt", "retry_after_ms", "pop_result",
        # the megastep loop (PR 16): planning and dispatching a fused
        # decode burst must never add a host sync — the burst's single
        # fetch happens inside the engine's _decode_burst, nowhere else
        "_plan_megastep", "_remaining_emit", "_decode_phase",
        "_dispatch_decode",
        # one ahead (PR 43): planning and enqueueing the next execution is
        # what the device's time is hidden behind; the wait is the engine's
        "_tick_ahead", "_enqueue", "_collect", "_drain", "_drain_outside",
        "_back_to_back", "_plan_prefill", "_book_first", "_book_runs",
        "_due_locked", "_ends_enqueued", "settle",
    },
    # the router front end's control loop + its load-signal reads: router
    # instrumentation must never add a device round trip to a worker's tick
    # (each engine already owns its one designed np.asarray fetch), and the
    # KV-handoff codec runs host-side numpy by design
    "serving/router.py": {"tick", "try_submit", "_route", "_route_to_worker",
                          "_candidates", "_maybe_migrate", "_kill_worker",
                          "_finish"},
    "serving/handoff.py": {"extract_request", "inject_request"},
    "serving/pool.py": {"load", "queue_depth", "running", "headroom_blocks",
                        "shedding"},
    # the socket wire: frame packing and the KV-handoff codec are pure host
    # byte work — a device round trip here would ride EVERY cross-process
    # message (racelint separately forbids socket I/O under any lock)
    "serving/transport.py": {"pack_frame", "encode_handoff",
                             "decode_handoff", "send_frame", "recv_frame",
                             # the step_burst RPC path (PR 16): the burst
                             # reply is pure host bookkeeping over the
                             # scheduler's already-fetched state
                             "_op_step_burst", "_request_views"},
    "serving/remote.py": {"begin_tick", "finish_tick", "request_view"},
    # traced model code: a host sync here is a trace-time bug by definition
    "inference/model_runner.py": {"*"},
    "inference/sampling.py": {"*"},
    "inference/paged.py": {"*"},
    # the packed-ctx Pallas kernel's dispatch + wrapper (ISSUE 19): rides
    # every chunked prefill / prefix-hit / spec-verify forward, so a host
    # sync here stalls the hottest prefill path in the engine
    "ops/pallas/ctx_attention.py": {"*"},
    # seq-striped allocation bookkeeping (ISSUE 18): these run under the
    # scheduler's intake lock on every admit/grow/evict — pure host list
    # arithmetic; a device sync or raw collective here would stall every
    # submitter behind the lock
    "inference/ragged.py": {"allocate", "can_allocate", "_evict_one",
                            "_push_free", "stripe_of", "free", "invalidate",
                            "ensure_capacity", "ensure_writable"},
    # the fleet collector's pull loop (ISSUE 20): it runs beside the router
    # thread and must stay pure host bookkeeping — a device sync inside a
    # pull would be charged to whichever worker the collector happened to
    # be reading, and the fold must never touch anything but its own lock
    "telemetry/fleet.py": {"pull_once", "_run", "ingest"},
}

# grandfathered `global` rebinds: (file, name).  Shrink-only.
GLOBAL_BASELINE: Set[Tuple[str, str]] = {
    ("accelerator/tpu_accelerator.py", "_accelerator"),
    ("comm/comm.py", "_comms_logger"),
    ("comm/comm.py", "_initialized"),
    ("inference/faults.py", "_GLOBAL"),
    ("ops/pallas/flash_kernel.py", "_INTERPRET"),
    ("ops/pallas/flash_kernel.py", "_BLOCK_Q"),
    ("ops/pallas/flash_kernel.py", "_BLOCK_K"),
    ("ops/pallas/flash_kernel.py", "_BLOCK_Q_BWD"),
    ("ops/pallas/flash_kernel.py", "_BLOCK_K_BWD"),
    ("ops/pallas/ctx_attention.py", "_INTERPRET"),
    ("ops/pallas/paged_attention.py", "_INTERPRET"),
    ("ops/pallas/quant_kernel.py", "_INTERPRET"),
    ("ops/pallas/quant_matmul.py", "_INTERPRET"),
    ("parallel/sharding.py", "_CURRENT_MESH"),
    ("runtime/engine.py", "_EXIT_HOOK_REGISTERED"),
}

# raw lax collectives allowed per file.  comm/* is the implementation
# layer; the training-side modules predate qcomm and keep their exact lax
# calls (ZeRO/pipeline/sequence graphs are passthrough-only by design).
# Serving code (inference/, ops/quantizer) must route through comm.qcomm.
LAX_COLLECTIVE_BASELINE: Set[str] = {
    "comm/comm.py",
    "comm/compressed.py",
    "comm/qcomm.py",
    "models/transformer.py",
    "moe/layer.py",
    "runtime/onebit.py",
    "runtime/pipeline/pipelined.py",
    "runtime/zeropp.py",
    "sequence/cross_entropy.py",
    "sequence/layer.py",
    "sequence/ring.py",
}

_LAX_COLLECTIVES = {
    "psum", "pmean", "pmax", "pmin", "all_gather", "all_to_all",
    "psum_scatter", "ppermute", "pshuffle", "all_gather_invariant",
}
_HOST_SYNC_ATTRS = {"block_until_ready", "item"}
_HOST_SYNC_FUNCS = {"device_get"}

# the adaptation controller's module path + its re-exported entry points:
# either one imported from a HOT_PATHS module is a layering inversion
_CONTROLLER_MODULE = "autotuning.controller"
_CONTROLLER_NAMES = {"OnlineController", "attach_controller"}

# the fleet observability plane gets the same layering rule: it OBSERVES
# the data plane (its collector thread pulls workers over sockets), so no
# tick-path module may import it — attachment is duck-typed
# (Router.attach_fleet), wired by the launcher
_FLEET_MODULE = "telemetry.fleet"
_FLEET_NAMES = {"FleetRegistry", "FleetCollector", "SloMonitor",
                "attach_fleet_collector", "fleet_chrome_trace"}


@dataclass(frozen=True)
class LintViolation:
    rule: str  # 'host-sync' | 'global-state' | 'lax-collective'
    path: str  # repo-relative file
    line: int
    message: str

    def __str__(self) -> str:  # pytest-friendly
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


def _allowed(source_lines: Sequence[str], lineno: int, rule: str) -> bool:
    if 0 < lineno <= len(source_lines):
        return f"lint: allow({rule})" in source_lines[lineno - 1]
    return False


class _Visitor(ast.NodeVisitor):
    def __init__(self, relpath: str, source_lines: Sequence[str]):
        self.relpath = relpath
        self.lines = source_lines
        self.hot_names = HOT_PATHS.get(relpath)
        self.func_stack: List[str] = []
        self.out: List[LintViolation] = []

    # -- helpers ----------------------------------------------------------
    def _emit(self, rule: str, node: ast.AST, msg: str) -> None:
        if not _allowed(self.lines, node.lineno, rule):
            self.out.append(LintViolation(rule, self.relpath, node.lineno, msg))

    def _in_hot_path(self) -> bool:
        if self.hot_names is None or not self.func_stack:
            return False
        return "*" in self.hot_names or bool(
            set(self.func_stack) & self.hot_names
        )

    # -- rule: global mutable state ---------------------------------------
    def visit_Global(self, node: ast.Global) -> None:
        for name in node.names:
            if (self.relpath, name) not in GLOBAL_BASELINE:
                self._emit(
                    "global-state", node,
                    f"new process-global mutable state 'global {name}' — "
                    "one call site reconfigures every engine in the process "
                    "(the set_fused_serving bug class); carry the state on "
                    "the engine/context object instead",
                )
        self.generic_visit(node)

    # -- rule: raw lax collectives ----------------------------------------
    def visit_Attribute(self, node: ast.Attribute) -> None:
        is_lax = (
            (isinstance(node.value, ast.Name) and node.value.id == "lax")
            or (isinstance(node.value, ast.Attribute)
                and node.value.attr == "lax")
        )
        if node.attr in _LAX_COLLECTIVES and is_lax:
            if self.relpath not in LAX_COLLECTIVE_BASELINE:
                self._emit(
                    "lax-collective", node,
                    f"raw lax.{node.attr} outside comm/ — route through "
                    "comm.qcomm so the fmt='none' A/B lever stays universal",
                )
        self.generic_visit(node)

    # -- rule: controller import from a tick path ---------------------------
    def _controller_import(self, node: ast.AST, what: str) -> None:
        self._emit(
            "controller-import", node,
            f"tick-path module imports the adaptation controller ({what}) "
            "— the controller thread may host-sync and is excluded from "
            "HOT_PATHS precisely because nothing on the tick path may call "
            "it; retunes flow controller -> scheduler.apply_knobs, never "
            "the reverse",
        )

    def _fleet_import(self, node: ast.AST, what: str) -> None:
        self._emit(
            "fleet-import", node,
            f"tick-path module imports the fleet observability plane "
            f"({what}) — the collector thread does socket I/O and is "
            "excluded from HOT_PATHS precisely because nothing on the "
            "tick path may call it; attachment is duck-typed "
            "(Router.attach_fleet), wired by the launcher",
        )

    def visit_Import(self, node: ast.Import) -> None:
        if self.hot_names is not None:
            for alias in node.names:
                if _CONTROLLER_MODULE in alias.name:
                    self._controller_import(node, alias.name)
                if _FLEET_MODULE in alias.name \
                        and self.relpath != "telemetry/fleet.py":
                    self._fleet_import(node, alias.name)
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if self.hot_names is not None:
            mod = node.module or ""
            if _CONTROLLER_MODULE in mod:
                self._controller_import(node, mod)
            elif mod == "autotuning" or mod.endswith(".autotuning") \
                    or (node.level > 0 and mod == "autotuning"):
                hits = [a.name for a in node.names
                        if a.name in _CONTROLLER_NAMES or a.name == "controller"]
                if hits:
                    self._controller_import(node, f"{mod}.{hits[0]}")
            if self.relpath != "telemetry/fleet.py":
                if _FLEET_MODULE in mod:
                    self._fleet_import(node, mod)
                elif mod == "telemetry" or mod.endswith(".telemetry") \
                        or (node.level > 0 and mod == "telemetry"):
                    hits = [a.name for a in node.names
                            if a.name in _FLEET_NAMES or a.name == "fleet"]
                    if hits:
                        self._fleet_import(node, f"{mod}.{hits[0]}")
        self.generic_visit(node)

    # -- rule: host sync in hot paths --------------------------------------
    def visit_Call(self, node: ast.Call) -> None:
        if self._in_hot_path():
            fn = node.func
            if isinstance(fn, ast.Attribute):
                if fn.attr in _HOST_SYNC_ATTRS or fn.attr in _HOST_SYNC_FUNCS:
                    self._emit(
                        "host-sync", node,
                        f".{fn.attr}() in hot path "
                        f"{'/'.join(self.func_stack)} — forces a device "
                        "round trip per call; fetch once per tick via the "
                        "designed np.asarray sync point",
                    )
            elif isinstance(fn, ast.Name):
                if fn.id in _HOST_SYNC_FUNCS:
                    self._emit(
                        "host-sync", node,
                        f"{fn.id}() in hot path — device round trip",
                    )
                elif fn.id == "float" and node.args and isinstance(
                        node.args[0], (ast.Call, ast.Subscript, ast.Attribute)):
                    # float(expr) on a computed value is the classic hidden
                    # blocking fetch; float(name)/float(literal) stay legal
                    self._emit(
                        "host-sync", node,
                        "float(<computed expr>) in hot path — if the operand "
                        "is a device array this blocks on it; hoist the "
                        "fetch to the tick's single sync point",
                    )
        self.generic_visit(node)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self.func_stack.append(node.name)
        self.generic_visit(node)
        self.func_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]


def lint_source(source: str, relpath: str) -> List[LintViolation]:
    """Lint one module's source as repo-relative ``relpath`` (the key space
    of the HOT_PATHS / baseline tables) — the seeded-regression seam."""
    tree = ast.parse(source)
    v = _Visitor(relpath, source.splitlines())
    v.visit(tree)
    return v.out


def lint_package(root: Optional[str] = None,
                 exclude: Sequence[str] = ("analysis/*",),
                 ) -> List[LintViolation]:
    """Lint every ``.py`` under ``deepspeed_tpu/`` (or ``root``).  The
    analysis package itself is excluded by default (its lint tables quote
    the forbidden names)."""
    root = root or PKG_ROOT
    out: List[LintViolation] = []
    for dirpath, _dirnames, filenames in os.walk(root):
        if "__pycache__" in dirpath:
            continue
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            if any(fnmatch.fnmatch(rel, pat) for pat in exclude):
                continue
            with open(path, encoding="utf-8") as fh:
                src = fh.read()
            out.extend(lint_source(src, rel))
    return out
