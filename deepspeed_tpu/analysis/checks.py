"""Checker passes over :class:`~deepspeed_tpu.analysis.hlo.ProgramFacts`.

Each checker returns a :class:`CheckResult` — ``passed`` plus typed
:class:`Violation` records and a JSON-able ``facts`` summary — so the same
pass serves pytest assertions and ad-hoc debugging.  Checkers never raise
on a failed invariant; they raise only on caller errors (e.g. an argument
name absent from the arg table).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from ..comm import qcomm
from ..comm.budget import PlannedCollective, plan_bytes
from .hlo import ProgramFacts

_NARROW = ("s8", "u8", "f8e4m3fn", "f8e5m2", "f8e4m3", "s4", "u4")


@dataclass(frozen=True)
class Violation:
    check: str
    message: str
    subject: str = ""  # line / path / param the violation anchors to

    def __str__(self) -> str:
        s = f" [{self.subject}]" if self.subject else ""
        return f"{self.check}: {self.message}{s}"


@dataclass
class CheckResult:
    check: str
    passed: bool
    violations: List[Violation] = field(default_factory=list)
    facts: Dict[str, object] = field(default_factory=dict)

    def to_json(self) -> Dict[str, object]:
        return {
            "check": self.check,
            "passed": self.passed,
            "violations": [str(v) for v in self.violations],
            "facts": self.facts,
        }


def _result(check: str, violations: List[Violation],
            facts: Dict[str, object]) -> CheckResult:
    return CheckResult(check=check, passed=not violations,
                       violations=violations, facts=facts)


# ---------------------------------------------------------------------------
# donation_audit
# ---------------------------------------------------------------------------
def check_donation(facts: ProgramFacts,
                   required: Dict[str, Sequence[int]]) -> CheckResult:
    """Every listed XLA parameter must be input-output aliased in the
    compiled module.  ``required`` maps an argument label to the parameter
    numbers its leaves occupy (``analysis.audit.donation_param_numbers``
    derives them from the example args, accounting for static and
    pruned-unused arguments).  A lost ``donate_argnums`` shows up as a
    fully-unaliased KV pool — a silent full copy of the largest buffer in
    the program every tick."""
    donated = facts.donated_param_numbers
    violations = []
    per_arg = {}
    for label, params in required.items():
        missing = [i for i in params if i not in donated]
        per_arg[label] = {"params": list(params),
                          "aliased": len(params) - len(missing)}
        if params and missing:
            violations.append(Violation(
                "donation_audit",
                f"{len(missing)}/{len(params)} leaves of donated arg "
                f"{label!r} have no input-output alias — the jit copies "
                "them every dispatch (lost donate_argnums?)",
                subject=f"params {missing[:8]}",
            ))
    return _result("donation_audit", violations, {
        "aliased_params": len(donated), "args": per_arg,
    })


# ---------------------------------------------------------------------------
# collective_budget
# ---------------------------------------------------------------------------
def check_collective_budget(
    facts: ProgramFacts,
    plan: List[PlannedCollective],
    *,
    transport_sources: Sequence[str] = ("qcomm.py",),
    tol: float = 0.05,
    total_tol: float = 0.25,
) -> CheckResult:
    """Enumerated wire bytes of the compiled program vs the analytic plan
    (``comm/budget``) — the accounting the telemetry ``comm/*`` counters
    and the roofline's wire term report.

    Two comparisons:

    - **transport** (tight, ``tol``): collectives whose source metadata
      points into the qcomm transport layer vs the plan's ``row_psum``
      group.  These are the bytes ``comm/bytes_on_wire`` claims; a drift
      here is a mis-accounting bug.  (GSPMD's region-boundary resharding
      gathers attribute to *quantizer.py* lines and are budgeted as
      overhead, not transport — which is why the source filter is
      qcomm-only.)
    - **total** (loose, ``total_tol``): every collective vs the full plan
      (transport + GSPMD overhead).  GSPMD has freedom in how it lowers
      the sharded embedding/head (gather vs reduce shapes, padding), so
      the bound is slack — it exists to catch a whole *category* of
      unaccounted wire (e.g. an accidental full weight gather), not
      byte-exactness.
    """
    emitted_transport = facts.wire_bytes_total(source_file=transport_sources)
    emitted_total = facts.wire_bytes_total()
    expected_transport = plan_bytes(plan, overhead=False)
    expected_total = plan_bytes(plan)
    violations = []

    def _rel(emitted: int, expected: int) -> float:
        if expected == 0:
            return 0.0 if emitted == 0 else float("inf")
        return abs(emitted - expected) / expected

    r_t = _rel(emitted_transport, expected_transport)
    if r_t > tol:
        violations.append(Violation(
            "collective_budget",
            f"transport wire bytes drift {r_t:.1%} from the analytic plan "
            f"(emitted {emitted_transport}, accounted {expected_transport}) "
            "— comm/bytes_on_wire is lying about this dispatch",
        ))
    r_a = _rel(emitted_total, expected_total)
    if r_a > total_tol:
        violations.append(Violation(
            "collective_budget",
            f"total wire bytes drift {r_a:.1%} from plan (emitted "
            f"{emitted_total}, planned {expected_total}) — unaccounted "
            "collectives on the wire",
        ))
    by_kind: Dict[str, int] = {}
    for c in facts.collectives:
        if c.phase != "done":
            by_kind[c.kind] = by_kind.get(c.kind, 0) + 1
    return _result("collective_budget", violations, {
        "emitted_transport_bytes": emitted_transport,
        "expected_transport_bytes": expected_transport,
        "emitted_total_bytes": emitted_total,
        "expected_total_bytes": expected_total,
        "collectives_by_kind": by_kind,
        "plan": [
            {"op": p.op, "n_elements": p.n_elements, "fmt": p.fmt,
             "world": p.world, "count": p.count, "label": p.label,
             "bytes": p.bytes_on_wire, "overhead": p.overhead}
            for p in plan
        ],
    })


# ---------------------------------------------------------------------------
# payload dtype audit
# ---------------------------------------------------------------------------
def check_payload_dtypes(
    facts: ProgramFacts,
    fmt: str,
    *,
    sources: Sequence[str] = ("qcomm.py",),
    chunk: int = qcomm.DEFAULT_CHUNK,
) -> CheckResult:
    """Exact dtype audit of the quantized transport: on a path claiming
    ``fmt`` in ('int8', 'fp8'), every qcomm-sourced wire payload must carry
    a narrow dtype — the only legal fp32 on those wires is the per-chunk
    scale vector (``<= payload_elements / chunk``, with 2x slack for
    padding).  A full-width fp32 payload hiding on an int8 path defeats
    the entire wire saving while the telemetry still reports narrow bytes.
    ``fmt='none'`` passes trivially (exact transport ships wide on
    purpose)."""
    if fmt in (None, "none"):
        return _result("dtype_audit", [], {"fmt": "none", "checked": 0})
    qc = [c for c in facts.collectives
          if c.source_file in sources and c.phase != "done"
          and c.kind in ("all-reduce", "all-gather", "reduce-scatter",
                         "all-to-all")]
    narrow = [c for c in qc if c.dtype in _NARROW]
    wide = [c for c in qc if c.dtype not in _NARROW]
    violations = []
    if not narrow:
        violations.append(Violation(
            "dtype_audit",
            f"path claims fmt={fmt!r} but no narrow-dtype collective was "
            "emitted from the transport layer",
        ))
    else:
        n_el = max(1, *(_elems(c.shape) for c in narrow))
        scale_budget = 2 * max(1, n_el // chunk)
        for c in wide:
            if _elems(c.shape) > scale_budget:
                violations.append(Violation(
                    "dtype_audit",
                    f"{c.dtype} {c.kind} of shape {list(c.shape)} on a "
                    f"path claiming {fmt} (scale budget is "
                    f"{scale_budget} elements)",
                    subject=c.line[:140],
                ))
    return _result("dtype_audit", violations, {
        "fmt": fmt, "checked": len(qc), "narrow": len(narrow),
        "wide": len(wide),
    })


def _elems(shape: Tuple[int, ...]) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


# ---------------------------------------------------------------------------
# overlap audit
# ---------------------------------------------------------------------------
def check_overlap(
    facts: ProgramFacts,
    *,
    kinds: Optional[Sequence[str]] = None,
    min_pairs: int = 1,
    min_compute: int = 1,
    dtype: Optional[str] = None,
    loose: bool = False,
) -> CheckResult:
    """At least ``min_pairs`` async start/done pairs (of ``kinds``, of
    payload ``dtype``) must have ``min_compute`` compute ops scheduled
    inside the window or span a scan back-edge — the structured version of
    the scheduled-HLO overlap proofs."""
    pairs = facts.overlapped(kinds=kinds, dtype=dtype,
                             min_compute=min_compute, loose=loose)
    violations = []
    if len(pairs) < min_pairs:
        violations.append(Violation(
            "overlap_audit",
            f"only {len(pairs)} async pair(s) with compute scheduled "
            f"between start and done (need {min_pairs}) — the transport is "
            "on the critical path",
        ))
    return _result("overlap_audit", violations, {
        "pairs": len(pairs),
        "total_async_pairs": len(facts.async_pairs),
        "backedge_pairs": sum(1 for p in pairs if p.spans_backedge),
    })


# ---------------------------------------------------------------------------
# sharding lint (param placement, not HLO)
# ---------------------------------------------------------------------------
def check_tp_param_sharding(params, shardings, cfg, tp: int,
                            model_axis: str = "model") -> CheckResult:
    """PR 7's TP placement rules, proven against the engine's actual
    parameter shardings:

    - attention kernels shard at HEAD granularity only — wq sharded
      requires ``num_heads % tp == 0``; wk/wv sharded require
      ``num_kv_heads % tp == 0`` (GQA with hkv < tp must replicate them);
    - quantizer scales (``.../s``) follow their kernel: column-parallel
      kernels shard scales on the same out dim, row-parallel kernels
      (wo / w_down) keep scales replicated;
    - row-parallel kernels shard in-features (dim -2), never out-features.
    """
    import jax

    from ..runtime.zero import path_str

    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_s = jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: hasattr(x, "spec"))
    if len(flat_p) != len(flat_s):
        raise ValueError("params/shardings trees disagree")

    def spec_of(sh):
        return tuple(getattr(sh, "spec", sh) or ())

    def axis_dims(spec, ndim):
        """dims (negative-indexed) carrying the model axis."""
        out = []
        spec = tuple(spec) + (None,) * (ndim - len(spec))
        for i, entry in enumerate(spec):
            names = entry if isinstance(entry, tuple) else (entry,)
            if model_axis in [n for n in names if n]:
                out.append(i - ndim)
        return out

    row_suffixes = ("attn/wo", "mlp/w_down")
    col_suffixes = ("attn/wq", "attn/wk", "attn/wv", "mlp/w_up",
                    "mlp/w_gate", "lm_head/kernel")
    violations = []
    checked = 0
    kernel_last_axis: Dict[str, bool] = {}  # dir path -> out-dim sharded?
    for (kp, leaf), sh in zip(flat_p, flat_s):
        path = path_str(kp)
        ndim = getattr(leaf, "ndim", 0)
        dims = axis_dims(spec_of(sh), ndim)
        is_scale = path.endswith("/s")
        base = path[:-2] if is_scale else path
        if not is_scale and ndim >= 2:
            if any(base.endswith(s) or base.endswith(s + "/q")
                   or base.endswith(s + "/packed") for s in row_suffixes):
                kernel_last_axis[base.rsplit("/", 1)[0]] = False
                if -1 in dims:
                    violations.append(Violation(
                        "sharding_lint",
                        "row-parallel kernel sharded on OUT features — "
                        "breaks the single-psum row contract",
                        subject=path,
                    ))
                checked += 1
            elif any(base.endswith(s) or base.endswith(s + "/q")
                     or base.endswith(s + "/packed") for s in col_suffixes):
                kernel_last_axis[base.rsplit("/", 1)[0]] = -1 in dims
                checked += 1
                if -1 in dims:
                    hq, hkv = cfg.num_heads, cfg.num_kv_heads
                    if (("attn/wq" in base and hq % tp)
                            or (("attn/wk" in base or "attn/wv" in base)
                                and hkv % tp)):
                        violations.append(Violation(
                            "sharding_lint",
                            "SUB-HEAD attention sharding: out-features "
                            "sharded though the head count does not divide "
                            f"tp={tp} (hq={hq}, hkv={hkv}) — rope pairs and "
                            "per-head attention consumers break",
                            subject=path,
                        ))
                if -2 in dims:
                    violations.append(Violation(
                        "sharding_lint",
                        "column-parallel kernel sharded on IN features",
                        subject=path,
                    ))
    # second pass: scales follow their kernel
    for (kp, leaf), sh in zip(flat_p, flat_s):
        path = path_str(kp)
        if not path.endswith("/s"):
            continue
        parent = path.rsplit("/", 1)[0]
        if parent not in kernel_last_axis:
            continue
        checked += 1
        dims = axis_dims(spec_of(sh), getattr(leaf, "ndim", 0))
        out_sharded = -1 in dims
        if kernel_last_axis[parent] and not out_sharded:
            violations.append(Violation(
                "sharding_lint",
                "column-parallel kernel's per-out-channel scales are NOT "
                "sharded with the out dim — every shard pulls the full "
                "scale vector",
                subject=path,
            ))
        if not kernel_last_axis[parent] and out_sharded:
            violations.append(Violation(
                "sharding_lint",
                "row-parallel kernel's scales sharded — the post-psum "
                "epilogue needs the full per-out-channel vector replicated",
                subject=path,
            ))
        if [d for d in dims if d != -1]:
            violations.append(Violation(
                "sharding_lint", "scale sharded on a non-out dim",
                subject=path,
            ))
    return _result("sharding_lint", violations,
                   {"checked_leaves": checked, "tp": tp})


# ---------------------------------------------------------------------------
# recompile sentinel
# ---------------------------------------------------------------------------
class RecompileSentinel:
    """Compilation-cache miss counter across a steady-state window.

    Snapshots the tracing-cache size of each tracked ``jax.jit`` callable;
    :meth:`misses` reports per-function growth since the snapshot.  A
    steady-state serve window must report zero — a recompile per tick (a
    drifting static arg, a weak-type flip, a shape leak) is the
    latency-cliff class of bug this guards.

    Usable as a context manager::

        with RecompileSentinel.for_engine(eng) as sentinel:
            serve_window()
        assert sentinel.total_misses() == 0, sentinel.misses()
    """

    ENGINE_JITS = ("_decode_jit", "_decode_burst_jit", "_packed_prefill_jit",
                   "_packed_prefill_ctx_jit", "_spec_jit", "_cow_jit")

    def __init__(self, **jits):
        self._jits = {name: fn for name, fn in jits.items()
                      if hasattr(fn, "_cache_size")}
        self._base: Dict[str, int] = {}
        self.snapshot()

    @classmethod
    def for_engine(cls, engine) -> "RecompileSentinel":
        jits = {}
        for name in cls.ENGINE_JITS:
            fn = getattr(engine, name, None)
            if fn is not None:
                jits[name.lstrip("_")] = fn
        return cls(**jits)

    def snapshot(self) -> None:
        self._base = {n: f._cache_size() for n, f in self._jits.items()}

    def misses(self) -> Dict[str, int]:
        return {n: f._cache_size() - self._base[n]
                for n, f in self._jits.items()
                if f._cache_size() != self._base[n]}

    def total_misses(self) -> int:
        return sum(self.misses().values())

    def to_result(self) -> CheckResult:
        m = self.misses()
        violations = [Violation(
            "recompile_sentinel",
            f"{n} recompiled {k} time(s) inside the steady-state window",
        ) for n, k in m.items()]
        return _result("recompile_sentinel", violations, {
            "tracked": sorted(self._jits), "misses": m,
        })

    def __enter__(self) -> "RecompileSentinel":
        self.snapshot()
        return self

    def __exit__(self, *exc) -> None:
        return None
