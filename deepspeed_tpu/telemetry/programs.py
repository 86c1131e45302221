"""What the compiled programs say about themselves, asked for lazily.

``jax.named_scope`` names (``attn``, ``mlp``, ``loss``, ``optimizer``, ...)
and the Pallas kernels' ``name=`` end up in each HLO instruction's
``metadata={op_name=...}``, but a profiler trace's ``XLA Ops`` event carries
only the instruction's text.  The training engine therefore registers its
jitted step through :func:`track` and notes each call's arguments: where the
call compiled, the argument SHAPES are kept (no arrays are held; a call costs
one ``_cache_size()`` read), in a process-wide weak set, whether or not
telemetry is enabled.  (The serving programs are not tracked: nothing reads
their scopes yet, and a name nothing reads is not written.)  Nothing here
lowers, compiles or reads HLO text until :func:`program_scopes` or
:func:`collective_bytes_per_step` is called, which a measuring loop does
after its window, never inside it: ``lower(*shapes).compile()`` then hits
JAX's in-memory caches, because the shapes carry what the executed arguments
had (dtype, weak type, and a sharding exactly where the argument was
committed to one).

A traced program may also say what it COUNTED: a model notes a step's counts
(``count_in_step``: device scalars, summed by name: (token, expert) pairs
routed, the keys a window let through) and facts (``note_step_fact``: Python
numbers known while tracing: tokens, layers with experts) while its loss is
traced; the training engine collects them (``step_counts``) around the loss it
differentiates, hands the counts out in the step's ``StepMetrics.counts`` and
books them into the registry's counters where it flushes its metrics buffer.
"""
from __future__ import annotations

import re
import weakref
import contextlib
import contextvars
from typing import Any, Dict, Iterator, List, Optional, Tuple


class StepNotes:
    """What the program traced inside ``step_counts()`` noted."""

    def __init__(self):
        self.counts: Dict[str, Any] = {}  # name -> traced scalar, summed by name
        self.facts: Dict[str, Any] = {}   # name -> a Python number


_STEP_NOTES: "contextvars.ContextVar[Optional[StepNotes]]" = contextvars.ContextVar(
    "step_notes", default=None)


@contextlib.contextmanager
def step_counts() -> Iterator[StepNotes]:
    """Collect what the function traced inside notes with ``count_in_step`` /
    ``note_step_fact`` (in this thread).  A value noted under an inner trace
    (``jax.checkpoint``, ``lax.scan``) must be handed out of it first."""
    notes = StepNotes()
    token = _STEP_NOTES.set(notes)
    try:
        yield notes
    finally:
        _STEP_NOTES.reset(token)


def count_in_step(name: str, value) -> None:
    """Add ``value`` (a scalar, traced or not) to the step's count ``name``;
    nothing where no step is collecting."""
    notes = _STEP_NOTES.get()
    if notes is not None:
        notes.counts[name] = notes.counts[name] + value if name in notes.counts else value


def note_step_fact(name: str, value) -> None:
    """A Python number the traced step knows of itself (a span's argument)."""
    notes = _STEP_NOTES.get()
    if notes is not None:
        notes.facts[name] = value


_TRACKED: "weakref.WeakSet[TrackedProgram]" = weakref.WeakSet()


def _shape_of(x: Any) -> Any:
    """A ``ShapeDtypeStruct`` that lowers like ``x`` did; non-arrays (static
    arguments) pass through."""
    import jax

    if not (hasattr(x, "shape") and hasattr(x, "dtype")):
        return x
    committed = isinstance(x, jax.Array) and getattr(x, "committed", False)
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding if committed else None,
        weak_type=bool(getattr(x, "weak_type", False)))


class TrackedProgram:
    """The shapes a jitted function compiled for."""

    __slots__ = ("fn", "signatures", "_seen", "__weakref__")

    def __init__(self, fn):
        self.fn = fn
        self.signatures: List[Tuple[Any, ...]] = []
        self._seen = fn._cache_size()

    def note(self, args) -> None:
        """After a call of ``fn(*args)``: keep the shapes if it compiled."""
        n = self.fn._cache_size()
        if n != self._seen:
            import jax

            self._seen = n
            self.signatures.append(jax.tree_util.tree_map(_shape_of, args))


def track(fn) -> "TrackedProgram | None":
    """Register a jitted function; the caller then calls ``note(args)`` on
    what this returns after each call of ``fn``.  The call itself is left
    alone: a wrapper around it would put one more Python frame under every
    traced op, and JAX's tracing of a 16-layer Pallas program slows by
    seconds per frame (PERF.md, PR 24).  None for anything that is not a
    jitted function (``compile.disable``)."""
    if not (hasattr(fn, "lower") and hasattr(fn, "_cache_size")):
        return None
    prog = TrackedProgram(fn)
    _TRACKED.add(prog)
    return prog


def _compiled_texts() -> Iterator[str]:
    for prog in list(_TRACKED):
        for sig in prog.signatures:
            yield prog.fn.lower(*sig).compile().as_text()


_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)
_OP_NAME = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name=\"([^\"]*)\"", re.M)


def program_scopes() -> Dict[str, Dict[str, str]]:
    """``{module name: {instruction name: op_name}}`` of every tracked
    program's compiled text: the scope path JAX recorded for the op an
    instruction (or a fusion's root) came from.  Programs compiled under one
    module name (two sampling settings of one dispatch) share an entry."""
    out: Dict[str, Dict[str, str]] = {}
    for text in _compiled_texts():
        m = _MODULE.search(text)
        if m:
            out.setdefault(m.group(1), {}).update(_OP_NAME.findall(text))
    return out


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(")
_ARRAY = re.compile(r"\b([a-z]+\d+[a-z0-9]*|pred)\[([\d,]*)\]")
_CALLED = re.compile(r"(?:calls|body|to_apply)=%?([\w.\-]+)")
_CONDITION = re.compile(r"condition=%?([\w.\-]+)")
_BOUND = re.compile(r" = s32\[\]\S* constant\((\d+)\)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_TRIPS = re.compile(r'"known_trip_count":\{"n":"(\d+)"')
_COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce",
                "collective-permute", "all-to-all")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
          "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}


def _result_bytes(shape_text: str) -> int:
    total = 0
    for dtype, dims in _ARRAY.findall(shape_text):
        n = _BYTES.get(dtype, (int(re.sub(r"\D", "", dtype) or 8) + 7) // 8)
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n
    return total


def collective_bytes(text: str) -> Optional[int]:
    """Result bytes of the collectives one execution of a compiled module
    runs on one device: every all-gather / reduce-scatter / all-reduce /
    collective-permute / all-to-all (an async pair counts once, at its
    ``-done``), a ``while`` body times its trip count: the instruction's
    ``known_trip_count`` where the backend writes one (CPU), else the bound
    its condition compares the counter with (``compare(i, constant(N)),
    direction=LT``, what a ``lax.scan`` lowers to on the TPU).  None where a
    loop whose body holds collectives shows neither: a scanned layer stack
    counted once would under-report by its depth, silently."""
    own: Dict[str, int] = {}
    calls: Dict[str, List[Tuple[str, Any]]] = {}
    bounds: Dict[str, List[int]] = {}   # computation -> its s32 constants, if it ends in an LT
    entry = cur = None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            cur = head.group(1)
            own[cur], calls[cur], bounds[cur] = 0, [], []
            if line.startswith("ENTRY"):
                entry = cur
            continue
        ins = _INSTR.match(line) if cur else None
        if not ins:
            continue
        bound = _BOUND.search(line)
        if bound:
            bounds[cur].append(int(bound.group(1)))
        if "ROOT" in line and "direction=LT" not in line:
            bounds[cur] = []  # not a counted loop's condition
        shapes, opcode = ins.groups()
        if opcode.endswith("-done"):
            opcode = opcode[:-5]
        if opcode in _COLLECTIVES:
            own[cur] += _result_bytes(re.sub(r"\{[^{}]*\}", "", shapes))
        n: Any = 1
        if opcode == "while":
            trips, cond = _TRIPS.search(line), _CONDITION.search(line)
            n = int(trips.group(1)) if trips else (cond.group(1) if cond else 1)
        called = _CALLED.findall(line)
        for group in _BRANCHES.findall(line):
            called += [c.strip().lstrip("%") for c in group.split(",")]
        calls[cur] += [(c, n) for c in called]

    def trips(n) -> Optional[int]:  # a count, or the name of the condition that holds the bound
        if isinstance(n, int):
            return n
        found = bounds.get(n, [])
        return found[0] if len(found) == 1 else None

    def total(comp: str, seen: Tuple[str, ...] = ()) -> Optional[int]:
        if comp not in own or comp in seen:
            return 0
        out = own[comp]
        for c, n in calls[comp]:
            inner, times = total(c, seen + (comp,)), trips(n)
            if inner is None or (inner and times is None):
                return None
            out += inner * (times or 0)
        return out

    return total(entry) if entry else 0


def collective_bytes_per_step() -> Dict[str, Optional[int]]:
    """``{module name: bytes}`` through :func:`collective_bytes`; of two
    programs under one module name the larger stands, and None if either
    could not be counted."""
    out: Dict[str, Optional[int]] = {}
    for text in _compiled_texts():
        m = _MODULE.search(text)
        if m:
            seen, new = out.get(m.group(1), 0), collective_bytes(text)
            out[m.group(1)] = None if None in (seen, new) else max(seen, new)
    return out
