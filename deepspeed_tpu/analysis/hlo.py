"""Structured parser over compiled XLA programs.

Turns the scheduled-HLO text of a compiled jit (``jitted.lower(*args)
.compile().as_text()``) into typed records — :class:`Collective`,
:class:`Donation`, :class:`AsyncPair` — so invariants that used to be
asserted by print-format-sensitive regexes (the class of breakage PR 9 had
to fix when XLA changed how it prints ``collective-permute-done`` operands)
become reusable, testable facts:

- every collective's kind / payload dtype / shape / channel / replica-group
  world size / source location, with the qcomm ring-convention
  ``bytes_on_wire`` derived per record;
- the module's input-output aliasing table (donation — a lost
  ``donate_argnums`` is a silent full copy of a multi-GB KV pool);
- async start/done pairing with intervening-compute counts, including the
  two printer quirks the old regex tests hit: TPU's
  ``AsyncCollectiveStart``/``Done`` custom-call *fusions* (paired by the
  wrapped collective's channel id) and ``collective-permute-done`` printing
  its operand with the full tuple type (the SSA name is the LAST token
  before the close paren), plus done-before-start scan back-edges.

A thin StableHLO scanner (:func:`stablehlo_collectives`) covers the
pre-partitioning view (``lowered.as_text()``) the quantization tests use.
The parser is text-shape tolerant: both ``replica_groups={{0,1}}`` and the
iota form ``replica_groups=[2,2]<=[4]`` parse, and unknown ops simply do
not produce records.  Two printers are read: the one that puts
``source_file="..." source_line=N`` into an instruction's metadata and types
before operand names, and the one (jax 0.9) that prints ``stack_frame_id=N``
against four tables at the head of the module (``FileNames`` /
``FunctionNames`` / ``FileLocations`` / ``StackFrames``) and operands by bare
name.  Which branch runs is decided by what the text holds.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

# bytes per element of an HLO primitive type on the wire
_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4,
    "f64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e4m3b11fnuz": 1,
    "c64": 8, "c128": 16,
}

_TYPE_RE = re.compile(r"(\w+)\[([0-9,]*)\](?:\{[^}]*\})?")
_COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)


def _parse_type(tok: str) -> Optional[Tuple[str, Tuple[int, ...]]]:
    m = _TYPE_RE.match(tok.strip())
    if not m or m.group(1) not in _DTYPE_BYTES:
        return None
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    return m.group(1), dims


def _nbytes(dtype: str, shape: Tuple[int, ...]) -> int:
    n = 1
    for d in shape:
        n *= d
    return int(n * _DTYPE_BYTES.get(dtype, 4))


@dataclass(frozen=True)
class Collective:
    """One collective instruction of a scheduled module."""

    kind: str  # 'all-reduce' | 'all-gather' | 'reduce-scatter' | ...
    phase: str  # '' (synchronous) | 'start' | 'done'
    dtype: str  # payload dtype (first tensor result; done ops: operand)
    shape: Tuple[int, ...]
    result_types: Tuple[Tuple[str, Tuple[int, ...]], ...]
    operand_types: Tuple[Tuple[str, Tuple[int, ...]], ...]
    channel_id: Optional[int]
    group_size: int  # ranks per replica group (1 if unknown)
    computation: str
    index: int  # instruction position within its computation
    async_wrapped: bool  # lives inside an AsyncCollectiveStart/Done fusion
    source_file: str  # basename of metadata source_file ('' if absent)
    source_line: Optional[int]
    op_name: str
    line: str = field(repr=False, default="")

    @property
    def result_bytes(self) -> int:
        return sum(_nbytes(d, s) for d, s in self.result_types)

    @property
    def operand_bytes(self) -> int:
        return sum(_nbytes(d, s) for d, s in self.operand_types)

    @property
    def bytes_on_wire(self) -> int:
        """Per-device bytes this collective SENDS, in the same ring
        convention as :func:`comm.qcomm.wire_bytes`: (W-1)/W of the payload
        per hop, two hops for all-reduce.  ``done`` halves report 0 (their
        ``start`` carries the payload).  A raw ``-start`` op's result is a
        TUPLE that also aliases the in-flight/destination buffers (e.g.
        ``(f32[shard], f32[full])`` for all-gather-start, the 4-tuple for
        collective-permute-start) — the payload is the LARGEST element,
        not the tuple sum."""
        if self.phase == "done":
            return 0
        if self.phase == "start":
            payload = max(
                (_nbytes(d, s) for d, s in self.result_types), default=0)
        else:
            payload = self.result_bytes
        if self.kind in ("collective-permute", "collective-broadcast"):
            # point-to-point: source_target_pairs, no replica_groups
            return payload
        w = max(self.group_size, 1)
        if w == 1:
            return 0
        if self.kind == "all-reduce":
            return 2 * payload * (w - 1) // w
        if self.kind == "all-gather":
            # payload is the gathered (full) tensor
            return payload * (w - 1) // w
        if self.kind == "reduce-scatter":
            # operand is the full tensor, result the reduced shard
            return self.operand_bytes * (w - 1) // w
        if self.kind == "all-to-all":
            return payload * (w - 1) // w
        return 0


@dataclass(frozen=True)
class Donation:
    """One input-output alias of the module header: output ``output_index``
    aliases parameter ``param_number`` (donated input)."""

    output_index: Tuple[int, ...]
    param_number: int
    param_index: Tuple[int, ...]
    kind: str  # 'may-alias' | 'must-alias'


@dataclass(frozen=True)
class AsyncPair:
    """A matched async start/done with scheduling facts between them."""

    kind: str  # collective kind of the started op
    channel_id: Optional[int]
    dtype: str  # wire payload dtype of the start
    computation: str
    start_index: int
    done_index: int
    compute_between: int  # dot/convolution ops (incl. inside called fusions)
    fusion_between: int  # any non-async fusion call between start and done
    spans_backedge: bool  # done scheduled before start: pair crosses a loop


@dataclass
class ProgramFacts:
    """Typed view of one compiled module."""

    module_name: str
    collectives: List[Collective]
    donations: List[Donation]
    async_pairs: List[AsyncPair]
    computations: Dict[str, List[str]]
    entry_param_types: List[Tuple[str, Tuple[int, ...]]]
    async_starts: int = 0  # scheduled start events (ops + wrapper fusions)
    async_dones: int = 0

    # -- filters ----------------------------------------------------------
    def find(self, kind: Optional[str] = None, dtype: Optional[str] = None,
             phase: Optional[str] = None,
             source_file: Optional[Sequence[str]] = None) -> List[Collective]:
        out = []
        for c in self.collectives:
            if kind is not None and c.kind != kind:
                continue
            if dtype is not None and c.dtype != dtype:
                continue
            if phase is not None and c.phase != phase:
                continue
            if source_file is not None and c.source_file not in source_file:
                continue
            out.append(c)
        return out

    def overlapped(self, kinds: Optional[Sequence[str]] = None,
                   dtype: Optional[str] = None, min_compute: int = 1,
                   loose: bool = False) -> List[AsyncPair]:
        """Async pairs with real work scheduled inside the start→done
        window (or spanning a scan back-edge — the gather issued at the end
        of iteration i consumed in i+1, a whole layer's compute between).
        ``loose`` also counts generic fusions as compute (the ring/pipeline
        tests' historical heuristic, where the math lives in fusions)."""
        out = []
        for p in self.async_pairs:
            if kinds is not None and p.kind not in kinds:
                continue
            if dtype is not None and p.dtype != dtype:
                continue
            n = p.compute_between + (p.fusion_between if loose else 0)
            if p.spans_backedge or n >= min_compute:
                out.append(p)
        return out

    def wire_bytes_total(self, source_file: Optional[Sequence[str]] = None,
                         kinds: Optional[Sequence[str]] = None) -> int:
        """Sum of per-device sent bytes over the module's collectives, each
        transfer once: a ``done`` half reports 0, and the collective inside
        the computation an async ``-start`` op ``calls=`` IS that start's
        transfer.  (Channel ids cannot tell transfers apart: jax 0.9 prints
        ``channel_id=1`` on every collective of every ``shard_map`` body.)
        NOTE: collectives inside ``while`` bodies are counted ONCE; byte
        budgets are only exact for unrolled (serving-style) programs."""
        started = set()
        for c in self.collectives:
            if c.phase == "start":
                started.update(_CALLS_RE.findall(c.line))
        total = 0
        for c in self.collectives:
            if c.phase == "done" or c.computation in started:
                continue
            if source_file is not None and c.source_file not in source_file:
                continue
            if kinds is not None and c.kind not in kinds:
                continue
            total += c.bytes_on_wire
        return total

    @property
    def donated_param_numbers(self) -> frozenset:
        return frozenset(d.param_number for d in self.donations)


# ---------------------------------------------------------------------------
# scheduled-HLO parsing
# ---------------------------------------------------------------------------
_COMP_RE = re.compile(r"^(%[\w.\-]+|ENTRY [%\w.\-]+)")
_INSTR_RE = re.compile(r"^  (?:ROOT )?%([\w.\-]+) = (.+)$")
_ALIAS_RE = re.compile(
    r"\{([0-9, ]*)\}:\s*\((\d+),\s*\{([0-9, ]*)\},\s*([\w\-]+)\)"
)
_CHANNEL_RE = re.compile(r"channel_id=(\d+)")
_GROUPS_BRACED_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[\d+,(\d+)\]<=\[\d+\]")
_SOURCE_RE = re.compile(r'source_file="([^"]+)"')
_SOURCE_LINE_RE = re.compile(r"source_line=(\d+)")
_OP_NAME_RE = re.compile(r'op_name="([^"]+)"')
_FRAME_ID_RE = re.compile(r"stack_frame_id=(\d+)")
_TABLE_ROW_RE = re.compile(r"^(\d+) (.+)$")
_FRAME_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_OPERAND_TYPE_RE = re.compile(r"\w+\[[0-9,]*\](?:\{[^}]*\})?")
_SSA_NAME_RE = re.compile(r"%([\w.\-]+)")
_CALLS_RE = re.compile(r"calls=(%[\w.\-]+)")
_COMPUTE_RE = re.compile(r"convolution|\bdot\(")


def _split_computations(text: str) -> Dict[str, List[str]]:
    comps: Dict[str, List[str]] = {}
    name = None
    for line in text.splitlines():
        m = _COMP_RE.match(line)
        if m and "{" in line:
            name = m.group(1).replace("ENTRY ", "")
            comps[name] = []
        elif name is not None and re.match(r"^  (ROOT )?%", line):
            comps[name].append(line)
    return comps


def _frame_sources(text: str) -> Dict[int, Tuple[str, int]]:
    """``stack_frame_id`` -> (file basename, line) from the module header's
    tables.  A frame row names a file location, a location row a file name
    and a line; the instruction's id is the innermost frame outside jax (the
    call site of the primitive), so no ``parent_frame_id`` walk is needed."""
    rows: Dict[str, Dict[int, str]] = {t: {} for t in _FRAME_TABLES}
    table = None
    for line in text.splitlines():
        if line in rows:
            table = rows[line]
        elif _COMP_RE.match(line):
            break  # first computation: the header is over
        elif table is not None:
            m = _TABLE_ROW_RE.match(line)
            if m:
                table[int(m.group(1))] = m.group(2)
            elif line:
                table = None

    def field_of(row: str, key: str) -> int:
        m = re.search(key + r"=(\d+)", row)
        return int(m.group(1)) if m else 0

    out: Dict[int, Tuple[str, int]] = {}
    for fid, frame in rows["StackFrames"].items():
        loc = rows["FileLocations"].get(field_of(frame, "file_location_id"), "")
        name = rows["FileNames"].get(field_of(loc, "file_name_id"), "")
        out[fid] = (name.strip('"').rsplit("/", 1)[-1], field_of(loc, "line"))
    return out


def _source_of(line: str, frames: Dict[int, Tuple[str, int]]
               ) -> Tuple[str, Optional[int]]:
    """(file basename, line) an instruction came from: the inline
    ``source_file=`` form where the text has it, else its ``stack_frame_id``
    through the header's tables; ``('', None)`` where the text has neither."""
    src = _SOURCE_RE.search(line)
    if src:
        sl = _SOURCE_LINE_RE.search(line)
        return (src.group(1).rsplit("/", 1)[-1],
                int(sl.group(1)) if sl else None)
    fid = _FRAME_ID_RE.search(line)
    if fid and int(fid.group(1)) in frames:
        return frames[int(fid.group(1))]
    return "", None


def _group_size(line: str) -> int:
    m = _GROUPS_BRACED_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(1))
    return 1


def _instr_rhs(rhs: str) -> Optional[Tuple[list, str, str]]:
    """rhs of ``%name = `` -> (result_types, op, args_and_attrs).  Tuple
    result types need a balanced-paren scan: TPU layout annotations nest
    parens inside the type (``bf16[...]{1,3,2,0:T(8,128)(2,1)S(1)}``), so
    the first ``)`` is NOT the tuple close."""
    rhs = rhs.strip()
    if rhs.startswith("("):
        depth = 0
        close = -1
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    close = i
                    break
        if close < 0:
            return None
        result_str, rest = rhs[1:close], rhs[close + 1:].strip()
    else:
        sp = rhs.find(" ")
        if sp < 0:
            return None
        result_str, rest = rhs[:sp], rhs[sp + 1:].strip()
    results = [t for t in
               (_parse_type(tok) for tok in result_str.split(", "))
               if t is not None]
    m = re.match(r"([\w\-]+)\(", rest)
    if not m:
        return None
    return results, m.group(1), rest[m.end():]


def _op_kind(op: str) -> Optional[Tuple[str, str]]:
    for base in _COLLECTIVE_OPS:
        if op == base:
            return base, ""
        if op == base + "-start":
            return base, "start"
        if op == base + "-done":
            return base, "done"
    return None


def _operand_section(rest: str) -> Tuple[str, str]:
    """Split ``args), attr=..., attr=...`` at the operand close paren
    (operand types carry ``[...]{...}`` but no parens, so the first ``)``
    that is not inside a brace group closes the operand list)."""
    depth = 0
    for i, ch in enumerate(rest):
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
        elif ch == ")" and depth == 0:
            return rest[:i], rest[i + 1:]
        elif ch == "(" and depth == 0:
            # nested call parens (to_apply inline etc.) — bail to whole rest
            break
    return rest, rest


def parse_scheduled_hlo(text: str) -> ProgramFacts:
    """Parse one scheduled-HLO module (``compiled.as_text()``)."""
    header = text.splitlines()[0] if text else ""
    mod = re.match(r"HloModule ([\w.\-]+)", header)
    donations = []
    if "input_output_alias=" in header:
        # the alias table nests braces ({0}: (6, {}, may-alias)); its entry
        # pattern is distinctive enough to findall over the whole header
        # (layout braces {1,0} are never followed by ': (')
        for om, pn, pi, kind in _ALIAS_RE.findall(header):
            donations.append(Donation(
                output_index=tuple(int(x) for x in om.replace(" ", "").split(",") if x),
                param_number=int(pn),
                param_index=tuple(int(x) for x in pi.replace(" ", "").split(",") if x),
                kind=kind,
            ))
    comps = _split_computations(text)
    frames = _frame_sources(text)

    # pass 1: classify each computation — async wrapper? contains compute?
    is_async_start: Dict[str, bool] = {}
    is_async_done: Dict[str, bool] = {}
    has_compute: Dict[str, bool] = {}
    for name, lines in comps.items():
        is_async_start[name] = any("AsyncCollectiveStart" in l for l in lines)
        is_async_done[name] = any("AsyncCollectiveDone" in l for l in lines)
        has_compute[name] = any(_COMPUTE_RE.search(l) for l in lines)

    # pass 2: collective records
    collectives: List[Collective] = []
    comp_channel: Dict[str, Optional[int]] = {}  # fused comp -> channel
    comp_payload: Dict[str, str] = {}  # fused comp -> payload dtype
    for name, lines in comps.items():
        wrapped = is_async_start[name] or is_async_done[name]
        instrs = []
        defined: Dict[str, list] = {}  # SSA name -> its result types
        for idx, line in enumerate(lines):
            m = _INSTR_RE.match(line)
            parsed = _instr_rhs(m.group(2)) if m else None
            if parsed is not None:
                instrs.append((idx, line, parsed))
                defined[m.group(1)] = parsed[0]
        for idx, line, (results, op, rest) in instrs:
            kindphase = _op_kind(op)
            if kindphase is None:
                continue
            kind, phase = kindphase
            operands_str, _ = _operand_section(rest)
            operands = [t for t in
                        (_parse_type(tok) for tok in
                         _OPERAND_TYPE_RE.findall(operands_str))
                        if t is not None]
            if not operands:
                # operands printed by bare name: each one's type is its
                # defining instruction's, in the same computation
                operands = [t for n in _SSA_NAME_RE.findall(operands_str)
                            for t in defined.get(n, ())]
            ch = _CHANNEL_RE.search(line)
            channel = int(ch.group(1)) if ch else None
            picks = results if phase != "done" else (operands or results)
            dtype, shape = (picks[0] if picks else ("f32", ()))
            source_file, source_line = _source_of(line, frames)
            opn = _OP_NAME_RE.search(line)
            collectives.append(Collective(
                kind=kind, phase=phase, dtype=dtype, shape=shape,
                result_types=tuple(results), operand_types=tuple(operands),
                channel_id=channel, group_size=_group_size(line),
                computation=name, index=idx, async_wrapped=wrapped,
                source_file=source_file, source_line=source_line,
                op_name=opn.group(1) if opn else "", line=line.strip(),
            ))
            if wrapped and channel is not None and name not in comp_channel:
                comp_channel[name] = channel
                comp_payload[name] = dtype

    # wrapper computations whose channel/payload did not come from an inner
    # collective line (some printers put the channel on the custom-call
    # itself): fall back to scanning the body text
    for name, lines in comps.items():
        if not (is_async_start[name] or is_async_done[name]):
            continue
        if name not in comp_channel:
            for l in lines:
                ch = _CHANNEL_RE.search(l)
                if ch:
                    comp_channel[name] = int(ch.group(1))
                    break
        if name not in comp_payload:
            for l in lines:
                if "AsyncCollective" in l:
                    t = _TYPE_RE.search(l)
                    if t and t.group(1) in _DTYPE_BYTES:
                        comp_payload[name] = t.group(1)
                    break

    # pass 3: async start/done pairing per scheduled computation
    by_pos = {(c.computation, c.index): c for c in collectives}
    async_pairs: List[AsyncPair] = []
    n_starts = n_dones = 0
    for name, lines in comps.items():
        if is_async_start[name] or is_async_done[name]:
            continue  # wrapper bodies are not schedules
        # event stream: (tag, keys, dtype, kind, line index).  ``keys`` is
        # a tuple of candidate pairing keys: for done events, every SSA
        # name the operand section mentions — XLA prints the operand with
        # its full tuple type on some versions (``done((bf16[...], ...)
        # %start)``), so the start's name is not at a fixed position.
        events = []
        for idx, line in enumerate(lines):
            m = _INSTR_RE.match(line)
            if not m:
                continue
            iname = m.group(1)
            parsed = _instr_rhs(m.group(2))
            op = parsed[1] if parsed else ""
            kp = _op_kind(op)
            if kp is not None:  # opcode FIRST: operand names like
                kind, phase = kp  # %fusion.7 must not shadow a start op
                c = by_pos.get((name, idx))
                if phase == "start":
                    events.append(("start", ("%" + iname,),
                                   c.dtype if c else "f32", kind, idx))
                elif phase == "done":
                    opnames = _SSA_NAME_RE.findall(parsed[2])
                    events.append(("done", tuple("%" + n for n in opnames),
                                   c.dtype if c else "f32", kind, idx))
                continue
            cm = _CALLS_RE.search(line)
            if cm:
                callee = cm.group(1)
                if is_async_start.get(callee):
                    events.append(("start", (comp_channel.get(callee),),
                                   comp_payload.get(callee, "f32"),
                                   "fused-async", idx))
                elif is_async_done.get(callee):
                    events.append(("done", (comp_channel.get(callee),),
                                   comp_payload.get(callee, "f32"),
                                   "fused-async", idx))
                elif has_compute.get(callee):
                    events.append(("compute", (), "", "", idx))
                else:
                    events.append(("fusion", (), "", "", idx))
                continue
            if op in ("dot", "convolution"):
                events.append(("compute", (), "", "", idx))
            elif op == "fusion":
                events.append(("fusion", (), "", "", idx))

        comp_has_compute = any(e[0] in ("compute", "fusion") for e in events)
        starts: Dict[object, Tuple[int, int, str, str]] = {}
        for pos, (tag, keys, dtype, kind, idx) in enumerate(events):
            if tag == "start":
                n_starts += 1
                if keys and keys[0] is not None:
                    starts[keys[0]] = (pos, idx, dtype, kind)
        for pos, (tag, keys, dtype, kind, idx) in enumerate(events):
            if tag != "done":
                continue
            n_dones += 1
            key = next((k for k in keys if k in starts), None)
            if key is None:
                continue
            spos, sidx, sdtype, skind = starts[key]
            if spos < pos:
                window = events[spos + 1:pos]
                async_pairs.append(AsyncPair(
                    kind=skind if skind != "fused-async" else "all-gather",
                    channel_id=key if isinstance(key, int) else None,
                    dtype=sdtype, computation=name,
                    start_index=sidx, done_index=idx,
                    compute_between=sum(1 for e in window if e[0] == "compute"),
                    fusion_between=sum(1 for e in window if e[0] == "fusion"),
                    spans_backedge=False,
                ))
            elif comp_has_compute:
                # done scheduled BEFORE start: the pair spans the scan
                # back-edge (gather issued at the end of iteration i is
                # consumed in i+1 with the whole body's compute between)
                async_pairs.append(AsyncPair(
                    kind=skind if skind != "fused-async" else "all-gather",
                    channel_id=key if isinstance(key, int) else None,
                    dtype=sdtype, computation=name,
                    start_index=sidx, done_index=idx,
                    compute_between=0, fusion_between=0, spans_backedge=True,
                ))

    # entry parameter types, straight off the ENTRY signature
    entry_params: List[Tuple[str, Tuple[int, ...]]] = []
    em = re.search(r"^ENTRY [%\w.\-]+ \(([^)]*)\)", text, re.M)
    if em:
        for tok in em.group(1).split(", "):
            if ":" in tok:
                t = _parse_type(tok.split(":", 1)[1])
                if t is not None:
                    entry_params.append(t)
    return ProgramFacts(
        module_name=mod.group(1) if mod else "",
        collectives=collectives, donations=donations,
        async_pairs=async_pairs, computations=comps,
        entry_param_types=entry_params,
        async_starts=n_starts, async_dones=n_dones,
    )


def program_facts(jitted, *args, **kwargs) -> ProgramFacts:
    """Lower + compile a jitted callable on example ``args`` and parse the
    scheduled module.  Also accepts an already-``lower()``-ed or
    ``compile()``-d object (no args)."""
    obj = jitted
    if args or kwargs:
        obj = obj.lower(*args, **kwargs)
    if hasattr(obj, "compile"):
        obj = obj.compile()
    return parse_scheduled_hlo(obj.as_text())


# ---------------------------------------------------------------------------
# StableHLO (pre-partitioning) collective scan
# ---------------------------------------------------------------------------
_SH_OP_RE = re.compile(
    r'"?stablehlo\.(all_reduce|all_gather|all_to_all|reduce_scatter|'
    r"collective_permute|collective_broadcast)"
)
_SH_TENSOR_RE = re.compile(r"tensor<((?:\d+x)*)([\w]+)>")


@dataclass(frozen=True)
class StableHloCollective:
    kind: str  # stablehlo op name ('all_reduce', 'all_gather', ...)
    dtype: str  # element type of the first tensor operand ('i8', 'f32', ...)
    shape: Tuple[int, ...]


def stablehlo_collectives(text: str) -> List[StableHloCollective]:
    """Collective ops of a StableHLO module (``lowered.as_text()``) with
    their operand element types.  Ops with a reduction region print their
    operand/result types on the trailing ``}) : (...) -> ...`` line — the
    scan pairs each op with the first type annotation at or after it."""
    lines = text.splitlines()
    out = []
    for i, line in enumerate(lines):
        m = _SH_OP_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        ty = None
        for j in range(i, min(i + 40, len(lines))):
            if j > i and _SH_OP_RE.search(lines[j]):
                break  # ran into the next op before a type annotation
            # the operand/result annotation is the LAST ` : ` segment of a
            # line carrying ` -> ` (single-line op or region trailer) —
            # earlier ` : ` segments belong to attributes like
            # ``dense<...> : tensor<..xi64>`` replica groups
            if " : " in lines[j] and " -> " in lines[j]:
                tms = _SH_TENSOR_RE.findall(lines[j].rsplit(" : ", 1)[-1])
                if tms:
                    ty = tms[0]
                    break
        if ty is None:
            ty = ("", "f32")
        dims = tuple(int(d) for d in ty[0].split("x") if d)
        out.append(StableHloCollective(kind=kind, dtype=ty[1], shape=dims))
    return out
