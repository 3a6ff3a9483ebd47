"""HLO-level program analysis: collective streams, donation, layouts.

Promotion of ``tests/wire_accounting.py`` (VERDICT r4 #6) into a real
analysis layer.  Parses BOTH program texts a jitted step can produce —

- **lowered stablehlo** (``fn.lower(...).as_text()``): what the trace
  emitted, before SPMD partitioning.  Collectives here are the ones the
  user's code issued (``shard_map`` bodies, explicit psums);
- **optimized HLO** (``fn.lower(...).compile().as_text()``): the
  post-GSPMD, post-layout program.  GSPMD *inserts* collectives during
  partitioning and XLA's entry-layout heuristic can insert whole-tensor
  ``transpose``/``copy`` ops (the r4 DLRM killer), so contracts about
  sharded train steps and layout pins must look here —

into one typed :class:`HloSummary`: the ordered collective stream with
per-device ring wire bytes (NCCL-tests convention, the north-star
formulas of ``benchmarks/collectives.py``)::

    all_reduce:     2(g-1)/g * operand_bytes
    reduce_scatter:  (g-1)/g * operand_bytes
    all_gather:      (g-1)/g * result_bytes
    all_to_all:      (g-1)/g * operand_bytes
    collective_permute: operand_bytes per (s, t) link (point-to-point)

plus the ``input_output_alias`` donation map, the layout-changing
``copy``/``transpose`` instructions with their shapes, and fusion/line
counts; and, for joining a device profile with the program's scopes,
:func:`program_map`: every instruction of the optimized module by the name
a profile gives its event, with its opcode, scope path and fused parts.
``analysis/contracts.py`` evaluates every shipped program family's
invariants against the summary; the legacy dict API
(:func:`collective_wire_costs`) is preserved verbatim for the
``tests/wire_accounting.py`` shim.
"""

import re
from typing import List, NamedTuple, Optional, Tuple

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2,
                "i64": 8, "ui64": 8, "i32": 4, "ui32": 4,
                "i16": 2, "ui16": 2, "i8": 1, "ui8": 1, "i1": 1}

# Optimized-HLO primitive types (s/u spellings, pred for bool).
_HLO_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2,
                    "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                    "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1}

_COLLECTIVES = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all")

# Optimized-HLO opcode -> normalized stablehlo-style name.
_HLO_OPCODES = {"all-reduce": "all_reduce", "all-gather": "all_gather",
                "reduce-scatter": "reduce_scatter",
                "all-to-all": "all_to_all",
                "collective-permute": "collective_permute"}


class HloCollective(NamedTuple):
    """One entry of a program's ordered collective stream."""
    op: str                       # normalized snake_case kind
    group_size: int               # replica-group size (permute: n_links)
    groups: Tuple[Tuple[int, ...], ...]   # replica groups (permute: ())
    pairs: Tuple[Tuple[int, int], ...]    # source_target_pairs (else ())
    n_links: int                  # permute links with s != t (else 0)
    operand_bytes: int
    result_bytes: int
    ring_bytes: float             # per-device wire bytes (formulas above)
    line: int                     # 1-based line in the parsed text


class DonationAlias(NamedTuple):
    """One ``input_output_alias`` entry of an optimized HloModule."""
    output_index: str             # e.g. "{}" or "{1}"
    param_number: int
    param_index: str
    kind: str                     # "may-alias" / "must-alias"


class LayoutMove(NamedTuple):
    """A data-moving ``transpose``/``copy`` instruction (optimized HLO) —
    the instruction class the DLRM entry-layout pin exists to keep away
    from table-shaped operands (CLAUDE.md, r4)."""
    op: str                       # "transpose" / "copy"
    shape: str                    # result shape, e.g. "f32[128,16]"
    line: int
    text: str                     # the full instruction line


class HloSummary(NamedTuple):
    flavor: str                   # "stablehlo" / "optimized"
    collectives: Tuple[HloCollective, ...]
    donation: Tuple[DonationAlias, ...]   # optimized only
    donated: bool                 # any donation evidence in either flavor
    layout_moves: Tuple[LayoutMove, ...]  # optimized only
    fusion_count: int             # optimized only (0 for stablehlo)
    n_lines: int

    def ops(self) -> List[str]:
        return [c.op for c in self.collectives]

    def count(self, op: str) -> int:
        return sum(1 for c in self.collectives if c.op == op)

    def permutes(self) -> List[HloCollective]:
        return [c for c in self.collectives
                if c.op == "collective_permute"]


# ------------------------------------------------------------ stablehlo

def _tensor_bytes(spec: str) -> int:
    """'16xf32' / '2x4xi64' / 'f32' (scalar) -> total bytes."""
    parts = spec.split("x")
    elems = 1
    for p in parts[:-1]:
        elems *= int(p)
    return elems * _DTYPE_BYTES[parts[-1]]


def _signature_at(lines: List[str], i: int):
    """The op's function signature ": (operands) -> results" sits on the
    same line (region-free ops) or on the region-closing line a few lines
    below; region bodies (add/min/...) carry no "->"."""
    for j in range(i, min(i + 16, len(lines))):
        sm = re.search(r":\s*\(([^)]*)\)\s*->\s*(.+)$", lines[j])
        if sm and "tensor<" in sm.group(1):
            return sm
    return None


def _stablehlo_collectives(hlo_text: str) -> List[HloCollective]:
    lines = hlo_text.splitlines()
    out = []
    for i, line in enumerate(lines):
        if re.search(r'"stablehlo\.collective_permute"', line):
            out.append(_stablehlo_permute(lines, i))
            continue
        m = re.search(r'"stablehlo\.(%s)"' % "|".join(_COLLECTIVES), line)
        if not m:
            continue
        op = m.group(1)
        gm = re.search(
            r"replica_groups = dense<(.*?)> : tensor<(\d+)x(\d+)xi64>",
            line)
        assert gm, f"no replica_groups on collective line: {line[:200]}"
        group_size = int(gm.group(3))
        groups = tuple(tuple(int(v) for v in grp.split(","))
                       for grp in re.findall(r"\[([\d,\s]+)\]", gm.group(1)))
        sig = _signature_at(lines, i)
        assert sig, f"no signature found for {op} at line {i}"
        operand_bytes = sum(_tensor_bytes(s) for s in
                            re.findall(r"tensor<([^>]+)>", sig.group(1)))
        result_bytes = sum(_tensor_bytes(s) for s in
                           re.findall(r"tensor<([^>]+)>", sig.group(2)))
        out.append(HloCollective(
            op, group_size, groups, (), 0, operand_bytes, result_bytes,
            _ring_bytes(op, group_size, operand_bytes, result_bytes),
            i + 1))
    return out


def _stablehlo_permute(lines: List[str], i: int) -> HloCollective:
    """``source_target_pairs = dense<[[s, t], ...]> : tensor<Nx2xi64>``
    (a single pair prints as ``dense<[s, t]> : tensor<1x2xi64>``); wire
    cost per participating device = the full operand (point-to-point:
    no ring discount, a device sends its whole buffer to its target)."""
    line = lines[i]
    pm = re.search(
        r"source_target_pairs = dense<(.*?)> : tensor<(\d+)x2xi64>", line)
    assert pm, f"no source_target_pairs on permute line: {line[:200]}"
    pairs = [tuple(int(v) for v in grp.split(","))
             for grp in re.findall(r"\[([\d,\s]+)\]", pm.group(1))]
    if not pairs:               # tensor<1x2xi64> prints without inner []
        flat = [int(v) for v in pm.group(1).split(",")]
        pairs = [tuple(flat[:2])]
    assert len(pairs) == int(pm.group(2)), (pairs, line[:200])
    sig = _signature_at(lines, i)
    assert sig, f"no signature found for collective_permute at line {i}"
    operand_bytes = sum(_tensor_bytes(s) for s in
                        re.findall(r"tensor<([^>]+)>", sig.group(1)))
    result_bytes = sum(_tensor_bytes(s) for s in
                       re.findall(r"tensor<([^>]+)>", sig.group(2)))
    n_links = sum(1 for s, t in pairs if s != t)
    return HloCollective(
        "collective_permute", n_links, (), tuple(pairs), n_links,
        operand_bytes, result_bytes, float(operand_bytes), i + 1)


def _ring_bytes(op, g, operand_bytes, result_bytes) -> float:
    if g <= 0:
        return 0.0
    return {"all_reduce": 2 * (g - 1) / g * operand_bytes,
            "reduce_scatter": (g - 1) / g * operand_bytes,
            "all_gather": (g - 1) / g * result_bytes,
            "all_to_all": (g - 1) / g * operand_bytes}[op]


# Stablehlo donation evidence: jax marks donated params with either
# attribute spelling depending on version.
_STABLEHLO_DONOR_MARKERS = ("jax.buffer_donor", "tf.aliasing_output")


def summarize_stablehlo(hlo_text: str) -> HloSummary:
    """Typed summary of a lowered (pre-partitioning) stablehlo module."""
    donated = any(m in hlo_text for m in _STABLEHLO_DONOR_MARKERS)
    return HloSummary(
        flavor="stablehlo",
        collectives=tuple(_stablehlo_collectives(hlo_text)),
        donation=(), donated=donated, layout_moves=(),
        fusion_count=0, n_lines=len(hlo_text.splitlines()))


# -------------------------------------------------------- optimized HLO

def _hlo_shape_bytes(spec: str) -> int:
    """'f32[2,4]{1,0}' / 'pred[]' / 'f32[8]' -> total bytes.  Tuples and
    token/opaque types return 0 (they carry no wire payload of their
    own; tuple elements are accounted when listed individually)."""
    m = re.match(r"([a-z]+\d*)\[([\d,\s]*)\]", spec.strip())
    if not m or m.group(1) not in _HLO_DTYPE_BYTES:
        return 0
    elems = 1
    dims = m.group(2).strip()
    if dims:
        for d in dims.split(","):
            elems *= int(d)
    return elems * _HLO_DTYPE_BYTES[m.group(1)]


_HLO_SHAPE_RE = r"[a-z]+\d*\[[\d,\s]*\](?:\{[^}]*\})?"

_HLO_COLLECTIVE_RE = re.compile(
    r"=\s+(\((?:[^()]|\([^)]*\))*\)|" + _HLO_SHAPE_RE + r")\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(-start)?\(")


def _parse_replica_groups(line: str):
    """Brace form ``replica_groups={{0,1},{2,3}}`` or iota form
    ``replica_groups=[2,4]<=[8]`` (2 groups of 4).  Returns
    (group_size, groups)."""
    bm = re.search(r"replica_groups=\{(\{[^=]*?\})\}", line)
    if bm:
        groups = tuple(tuple(int(v) for v in grp.split(",") if v.strip())
                       for grp in re.findall(r"\{([\d,\s]*)\}", bm.group(1)))
        groups = tuple(g for g in groups if g)
        size = len(groups[0]) if groups else 0
        return size, groups
    im = re.search(r"replica_groups=\[(\d+),(\d+)\]<=", line)
    if im:
        n_groups, size = int(im.group(1)), int(im.group(2))
        groups = tuple(tuple(range(g * size, (g + 1) * size))
                       for g in range(n_groups))
        return size, groups
    return 0, ()


def _optimized_collectives(hlo_text: str) -> List[HloCollective]:
    out = []
    for i, line in enumerate(hlo_text.splitlines()):
        m = _HLO_COLLECTIVE_RE.search(line)
        if not m:
            continue
        op = _HLO_OPCODES[m.group(2)]
        result_bytes = sum(_hlo_shape_bytes(s)
                           for s in re.findall(_HLO_SHAPE_RE, m.group(1)))
        # Operand shapes print inside the call parens:
        # all-reduce(f32[2,4]{1,0} %x, f32[8]{0} %y)
        operands = line[m.end():]
        depth, j = 1, 0
        while j < len(operands) and depth:
            if operands[j] == "(":
                depth += 1
            elif operands[j] == ")":
                depth -= 1
            j += 1
        operand_bytes = sum(
            _hlo_shape_bytes(s)
            for s in re.findall(_HLO_SHAPE_RE, operands[:j - 1]))
        if op == "collective_permute":
            pairs = tuple(
                (int(a), int(b)) for a, b in re.findall(
                    r"\{(\d+)\s*,\s*(\d+)\}",
                    _braced_span(line, "source_target_pairs=")))
            n_links = sum(1 for s, t in pairs if s != t)
            out.append(HloCollective(
                op, n_links, (), pairs, n_links, operand_bytes,
                result_bytes, float(operand_bytes), i + 1))
        else:
            size, groups = _parse_replica_groups(line)
            out.append(HloCollective(
                op, size, groups, (), 0, operand_bytes, result_bytes,
                _ring_bytes(op, size, operand_bytes, result_bytes), i + 1))
    return out


_ALIAS_ENTRY_RE = re.compile(
    r"\{([\d,\s]*)\}:\s*\((\d+),\s*\{([\d,\s]*)\},\s*(\w+-alias)\)")


def _braced_span(text: str, marker: str) -> str:
    """The brace-balanced span following ``marker={`` (inner braces
    included, outer braces stripped); "" when the marker is absent."""
    start = text.find(marker + "{")
    if start < 0:
        return ""
    i = start + len(marker)
    depth, j = 0, i
    while j < len(text):
        if text[j] == "{":
            depth += 1
        elif text[j] == "}":
            depth -= 1
            if depth == 0:
                return text[i + 1:j]
        j += 1
    return text[i + 1:]


def _parse_donation(hlo_text: str) -> Tuple[DonationAlias, ...]:
    for line in hlo_text.splitlines():
        if "input_output_alias=" not in line:
            continue
        body = _braced_span(line, "input_output_alias=")
        return tuple(
            DonationAlias("{%s}" % out_ix.strip(), int(param),
                          "{%s}" % p_ix.strip(), kind)
            for out_ix, param, p_ix, kind in _ALIAS_ENTRY_RE.findall(body))
    return ()


_LAYOUT_MOVE_RE = re.compile(
    r"=\s+(" + _HLO_SHAPE_RE + r")\s+(transpose|copy)\(")


def _layout_moves(hlo_text: str) -> List[LayoutMove]:
    out = []
    for i, line in enumerate(hlo_text.splitlines()):
        m = _LAYOUT_MOVE_RE.search(line)
        if m:
            shape = re.match(r"[a-z]+\d*\[[\d,\s]*\]", m.group(1))
            out.append(LayoutMove(m.group(2), shape.group(0), i + 1,
                                  line.strip()))
    return out


def summarize_optimized(hlo_text: str) -> HloSummary:
    """Typed summary of an optimized (post-GSPMD) HLO module text
    (``fn.lower(...).compile().as_text()``)."""
    donation = _parse_donation(hlo_text)
    return HloSummary(
        flavor="optimized",
        collectives=tuple(_optimized_collectives(hlo_text)),
        donation=donation,
        donated=bool(donation) or "input_output_alias" in hlo_text,
        layout_moves=tuple(_layout_moves(hlo_text)),
        fusion_count=hlo_text.count("fusion("),
        n_lines=len(hlo_text.splitlines()))


# ------------------------------------------- the program's map of itself

class HloInstruction(NamedTuple):
    """One instruction of an optimized module, as a profile names it."""
    name: str                     # "fusion.4749": the device event's name
    opcode: str                   # "fusion", "custom-call", "while", ...
    # metadata op_name: the scope path, or "". An instruction XLA made
    # without one inside the body of a while, conditional or call takes
    # that caller's, as the profiler's ``tf_op`` stat does.
    op_name: str
    computation: str              # the computation that holds it
    umbrella: bool                # its event spans its body's leaf events
    target: str                   # custom-call: custom_call_target, else ""
    # fusion: ``(op_name, output bytes)`` of what was fused, one entry a
    # distinct op_name (parameters, which do no work, left out)
    parts: Tuple[Tuple[str, int], ...]


# ``while``, ``conditional`` and ``call`` run other computations whose
# instructions have device events of their own; the ``-start``/``-done``
# halves of an asynchronous operation are leaf events (chipbench/trace.py)
UMBRELLA_OPCODES = ("while", "conditional", "call")

_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_INSTRUCTION_RE = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+")
_OPCODE_RE = re.compile(r"\s*([a-z][\w\-]*)\(")
_CALLS_RE = re.compile(r"\bcalls=%?([\w.\-]+)")
_BODIES_RE = re.compile(
    r"\b(?:body|condition|to_apply|true_computation|false_computation)="
    r"%?([\w.\-]+)|\bbranch_computations=\{([^}]*)\}")


def _attribute(line: str, key: str) -> str:
    """``key="value"`` of an instruction line ("" when absent). Searched by
    ``str.find``: a Mosaic call's line carries megabytes of kernel body."""
    i = line.find(key + '="')
    if i < 0:
        return ""
    i += len(key) + 2
    return line[i:line.find('"', i)]


def _skip_shape(line: str, i: int) -> int:
    """Index just past the result shape that starts at ``line[i]``: a tuple
    ``(f32[8]{0}, (s32[], ...))`` or one array ``f32[8]{1,0:T(8,128)}``."""
    if line[i] != "(":
        return line.find(" ", i)
    depth = 0
    for j in range(i, len(line)):
        if line[j] == "(":
            depth += 1
        elif line[j] == ")":
            depth -= 1
            if depth == 0:
                return j + 1
    return len(line)


def program_map(hlo_text: str) -> "dict[str, HloInstruction]":
    """``{instruction name: HloInstruction}`` over EVERY computation of an
    optimized module (``fn.lower(...).compile().as_text()``), fused ones
    too. A device profile names each event after the instruction it ran
    (``%fusion.4749 = ...``) and instruction names are unique in a module,
    so this joins a profile with the scopes (``jax.named_scope``) that the
    program text carries and the profile's event names do not."""
    found: dict = {}
    bodies: dict = {}             # computation -> [(opcode, op_name, bytes)]
    fusions = []                  # (name, fused computation)
    callers: dict = {}            # computation -> the umbrella that runs it
    computation = None
    for line in hlo_text.splitlines():
        if computation is None:
            m = _COMPUTATION_RE.match(line)
            if m:
                computation = m.group(1)
                bodies[computation] = []
            continue
        if line.startswith("}"):
            computation = None
            continue
        m = _INSTRUCTION_RE.match(line)
        if not m:
            continue
        name = m.group(1)
        shape_end = _skip_shape(line, m.end())
        om = _OPCODE_RE.match(line, shape_end) if shape_end > 0 else None
        if not om:
            continue
        opcode = om.group(1)
        op_name = _attribute(line, "op_name")
        bodies[computation].append(
            (opcode, op_name, sum(_hlo_shape_bytes(s) for s in re.findall(
                _HLO_SHAPE_RE, line[m.end():shape_end]))))
        if opcode == "fusion":
            cm = _CALLS_RE.search(line, om.end())
            if cm:
                fusions.append((name, cm.group(1)))
        elif opcode in UMBRELLA_OPCODES:
            for one, several in _BODIES_RE.findall(line, om.end()):
                for body in [one] if one else several.split(","):
                    callers.setdefault(body.strip().lstrip("%"), name)
        found.setdefault(name, HloInstruction(
            name, opcode, op_name, computation,
            opcode in UMBRELLA_OPCODES,
            _attribute(line, "custom_call_target")
            if opcode == "custom-call" else "", ()))
    for name, fused in fusions:
        per: dict = {}
        for opcode, op_name, nbytes in bodies.get(fused, ()):
            if opcode != "parameter":
                per[op_name] = per.get(op_name, 0) + nbytes
        found[name] = found[name]._replace(parts=tuple(per.items()))
    for name, inst in found.items():
        caller = inst
        while not caller.op_name and caller.computation in callers:
            caller = found[callers[caller.computation]]
        if caller.op_name and not inst.op_name:
            found[name] = inst._replace(op_name=caller.op_name)
    return found


def summarize(hlo_text: str,
              flavor: Optional[str] = None) -> HloSummary:
    """Dispatching entry point: sniffs stablehlo vs optimized HLO when
    ``flavor`` is not given (stablehlo text is full of ``stablehlo.``
    qualified ops; optimized HLO is not)."""
    if flavor is None:
        flavor = "stablehlo" if "stablehlo." in hlo_text else "optimized"
    if flavor == "stablehlo":
        return summarize_stablehlo(hlo_text)
    if flavor == "optimized":
        return summarize_optimized(hlo_text)
    raise ValueError(f"unknown HLO flavor {flavor!r}")


# ------------------------------------------- legacy dict API (the shim)

def collective_wire_costs(hlo_text: str) -> list:
    """Find every stablehlo collective; return a list (program order) of
    dicts: op, group_size, groups (list of device-id lists),
    operand_bytes, result_bytes, ring_bytes — permutes carry pairs /
    n_links instead of group_size / groups.  This is the original
    ``tests/wire_accounting.py`` API, preserved verbatim; that module
    now re-exports from here."""
    out = []
    for c in _stablehlo_collectives(hlo_text):
        if c.op == "collective_permute":
            out.append({"op": c.op,
                        "pairs": [list(p) for p in c.pairs],
                        "n_links": c.n_links,
                        "operand_bytes": c.operand_bytes,
                        "result_bytes": c.result_bytes,
                        "ring_bytes": c.ring_bytes})
        else:
            out.append({"op": c.op, "group_size": c.group_size,
                        "groups": [list(g) for g in c.groups],
                        "operand_bytes": c.operand_bytes,
                        "result_bytes": c.result_bytes,
                        "ring_bytes": c.ring_bytes})
    return out
