"""Which part of the model each instruction of a compiled step program is.

A device trace names an operation by its HLO instruction (``fusion.187``),
and nothing in the trace says what the fusion computes. The compiled
executable's text does: every instruction line carries the
``jax.named_scope`` path it was traced under as ``op_name`` metadata
(``jit(_unified)/while/body/closed_call/ffn/...d,df->...f/dot_general``).
``parts_of_text`` reads that text once a compiled signature and gives every
instruction that can run as an operation of its own (the entry computation,
loop and branch bodies, called computations; a fusion by its own line, not
by what it fused) the INNERMOST name of ``MODEL_PARTS`` on its path, else
``unscoped``. ``ProgramParts`` holds the maps of an engine's programs and
renders them as the info series

    llmd_tpu:program_part_ops{program="jit__unified", part="ffn",
                              stale="0", ops="fusion.187 fusion.188"} 2

one a program and part, ``program`` the executable's module name (what a
trace's ``XLA Modules`` line shows). A reader joins one scrape with a
capture's seconds by operation (``perfbench/kernels/step_parts.py``;
``observability/device-plane.md`` has the operator's procedure).

A program that compiled for several signatures holds the union: an
instruction name that two signatures put in different parts is
``ambiguous``. ``stale="1"`` marks a program one of whose executables names
no part at all: its text was not compiled from this tree's scopes (an
executable loaded from a compile cache whose key left the metadata out;
``jax_init.py`` keeps the metadata in the key, so this should not happen),
and a reader must give nothing rather than a wrong share.
"""

from __future__ import annotations

import math
import re

from llmd_tpu.models.parts import MODEL_PARTS

UNSCOPED = "unscoped"
AMBIGUOUS = "ambiguous"

_PARTS = frozenset(MODEL_PARTS)
_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
# a computation's header: ``[ENTRY ]%name (params) -> result {``
_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
# an instruction: ``  [ROOT ]%name = type opcode(operands), attributes``; the
# opcode is the first word before a ``(`` after the result's type
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OPCODE = re.compile(r"[\]\})] ([\w\-]+)\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
# computations an instruction runs as operations of their own (a fusion's
# ``calls`` and a reducer's ``to_apply`` run inside their instruction)
_RUNS = re.compile(r"\b(?:body|condition|true_computation|false_computation)"
                   r"=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_TO_APPLY = re.compile(r"\bto_apply=%?([\w.\-]+)")
# never an operation of the device's timeline: no work of their own
_NO_WORK = frozenset({"parameter", "get-tuple-element", "tuple", "constant",
                      "bitcast", "after-all", "partition-id", "replica-id"})


def part_of_path(op_name: str) -> str:
    """The innermost ``MODEL_PARTS`` name on an ``op_name`` path."""
    for piece in reversed(op_name.split("/")):
        if piece in _PARTS:
            return piece
    return UNSCOPED


def _computations(text: str) -> tuple[str, str | None, dict[str, list]]:
    """``(module name, entry computation, {computation: [(instruction,
    opcode, line)]})`` of a compiled executable's text."""
    module = ""
    computations: dict[str, list] = {}
    entry, current = None, None
    for line in text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = computations.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            elif not module:
                m = _MODULE.match(line)
                if m:
                    module = m.group(1)
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if m:
            op = _OPCODE.search(line, m.end())
            current.append((m.group(1), op.group(1) if op else "", line))
    return module, entry, computations


def _operations(entry, computations):
    """``(computation, instruction, opcode, line)`` of every instruction of
    the computations that run as operations of their own, from the entry
    down."""
    seen, todo = set(), [entry] if entry else []
    while todo:
        name = todo.pop()
        if name in seen or name not in computations:
            continue
        seen.add(name)
        for ins, opcode, line in computations[name]:
            todo.extend(_RUNS.findall(line))
            for group in _BRANCHES.findall(line):
                todo.extend(n.strip().lstrip("%") for n in group.split(","))
            if opcode == "call":
                todo.extend(_TO_APPLY.findall(line))
            elif opcode == "async-start":
                todo.extend(_CALLS.findall(line))
            yield name, ins, opcode, line


def parts_of_text(text: str) -> tuple[str, dict[str, str]]:
    """``(module name, {instruction: part})`` of a compiled executable's
    text (``jax.stages.Compiled.as_text()``)."""
    module, entry, computations = _computations(text)
    parts: dict[str, str] = {}
    for _, ins, opcode, line in _operations(entry, computations):
        if opcode in _NO_WORK:
            continue
        m = _OP_NAME.search(line)
        parts[ins] = part_of_path(m.group(1)) if m else UNSCOPED
    return module, parts


# ``dtype[dims]`` of an instruction's result, behind its ``=``
_RESULT = re.compile(r" = (\w+)\[([\d,]*)\](\{[^ ]*\})? ")
_LEAF = re.compile(r"op_name=\"params\[\\?'(\w+)\\?'\]\"")
_OPERAND = re.compile(r"%([\w.\-]+)")
# what moves a value and computes nothing
_MOVES = frozenset({"dynamic-slice", "slice", "copy", "transpose"})
_PRODUCTS = frozenset({"convolution", "dot", "custom-call"})
_ITEM = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
         "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8}


def _result(line: str):
    """``(dtype, dims)`` of an instruction's array result, else None."""
    m = _RESULT.search(line)
    if not m:
        return None
    return m.group(1), tuple(int(d) for d in m.group(2).split(",") if d)


def _operands(line: str) -> list[str]:
    """The names an instruction's own operand list holds."""
    m = _INSTRUCTION.match(line)
    op = m and _OPCODE.search(line, m.end())
    if not op:
        return []
    depth, i = 1, op.end()
    while i < len(line) and depth:
        depth += {"(": 1, ")": -1}.get(line[i], 0)
        i += 1
    return _OPERAND.findall(line, op.end(), i)


def _refines(fine, coarse) -> bool:
    """Whether every dimension of ``coarse`` is a product of consecutive
    dimensions of ``fine``: ``fine`` is a reshape of ``coarse`` that splits
    dimensions and moves nothing."""
    i = 0
    for c in coarse:
        n = 1
        while n < c and i < len(fine):
            n, i = n * fine[i], i + 1
        if n != c:
            return False
    return i == len(fine)


def _is_layer(dims, layer) -> bool:
    """Whether a result of ``dims`` can be one layer ``layer`` of a leaf:
    the same dimensions in any order (a relayout), or the layer with
    dimensions split (a view: [32, 128, 4096] of [4096, 4096]), ones aside.
    Merged dimensions are not taken: a pool of as many elements
    ([131072, 128] beside a [4096, 32, 128] leaf) would pass for one."""
    a = tuple(d for d in dims if d != 1)
    b = tuple(d for d in layer if d != 1)
    return sorted(a) == sorted(b) or _refines(a, b)


def weight_copies(text: str) -> list[dict]:
    """The operations of a compiled executable's text that hand on ONE
    LAYER OF A STACKED WEIGHT LEAF and compute nothing: a ``dynamic-slice``,
    ``slice`` or ``copy`` instruction that runs as an operation of its own,
    or a fusion that holds only such moves and no product, whose result has
    the type and the dimensions of one layer of a leaf (``_is_layer``: in any
    order, or split). A product
    that reads its layer's matrix where it lies in the stack holds the slice
    INSIDE its own fusion and is not listed; a row here is a matrix written
    out (into on-chip memory where the result's layout says ``S(1)``) at the
    memory's rate before anything multiplies by it (PERF.md section 5,
    PR 56).

    The leaves are the entry computation's parameters named
    ``params['<leaf>']`` with a layer axis and a matrix or more behind it
    (rank 3 up). A row is ``{"instruction", "opcode", "result", "bytes",
    "leaves" (the leaves of that layer's type and size), "computation",
    "async" (an ``async-start``'s computation, or a ``slice-done`` /
    ``copy-done``: the compiler runs it beside other work), "consumers":
    [{"instruction", "opcode", "op_name"}]}``; a consumer reached through
    bitcasts is the instruction behind them. Rows come in the text's
    order."""
    _, entry, computations = _computations(text)
    stacks = []  # (leaf, dtype, a layer's dimensions)
    for _, opcode, line in computations.get(entry, ()):
        leaf, res = _LEAF.search(line), _result(line)
        if opcode == "parameter" and leaf and res and len(res[1]) >= 3:
            stacks.append((leaf.group(1), res[0], res[1][1:]))

    def leaves_of(res) -> list:
        return [leaf for leaf, dtype, layer in stacks if dtype == res[0]
                and math.prod(layer) == math.prod(res[1])
                and _is_layer(res[1], layer)]

    def only_moves(fusion_line: str, key) -> bool:
        """A fusion whose result is a move of a layer: one of ``_MOVES``
        gives a value of the layer's size, and nothing in it multiplies."""
        called = _CALLS.search(fusion_line)
        body = computations.get(called.group(1), ()) if called else ()
        moved = False
        for _, opcode, line in body:
            if opcode in _PRODUCTS:
                return False
            res = _result(line)
            if res and (res[0], math.prod(res[1])) == key:
                if opcode in _MOVES:
                    moved = True
                elif opcode not in _NO_WORK:
                    return False  # arithmetic on a value of that size
        return moved

    # a slice the compiler runs beside other work is a computation of its
    # own: its consumers are those of the caller's ``async-done``
    started = {called: (comp, name) for comp, body in computations.items()
               for name, op, ln in body if op == "async-start"
               for called in _CALLS.findall(ln)}
    rows = []
    for comp, ins, opcode, line in _operations(entry, computations):
        if opcode not in ("dynamic-slice", "slice", "copy", "fusion",
                          "slice-done", "copy-done"):
            continue
        res = _result(line)
        leaves = leaves_of(res) if res else []
        key = res and (res[0], math.prod(res[1]))
        if not leaves or opcode == "fusion" and not only_moves(line, key):
            continue
        home, first = started.get(comp, (comp, ins))
        consumers, todo, seen = [], [first], {first}
        while todo:
            producer = todo.pop()
            for name, op, ln in computations[home]:
                if name in seen or producer not in _operands(ln):
                    continue
                seen.add(name)
                if op in ("bitcast", "async-done"):
                    todo.append(name)
                    continue
                m = _OP_NAME.search(ln)
                consumers.append({"instruction": name, "opcode": op,
                                  "op_name": m.group(1) if m else ""})
        rows.append({
            "instruction": ins, "opcode": opcode,
            "result": line[line.index(" = ") + 3:].split(" ", 1)[0],
            "bytes": key[1] * _ITEM.get(key[0], 0), "leaves": leaves,
            "computation": comp,
            "async": comp in started or opcode.endswith("-done"),
            "consumers": consumers})
    return rows


class ProgramParts:
    """The maps of an engine's compiled step programs, by module name."""

    def __init__(self) -> None:
        self.maps: dict[str, dict[str, str]] = {}
        self.stale: set[str] = set()
        self.signatures: dict[str, int] = {}

    def add(self, text: str) -> str:
        """Fold in one compiled signature's text; returns its module name."""
        module, parts = parts_of_text(text)
        self.signatures[module] = self.signatures.get(module, 0) + 1
        if not _PARTS.intersection(parts.values()):
            self.stale.add(module)
        held = self.maps.setdefault(module, {})
        for ins, part in parts.items():
            if held.setdefault(ins, part) != part:
                held[ins] = AMBIGUOUS
        return module

    def series(self) -> list[tuple[dict, int]]:
        """``[(labels, value)]`` of ``llmd_tpu:program_part_ops``."""
        out = []
        for module in sorted(self.maps):
            by_part: dict[str, list] = {}
            for ins, part in self.maps[module].items():
                by_part.setdefault(part, []).append(ins)
            for part in sorted(by_part):
                ops = sorted(by_part[part])
                out.append(({"program": module, "part": part,
                             "stale": "1" if module in self.stale else "0",
                             "ops": " ".join(ops)}, len(ops)))
        return out

