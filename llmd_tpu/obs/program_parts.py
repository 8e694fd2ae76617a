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


def parts_of_text(text: str) -> tuple[str, dict[str, str]]:
    """``(module name, {instruction: part})`` of a compiled executable's
    text (``jax.stages.Compiled.as_text()``)."""
    module = ""
    computations: dict[str, list] = {}  # name -> [(instruction, opcode, line)]
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
    parts: dict[str, str] = {}
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
            if opcode in _NO_WORK:
                continue
            m = _OP_NAME.search(line)
            parts[ins] = part_of_path(m.group(1)) if m else UNSCOPED
    return module, parts


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

