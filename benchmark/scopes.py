"""Put each instruction of a compiled search program down to its stage.

The program names its stages with ``jax.named_scope`` (the program's
``core.tracing.annotate``): a scope only adds a component to the
``op_name`` metadata of the operations traced inside it, and the
optimized HLO text of the executable keeps that path on every fusion,
custom call and plain instruction (``jit(knn)/while/body/closed_call/
raft_tpu.brute_force.distance/dot_general``). The trace's op events are
named by the same instructions (``fusion.15``), so a map from
instruction name to stage, taken from the executable that ran, splits
the program's device time by stage.

The map covers the computations that run as ops (the entry, loop bodies
and conditions, called computations), not the bodies of fusions or the
reducers and comparators that ops apply. For each instruction:

- its stage is the innermost known stage scope on its ``op_name`` path;
- a fusion whose own metadata names no stage takes the stage most of
  its fused instructions name;
- an instruction with no scope (a copy that layout assignment
  inserted, the slice of a loop's input) takes the stage of its
  consumer, and failing that the stage of its operand;
- whatever stays unmapped is ``other``.
"""

from __future__ import annotations

import dataclasses
import re
from collections import Counter
from typing import Dict, List, Mapping, Optional

from benchmark import trace

#: scope name -> stage of the exact search's program. A scope that is
#: not in a program's table is no stage of that program (an IVF scan's
#: select keeps the scan's stage, though it runs under
#: ``raft_tpu.select_k``).
KNN_STAGES = {
    "raft_tpu.brute_force.distance": "distance",
    "raft_tpu.brute_force.tile_cut": "tile_cut",
    "raft_tpu.brute_force.merge": "merge",
    "raft_tpu.brute_force.prepare": "prepare",
    "raft_tpu.select_k": "select_k",
}
OTHER = "other"

#: instructions that run no work of their own on the device
TRIVIAL = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "while", "conditional", "call", "after-all", "opt-barrier"})

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r" ([a-z][\w-]*)\(")
_REF = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\b(calls|to_apply|called_computations)="
                    r"(\{[^}]*\}|%?[\w.\-]+)")
_SCALAR = re.compile(r"^[a-z]\w*\[\]")


@dataclasses.dataclass
class Instruction:
    name: str
    opcode: str
    operands: List[str]
    op_name: str
    calls: Dict[str, List[str]]
    scalar: bool

    @property
    def trivial(self) -> bool:
        """No work of its own: a structural instruction, or the scalar
        bookkeeping of a loop (its counter and its condition)."""
        return self.opcode in TRIVIAL or self.scalar


def _operand_text(text: str, start: int) -> str:
    """The text inside the parenthesis that opens at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return text[start + 1:i]
    return text[start + 1:]


def parse(text: str) -> Dict[str, List[Instruction]]:
    """Computation name -> its instructions, in program order."""
    comps: Dict[str, List[Instruction]] = {}
    current: Optional[str] = None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            current = m.group(1) if m else None
            if current is not None:
                comps[current] = []
            continue
        m = _INSTRUCTION.match(line)
        if current is None or not m:
            continue
        name, rest = m.groups()
        padded = " " + rest
        op = _OPCODE.search(padded)
        if not op:
            continue
        attrs = padded[op.end() - 1:]
        args = _operand_text(attrs, 0)
        tail = attrs[len(args) + 2:]
        meta = _OP_NAME.search(tail)
        calls: Dict[str, List[str]] = {}
        for key, val in _CALLS.findall(tail.split("metadata=")[0]):
            calls.setdefault(key, []).extend(
                _REF.findall(val) or [val.lstrip("%")])
        comps[current].append(Instruction(
            name=name, opcode=op.group(1), operands=_REF.findall(args), op_name=meta.group(1) if meta
            else "", calls=calls, scalar=bool(_SCALAR.match(rest))))
    return comps


def stage_of(op_name: str, stages: Mapping[str, str]) -> Optional[str]:
    """The innermost known stage scope on an ``op_name`` path."""
    for part in reversed(op_name.split("/")):
        if part in stages:
            return stages[part]
    return None


def _hidden(comps: Dict[str, List[Instruction]]) -> set:
    """Computations that do not run as ops of their own: fusion bodies,
    and what an op applies (a reducer, a sort's comparator)."""
    hidden = set()
    for instrs in comps.values():
        for ins in instrs:
            for key, names in ins.calls.items():
                if key == "calls" and ins.opcode == "fusion":
                    hidden.update(names)
                elif key == "to_apply" and ins.opcode != "call":
                    hidden.update(names)
                elif key == "called_computations" and \
                        ins.opcode == "custom-call":
                    hidden.update(names)
    return hidden


def executed(comps: Dict[str, List[Instruction]]) -> List[Instruction]:
    """The instructions of the computations that run as ops."""
    hidden = _hidden(comps)
    return [ins for name, instrs in comps.items() if name not in hidden
            for ins in instrs]


def stage_map(text: str, stages: Mapping[str, str]) -> Dict[str, str]:
    """Instruction name -> stage, for every instruction that runs as an
    op in the optimized HLO ``text``; unmapped ones are ``other``."""
    comps = parse(text)
    run = executed(comps)
    found: Dict[str, str] = {}
    for ins in run:
        stage = stage_of(ins.op_name, stages)
        if stage is None and ins.opcode == "fusion":
            inner = Counter(
                s for body in ins.calls.get("calls", [])
                for s in (stage_of(i.op_name, stages)
                          for i in comps.get(body, [])) if s)
            if inner:
                stage = inner.most_common(1)[0][0]
        if stage is not None:
            found[ins.name] = stage
    users: Dict[str, List[str]] = {}
    for ins in run:
        for operand in ins.operands:
            users.setdefault(operand, []).append(ins.name)
    operands = {ins.name: ins.operands for ins in run}
    by_name = {ins.name: ins for ins in run}

    def near(name: str, table) -> List[str]:
        """The nearest instructions that do work, through ``table``
        (users or operands), looking through tuples and their
        elements."""
        out, seen, todo = [], set(), list(table.get(name, ()))
        while todo:
            other = todo.pop(0)
            if other in seen or other not in by_name:
                continue
            seen.add(other)
            if by_name[other].trivial:
                todo.extend(table.get(other, ()))
            else:
                out.append(other)
        return out

    open_ = [ins.name for ins in run
             if ins.name not in found and not ins.trivial]
    links = {name: (near(name, users), near(name, operands))
             for name in open_}

    def inherit(side: int) -> bool:
        changed = False
        for name in open_:
            if name in found:
                continue
            for other in links[name][side]:
                if other in found:
                    found[name] = found[other]
                    changed = True
                    break
        return changed

    while inherit(0) or inherit(1):
        pass
    return {ins.name: found.get(ins.name, OTHER) for ins in run}


def scopes_in(text: str, prefix: str) -> set:
    """The scope names that start with ``prefix`` on the ``op_name``
    paths of ``text``."""
    return {part for m in _OP_NAME.finditer(text)
            for part in m.group(1).split("/") if part.startswith(prefix)}


def declared(module, prefix: str) -> set:
    """The scope names a program module declares: its module-level
    strings that start with ``prefix``."""
    return {v for v in vars(module).values()
            if isinstance(v, str) and v.startswith(prefix)}


# -- the exact search's program -------------------------------------------

PROGRAM = "jit_knn"
#: what each per-stage metric of the exact search sums
GROUPS = {"distance": ("distance",),
          "select": ("tile_cut", "merge", "select_k")}


#: the exact search's own scopes, and its entry point's annotation
_KNN_PREFIX = "raft_tpu.brute_force."
_KNN_ENTRY = "raft_tpu.brute_force.knn"


def knn_stage_map(ctx) -> Optional[Dict[str, str]]:
    """The stage map of the exact search's program at the cell's shapes:
    lowered and compiled as the window's calls were (the compile caches
    hand back the executable that ran). None where the program declares
    no stage scopes of its own.

    The persistent cache's key leaves the metadata out, so a cache
    filled by code with other scopes hands back an executable whose
    ``op_name`` paths are not this program's: that raises, since its
    split would be wrong."""
    from raft_tpu.neighbors import brute_force

    jitted = brute_force.knn.__wrapped__  # under the traced wrapper
    text = jitted.lower(ctx.index, ctx.session.batches[0],
                        ctx.config["search"]["k"]).compile().as_text()
    own = declared(brute_force, _KNN_PREFIX) - {_KNN_ENTRY}
    found = scopes_in(text, _KNN_PREFIX) - {_KNN_ENTRY}
    if not own and not found:
        return None
    if not found or not found <= own:
        raise RuntimeError(
            f"{PROGRAM}'s compiled scopes {sorted(found)} are not the "
            f"ones its code declares {sorted(own)}: the compile cache "
            "holds an executable built from other code; clear it")
    return stage_map(text, KNN_STAGES)


def group_ms(ops, stages: Mapping[str, str], window, calls: int
             ) -> Dict[str, float]:
    """Device ms per call of each group of ``GROUPS`` and of ``other``
    (every other stage and every unmapped instruction), from ``ops``
    clipped to ``window``; a control-flow op, whose event spans the ops
    of its body, is left out."""
    lo, hi = window
    group = {stage: name for name, members in GROUPS.items()
             for stage in members}
    total = {name: 0.0 for name in list(GROUPS) + [OTHER]}
    for op in ops:
        if op.opcode in trace._CONTAINERS:
            continue
        seconds = max(0.0, min(op.end, hi) - max(op.start, lo))
        total[group.get(stages.get(op.name, OTHER), OTHER)] += seconds
    return {name: 1e3 * secs / calls for name, secs in total.items()}


def knn_group_ms(ctx) -> Optional[Dict[str, float]]:
    """Device ms per call of the exact search's stages in the traced
    window (``group_ms``), over the window's calls as
    ``knn_roofline`` counts them; None where the trace holds no run of
    the program or the program has no stage scopes. Computed once per
    run and kept on ``ctx``."""
    if not hasattr(ctx, "knn_group_ms"):
        ops = [op for op in ctx.trace.ops if op.module == PROGRAM]
        calls = len(ctx.window.batches)
        stages = knn_stage_map(ctx) if ops and calls else None
        ctx.knn_group_ms = (None if stages is None else
                            group_ms(ops, stages, ctx.trace.window, calls))
    return ctx.knn_group_ms
