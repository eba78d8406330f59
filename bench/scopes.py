"""The program's layer of each op of a compiled solve.

The solves name their layers with ``jax.named_scope``: ``solver.spmv``,
``solver.vector`` and ``solver.precond`` in the solver loop,
``mg.l<k>.smooth``, ``.residual``, ``.restrict`` and ``.prolong`` for
multigrid level ``k``, and ``dist.halo`` and ``dist.remote`` in the
distributed SpMV. XLA keeps the scopes in the ``op_name`` metadata of
every instruction it lowers them to, fusions and ops hoisted out of a
loop included: ``op_name="jit(solve)/while/body/solver.spmv/..."`` in the
compiled module's text (``compiled.as_text()``). The innermost scope in
that path is the op's layer. An op with no ``op_name``, or none of these
scopes in it, has no layer (``None``).

A TPU profile names each op by its HLO text without the metadata
(``%dia_spmv.9 = f32[8788,128]{...} custom-call(...)``), so an op of a
profile is placed by its instruction name, unique in its module:
``instruction_scopes(compiled_text)[instruction(event_text)]``.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"(?<![\w.])(solver\.[a-z]+|mg\.l\d+\.[a-z]+|dist\.[a-z]+)"
                    r"(?![\w.])")


def scope_of(text: str) -> Optional[str]:
    """The innermost program scope in the ``op_name`` of an instruction's
    text."""
    m = _OP_NAME.search(text)
    if m is None:
        return None
    found = _SCOPE.findall(m.group(1))
    return found[-1] if found else None


def instruction(text: str) -> str:
    """``%dia_spmv.9 = f32[...] custom-call(...)`` -> ``dia_spmv.9``."""
    name = text.strip().partition(" = ")[0]
    return name.removeprefix("ROOT ").lstrip("%")


def _computations(hlo_text: str):
    """``({name: [instruction lines]}, entry name)`` of a module's text."""
    comps: Dict[str, list] = {}
    cur = entry = None
    for line in hlo_text.split("\n"):
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            cur = head.group(2)
            comps[cur] = []
            entry = cur if head.group(1) else entry
        elif cur is not None and line.startswith("  ") and " = " in line:
            comps[cur].append(line.strip())
    return comps, entry


def instruction_scopes(hlo_text: str) -> Dict[str, Optional[str]]:
    """``{instruction name: scope}`` of every instruction of a module."""
    comps, _ = _computations(hlo_text)
    return {instruction(line): scope_of(line)
            for lines in comps.values() for line in lines}


def loop_ops(hlo_text: str) -> List[str]:
    """The fusions, custom calls and dots that run inside the module's
    ``while`` bodies, nested loops and the branches of conditionals
    included: the ops a solve repeats. Each as its instruction line."""
    comps, entry = _computations(hlo_text)
    todo = [m.group(1) for line in comps.get(entry, [])
            for m in [re.search(r"\bbody=%?([\w.\-]+)", line)] if m]
    seen, out = set(), []
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            op = re.search(r"\s(fusion|custom-call|dot|while|conditional|call)\(",
                           line.split(" = ", 1)[1])
            if op is None:
                continue
            if op.group(1) in ("fusion", "custom-call", "dot"):
                out.append(line)
                continue
            key = {"while": r"\bbody", "call": r"\bto_apply"}.get(op.group(1))
            if key is not None:
                todo += re.findall(key + r"=%?([\w.\-]+)", line)
            branches = re.search(r"branch_computations=\{([^}]*)\}", line)
            if branches:
                todo += [b.strip().lstrip("%")
                         for b in branches.group(1).split(",")]
            todo += re.findall(r"(?:true|false)_computation=%?([\w.\-]+)",
                               line)
    return out
