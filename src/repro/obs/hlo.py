"""Which layer of the program each compiled instruction belongs to.

``jax.named_scope`` writes its path into the ``op_name`` metadata of
every HLO instruction it covers (``jit(fwd)/encoder/block_3/msda/sample/
pallas_call``), and that metadata survives compilation. The profiler
names a device operation by its HLO instruction (``fusion.13``,
``msgs_fused_packed.23``), so a map from instruction name to scope path
splits device time by layer. Only the compiled text is read: nothing
here imports JAX or changes the program.
"""
from __future__ import annotations

import re
from collections import defaultdict

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=%]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_REF = re.compile(r"%([^\s,(){}]+)")
_SCOPE = re.compile(r"[A-Za-z_][\w.-]*")


def scope_path(op_name: str) -> str:
    """The named scopes of an ``op_name``:
    ``jit(fwd)/encoder/block_0/jit(take)/gather`` -> ``encoder/block_0``.
    Transformations (``jit(...)``), einsum specs and the primitive that
    ends the path are dropped; no scope gives ``""``."""
    parts = op_name.split("/")
    if "(" not in parts[-1]:
        parts = parts[:-1]
    return "/".join(p for p in parts if _SCOPE.fullmatch(p))


def hlo_scopes(compiled) -> dict:
    """``{hlo_instruction_name: scope_path}`` for every instruction of a
    compiled program (a ``jax.stages.Compiled``, or its ``as_text()``).

    An instruction the compiler made without metadata (a layout copy, a
    constant, a rewritten convolution) takes the scope of an operand, else
    of a user: its time belongs to the layer it feeds or finishes. ``""``
    is left where neither has a scope."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    scope, operands = {}, {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            name, rhs = m.groups()
            meta = _OP_NAME.search(rhs)
            scope[name] = scope_path(meta.group(1)) if meta else ""
            operands[name] = [r for r in _REF.findall(rhs) if r != name]
    users = defaultdict(list)
    for name, refs in operands.items():
        operands[name] = [r for r in refs if r in scope]
        for r in operands[name]:
            users[r].append(name)
    todo = [n for n, s in scope.items() if not s]
    while todo:
        left = []
        for name in todo:
            near = [scope[r] for r in operands[name] + users[name]
                    if scope[r]]
            if near:
                scope[name] = near[0]
            else:
                left.append(name)
        if len(left) == len(todo):
            break
        todo = left
    return scope
