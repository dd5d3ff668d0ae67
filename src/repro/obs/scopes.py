"""Layer names on the device trace, and how to find them there.

The engine and the halo exchange mark each layer boundary with
``jax.named_scope`` (DESIGN.md §15.4). A scope is metadata: it lands in
the compiled HLO as ``metadata={op_name="jit(f)/.../engine.pad/pad"}``
and leaves the program's ops, fusions and layouts as they were. Each
engine ``pallas_call`` carries a kernel name of its family, which
becomes the name of its custom-call instruction (``%repro_window.1``).

A profiler trace names each device op by its HLO instruction
(``%pad.2 = f32[...] pad(...)``) and carries no ``op_name``, so a
reader joins the trace to the layers through the compiled module's
text: :func:`instruction_layers` maps each instruction to its
``op_name`` path, and :func:`layer_of` picks a path's innermost scope of
one family (``"engine."`` or ``"halo."``).

Stdlib only, so any module of the package may import it.
"""
from __future__ import annotations

import re

# Kernel names, one per engine kernel family (the custom call's name).
WINDOW_KERNEL = "repro_window"
WGRAD_KERNEL = "repro_wgrad"
SCAN_KERNEL = "repro_scan"

# Engine lowering: the input's origin and round-up pad (and the padding
# of epilogue operands), the pallas_call, the crop back to the output.
ENGINE_PAD = "engine.pad"
ENGINE_KERNEL = "engine.kernel"
ENGINE_CROP = "engine.crop"
# Sharded halo exchange, per shard: the halo-extended block (ppermutes
# and concatenates), the interior engine call, the frame strips and
# their engine calls, the splice of the strips over the interior.
HALO_EXCHANGE = "halo.exchange"
HALO_INTERIOR = "halo.interior"
HALO_FRAME = "halo.frame"
HALO_SPLICE = "halo.splice"
LAYERS = (ENGINE_PAD, ENGINE_KERNEL, ENGINE_CROP, HALO_EXCHANGE,
          HALO_INTERIOR, HALO_FRAME, HALO_SPLICE)

_INSTR = re.compile(r"\s*(?:ROOT\s+)?%([^\s=]+)\s*=\s*")
_OPCODE = re.compile(r"([a-z][a-z0-9-]*)\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*?\bop_name="((?:[^"\\]|\\.)*)"')
_OPERAND = re.compile(r"%([^\s,()]+)")
# Instructions that hold data rather than work: never a source of a path.
_INPUTS = ("parameter", "constant")


def _closing(text: str, i: int) -> int:
    """Index just past the parenthesis that closes ``text[i] == '('``."""
    depth = 0
    for j in range(i, len(text)):
        if text[j] == "(":
            depth += 1
        elif text[j] == ")":
            depth -= 1
            if depth == 0:
                return j + 1
    return len(text)


def _parse(line: str):
    """``(name, opcode, operand names, op_name or None)`` of one
    instruction line, or None for any other line."""
    m = _INSTR.match(line)
    if m is None:
        return None
    rest = line[m.end():]
    # skip the result type: a tuple type is parenthesized
    i = _closing(rest, 0) if rest.startswith("(") else rest.find(" ")
    op = _OPCODE.search(rest, max(i, 0))
    if op is None:
        return None
    args = rest[op.end() - 1:_closing(rest, op.end() - 1)]
    path = _OP_NAME.search(rest)
    return (m[1], op[1], _OPERAND.findall(args),
            path[1] if path else None)


def instruction_layers(hlo_text: str) -> dict[str, str]:
    """``{instruction name: op_name path}`` of a compiled HLO module's
    text (``compiled.as_text()``), over all its computations.

    An instruction without an ``op_name`` of its own (a copy, slice or
    concatenation the compiler inserted) takes the path of the first of
    its operands that has one, through operands that have none, and
    failing that the path of its first user that has one. Parameters
    and constants lend no path.
    """
    parsed = [p for p in map(_parse, hlo_text.splitlines()) if p]
    opcode = {n: op for n, op, _, _ in parsed}
    operands = {n: args for n, _, args, _ in parsed}
    users: dict[str, list[str]] = {}
    for n, _, args, _ in parsed:
        for a in args:
            users.setdefault(a, []).append(n)
    own = {n: p for n, _, _, p in parsed if p is not None}

    def inherit(n, edges, seen):
        for m in edges.get(n, ()):
            if m in seen or opcode.get(m) in _INPUTS:
                continue
            seen.add(m)
            if m in own:
                return own[m]
            found = inherit(m, edges, seen)
            if found is not None:
                return found
        return None

    paths = dict(own)
    for n, op, _, p in parsed:
        if p is None and op not in _INPUTS:
            found = (inherit(n, operands, {n})
                     or inherit(n, users, {n}))
            if found is not None:
                paths[n] = found
    return paths


def layer_of(path: str | None, prefix: str) -> str | None:
    """The innermost scope of ``path`` that starts with ``prefix``
    (``"engine."`` or ``"halo."``), or None. Transform wrappers such as
    ``transpose(jvp(engine.pad))`` are seen through."""
    found = None
    for part in re.split(r"[/()]", path or ""):
        if part.startswith(prefix):
            found = part
    return found
