"""Device scopes: names on the engine's device work, read back from the
compiled program.

The engine wraps its device work in ``jax.named_scope`` names declared
in ``registry.DEVICE_SCOPES`` (:func:`device_scope` refuses any other).
A scope changes only the HLO metadata, never what XLA fuses: it reaches
the compiled module as ``metadata={op_name="jit(fn)/.../superstep.step/
bfs.pull/localops.frontier_pull/ell_in.b3/gather"}``.  A device trace
names each operation by its HLO instruction (``fusion.82``) and carries
no metadata, so :func:`op_scopes` joins the two: it parses a compiled
executable's ``as_text()`` into ``{instruction name: scope path}``,
where the path keeps only the declared components of the op_name
(``superstep.step/bfs.pull/localops.frontier_pull/ell_in.b3``) and
``unscoped`` stands for none.

Resolution, for an instruction:

  * a fusion takes the op_name of its fused computation's root (what
    XLA copies onto the fusion), else its own, else that of the first
    fused instruction that has one;
  * another instruction takes its own op_name;
  * one XLA added with no op_name at all (a copy of the loop carry, a
    wrapped reduce-window) takes the scope of its first operand that
    resolves, else that of the instruction calling its computation (a
    ``while``, ``conditional`` or ``call``).

:func:`keep` records a compiled executable (``CompiledProgram.lower``
does, for whatever its caller compiles); :func:`compiled_scopes` parses
the recorded ones by module name (``jit_fn``, the name a device trace's
``XLA Modules`` line gives), so a reader of a profiler trace can name
the device time of the programs the engine compiled.

Stdlib only, apart from the lazy ``jax`` import of :func:`device_scope`.
"""

from __future__ import annotations

import re
import threading
from collections import deque

from repro.obs.registry import declared

UNSCOPED = "unscoped"

_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*")
_OPCODE = re.compile(r"\s*([\w\-]+)\(")
_REF = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPER = re.compile(r"^[\w\-]+\((.*)\)$")


def device_scope(name: str):
    """``jax.named_scope(name)`` for a declared device scope; usable as
    a ``with`` block or a decorator."""
    if not declared(name, "device"):
        raise KeyError(f"{name!r} is not a declared device scope (add it "
                       "to obs.registry.DEVICE_SCOPES)")
    import jax
    return jax.named_scope(name)


def scope_path(op_name: str) -> str:
    """The declared components of an ``op_name``, joined with ``/``
    (``jit(fn)/while/body/superstep.step/add`` -> ``superstep.step``);
    a transform's wrapper counts as what it wraps (a batched program's
    ``vmap(superstep.loop)`` as ``superstep.loop``)."""
    kept = []
    for part in op_name.split("/"):
        while (m := _WRAPPER.match(part)) is not None:
            part = m.group(1)
        if declared(part, "device"):
            kept.append(part)
    return "/".join(kept) or UNSCOPED


def _closing(text: str, start: int) -> int:
    """Index just past the parenthesis group opening at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def _parse(hlo_text: str):
    """Instructions as ``{name: (computation, is_root, opcode, operands,
    called, op_name)}`` and computations as ``{name: [instruction
    names]}``."""
    instrs, comps = {}, {}
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None or comp is None:
            h = _HEADER.match(line) if m is None else None
            if h is not None:
                comp = h.group(2)
                comps[comp] = []
            elif line.strip() == "}":
                comp = None
            continue
        rest = line[m.end():]
        if rest.startswith("("):                # a tuple type
            rest = rest[_closing(rest, 0):]
        else:
            rest = rest.split(" ", 1)[1] if " " in rest else ""
        op = _OPCODE.match(rest)
        if op is None:
            continue
        end = _closing(rest, op.end() - 1)
        meta = _OP_NAME.search(rest, end)
        instrs[m.group(2)] = (comp, m.group(1) is not None, op.group(1),
                              _REF.findall(rest[op.end():end]),
                              _REF.findall(rest[end:]),
                              meta.group(1) if meta else None)
        comps[comp].append(m.group(2))
    return instrs, comps


def op_scopes(hlo_text: str) -> dict[str, str]:
    """``{instruction name: declared scope path or "unscoped"}`` for
    every instruction of a compiled module's text (module doc)."""
    instrs, comps = _parse(hlo_text)
    roots = {c: next((i for i in names if instrs[i][1]), None)
             for c, names in comps.items()}
    caller = {}
    for name, (_, _, _, _, called, _) in instrs.items():
        for c in called:
            if c in comps:
                caller.setdefault(c, name)
    memo: dict[str, str | None] = {}

    def fused_op_name(name):
        _, _, opcode, _, called, own = instrs[name]
        inner = [c for c in called if c in comps]
        if opcode != "fusion" or not inner:
            return own
        root = roots[inner[0]]
        for cand in [fused_op_name(root) if root else None, own] + [
                instrs[i][5] for c in inner for i in comps[c]]:
            if cand is not None:
                return cand
        return None

    def resolve(name, seen=()):
        if name in memo:
            return memo[name]
        if name in seen or name not in instrs:
            return None
        seen = seen + (name,)
        op_name = fused_op_name(name)
        if op_name is not None:
            scope = scope_path(op_name)
        else:
            comp, _, _, operands, _, _ = instrs[name]
            scope = next((s for s in (resolve(o, seen) for o in operands)
                          if s is not None), None)
            if scope is None and comp in caller:
                scope = resolve(caller[comp], seen)
        memo[name] = scope
        return scope

    return {name: resolve(name) or UNSCOPED for name in instrs}


def module_name(hlo_text: str) -> str:
    """``HloModule jit_fn, ...`` -> ``jit_fn``."""
    m = re.match(r"\s*HloModule\s+([\w.\-]+)", hlo_text)
    return m.group(1) if m else ""


# the executables the engine compiled most recently, newest last
_KEPT: deque = deque(maxlen=8)
_PARSED: dict[int, tuple[str, dict[str, str]]] = {}
_LOCK = threading.Lock()


def keep(executable) -> None:
    """Record a compiled executable (anything with ``as_text()``) for
    :func:`compiled_scopes`; the record holds the newest eight."""
    with _LOCK:
        _KEPT.append(executable)
        live = {id(e) for e in _KEPT}
        for key in [k for k in _PARSED if k not in live]:
            del _PARSED[key]


def compiled_scopes() -> dict[str, dict[str, str]]:
    """``{module name: op_scopes(text)}`` of the kept executables; where
    two share a module name, the newer one's."""
    with _LOCK:
        kept = list(_KEPT)
    out = {}
    for exe in kept:
        key = id(exe)
        if key not in _PARSED:
            text = exe.as_text()
            _PARSED[key] = (module_name(text), op_scopes(text))
        name, scopes = _PARSED[key]
        out[name] = scopes
    return out
