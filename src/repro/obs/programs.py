"""The device programs the engine compiled, and the named scope of each of
their operations.

A profiler names a device operation by its HLO instruction
(``fusion.151``) in its program (``jit_run``).  The ``jax.named_scope``
the operation came from (``wavefront.box``) is in the compiled program's
metadata, its ``op_name``; a fusion carries its root's.  A trace reader
that keeps only the operation's name can still attribute it:
:func:`note` remembers a jitted function with the abstract arguments of
a call it made, and :func:`op_scopes` reads each remembered program's
compiled text.  The jit caches already hold every noted program, so
reading it lowers and compiles nothing again.

The engine notes each program it runs (``core/session.py``); a note holds
the function weakly, so a dropped engine's programs drop out.
"""
from __future__ import annotations

import re
import weakref

import jax

__all__ = ["note", "op_scopes", "parse_hlo"]

#: id(fn) -> (weak reference to fn, the abstract arguments of one call)
_NOTED: dict = {}

_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_OP = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?"
                 r"metadata=\{[^}]*?op_name=\"([^\"]*)\"", re.M)


def _abstract(x):
    if not isinstance(x, jax.Array):
        return x
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding if x.committed else None)


def note(fn, *args) -> None:
    """Remember the jitted ``fn`` with the shapes, dtypes and placement of
    ``args``, a call it has made; a second note of ``fn`` is a dict
    lookup."""
    key = id(fn)
    held = _NOTED.get(key)
    if held is not None and held[0]() is fn:
        return
    _NOTED[key] = (weakref.ref(fn, lambda _: _NOTED.pop(key, None)),
                   jax.tree_util.tree_map(_abstract, args))


def parse_hlo(text: str) -> tuple:
    """``(module name, {instruction: op_name})`` of one compiled HLO
    module's text."""
    found = _MODULE.search(text)
    return (found.group(1) if found else "",
            {m.group(1): m.group(2) for m in _OP.finditer(text)})


def op_scopes() -> dict:
    """``{HLO module name: {instruction: op_name}}`` of every noted
    program still alive.  ``op_name`` is the ``/``-separated path of jit
    names, transformations and named scopes; an instruction without one
    is left out."""
    out: dict = {}
    for ref, args in list(_NOTED.values()):
        fn = ref()
        if fn is None:
            continue
        name, ops = parse_hlo(fn.lower(*args).compile().as_text())
        out.setdefault(name, {}).update(ops)
    return out
