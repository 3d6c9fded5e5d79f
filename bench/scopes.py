"""Map a compiled module's instructions to the program's named scopes.

The profiler's ``XLA Ops`` events name an instruction but do not carry its
``op_name``; the compiled executable's ``as_text()`` has both, and its
instruction names are the trace's op names.  ``op_scopes`` reads that text
into {instruction: op_name path}; ``classify`` turns a path into the layer
it belongs to.

The scope names are literals here and not imported from the program: a
scope renamed in the program then shows as a missing reading instead of
being followed silently.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

SCOPES = ("fwd", "optimizer", "materialize", "sample")
BWD = "bwd"

_OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """{instruction name: op_name path} of every instruction in the text;
    an instruction without metadata maps to ``""``."""
    out = {}
    for line in hlo_text.splitlines():
        head, sep, rest = line.strip().partition(" = ")
        if not sep or head.startswith(("HloModule", "//")):
            continue
        name = head.split()[-1].lstrip("%")
        m = _OP_NAME.search(rest)
        out[name] = m.group(1) if m else ""
    return out


def _unwrap(component: str) -> str:
    while component.startswith("jvp(") and component.endswith(")"):
        component = component[4:-1]
    return component


def classify(path: str) -> Optional[str]:
    """``bwd`` for a path through a ``transpose(...)`` (the backward pass),
    else the innermost component that names a scope (``jvp(fwd)`` is
    ``fwd``), else None."""
    parts = path.split("/")
    if any(p.startswith("transpose(") for p in parts):
        return BWD
    for p in reversed(parts):
        if _unwrap(p) in SCOPES:
            return _unwrap(p)
    return None
