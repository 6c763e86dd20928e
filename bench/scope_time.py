"""Device seconds under one of the program's name scopes, wherever an
op's scope path holds it: plain (`MLA`), as forward and rematerialised
ops carry it, or wrapped by JAX's transformations (`jvp(MLA)`,
`transpose(jvp(MLA))`), as some forward and backward ops do. The
trace's reduction (`trace_reduce.scope_parts`) splits a path on `/`
only, so the wrapped parts are its keys as they stand."""
from __future__ import annotations

import re

_WRAPPED = re.compile(r"[\w.\-]+\((.*)\)")


def unwrap(part: str) -> str:
    """`transpose(jvp(MLA))` -> `MLA`."""
    while (m := _WRAPPED.fullmatch(part)) is not None:
        part = m.group(1)
    return part


def scope_seconds(reduced, name: str) -> float:
    """Seconds of the ops whose path has a part that unwraps to `name`
    (the program enters each such scope once on a path, never nested
    in itself, so no op is counted twice)."""
    return sum(s for part, s in reduced.scope_s.items()
               if unwrap(part) == name)
