"""How many times one run of a program calls a jitted function, read from
the program's jaxpr: each call of the function named `name` counts once,
times the length of every scan around it.

A call under a `cond` or inside a `while` runs a number of times that
depends on the data, so no count is given (None): a metric that needs it
then reports nothing rather than a number the program may not have run.
"""
from __future__ import annotations

from typing import Iterator, Optional

# primitives whose body runs a number of times that depends on the data
_DATA_DEPENDENT = ("while", "cond")


def _subjaxprs(params: dict) -> Iterator:
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for v in params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, Jaxpr):
                yield x


def count_calls(jaxpr, name: str) -> Optional[int]:
    """Calls of the jitted function `name` in one run of `jaxpr`, or None
    where the count depends on the data."""
    total = 0
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim in ("pjit", "jit") and eqn.params.get("name") == name:
            total += 1
            continue
        inner = 0
        for sub in _subjaxprs(eqn.params):
            n = count_calls(sub, name)
            if n is None:
                return None
            inner += n
        if inner and prim in _DATA_DEPENDENT:
            return None
        total += inner * (eqn.params["length"] if prim == "scan" else 1)
    return total


def calls_per_run(fn, *args, name: str) -> Optional[int]:
    """`count_calls` on the jaxpr of ``fn(*args)`` (traced, not run)."""
    import jax

    return count_calls(jax.make_jaxpr(fn)(*args).jaxpr, name)
