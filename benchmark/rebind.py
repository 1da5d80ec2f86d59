"""Functions of one benchmark file run for another family: the same code
objects with some of their module's names bound anew, so that a family
whose builder or trace reader differs from an earlier one in a name or
two runs that file's code and not a copy of it."""

from __future__ import annotations

import types


def rebound(names: dict, *fns):
    """Each of ``fns`` (functions of one module, or methods of one of its
    classes) with its module's globals and ``names`` over them; where one
    of ``fns`` is a global of that module the others call it rebound too.
    Returns them in order."""
    space = {**fns[0].__globals__, **names}
    out = [types.FunctionType(f.__code__, space, f.__name__, f.__defaults__,
                              f.__closure__) for f in fns]
    space.update((f.__name__, g) for f, g in zip(fns, out)
                 if space.get(f.__name__) is f)
    return out
