"""Stage 1 — trace: any jittable model function to a closed jaxpr.

``trace_model(fn, *args, **kwargs)`` flattens the example arguments (arrays
or ``jax.ShapeDtypeStruct`` placeholders — tracing is shape-only, so a
132B-parameter config traces without allocating a byte), runs
``jax.make_jaxpr`` on the flattened function, and records the input/output
pytree structure so the dispatcher can later execute the jaxpr against real
arguments with the exact calling convention of ``fn``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
from jax.extend import core


@dataclasses.dataclass(frozen=True)
class TracedModel:
    """A model function frozen into a closed jaxpr + its pytree contract."""

    name: str
    closed_jaxpr: core.ClosedJaxpr
    in_tree: Any     # treedef of (args, kwargs)
    out_tree: Any    # treedef of fn's return value
    num_eqns: int    # equation count including nested jaxprs

    @property
    def jaxpr(self) -> core.Jaxpr:
        return self.closed_jaxpr.jaxpr


def subjaxprs(eqn: core.JaxprEqn):
    """Yield every (Closed)Jaxpr nested in an equation's params.

    A Pallas kernel's body is the one exception: Mosaic compiles it as a
    unit and the dispatcher binds the ``pallas_call`` as it is, so its dots
    are no dispatch sites and no walk looks inside.
    """
    if eqn.primitive.name == "pallas_call":
        return
    for val in eqn.params.values():
        if isinstance(val, core.ClosedJaxpr):
            yield val.jaxpr
        elif isinstance(val, core.Jaxpr):
            yield val
        elif isinstance(val, (tuple, list)):
            for item in val:
                if isinstance(item, core.ClosedJaxpr):
                    yield item.jaxpr
                elif isinstance(item, core.Jaxpr):
                    yield item


def count_eqns(jaxpr: core.Jaxpr) -> int:
    """Total equations in a jaxpr, recursing into nested jaxprs."""
    total = 0
    for eqn in jaxpr.eqns:
        total += 1
        for sub in subjaxprs(eqn):
            total += count_eqns(sub)
    return total


def trace_model(fn: Callable, *args, name: str | None = None,
                **kwargs) -> TracedModel:
    """Trace ``fn(*args, **kwargs)`` to a :class:`TracedModel`.

    ``args``/``kwargs`` may be pytrees of real arrays or of
    ``jax.ShapeDtypeStruct`` — only shapes and dtypes are consumed.  Static
    configuration (dataclasses, strings) must be closed over by ``fn``
    (e.g. via ``functools.partial``), exactly as with ``jax.jit``.
    """
    flat_args, in_tree = jax.tree_util.tree_flatten((args, kwargs))
    out_tree_store = []

    def flat_fn(*flat):
        call_args, call_kwargs = jax.tree_util.tree_unflatten(in_tree, flat)
        out = fn(*call_args, **call_kwargs)
        flat_out, out_tree = jax.tree_util.tree_flatten(out)
        out_tree_store.append(out_tree)
        return flat_out

    closed = jax.make_jaxpr(flat_fn)(*flat_args)
    return TracedModel(
        name=name or getattr(fn, "__name__", None) or "model",
        closed_jaxpr=closed,
        in_tree=in_tree,
        out_tree=out_tree_store[0],
        num_eqns=count_eqns(closed.jaxpr),
    )
