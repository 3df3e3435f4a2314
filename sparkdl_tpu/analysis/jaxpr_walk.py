"""Recursive jaxpr traversal shared by the graph passes.

A jaxpr is a tree: equations whose params may hold sub-jaxprs (cond
branches, while/scan bodies, pjit bodies, custom_vjp closures...). The
walker makes no assumptions about which primitives nest — it recurses
into *any* param value that is a (Closed)Jaxpr or a tuple/list of
them, so new jax versions' wrappers are traversed for free.
"""

from dataclasses import dataclass

# Primitives that are gang collectives: every rank must reach them in
# the same order or the gang deadlocks (ICI collectives have no
# timeout). Matched by jaxpr primitive name.
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "pmean", "all_gather", "all_to_all",
    "ppermute", "pbroadcast", "reduce_scatter", "psum_scatter",
    "pgather", "axis_index",  # axis_index is divergence *input*, not a
    # collective, but it is cheap to track for diagnostics
})

_REAL_COLLECTIVES = COLLECTIVE_PRIMS - {"axis_index"}

# Primitives that force a device->host round trip (or a host->device
# one) inside the step. jax.debug.print is its own primitive
# (``debug_print``), not a ``debug_callback``.
HOST_CALLBACK_MARKERS = ("callback", "debug_print", "outside_call",
                         "infeed", "outfeed")


def _subjaxprs(params):
    for key, val in params.items():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for i, v in enumerate(vals):
            # ClosedJaxpr has .jaxpr; raw Jaxpr has .eqns.
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield key, i, inner


def iter_eqns(jaxpr, path=()):
    """Yield ``(eqn, path)`` depth-first; ``path`` is a tuple of
    ``(primitive_name, param_key, index)`` frames naming the nesting
    (e.g. ``(("cond", "branches", 1),)`` = second cond branch)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        yield eqn, path
        for key, i, sub in _subjaxprs(eqn.params):
            yield from iter_eqns(
                sub, path + ((eqn.primitive.name, key, i),)
            )


def source_location(eqn):
    """Best-effort user-source "file:line" for an equation."""
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info)
        if frame is not None:
            return f"{frame.file_name}:{frame.start_line}"
        return source_info_util.summarize(eqn.source_info)
    except Exception:
        return ""


def _axis_names(params):
    for key in ("axes", "axis_name", "axis_names"):
        if key in params:
            val = params[key]
            if isinstance(val, (tuple, list)):
                return tuple(str(v) for v in val)
            return (str(val),)
    return ()


@dataclass(frozen=True)
class CollectiveEqn:
    prim: str
    axes: tuple
    dtype: str
    path: tuple
    location: str


def collectives(jaxpr, include_axis_index=False):
    """Ordered :class:`CollectiveEqn` list over the whole jaxpr tree."""
    wanted = COLLECTIVE_PRIMS if include_axis_index else _REAL_COLLECTIVES
    out = []
    for eqn, path in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name not in wanted:
            continue
        dtype = ""
        if eqn.invars:
            aval = getattr(eqn.invars[0], "aval", None)
            dtype = str(getattr(aval, "dtype", ""))
        out.append(CollectiveEqn(
            prim=name,
            axes=_axis_names(eqn.params),
            dtype=dtype,
            path=path,
            location=source_location(eqn),
        ))
    return out


def signature(jaxpr):
    """Hashable ordered collective signature of a program: the thing
    every rank of a gang must agree on. ``(prim, axes, dtype)``
    triples in traversal order."""
    return tuple(
        (c.prim, c.axes, c.dtype) for c in collectives(jaxpr)
    )


def callbacks(jaxpr):
    """(eqn, path) for every host-callback-style primitive."""
    out = []
    for eqn, path in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if any(marker in name for marker in HOST_CALLBACK_MARKERS):
            out.append((eqn, path))
    return out
