"""Generic jaxpr walking for the static auditor.

``jax.make_jaxpr`` under abstract values gives the FULL structural
graph of a routed op — every ``dot_general`` (the MXU contraction
sites), every collective, every ``pallas_call`` — without executing a
single kernel.  This module is the traversal layer: it recurses
through call/control-flow primitives (``pjit``, ``scan``, ``while``,
``cond`` branches, ``custom_jvp_call`` / ``custom_vjp_call``,
``shard_map``, ``remat``/``checkpoint``, ``pallas_call``) by walking
every eqn param that IS a jaxpr — including params that are tuples or
lists of jaxprs, which is how ``cond`` carries its branches — and
collects the sites the rule modules judge.

Counting convention: a dot inside a ``scan``/``while`` BODY is counted
once (the static decomposition structure, not the dynamic trip count),
which is exactly what the pass-count rule wants — the precision
ladder's passes are unrolled in the traced graph, never loop-carried.
"""

from __future__ import annotations

import dataclasses
from collections.abc import Iterator
from typing import Any

import jax

__all__ = [
    "DotSite",
    "CollectiveSite",
    "PallasSite",
    "ScanResult",
    "COLLECTIVE_PRIMS",
    "iter_subjaxprs",
    "walk_eqns",
    "scan_jaxpr",
    "trace_jaxpr",
]

# Cross-device primitives the sharding rules compare against declared
# ``Partitioning.collectives`` (order matters only for prefix-matching
# declared names elsewhere).
COLLECTIVE_PRIMS = ("psum", "all_gather", "all_to_all", "ppermute",
                    "reduce_scatter", "psum_scatter")


@dataclasses.dataclass(frozen=True)
class DotSite:
    """One ``dot_general`` eqn: the MXU contraction unit."""

    lhs_dtype: Any
    rhs_dtype: Any
    out_dtype: Any
    preferred: Any               # preferred_element_type param (or None)
    in_pallas: bool


@dataclasses.dataclass(frozen=True)
class CollectiveSite:
    """One cross-device reduction/gather eqn inside a shard_map body."""

    prim: str                    # "psum" / "all_gather" / ...
    axes: tuple[str, ...]        # mesh axis names the op runs over
    dtype: Any                   # operand dtype (psum_f32 contract)


@dataclasses.dataclass(frozen=True)
class PallasSite:
    """One ``pallas_call`` eqn with the structure the Pallas rules need."""

    name: str
    interpret: bool
    grid: tuple[Any, ...]
    # (block_shape, array_shape, index_map ClosedJaxpr) per operand
    # (inputs then outputs, the grid_mapping order).
    block_mappings: tuple[tuple[tuple[Any, ...], tuple[int, ...], Any], ...]
    scratch_avals: tuple[Any, ...]
    num_index_operands: int


@dataclasses.dataclass
class ScanResult:
    """Everything one trace yields for the rule engine."""

    dots: list[DotSite]
    collectives: list[CollectiveSite]
    pallas: list[PallasSite]
    # (src_dtype, dst_dtype) for each dot output that is converted to a
    # NARROWER float and then fed into an add — the "silent downcast
    # between multiply and accumulate" shape.
    downcasts: list[tuple[Any, Any]]

    @property
    def outer_dots(self) -> int:
        return sum(1 for d in self.dots if not d.in_pallas)

    @property
    def pallas_calls(self) -> int:
        return len(self.pallas)


def iter_subjaxprs(eqn) -> Iterator[Any]:
    """Every jaxpr carried by one eqn's params (open or closed), looking
    inside tuple/list params too — ``cond`` stores its branches as a
    tuple of ClosedJaxprs and would otherwise be invisible."""
    for val in eqn.params.values():
        items = val if isinstance(val, (tuple, list)) else (val,)
        for item in items:
            if hasattr(item, "eqns"):                 # open Jaxpr
                yield item
            elif hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                yield item.jaxpr                      # ClosedJaxpr


def walk_eqns(jaxpr, in_pallas: bool = False) -> Iterator[tuple[Any, bool]]:
    """Depth-first (eqn, inside-a-pallas-kernel?) over all sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn, in_pallas
        inner = in_pallas or eqn.primitive.name == "pallas_call"
        for sub in iter_subjaxprs(eqn):
            yield from walk_eqns(sub, inner)


def _aval_dtype(aval):
    """dtype of a (possibly Ref-wrapped) abstract value."""
    dt = getattr(aval, "dtype", None)
    if dt is None:
        dt = getattr(getattr(aval, "inner_aval", None), "dtype", None)
    return dt


def _pallas_site(eqn) -> PallasSite:
    params = eqn.params
    gm = params.get("grid_mapping")
    grid = tuple(getattr(gm, "grid", ()) or ())
    mappings = []
    for bm in getattr(gm, "block_mappings", ()) or ():
        mappings.append((tuple(bm.block_shape), tuple(bm.array_aval.shape),
                         bm.index_map_jaxpr))
    n_scratch = getattr(gm, "num_scratch_operands", 0) or 0
    inner = params.get("jaxpr")
    scratch = tuple(_aval_dtype(v.aval)
                    for v in inner.invars[len(inner.invars) - n_scratch:]
                    ) if (inner is not None and n_scratch) else ()
    name = str(getattr(params.get("name_and_src_info"), "name", "")
               or "pallas_call")
    return PallasSite(
        name=name,
        interpret=bool(params.get("interpret", False)),
        grid=grid,
        block_mappings=tuple(mappings),
        scratch_avals=scratch,
        num_index_operands=getattr(gm, "num_index_operands", 0) or 0,
    )


def _float_bits(dtype) -> int | None:
    try:
        import jax.numpy as jnp
        if jnp.issubdtype(dtype, jnp.floating):
            return jnp.finfo(dtype).bits
    except (TypeError, ValueError):
        pass
    return None


def _scope_downcasts(jaxpr) -> list[tuple[Any, Any]]:
    """Per-scope dot -> narrowing convert -> add chains (the structural
    form of 'downcast between multiply and accumulate')."""
    dot_out_ids: set[int] = set()
    narrowed: dict[int, tuple[Any, Any]] = {}
    hits: list[tuple[Any, Any]] = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            dot_out_ids.add(id(eqn.outvars[0]))
        elif name == "convert_element_type" and eqn.invars:
            src = eqn.invars[0]
            if id(src) in dot_out_ids:
                src_bits = _float_bits(src.aval.dtype)
                dst_bits = _float_bits(eqn.outvars[0].aval.dtype)
                if src_bits and dst_bits and dst_bits < src_bits:
                    narrowed[id(eqn.outvars[0])] = (
                        src.aval.dtype, eqn.outvars[0].aval.dtype)
        elif name in ("add", "add_any", "sub"):
            for v in eqn.invars:
                if id(v) in narrowed:
                    hits.append(narrowed[id(v)])
    return hits


def scan_jaxpr(jaxpr) -> ScanResult:
    """Collect every audit-relevant site from a (closed) jaxpr."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    result = ScanResult(dots=[], collectives=[], pallas=[], downcasts=[])
    result.downcasts.extend(_scope_downcasts(jaxpr))
    seen_scopes = {id(jaxpr)}
    for eqn, in_pallas in walk_eqns(jaxpr):
        name = eqn.primitive.name
        if name == "dot_general":
            result.dots.append(DotSite(
                lhs_dtype=eqn.invars[0].aval.dtype,
                rhs_dtype=eqn.invars[1].aval.dtype,
                out_dtype=eqn.outvars[0].aval.dtype,
                preferred=eqn.params.get("preferred_element_type"),
                in_pallas=in_pallas))
        elif name in COLLECTIVE_PRIMS:
            axes = eqn.params.get("axes")
            if axes is None:
                axes = eqn.params.get("axis_name")
            if not isinstance(axes, (tuple, list)):
                axes = (axes,)
            axes = tuple(a for a in axes if isinstance(a, str))
            result.collectives.append(CollectiveSite(
                prim=name, axes=axes,
                dtype=_aval_dtype(eqn.invars[0].aval)))
        elif name == "pallas_call":
            result.pallas.append(_pallas_site(eqn))
        for sub in iter_subjaxprs(eqn):
            if id(sub) not in seen_scopes:
                seen_scopes.add(id(sub))
                result.downcasts.extend(_scope_downcasts(sub))
    return result


def trace_jaxpr(fn, *args) -> Any:
    """``jax.make_jaxpr`` under abstract values — the auditor's ONLY
    tracing entry (nothing in the subsystem ever executes a kernel)."""
    return jax.make_jaxpr(fn)(*args)
