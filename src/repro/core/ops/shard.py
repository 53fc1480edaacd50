"""Mesh-aware kernel variants: ``shard_map`` wrappers over routed impls.

The paper saturates all 640 Tensor Cores of one card; our analogue of
"use all the silicon" is multi-device execution.  This module is the
bridge between the op registry and a device mesh: given a ``Route``
whose ``mesh`` field names a non-trivial ``MeshSpec``, the family
dispatchers delegate here and the routed impl runs INSIDE a
``shard_map`` whose in/out specs are derived from the impl's declared
``Partitioning`` capability plus runtime divisibility checks.

Schemes (all collectives are jnp-level so every impl — XLA reference
and Pallas kernels alike — shards without kernel changes):

  * GEMM: column-parallel when the n dim divides the tp degree (weights
    ``P(None, 'model')`` — each output column is computed WHOLE on one
    device, so every precision rung stays bit-exact; this is also the
    ``gemm@logits`` vocab-TP path), else row-parallel on the k dim with
    an f32 ``psum`` epilogue (per-device partials accumulate in f32 and
    reduce in f32, the Ootomo & Yokota error-corrected-accumulation
    posture — exact for f32 summands up to reordering, hence "within
    ladder bounds" for the refinement rungs).  The m dim additionally
    shards over dp.
  * Attention: batch over dp and KV heads over tp call the impl
    unchanged (head groups are independent — exact).  When the batch
    cannot shard, the SEQUENCE shards over the data axis: q stays
    local, k/v are all-gathered, and the causal walk runs the
    reference online-softmax machinery with the q-row offset folded
    into the mask (score/value contractions still route through the
    gemm family under the same route).
  * Grouped MoE: expert-parallel — weights shard the E dim over the
    expert axis; inside the body each device slices ITS window of the
    global group-offset vector (the PR-4 sort-based dispatch metadata),
    brackets it with zero-weight sentinel groups so the family contract
    (offsets[0]=0, offsets[-1]=N, bm-aligned) holds per device, runs
    the routed impl on its local ragged runs, and an f32 ``psum`` over
    the expert axis reassembles the disjoint regions — the sorted
    all-to-all; exact, because off-region rows contribute exact zeros.

An identity mesh (``MeshSpec()`` / ``mesh=None``) short-circuits before
any of this: the single-device route emits a byte-identical jaxpr.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["MeshSpec", "make_mesh", "active_mesh", "unsharded_route",
           "abstract_meshes", "sharded_gemm_2d", "sharded_attention_forward",
           "sharded_attention_decode", "sharded_grouped_matmul"]


# ================================================================ MeshSpec

@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Hashable logical mesh description: parallel degrees per ROLE.

    Roles map onto mesh axis names: ``dp`` -> ``data`` (batch /
    FSDP), ``tp`` -> ``model`` (tensor parallel), ``ep`` -> ``expert``
    (expert parallel), ``pod`` -> ``pod`` (pure DP across pods).  Plain
    ints only, so a MeshSpec rides inside ``Route`` / ``ExecutionPolicy``
    as static metadata; ``build()`` resolves it to a concrete
    ``jax.sharding.Mesh`` over the process's devices at dispatch time.
    """

    dp: int = 1
    tp: int = 1
    ep: int = 1
    pod: int = 1

    # (axis_name, role_field) in mesh-major order.
    AXES = (("pod", "pod"), ("data", "dp"), ("expert", "ep"),
            ("model", "tp"))

    def __post_init__(self) -> None:
        for axis, role in self.AXES:
            v = getattr(self, role)
            if not isinstance(v, int) or v < 1:
                raise ValueError(
                    f"mesh degree {role}={v!r} must be a positive int")

    @property
    def size(self) -> int:
        return self.dp * self.tp * self.ep * self.pod

    @property
    def is_identity(self) -> bool:
        return self.size == 1

    def describe(self) -> str:
        """The canonical flag spelling, e.g. ``dp=2,tp=2,ep=2``."""
        parts = [f"dp={self.dp}", f"tp={self.tp}", f"ep={self.ep}"]
        if self.pod > 1:
            parts.append(f"pod={self.pod}")
        return ",".join(parts)

    @classmethod
    def parse(cls, text: str) -> MeshSpec:
        """Parse the unified ``--mesh`` grammar: ``dp=2,tp=2,ep=2``
        (any subset of dp/tp/ep/pod, missing roles default to 1);
        ``none`` / ``1`` mean the identity mesh."""
        text = text.strip().lower()
        if text in ("", "none", "1", "identity"):
            return cls()
        roles = {role for _, role in cls.AXES}
        kw: dict[str, int] = {}
        for token in text.split(","):
            key, sep, val = token.partition("=")
            key = key.strip()
            if not sep or key not in roles:
                raise ValueError(
                    f"bad --mesh token {token!r}; grammar: "
                    f"dp=<int>,tp=<int>,ep=<int>[,pod=<int>] or 'none'")
            try:
                kw[key] = int(val)
            except ValueError:
                raise ValueError(
                    f"bad --mesh degree {val!r} for {key!r}") from None
        return cls(**kw)

    @classmethod
    def from_shape(cls, shape: tuple[int, ...], axes: tuple[str, ...],
                   ) -> MeshSpec:
        """Lift a (shape, axis-names) mesh description (the historical
        ``choose_mesh_shape`` return) into a MeshSpec."""
        by_axis = dict(zip(axes, shape))
        role_of = {axis: role for axis, role in cls.AXES}
        kw = {role_of[a]: s for a, s in by_axis.items() if a in role_of}
        return cls(**kw)

    def build(self):
        """The concrete Mesh (cached — all callers share one object, so
        in_shardings and shard_map agree).  Axes are always
        ``(data, expert, model)`` (+ leading ``pod`` when pod > 1);
        size-1 axes are kept, which keeps PartitionSpecs uniform."""
        return _build_mesh(self)

    def abstract(self):
        """AbstractMesh twin of ``build()`` — spec derivation with zero
        accelerators (tests, eval_shape)."""
        from jax.sharding import AbstractMesh
        items = self._axis_items()
        return AbstractMesh(tuple(s for _, s in items),
                            tuple(a for a, _ in items))

    def _axis_items(self) -> tuple[tuple[str, int], ...]:
        items = [("data", self.dp), ("expert", self.ep),
                 ("model", self.tp)]
        if self.pod > 1:
            items.insert(0, ("pod", self.pod))
        return tuple(items)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices):
    """``jax.make_mesh`` with every axis ``Auto``: shardings come from
    ``jit`` in/out shardings and ``shard_map`` specs and XLA propagates
    the rest (the default since JAX 0.9, ``Explicit``, would instead
    demand an output sharding on every reshape of a sharded array)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


@functools.lru_cache(maxsize=None)
def _build_mesh(spec: MeshSpec):
    devices = jax.devices()
    if len(devices) < spec.size:
        raise ValueError(
            f"mesh {spec.describe()} needs {spec.size} devices; "
            f"only {len(devices)} visible")
    items = spec._axis_items()
    return make_mesh(tuple(s for _, s in items), tuple(a for a, _ in items),
                     devices[:spec.size])


# When True, the sharded dispatchers resolve MeshSpecs to ABSTRACT
# meshes: ``shard_map`` then traces (jaxprs, eval_shape) without any
# devices.  This is the static auditor's hook — it must see the sharded
# jaxpr (collectives included) on a single-CPU CI runner.
_ABSTRACT_BUILD = False


@contextlib.contextmanager
def abstract_meshes():
    """Trace sharded dispatch on ``AbstractMesh``es (no devices needed).

    Within this context every ``spec.build()`` the sharded variants
    perform returns ``spec.abstract()`` instead, so ``jax.make_jaxpr``
    over a mesh-carrying route succeeds on any host.  Tracing only —
    executing the traced computation still requires real devices.
    """
    global _ABSTRACT_BUILD
    prev = _ABSTRACT_BUILD
    _ABSTRACT_BUILD = True
    try:
        yield
    finally:
        _ABSTRACT_BUILD = prev


def _mesh_for(spec: MeshSpec):
    return spec.abstract() if _ABSTRACT_BUILD else spec.build()


def active_mesh(mesh: MeshSpec | None) -> MeshSpec | None:
    """None unless ``mesh`` actually distributes anything — the identity
    short-circuit every dispatcher checks first."""
    if mesh is None or mesh.is_identity:
        return None
    return mesh


def unsharded_route(route):
    """The route the impl runs INSIDE the shard_map body (per-device
    shapes; no nested mesh dispatch)."""
    return dataclasses.replace(route, mesh=None)


# ============================================================== TP/DP GEMM

def sharded_gemm_2d(impl, a: jax.Array, b: jax.Array, route) -> jax.Array:
    """One 2-D GEMM under the route's mesh (see module docstring)."""
    from repro.core.ops.gemm import _impl_gemm_2d
    spec: MeshSpec = route.mesh
    roles = impl.capabilities.partitioning.roles
    m, k = a.shape
    n = b.shape[1]
    dp = spec.dp if "dp" in roles and m % spec.dp == 0 else 1
    tp = spec.tp if "tp" in roles else 1
    col = tp > 1 and n % tp == 0
    row = tp > 1 and not col and k % tp == 0
    if dp == 1 and not col and not row:
        return _impl_gemm_2d(impl, a, b, unsharded_route(route))

    mesh = _mesh_for(spec)
    m_ax = "data" if dp > 1 else None
    inner = unsharded_route(route)
    if col:
        in_specs = (P(m_ax, None), P(None, "model"))
        out_specs = P(m_ax, "model")
    elif row:
        in_specs = (P(m_ax, "model"), P("model", None))
        out_specs = P(m_ax, None)
    else:
        in_specs = (P(m_ax, None), P(None, None))
        out_specs = P(m_ax, None)

    def body(ab, bb):
        out = _impl_gemm_2d(impl, ab, bb, inner)
        if row:
            # f32 psum epilogue: impls accumulate in f32, partials
            # reduce in f32 — the precision ladder's bounds survive the
            # k-split (Ootomo & Yokota-style error-corrected reduce).
            out = jax.lax.psum(out, "model")
        return out

    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(a, b)


# ============================================================== attention

def _offset_mask_fn(causal: bool, window: int | None, q_offset):
    """The reference mask closures with a GLOBAL q-row offset folded in
    (models/attention builds the same shapes with offset 0)."""
    if causal and window:
        return lambda qi, ki: ((ki <= qi + q_offset)
                               & (ki > qi + q_offset - window))
    if causal:
        return lambda qi, ki: ki <= qi + q_offset
    return lambda qi, ki: (ki >= 0) & (qi >= -1)


def sharded_attention_forward(impl, q, k, v, *, causal, window, softcap,
                              route, kv_chunk) -> jax.Array:
    spec: MeshSpec = route.mesh
    roles = impl.capabilities.partitioning.roles
    b, sq, kvh, grp, hd = q.shape
    skv = k.shape[1]
    dp = spec.dp if "dp" in roles and b % spec.dp == 0 else 1
    tp = spec.tp if "tp" in roles and kvh % spec.tp == 0 else 1
    sp = 1
    if (dp == 1 and spec.dp > 1 and "sp" in roles
            and sq % spec.dp == 0 and skv % spec.dp == 0
            and (not causal or sq == skv)):
        sp = spec.dp
    if dp == 1 and tp == 1 and sp == 1:
        return impl.fn.forward(q, k, v, causal=causal, window=window,
                               softcap=softcap, route=unsharded_route(route),
                               kv_chunk=kv_chunk)

    mesh = _mesh_for(spec)
    b_ax = "data" if dp > 1 else None
    h_ax = "model" if tp > 1 else None
    inner = unsharded_route(route)

    if sp == 1:
        in_specs = (P(b_ax, None, h_ax, None, None),
                    P(b_ax, None, h_ax, None), P(b_ax, None, h_ax, None))
        out_specs = P(b_ax, None, h_ax, None, None)

        def body(qb, kb, vb):
            return impl.fn.forward(qb, kb, vb, causal=causal, window=window,
                                   softcap=softcap, route=inner,
                                   kv_chunk=kv_chunk)
    else:
        # Sequence sharding: q rows stay local, KV is all-gathered and
        # the causal walk runs the reference online-softmax scan with
        # the shard's global q offset in the mask.  Chunking matches the
        # single-device reference (same S, same kv_chunk), so every q
        # row sees identical arithmetic — bit-exact parity.
        from repro.models.attention import _flash_over_kv
        q_blk = sq // sp
        in_specs = (P(None, "data", h_ax, None, None),
                    P(None, "data", h_ax, None), P(None, "data", h_ax, None))
        out_specs = P(None, "data", h_ax, None, None)

        def body(qb, kb, vb):
            off = jax.lax.axis_index("data") * q_blk
            kf = jax.lax.all_gather(kb, "data", axis=1, tiled=True)
            vf = jax.lax.all_gather(vb, "data", axis=1, tiled=True)
            mask_fn = _offset_mask_fn(causal, window, off)
            return _flash_over_kv(qb, kf, vf, mask_fn, inner, softcap,
                                  kv_chunk=min(kv_chunk, skv))

    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(q, k, v)


def sharded_attention_decode(impl, q, k_cache, v_cache, pos, *, window,
                             softcap, route) -> jax.Array:
    spec: MeshSpec = route.mesh
    roles = impl.capabilities.partitioning.roles
    b, _, kvh, _, _ = q.shape
    dp = spec.dp if "dp" in roles and b % spec.dp == 0 else 1
    tp = spec.tp if "tp" in roles and kvh % spec.tp == 0 else 1
    inner = unsharded_route(route)
    if dp == 1 and tp == 1:
        return impl.fn.decode(q, k_cache, v_cache, pos, window=window,
                              softcap=softcap, route=inner)
    mesh = _mesh_for(spec)
    b_ax = "data" if dp > 1 else None
    h_ax = "model" if tp > 1 else None
    in_specs = (P(b_ax, None, h_ax, None, None),
                P(b_ax, None, h_ax, None), P(b_ax, None, h_ax, None),
                P(b_ax))
    out_specs = P(b_ax, None, h_ax, None, None)

    def body(qb, kb, vb, pb):
        return impl.fn.decode(qb, kb, vb, pb, window=window,
                              softcap=softcap, route=inner)

    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         check_vma=False)(q, k_cache, v_cache, pos)


# ============================================================== grouped EP

def sharded_grouped_matmul(impl, x, w, group_offsets, route) -> jax.Array:
    spec: MeshSpec = route.mesh
    roles = impl.capabilities.partitioning.roles
    e, d, f = w.shape
    ep = spec.ep if "ep" in roles and e % spec.ep == 0 else 1
    tp = spec.tp if "tp" in roles and f % spec.tp == 0 else 1
    inner = unsharded_route(route)
    if ep == 1 and tp == 1:
        return impl.fn(x, w, group_offsets, route=inner)
    if inner.tiles is None:
        # Pin tiles from the GLOBAL problem so the per-device row tile
        # (= the group alignment the caller built offsets with) cannot
        # drift when the local f dim changes the shape key.
        from repro.core.ops.grouped import grouped_tiles
        inner = dataclasses.replace(
            inner, tiles=grouped_tiles(inner, x.shape[0], f, d))

    mesh = _mesh_for(spec)
    e_ax = "expert" if ep > 1 else None
    f_ax = "model" if tp > 1 else None
    in_specs = (P(None, None), P(e_ax, None, f_ax), P(None))
    out_specs = P(None, f_ax)
    e_loc = e // ep

    def body(xb, wb, ob):
        if ep == 1:
            return impl.fn(xb, wb, ob, route=inner)
        # This device's window of the global offsets, bracketed by
        # zero-weight sentinel groups so the family contract holds
        # locally (offsets[0]=0, offsets[-1]=N, all bm-aligned — the
        # global offsets are aligned and so are the window's ends).
        # Rows outside the window fall into the sentinels, multiply
        # zero weights, and contribute exact zeros; the psum over the
        # expert axis reassembles the disjoint regions exactly.
        i = jax.lax.axis_index("expert")
        lo = jax.lax.dynamic_slice_in_dim(ob, i * e_loc, e_loc + 1)
        n_rows = jnp.full((1,), xb.shape[0], ob.dtype)
        offs = jnp.concatenate([jnp.zeros((1,), ob.dtype), lo, n_rows])
        wz = jnp.zeros((1,) + wb.shape[1:], wb.dtype)
        out = impl.fn(xb, jnp.concatenate([wz, wb, wz], axis=0), offs,
                      route=inner)
        return jax.lax.psum(out, "expert")

    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         check_vma=False)(x, w, group_offsets)
