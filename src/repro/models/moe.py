"""Mixture-of-Experts FFN: top-k router + two dispatch layouts.

Expert compute is E data-dependent ragged GEMMs — structurally the
paper's Fig.-7 batched-GEMM workload, the regime where the matrix unit
loses the most headroom to occupancy.  The router (fp32, VPU — the
paper's 'use CUDA cores for what Tensor Cores are bad at' point) picks
top-k experts per token; what happens next depends on the GROUPED
kernel-family backend carried by the matmul route:

``grouped`` = the family's reference impl (default) — capacity-padded
  dispatch:
  position-in-expert via a (T*k, E) cumsum, a materialized (E, C, D)
  one-slot-per-capacity gather, tokens over capacity DROPPED (Switch
  semantics, ``capacity_factor``), expert GEMMs as the vmap-batched
  ``ecd,edf->ecf`` policy einsum, weighted scatter-add combine.

``grouped="pallas_grouped"`` (or any registered impl) — sort-based
  DROPLESS dispatch: argsort tokens by expert, per-expert run lengths
  via bincount, cumsum group offsets with each run padded only to the
  row-TILE multiple (``core.ops.grouped_tiles(...).bm``) instead of
  to worst-case capacity, then three ``grouped_matmul`` calls (wi / wg
  / wo) through the grouped kernel registry — one Pallas kernel walking
  the sorted token dim with scalar-prefetched offsets selecting each
  tile's expert weight block (``kernels.gemm_grouped``).  No token is
  ever dropped, no (E, C, D) tensor exists, and per-token outputs are
  independent of batch composition (each output row is its own dot
  product), which is what makes decode under continuous batching
  token-exact.

Expert share: a layer may hold only some of the experts its router
scores (``num_experts`` held out of the router's width, from
``first_expert``), as one chip of an expert-parallel deployment does.
It routes every token over all router outputs and computes only the
part of the result its own experts give; assignments to experts held
elsewhere are dropped here.  Shared experts (``p["shared"]``, one FFN
every token meets) are added on top.

Sharding: the expert dim maps to the `model` mesh axis when divisible
(dbrx: 16 experts on 16-way model axis = true EP); otherwise experts
stay replicated and the FFN hidden dim takes the TP sharding (mixtral:
8 experts on a 16-way axis). See runtime/sharding.py.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import ops
from repro.core.ops import Route
from repro.core.refined_matmul import peinsum
from repro.models import layers as L

__all__ = ["init_moe", "moe_ffn"]


def init_moe(key, d: int, d_ff: int, num_experts: int, mlp_kind: str,
             *, stack: tuple[int, ...] = (), router_experts: int = 0,
             shared_d_ff: int = 0) -> dict:
    """Router over ``router_experts`` (default: the ``num_experts``
    held), the held experts' stacks, and a shared FFN of width
    ``shared_d_ff`` where it is not 0."""
    kr, k1, k2, k3 = jax.random.split(key, 4)
    estack = (*stack, num_experts)
    p = {
        "router": L.init_linear(kr, d, router_experts or num_experts,
                                stack=stack),
        "wi": L.init_linear(k1, d, d_ff, stack=estack),
        "wo": L.init_linear(k3, d_ff, d, stack=estack,
                            scale=d_ff ** -0.5),
    }
    if mlp_kind == "swiglu":
        p["wg"] = L.init_linear(k2, d, d_ff, stack=estack)
    if shared_d_ff:
        p["shared"] = L.init_mlp(jax.random.fold_in(key, 4), d, shared_d_ff,
                                 mlp_kind, stack=stack)
    return p


def _activate(h, g, mlp_kind: str):
    if mlp_kind == "swiglu":
        return jax.nn.silu(g) * h
    if mlp_kind == "squared_relu":
        return jnp.square(jax.nn.relu(h))
    return jax.nn.gelu(h)


# ===================================================== capacity dispatch

def _capacity_ffn(p: dict, xf: jax.Array, gate_vals, expert_idx, *,
                  num_experts: int, top_k: int, capacity: int,
                  mlp_kind: str, policy, dtype) -> jax.Array:
    """The capacity-padded reference dispatch (Switch semantics).

    Position-in-expert via a (T*k, E) cumsum; assignments past
    ``capacity`` are dropped; the (E, C, D) gather feeds the vmap-
    batched ``ecd,edf->ecf`` expert einsum; the combine is a scatter-add
    weighted by router probabilities.  An ``expert_idx`` of
    ``num_experts`` (an expert held elsewhere) lands on the dropped
    dummy row.  xf: (T, D) -> (T, D) fp32.
    """
    t = xf.shape[0]
    flat_expert = expert_idx.reshape(-1)                          # (T*k,)
    onehot = jax.nn.one_hot(flat_expert, num_experts, dtype=jnp.int32)
    pos_in_expert = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1
    keep = pos_in_expert < capacity

    # dispatch_idx[e, c] = flat token id filling slot c of expert e
    # (capacity overflow rows scatter to a dropped dummy row).
    tok_ids = jnp.arange(t * top_k) // top_k
    e_safe = jnp.where(keep, flat_expert, num_experts)            # drop row
    c_safe = jnp.where(keep, pos_in_expert, 0)
    dispatch = jnp.zeros((num_experts + 1, capacity), jnp.int32)
    dispatch = dispatch.at[e_safe, c_safe].set(tok_ids.astype(jnp.int32),
                                               mode="drop")
    filled = jnp.zeros((num_experts + 1, capacity), bool)
    filled = filled.at[e_safe, c_safe].set(keep, mode="drop")
    dispatch, filled = dispatch[:num_experts], filled[:num_experts]

    xe = xf[dispatch] * filled[..., None].astype(dtype)           # (E, C, D)

    # Expert FFN — batched GEMMs under the moe policy.
    h = peinsum("ecd,edf->ecf", xe, p["wi"]["w"], policy)
    g = (peinsum("ecd,edf->ecf", xe, p["wg"]["w"], policy)
         if mlp_kind == "swiglu" else None)
    h = _activate(h, g, mlp_kind)
    ye = peinsum("ecf,efd->ecd", h.astype(dtype), p["wo"]["w"], policy)

    # Combine: scatter-add each expert slot back, weighted by its gate.
    gates_flat = gate_vals.reshape(-1)                            # (T*k,)
    slot_gate = jnp.zeros((num_experts + 1, capacity), jnp.float32)
    slot_gate = slot_gate.at[e_safe, c_safe].set(
        jnp.where(keep, gates_flat, 0.0), mode="drop")
    slot_gate = slot_gate[:num_experts]

    out = jnp.zeros((t, xf.shape[1]), jnp.float32)
    return out.at[dispatch].add(ye * slot_gate[..., None], mode="drop")


# ======================================================= sorted dispatch

def _sorted_ffn(p: dict, xf: jax.Array, gate_vals, expert_idx, *,
                num_experts: int, top_k: int, mlp_kind: str,
                route: Route, dtype) -> jax.Array:
    """Dropless sort-based dispatch onto the grouped-GEMM registry.

    Assignments are argsorted by expert into a flat buffer whose
    per-expert runs are padded only to the row-tile multiple (every run
    gets at least one tile so each expert's weight gradient block is
    defined); ``grouped_matmul`` then runs the expert FFN as ragged
    grouped GEMMs.  xf: (T, D) -> (T, D) fp32.
    """
    t, d = xf.shape
    tk = t * top_k
    d_ff = p["wi"]["w"].shape[-1]
    # One tile config for dispatcher AND kernel: bm is the group align.
    tiles = ops.grouped_tiles(route, tk, d_ff, d)
    route = dataclasses.replace(route, tiles=tiles)
    bm = tiles.bm

    flat_expert = expert_idx.reshape(-1)                          # (T*k,)
    order = jnp.argsort(flat_expert)                              # stable
    counts = jnp.bincount(flat_expert, length=num_experts)
    aligned = ops.align_group_counts(counts, bm)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(aligned).astype(jnp.int32)])                  # (E+1,)
    # Static buffer bound: sum(aligned) <= round_up(T*k, bm) + E*bm.
    n_buf = ops.round_up(tk, bm) + num_experts * bm

    # Destination row of each sorted assignment: its group's aligned
    # start plus its rank within the group (sorted order is by expert,
    # so ranks are positions past the group's first occurrence).
    sorted_e = flat_expert[order]
    group_first = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(tk) - group_first[sorted_e]
    dest = (offsets[:-1][sorted_e] + rank).astype(jnp.int32)      # (T*k,)
    tok = (order // top_k).astype(jnp.int32)                      # (T*k,)

    xs = jnp.zeros((n_buf, d), dtype).at[dest].set(xf[tok].astype(dtype))
    h = ops.grouped_matmul(xs, p["wi"]["w"], offsets, policy=route)
    g = (ops.grouped_matmul(xs, p["wg"]["w"], offsets, policy=route)
         if mlp_kind == "swiglu" else None)
    h = _activate(h, g, mlp_kind)
    ys = ops.grouped_matmul(h.astype(dtype), p["wo"]["w"], offsets,
                            policy=route)                         # (N, D)

    gates = gate_vals.reshape(-1)[order]                          # (T*k,)
    out = jnp.zeros((t, d), jnp.float32)
    return out.at[tok].add(ys[dest] * gates[:, None])


# ================================================================== FFN

def moe_ffn(p: dict, x: jax.Array, *, num_experts: int, top_k: int,
            capacity_factor: float, mlp_kind: str, policy: str | Route,
            router_policy: str = "f32", dropless: bool = False,
            first_expert: int = 0) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar).

    Router runs in fp32 regardless of the matmul policy (standard
    practice: routing decisions are precision-sensitive, cheap, and on
    the VPU anyway).

    Dispatch follows the route's grouped-family impl (module
    docstring): the reference impl keeps capacity-padded Switch
    semantics, any other registered impl runs the sort-based dropless
    path.
    ``dropless=True`` lifts the reference path's capacity to the worst
    case (t: a token meets each expert at most once) — used at
    inference, where capacity-based dropping would make generation
    depend on batch composition.  The sorted path is dropless by
    construction, so the flag is moot there.

    The router has ``p["router"]["w"].shape[-1]`` outputs; the layer
    holds experts ``first_expert .. first_expert + num_experts - 1`` of
    them (module docstring).
    """
    b, s, d = x.shape
    t = b * s
    dtype = x.dtype
    xf = x.reshape(t, d)

    scored = p["router"]["w"].shape[-1]
    logits = peinsum("td,de->te", xf, p["router"]["w"], router_policy)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)          # (T, k)

    # Load-balancing auxiliary loss (Switch -> Mixtral form): density
    # counts ALL top-k assignments, not just the top-1 column, so a
    # top-k>1 router is pushed to balance its full assignment load.
    density = jnp.mean(
        jax.nn.one_hot(expert_idx, scored, dtype=jnp.float32),
        axis=(0, 1))
    density_proxy = jnp.mean(probs, axis=0)
    aux_loss = scored * jnp.sum(density * density_proxy)

    route = ops.as_route(policy)
    reference = route.uses_reference("grouped")
    if scored != num_experts:
        # a share: assignments to experts held elsewhere go to the
        # capacity path's dropped row, or to expert 0 with no weight
        local = expert_idx - first_expert
        held = (local >= 0) & (local < num_experts)
        gate_vals = jnp.where(held, gate_vals, 0.0)
        expert_idx = jnp.where(held, local, num_experts if reference else 0)
    if reference:
        if dropless:
            capacity = t                # worst case: every token one expert
        else:
            capacity = int(capacity_factor * top_k * t / scored)
            capacity = max(capacity, top_k)
        out = _capacity_ffn(p, xf, gate_vals, expert_idx,
                            num_experts=num_experts, top_k=top_k,
                            capacity=capacity, mlp_kind=mlp_kind,
                            policy=policy, dtype=dtype)
    else:
        out = _sorted_ffn(p, xf, gate_vals, expert_idx,
                          num_experts=num_experts, top_k=top_k,
                          mlp_kind=mlp_kind, route=route, dtype=dtype)
    out = out.astype(dtype)
    if "shared" in p:
        out = out + L.mlp(p["shared"], xf, mlp_kind, policy)
    return out.reshape(b, s, d), aux_loss
