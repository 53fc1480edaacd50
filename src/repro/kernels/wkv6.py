"""Fused WKV6 (RWKV-6 linear-attention recurrence) — Pallas TPU kernel.

Why (EXPERIMENTS.md §Perf cell B): the pure-XLA chunked WKV materializes
every intra-chunk intermediate — the (C,C,K) decay tensor, scores,
per-chunk cumsums — in HBM between fusions; after all pure-JAX
restructurings the rwkv6 train cell is still memory-bound on that churn.
This kernel keeps the ENTIRE chunk computation (cumsum, decay tensor,
scores, output, state update) in VMEM: HBM traffic per chunk step is
exactly read r/k/v/w tiles + write the out tile (+ one (K,K) state
carried in a VMEM scratch across the sequential chunk axis).

Mapping: grid = (B*H, S/C); the second axis is "arbitrary" (sequential)
so the per-(b,h) recurrent state in VMEM scratch is carried across chunk
steps. VMEM working set at C=64, K=64: 4 in-tiles (C,K) f32 64 KiB +
r_ed (C,C,K) f32 1 MiB + state (K,K) 16 KiB + out (C,K) — ~1.2 MiB.

Forward only: this is the serving/prefill path and the validated
foundation; the training VJP (reverse chunk scan for dr/dk/dv/dw) is the
documented next step (§Perf stopping rule). Oracle: kernels/ref.py
``wkv6_ref`` — the exact O(S) sequential recurrence.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["wkv6"]


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_out_ref,
                 state_ref, *, n_chunks: int, chunk: int, kd: int):
    """One (C, K) chunk of one (b, h) stream; state carried in VMEM."""

    @pl.when(pl.program_id(1) == 0)
    def _init_state():
        state_ref[...] = jnp.zeros_like(state_ref)

    rr = r_ref[0].astype(jnp.float32)          # (C, K)
    kk = k_ref[0].astype(jnp.float32)
    vv = v_ref[0].astype(jnp.float32)
    lw = w_ref[0].astype(jnp.float32)          # log decay, <= 0
    u = u_ref[0].astype(jnp.float32)           # (1, K) bonus

    la = jnp.cumsum(lw, axis=0)                # (C, K) inclusive
    lae = la - lw                              # exclusive

    # inter-chunk: r_t decayed to chunk start reads the carried state
    state = state_ref[...]
    inter = jnp.dot(rr * jnp.exp(lae), state,
                    preferred_element_type=jnp.float32)        # (C, K)

    # intra-chunk: scores[t,s] = sum_k r[t,k] k[s,k] e^{lae_t - la_s}
    r_ed = rr[:, None, :] * jnp.exp(
        jnp.clip(lae[:, None, :] - la[None, :, :], None, 0.0))  # (C,C,K)
    scores = jnp.einsum("tsk,sk->ts", r_ed, kk,
                        preferred_element_type=jnp.float32)
    mask = jnp.tril(jnp.ones((chunk, chunk), bool), k=-1)
    scores = jnp.where(mask, scores, 0.0)
    intra = jnp.dot(scores, vv, preferred_element_type=jnp.float32)

    # current-token bonus
    bonus = jnp.sum(rr * u * kk, axis=1, keepdims=True)        # (C, 1)
    o_ref[0] = (inter + intra + bonus * vv).astype(o_ref.dtype)

    # state update: decay to chunk end, add decayed outer products
    dec_end = jnp.exp(la[-1:, :] - la)                         # (C, K)
    new_state = state * jnp.exp(la[-1])[:, None] + jnp.dot(
        (kk * dec_end).T, vv, preferred_element_type=jnp.float32)
    state_ref[...] = new_state

    @pl.when(pl.program_id(1) == n_chunks - 1)
    def _emit_state():
        s_out_ref[0] = new_state.astype(s_out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
         u: jax.Array, *, chunk: int = 64, interpret: bool = False,
         ) -> tuple[jax.Array, jax.Array]:
    """Fused WKV6 forward.

    r/k/v/logw: (B, S, H, K); u: (H, K). S must be a multiple of
    ``chunk`` (pad upstream with logw=0, k=v=0 identity steps).
    Returns (out (B, S, H, K) f32, final_state (B, H, K, K) f32).
    """
    b, s, h, kd = r.shape
    if s % chunk:
        raise ValueError(f"S={s} not a multiple of chunk={chunk}")
    n_chunks = s // chunk

    def bh(x):  # (B,S,H,K) -> (B*H, S, K)
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, kd)

    rr, kk, vv, ww = bh(r), bh(k), bh(v), bh(logw)
    uu = jnp.broadcast_to(u.astype(jnp.float32)[:, None, :],
                          (h, 1, kd))
    uu = jnp.tile(uu, (b, 1, 1))                     # (B*H, 1, K)

    kernel = functools.partial(_wkv6_kernel, n_chunks=n_chunks,
                               chunk=chunk, kd=kd)
    out, state = pl.pallas_call(
        kernel,
        grid=(b * h, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, kd), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, kd), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, kd), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, kd), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, 1, kd), lambda i, c: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, kd), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, kd, kd), lambda i, c: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, kd), jnp.float32),
            jax.ShapeDtypeStruct((b * h, kd, kd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((kd, kd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rr, kk, vv, ww, uu)

    out = out.reshape(b, h, s, kd).transpose(0, 2, 1, 3)
    state = state.reshape(b, h, kd, kd)
    return out, state
