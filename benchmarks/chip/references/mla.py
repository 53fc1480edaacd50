"""Plain float32 reference of DeepSeek-V2 (arXiv:2405.04434): latent
attention and a mixture of fine-grained and shared experts, in
straightforward ``jax.numpy`` at ``Precision.HIGHEST``, with no
kernels, cache or batching.

It imports nothing of the program.  It reads the configuration file's
Hugging Face keys and the weights the benchmark made, laid out as the
program takes them: ``embed.table``, ``unembed.table``,
``final_norm.scale``; under ``seg0`` the ``first_k_dense_replace`` dense
layers and under ``seg1`` the expert layers, each stacked on a leading
axis, ``pos0`` the attention sublayer (``norm.scale``, ``wq``,
``wkv_a``, ``kv_norm.scale``, ``wkv_b``, ``wo``, each ``w`` a
``(d_in, d_out)`` matrix) and ``pos1`` the feed-forward sublayer
(``norm.scale`` and ``wi``/``wg``/``wo``; in an expert layer also
``router`` and ``shared``, and the expert stacks).

Each layer: RMSNorm, attention, residual; RMSNorm, feed-forward,
residual.  Attention: per head, the query is ``x @ wq``
(``qk_nope_head_dim`` then ``qk_rope_head_dim`` dims); ``x @ wkv_a``
gives the latent (``kv_lora_rank``, then RMSNorm with
``kv_norm.scale``) and one rope key shared by the heads; ``latent @
wkv_b`` gives each head's no-rope key and value.  Rope rotates pairs
``(2i, 2i + 1)`` of the rope dims with YaRN frequencies
(``rope_scaling``).  Scores are scaled by ``(nope + rope)**-0.5 *
mscale(factor, mscale_all_dim)**2``, DeepSeek's own choice (the
configuration file's ``departures`` says where that differs), under a
causal mask; queries are taken in blocks.

Feed-forward: SwiGLU, ``silu(x @ wg) * (x @ wi) @ wo``.  An expert
layer's router is a softmax over all ``n_routed_experts``; each token
takes the ``num_experts_per_tok`` largest probabilities as gates, not
renormalised, times ``routed_scaling_factor``.  This chip holds
``num_local_experts`` of them, from ``first_local_expert`` (0 if not
stated): the layer adds only their part, and every token meets the
shared experts (one SwiGLU of width ``n_shared_experts *
moe_intermediate_size``).  The head is ``x @ unembed.table.T`` after a
final RMSNorm.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["logits", "router_margin", "Q_BLOCK"]

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HI,
                      preferred_element_type=jnp.float32)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _inv_freq(c):
    """YaRN inverse frequencies of the rope pairs."""
    dim, base = c["qk_rope_head_dim"], c["rope_theta"]
    y = c.get("rope_scaling")
    freq = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if not y:
        return freq
    orig = y["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(corr(y.get("beta_fast", 32))), 0)
    high = min(math.ceil(corr(y.get("beta_slow", 1))), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return freq / y["factor"] * ramp + freq * (1.0 - ramp)


def _rope(x, c):
    """x: (S, ..., r); position i rotates pair (2j, 2j+1) by i * f_j."""
    s = x.shape[0]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * _inv_freq(c)
    y = c.get("rope_scaling") or {}
    if y.get("mscale_all_dim"):
        ang_scale = (_mscale(y["factor"], y.get("mscale", 1.0))
                     / _mscale(y["factor"], y["mscale_all_dim"]))
    else:
        ang_scale = 1.0
    sin = (jnp.sin(ang) * ang_scale).reshape(s, *(1,) * (x.ndim - 2), -1)
    cos = (jnp.cos(ang) * ang_scale).reshape(sin.shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


def _scale(c):
    scale = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5
    y = c.get("rope_scaling") or {}
    if y.get("mscale_all_dim"):
        scale *= _mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return scale


def _attention(p, x, c):
    s = x.shape[0]
    h = c["num_attention_heads"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    rank, vd = c["kv_lora_rank"], c["v_head_dim"]
    q = _mm("sd,df->sf", x, p["wq"]["w"]).reshape(s, h, nope + rope)
    ckv = _mm("sd,df->sf", x, p["wkv_a"]["w"])
    lat = _rmsnorm(ckv[:, :rank], p["kv_norm"]["scale"], c["rms_norm_eps"])
    kv = _mm("sr,rf->sf", lat, p["wkv_b"]["w"]).reshape(s, h, nope + vd)
    k_pe = _rope(ckv[:, rank:], c)                              # (S, r)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], c)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe[:, None], (s, h, rope))], -1)
    v = kv[..., nope:]
    q = q * _scale(c)
    kj = jnp.arange(s)

    @jax.checkpoint
    def block(qb, start):
        qi = start + jnp.arange(qb.shape[0])
        keep = kj[None, :] <= qi[:, None]
        sc = _mm("qhd,khd->hqk", qb, k)
        sc = jnp.where(keep[None], sc, -jnp.inf)
        return _mm("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v)

    out = jnp.concatenate([block(q[i:i + Q_BLOCK], i)
                           for i in range(0, s, Q_BLOCK)], 0)
    return _mm("sf,fd->sd", out.reshape(s, h * vd), p["wo"]["w"])


def _swiglu(p, x):
    h = jax.nn.silu(_mm("sd,df->sf", x, p["wg"]["w"])) \
        * _mm("sd,df->sf", x, p["wi"]["w"])
    return _mm("sf,fd->sd", h, p["wo"]["w"])


def _moe(p, x, c):
    """Returns (output, per token the margin between the last router
    probability a token takes and the first it leaves)."""
    e, k = c["n_routed_experts"], c["num_experts_per_tok"]
    first = c.get("first_local_expert", 0)
    held = c["num_local_experts"]
    probs = jax.nn.softmax(_mm("sd,de->se", x, p["router"]["w"]), -1)
    top_vals, top_idx = jax.lax.top_k(probs, k)
    gates = jnp.einsum("sk,ske->se", top_vals,
                       jax.nn.one_hot(top_idx, e, dtype=jnp.float32))
    gates = gates * c.get("routed_scaling_factor", 1.0)
    out = _swiglu(p["shared"], x)
    for i in range(held):
        expert = {n: {"w": p[n]["w"][i]} for n in ("wi", "wg", "wo")}
        out = out + gates[:, first + i:first + i + 1] * _swiglu(expert, x)
    top = jax.lax.top_k(probs, k + 1)[0]
    return out, top[:, k - 1] - top[:, k]


def _forward(params, tokens, c):
    """tokens (S,) -> (hidden (S, D) after the final norm, router margin
    per expert layer (layers, S))."""
    eps = c["rms_norm_eps"]
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    margins = []
    dense = c.get("first_k_dense_replace", 0)
    for layer in range(c["num_hidden_layers"]):
        seg, i = ("seg0", layer) if layer < dense else ("seg1", layer - dense)
        pa = jax.tree.map(lambda w: w[i], params[seg]["pos0"])
        pf = jax.tree.map(lambda w: w[i], params[seg]["pos1"])
        x = x + _attention(pa, _rmsnorm(x, pa["norm"]["scale"], eps), c)
        xn = _rmsnorm(x, pf["norm"]["scale"], eps)
        if layer < dense:
            y = _swiglu(pf, xn)
        else:
            y, margin = _moe(pf, xn, c)
            margins.append(margin)
        x = x + y
    return (_rmsnorm(x, params["final_norm"]["scale"], eps),
            jnp.stack(margins))


def logits(params, tokens, c):
    """tokens (S,) int32 -> logits (S, V) float32."""
    x, _ = _forward(params, tokens, c)
    return _mm("sd,vd->sv", x, params["unembed"]["table"])


def router_margin(params, tokens, c):
    """Per position, the smallest margin over the expert layers between
    the last of all ``n_routed_experts`` router probabilities a token
    takes and the first it leaves: where it is near 0, rounding can
    flip which experts the token meets."""
    return _forward(params, tokens, c)[1].min(0)
