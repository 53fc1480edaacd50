"""Plain float32 reference of a decoder-only transformer: the
mathematics the configuration states, in straightforward ``jax.numpy``
at ``Precision.HIGHEST``, with no kernels, cache or batching.

It imports nothing of the program.  It reads the configuration file's
Hugging Face keys and the weights the benchmark made, laid out as the
program takes them: ``embed.table``, ``unembed.table``,
``final_norm.scale`` and, under ``seg0``, the layers stacked on a
leading axis, ``pos0`` the attention sublayer (``norm.scale``,
``wq``/``wk``/``wv``/``wo`` with ``w`` and, where the model has biases,
``b``) and ``pos1`` the feed-forward sublayer (``norm.scale`` and
``wi``/``wg``/``wo``, or ``router`` and per-expert stacks of those).

Each layer: RMSNorm, grouped-query attention with rotate-half RoPE under
a causal (and sliding-window) mask, residual; RMSNorm, feed-forward,
residual.  The feed-forward is SwiGLU (``hidden_act`` silu), the
tanh-approximated GELU with biases (``gelu_pytorch_tanh``), or a
mixture of experts whose router is a softmax over all experts, of
which each token takes the ``num_experts_per_tok`` largest
probabilities as its gates, unrenormalised, with no token dropped.
The head is ``x @ unembed.table.T`` after a final RMSNorm.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["logits", "router_margin", "loss_and_grads", "adamw", "Q_BLOCK"]

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HI,
                      preferred_element_type=jnp.float32)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _linear(p, x):
    y = _mm("sd,df->sf", x, p["w"])
    return y + p["b"] if "b" in p else y


def _rope(x, theta):
    """x: (S, H, hd); position i rotates pair (j, j + hd/2) by
    i * theta^(-j / (hd/2))."""
    s, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, x, c):
    s = x.shape[0]
    h, kvh = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // h
    window = c.get("sliding_window")
    theta = c["rope_theta"]
    q = _rope(_linear(p["wq"], x).reshape(s, h, hd), theta) / math.sqrt(hd)
    k = _rope(_linear(p["wk"], x).reshape(s, kvh, hd), theta)
    v = _linear(p["wv"], x).reshape(s, kvh, hd)
    # every query head reads the key/value head of its group
    k = jnp.repeat(k, h // kvh, axis=1)
    v = jnp.repeat(v, h // kvh, axis=1)
    kj = jnp.arange(s)

    @jax.checkpoint
    def block(qb, start):
        qi = start + jnp.arange(qb.shape[0])
        keep = kj[None, :] <= qi[:, None]
        if window is not None:
            keep &= kj[None, :] > qi[:, None] - window
        sc = _mm("qhd,khd->hqk", qb, k)
        sc = jnp.where(keep[None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        return _mm("hqk,khd->qhd", pr, v)

    outs = [block(q[i:i + Q_BLOCK], i) for i in range(0, s, Q_BLOCK)]
    out = jnp.concatenate(outs, 0).reshape(s, h * hd)
    return _linear(p["wo"], out)


def _gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi)
                                   * (x + 0.044715 * x ** 3)))


def _ffn(p, x, act):
    h = _linear(p["wi"], x)
    if act == "silu":
        h = jax.nn.silu(_linear(p["wg"], x)) * h
    else:
        h = _gelu_tanh(h)
    return _linear(p["wo"], h)


def _moe(p, x, c):
    """Returns (output, per-expert count of assignments, per-expert sum
    of router probabilities) over this sequence's tokens."""
    e, k = c["num_local_experts"], c["num_experts_per_tok"]
    probs = jax.nn.softmax(_mm("sd,de->se", x, p["router"]["w"]), -1)
    top_vals, top_idx = jax.lax.top_k(probs, k)
    onehot = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)   # (S, k, E)
    gates = jnp.einsum("sk,ske->se", top_vals, onehot)
    out = jnp.zeros_like(x)
    for i in range(e):
        expert = jax.tree.map(lambda w: w[i], {n: p[n] for n in
                                               ("wi", "wg", "wo") if n in p})
        out = out + gates[:, i:i + 1] * _ffn(expert, x, c["hidden_act"])
    top = jax.lax.top_k(probs, min(k + 1, e))[0]
    margin = top[:, k - 1] - top[:, -1] if e > k else jnp.ones(x.shape[0])
    return out, onehot.sum((0, 1)) / k, probs.sum(0), margin


def _forward(params, tokens, c):
    """tokens (S,) -> (hidden (S, D) after the final norm, router
    statistics per layer: assignments and probability sums per expert,
    each (layers, E), or None for a dense model)."""
    eps = c["rms_norm_eps"]
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    stats = []
    seg = params["seg0"]
    for layer in range(c["num_hidden_layers"]):
        pa = jax.tree.map(lambda w: w[layer], seg["pos0"])
        pf = jax.tree.map(lambda w: w[layer], seg["pos1"])
        x = x + _attention(pa, _rmsnorm(x, pa["norm"]["scale"], eps), c)
        xn = _rmsnorm(x, pf["norm"]["scale"], eps)
        if c.get("num_local_experts"):
            y, count, prob, margin = _moe(pf, xn, c)
            stats.append((count, prob, margin))
        else:
            y = _ffn(pf, xn, c["hidden_act"])
        x = x + y
    if stats:
        stats = tuple(jnp.stack(z) for z in zip(*stats))
    return _rmsnorm(x, params["final_norm"]["scale"], eps), stats or None


def logits(params, tokens, c):
    """tokens (S,) int32 -> logits (S, V) float32."""
    x, _ = _forward(params, tokens, c)
    return _mm("sd,vd->sv", x, params["unembed"]["table"])


def router_margin(params, tokens, c):
    """Per position, the smallest margin over layers between the last
    router probability a token takes and the first it leaves: where it
    is near 0, rounding can flip which experts the token meets."""
    _, stats = _forward(params, tokens, c)
    return stats[2].min(0)


def _seq_nll(params, tokens, labels, c):
    x, stats = _forward(params, tokens, c)
    lg = _mm("sd,vd->sv", x, params["unembed"]["table"])
    nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, labels[:, None], -1)[:, 0]
    return nll.mean(), stats


def loss_and_grads(params, tokens, labels, c, *, aux_weight=0.01):
    """Mean next-token loss over the batch (B, S) and the gradient of
    that loss plus ``aux_weight`` times the load-balancing loss of a
    mixture of experts, one sequence at a time.

    The load-balancing loss is ``E * sum_e density_e * prob_e`` per
    layer, with both means over every token of the batch; the density
    (which assignments took) carries no gradient, so a first pass
    without gradients finds it and the second pass differentiates
    ``density . prob`` sequence by sequence.  Returns (loss without the
    load-balancing term, grads)."""
    b, s = tokens.shape
    moe = bool(c.get("num_local_experts"))
    density = None
    if moe:
        counts = jax.lax.map(
            lambda ts: _forward(params, ts, c)[1][0], tokens)
        density = counts.sum(0) / (b * s)                  # (layers, E)

    def one(carry, xs):
        loss_sum, g_sum = carry
        t, lab = xs

        def f(p):
            nll, stats = _seq_nll(p, t, lab, c)
            total = nll / b
            if moe:
                e = c["num_local_experts"]
                total = total + aux_weight * e * jnp.sum(
                    density * stats[1]) / (b * s)
            return total, nll

        (_, nll), g = jax.value_and_grad(f, has_aux=True)(params)
        return (loss_sum + nll, jax.tree.map(jnp.add, g_sum, g)), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (loss_sum, g_sum), _ = jax.lax.scan(
        one, (jnp.zeros((), jnp.float32), zeros), (tokens, labels))
    return loss_sum / b, g_sum


def adamw(opt: dict, step, params, grads, m, v):
    """One AdamW update as the training job states it: global-norm
    clipping, linear warm-up then cosine decay, bias correction,
    decoupled weight decay on matrices only.  ``step`` counts from 1 and
    may be traced.  Returns (params, m, v)."""
    step = jnp.asarray(step, jnp.float32)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    if opt.get("clip_norm") is not None:
        scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
    warmup, total = opt["warmup_steps"], opt["total_steps"]
    warm = jnp.minimum(step / max(warmup, 1), 1.0)
    frac = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * frac))
    lr = opt["lr"] * warm * (opt["min_lr_ratio"]
                             + (1 - opt["min_lr_ratio"]) * cos)
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)

    def upd(p, m_, v_):
        d = (m_ / (1 - b1 ** step)) / (jnp.sqrt(v_ / (1 - b2 ** step)) + eps)
        if p.ndim >= 2:
            d = d + wd * p
        return p - lr * d

    return jax.tree.map(upd, params, m, v), m, v
