"""Operations and bytes a decoder-only transformer needs, from its
published sizes.

Reads the Hugging Face ``config.json`` keys of the configuration file
(``hidden_size``, ``intermediate_size``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``num_hidden_layers``,
``vocab_size``, ``sliding_window``, ``num_local_experts``,
``num_experts_per_tok``, ``hidden_act``, ``use_bias``), never the
program's own config objects, so that a change to the program cannot
change the yardstick.

What is counted is what the algorithm needs, not what a given program
happens to compute:

* matrix products count 2 FLOPs per multiply-add; a token meets its
  attention projections, the router, its ``num_experts_per_tok``
  experts (or the dense FFN) and, where logits are needed, the head;
* attention scores and values count 4 * heads * head_dim FLOPs per
  (query, key) pair that the causal (and window) mask keeps;
* bytes are the weights once per call in bfloat16 (the precision the
  configuration computes in) and the KV rows a call has to read or
  write, also in bfloat16.

So a program that stores f32 weights, computes masked-out attention
blocks or every expert for every token is measured against less work
than it does, and its roofline share says so.
"""

from __future__ import annotations

import dataclasses

__all__ = ["Dims", "dims", "params", "matmul_flops_per_token",
           "attention_flops", "prefill", "decode_tick", "train_step"]

BYTES = 2  # bfloat16


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    f: int
    heads: int
    kv_heads: int
    head_dim: int
    layers: int
    vocab: int
    window: int | None
    experts: int           # 0 = dense FFN
    top_k: int
    gated: bool            # SwiGLU: three FFN matrices, else two
    bias: bool


def dims(c: dict) -> Dims:
    heads = c["num_attention_heads"]
    return Dims(
        d=c["hidden_size"], f=c["intermediate_size"], heads=heads,
        kv_heads=c.get("num_key_value_heads", heads),
        head_dim=c.get("head_dim") or c["hidden_size"] // heads,
        layers=c["num_hidden_layers"], vocab=c["vocab_size"],
        window=c.get("sliding_window"),
        experts=c.get("num_local_experts", 0),
        top_k=c.get("num_experts_per_tok", 0),
        gated=c.get("hidden_act", "silu") == "silu",
        bias=bool(c.get("use_bias", False)))


def _attn_params(m: Dims) -> int:
    q, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    return m.d * q + 2 * m.d * kv + q * m.d


def _ffn_params(m: Dims) -> int:
    """One expert's (or the dense FFN's) matrix parameters."""
    return (3 if m.gated else 2) * m.d * m.f


def params(c: dict) -> dict[str, int]:
    """Matrix parameters by part (biases and norms are negligible and
    left out)."""
    m = dims(c)
    ffn = _ffn_params(m) * max(m.experts, 1) + m.d * m.experts
    return {"attention": m.layers * _attn_params(m),
            "ffn": m.layers * ffn,
            "embed": m.vocab * m.d,
            "head": m.vocab * m.d}


def matmul_flops_per_token(c: dict, *, head: bool) -> int:
    """Projection, router, expert/FFN and (optionally) head FLOPs of one
    token; attention scores and values are apart."""
    m = dims(c)
    k = m.top_k if m.experts else 1
    per_layer = _attn_params(m) + k * _ffn_params(m) + m.d * m.experts
    return 2 * (m.layers * per_layer + (m.vocab * m.d if head else 0))


def _keys(m: Dims, n: int) -> int:
    return n if m.window is None else min(n, m.window)


def attention_flops(c: dict, ctx: int) -> int:
    """Scores and values of one query that attends to ``ctx`` keys
    (itself included) before the window cuts them."""
    m = dims(c)
    return 4 * m.layers * m.heads * m.head_dim * _keys(m, ctx)


def _causal_pairs(m: Dims, s: int) -> int:
    """Query-key pairs the causal window mask keeps over ``s`` tokens."""
    if m.window is None or m.window >= s:
        return s * (s + 1) // 2
    w = m.window
    return w * (w + 1) // 2 + (s - w) * w


def prefill(c: dict, s: int) -> dict[str, float]:
    """One prompt of ``s`` tokens: logits of its last position only."""
    m = dims(c)
    flops = (matmul_flops_per_token(c, head=False) * s
             + 2 * m.vocab * m.d
             + 4 * m.layers * m.heads * m.head_dim * _causal_pairs(m, s))
    kv = 2 * m.layers * m.kv_heads * m.head_dim * _keys(m, s) * BYTES
    return {"flops": float(flops),
            "bytes": float(_weight_bytes(m, tokens=s) + kv)}


def _weight_bytes(m: Dims, *, tokens: int) -> int:
    """Weights a call over ``tokens`` tokens must read once: every
    expert that ``tokens * top_k`` assignments can reach."""
    experts = min(m.experts, tokens * m.top_k) if m.experts else 1
    per_layer = _attn_params(m) + experts * _ffn_params(m) \
        + m.d * m.experts
    return (m.layers * per_layer + m.vocab * m.d) * BYTES


def decode_tick(c: dict, ctx: list[int]) -> dict[str, float]:
    """One engine tick: one new token for each active slot; ``ctx`` is
    each active slot's key count including the new token."""
    m = dims(c)
    n = len(ctx)
    flops = n * matmul_flops_per_token(c, head=True) \
        + sum(attention_flops(c, x) for x in ctx)
    kv_read = sum(2 * m.layers * m.kv_heads * m.head_dim * _keys(m, x)
                  for x in ctx) * BYTES
    return {"flops": float(flops),
            "bytes": float(_weight_bytes(m, tokens=n) + kv_read)}


def train_step(c: dict, batch: int, seq: int) -> dict[str, float]:
    """Forward and backward over ``batch`` sequences of ``seq`` tokens,
    head on every position: three times the forward FLOPs, no
    recomputation counted."""
    m = dims(c)
    fwd = batch * (matmul_flops_per_token(c, head=True) * seq
                   + 4 * m.layers * m.heads * m.head_dim
                   * _causal_pairs(m, seq))
    return {"flops": float(3 * fwd)}
