"""Operations and bytes DeepSeek-V2 needs (latent attention, fine-grained
and shared experts), from its published sizes.

Reads the Hugging Face ``config.json`` keys of the configuration file
(``hidden_size``, ``num_attention_heads``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``, ``kv_lora_rank``,
``num_hidden_layers``, ``first_k_dense_replace``, ``intermediate_size``,
``moe_intermediate_size``, ``n_shared_experts``, ``n_routed_experts``,
``num_local_experts``, ``num_experts_per_tok``, ``vocab_size``), never
the program's own config objects.

What is counted is what the algorithm needs on this chip:

* matrix products count 2 FLOPs per multiply-add.  Each token meets
  the attention projections, the dense FFN or, in an expert layer, the
  router, the shared experts and its ``num_experts_per_tok`` routed
  experts, of which this chip computes the share it holds
  (``num_local_experts / n_routed_experts`` of them, on average); where
  logits are needed, the head;
* prefill attention is the expanded form: ``wkv_b`` lifts each token's
  latent to per-head keys and values, and each (query, key) pair the
  causal mask keeps costs ``2 * heads * (nope + rope + v)``;
* decode attention is the absorbed form: per token ``W_UK`` folds into
  the query and ``W_UV`` into the output (``2 * heads * rank * (nope +
  v)``), and each cached key costs ``2 * heads * (2 * rank + rope)``
  (scores over the 576-wide latent row, values over its 512-wide part);
* bytes are the weights once per call in bfloat16 (the precision the
  configuration computes in), counting the held experts that the
  call's assignments can reach, and the latent rows (``rank + rope``
  values) a call has to write or read, also in bfloat16.
"""

from __future__ import annotations

import dataclasses

__all__ = ["Dims", "dims", "params", "matmul_flops_per_token",
           "prefill", "decode_tick"]

BYTES = 2  # bfloat16


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    nope: int
    rope: int
    v: int
    rank: int
    layers: int
    dense_layers: int
    dense_f: int
    moe_f: int
    shared_f: int
    routed: int            # experts the router scores
    held: int              # experts this chip holds
    top_k: int
    vocab: int

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def latent(self) -> int:
        return self.rank + self.rope

    @property
    def assignments(self) -> float:
        """Routed experts a token meets on this chip, on average."""
        return self.top_k * self.held / self.routed


def dims(c: dict) -> Dims:
    return Dims(
        d=c["hidden_size"], heads=c["num_attention_heads"],
        nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
        v=c["v_head_dim"], rank=c["kv_lora_rank"],
        layers=c["num_hidden_layers"],
        dense_layers=c.get("first_k_dense_replace", 0),
        dense_f=c["intermediate_size"], moe_f=c["moe_intermediate_size"],
        shared_f=c.get("n_shared_experts", 0) * c["moe_intermediate_size"],
        routed=c["n_routed_experts"],
        held=c.get("num_local_experts", c["n_routed_experts"]),
        top_k=c["num_experts_per_tok"], vocab=c["vocab_size"])


def _attn_params(m: Dims) -> int:
    return (m.d * m.heads * (m.nope + m.rope) + m.d * m.latent
            + m.rank * m.heads * (m.nope + m.v) + m.heads * m.v * m.d)


def _swiglu(d: int, f: int) -> int:
    return 3 * d * f


def params(c: dict) -> dict[str, int]:
    """Matrix parameters on this chip by part (norms left out)."""
    m = dims(c)
    expert_layer = (m.held * _swiglu(m.d, m.moe_f) + _swiglu(m.d, m.shared_f)
                    + m.d * m.routed)
    return {"attention": m.layers * _attn_params(m),
            "ffn": (m.dense_layers * _swiglu(m.d, m.dense_f)
                    + m.moe_layers * expert_layer),
            "embed": m.vocab * m.d,
            "head": m.vocab * m.d}


def _ffn_macs_per_token(m: Dims) -> float:
    expert_layer = (m.assignments * _swiglu(m.d, m.moe_f)
                    + _swiglu(m.d, m.shared_f) + m.d * m.routed)
    return (m.dense_layers * _swiglu(m.d, m.dense_f)
            + m.moe_layers * expert_layer)


def matmul_flops_per_token(c: dict, *, head: bool,
                           absorbed: bool = False) -> float:
    """Projection and FFN FLOPs of one token (and, optionally, the
    head); attention over keys is apart.  ``absorbed``: the decode
    form, where ``W_UK`` and ``W_UV`` act on the query and output
    instead of ``wkv_b`` on the token's latent."""
    m = dims(c)
    attn = (m.d * m.heads * (m.nope + m.rope) + m.d * m.latent
            + m.heads * m.v * m.d)
    attn += (m.heads * m.rank * (m.nope + m.v) if absorbed
             else m.rank * m.heads * (m.nope + m.v))
    return 2 * (m.layers * attn + _ffn_macs_per_token(m)
                + (m.vocab * m.d if head else 0))


def _weight_bytes(m: Dims, *, tokens: int) -> int:
    experts = min(m.held, tokens * m.top_k)
    per_expert_layer = (experts * _swiglu(m.d, m.moe_f)
                        + _swiglu(m.d, m.shared_f) + m.d * m.routed)
    return (m.layers * _attn_params(m)
            + m.dense_layers * _swiglu(m.d, m.dense_f)
            + m.moe_layers * per_expert_layer + m.vocab * m.d) * BYTES


def prefill(c: dict, s: int) -> dict[str, float]:
    """One prompt of ``s`` tokens: logits of its last position only."""
    m = dims(c)
    pairs = s * (s + 1) // 2
    flops = (matmul_flops_per_token(c, head=False) * s + 2 * m.vocab * m.d
             + 2 * m.layers * m.heads * (m.nope + m.rope + m.v) * pairs)
    latent = m.layers * m.latent * s * BYTES
    return {"flops": float(flops),
            "bytes": float(_weight_bytes(m, tokens=s) + latent)}


def decode_tick(c: dict, ctx: list[int]) -> dict[str, float]:
    """One engine tick: one new token for each active slot; ``ctx`` is
    each active slot's key count including the new token."""
    m = dims(c)
    n = len(ctx)
    keys = sum(ctx)
    flops = (n * matmul_flops_per_token(c, head=True, absorbed=True)
             + 2 * m.layers * m.heads * (2 * m.rank + m.rope) * keys)
    latent = m.layers * m.latent * keys * BYTES
    return {"flops": float(flops),
            "bytes": float(_weight_bytes(m, tokens=n) + latent)}
