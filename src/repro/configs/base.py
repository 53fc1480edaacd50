"""Model/config schema shared by all assigned architectures.

A model is a sequence of *segments*; each segment is a run of identical
layer "kinds" whose params are stacked on a leading axis and executed
with ``lax.scan`` (keeps HLO small for 26-96-layer stacks so the 80
dry-run compiles stay fast). Heterogeneous stacks (gemma3's 5 local : 1
global, zamba2's mamba + shared-attention interleave) become short
segment lists.

Layer kinds:
  attn          GQA self-attention sublayer (+RoPE, optional window)
  attn_local    ``attn`` under the sliding window
  mla           multi-head latent attention (DeepSeek-V2): a latent KV
                cache, expanded for prefill/train, absorbed for decode
  mlp           dense FFN sublayer (swiglu / squared_relu / gelu)
  moe           mixture-of-experts FFN sublayer
  mamba2        Mamba-2 SSD mixer sublayer
  rwkv6         RWKV-6 time-mix + channel-mix layer
  shared_attn   zamba2-style shared transformer block (params shared
                across all its occurrences; stored once, not stacked)
"""

from __future__ import annotations

import dataclasses
import warnings

from repro.core.matmul import MatmulPolicy
from repro.core.ops import ExecutionPolicy, TileConfig, normalize_backends

__all__ = ["Segment", "Yarn", "ModelConfig", "ShapeSpec", "LM_SHAPES",
           "execution_policy_for", "matmul_policy_for"]

MIXER_KINDS = ("attn", "attn_local", "mla", "mamba2", "rwkv6",
               "shared_attn")


@dataclasses.dataclass(frozen=True)
class Segment:
    """``count`` repetitions of the layer-kind tuple ``pattern``.

    E.g. gemma3: Segment(("attn_local", "mlp") * 5 + ("attn", "mlp"), 4)
    runs 4 periods of [5 local layers + 1 global layer].
    """

    pattern: tuple[str, ...]
    count: int = 1


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN rope scaling (arXiv:2309.00071), as DeepSeek-V2 configures
    it: frequencies interpolated by ``factor`` below the correction
    range set by ``beta_fast``/``beta_slow`` over the original context,
    and the softmax scale multiplied by ``mscale(mscale_all_dim)**2``."""

    factor: float
    original_max_pos: int
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    beta_fast: float = 32.0
    beta_slow: float = 1.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | audio | vlm
    d_model: int
    num_layers: int                  # total layers (bookkeeping; segments rule)
    segments: tuple[Segment, ...]
    vocab_size: int
    # attention
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0
    rope_theta: float = 10_000.0
    window: int | None = None        # sliding window for attn_local kind
    attn_logit_softcap: float | None = None
    qkv_bias: bool = False
    # latent attention (mla): head_dim is the no-rope part of q and k
    kv_lora_rank: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    yarn: Yarn | None = None
    # ffn
    d_ff: int = 0
    mlp_kind: str = "swiglu"         # swiglu | squared_relu | gelu
    mlp_bias: bool = False
    # moe
    num_experts: int = 0             # experts this layer holds
    top_k: int = 0
    capacity_factor: float = 1.25
    num_routed_experts: int = 0      # experts the router scores (0: held)
    first_expert: int = 0            # router index of the first held one
    moe_d_ff: int = 0                # routed expert width (0: d_ff)
    shared_d_ff: int = 0             # shared experts as one FFN (0: none)
    # ssm (mamba2)
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_chunk: int = 256
    conv_width: int = 4
    # rwkv6
    rwkv_head_dim: int = 64
    rwkv_chunk: int = 64        # WKV chunk; §Perf B2: per-chunk overhead
                                # scales 1/C, the (C,C,K) tensor scales C
    # enc-dec (whisper)
    encoder_layers: int = 0
    encoder_seq: int = 0             # stubbed frontend: frames arrive embedded
    encoder_segments: tuple[Segment, ...] = ()
    # vlm (internvl2)
    num_image_tokens: int = 0
    # norm / misc
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    activation_dtype: str = "bfloat16"
    # which registered impl each op family runs by default for this
    # arch: a {family: impl} mapping over the repro.core.ops registry
    # ("gemm" / "attention" / "grouped", optionally layer-scoped keys
    # like "gemm@logits"; CLI --backend family=impl overrides).
    # Families absent here resolve to their reference impl.
    backends: tuple[tuple[str, str], ...] = ()
    # DEPRECATED per-family fields (the pre-registry surface): merged
    # into the ``backends`` mapping by execution_policy_for /
    # matmul_policy_for; explicit ``backends`` entries win.
    matmul_backend: str = "xla"
    attn_backend: str = "xla"
    grouped_backend: str = "xla"
    # which shapes this arch supports (long_500k dropped for pure full-attn)
    supported_shapes: tuple[str, ...] = (
        "train_4k", "prefill_32k", "decode_32k")

    def __post_init__(self) -> None:
        object.__setattr__(self, "backends",
                           normalize_backends(self.backends))
        # "num_layers" counts mixer sublayers (MIXER_KINDS); mlp/moe
        # sublayers ride along inside the same layer.
        mixers = sum(s.count * sum(k in MIXER_KINDS for k in s.pattern)
                     for s in self.segments)
        if mixers != self.num_layers:
            raise ValueError(
                f"{self.name}: segments define {mixers} mixer layers, "
                f"config says num_layers={self.num_layers}")

    @property
    def qk_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def router_experts(self) -> int:
        return self.num_routed_experts or self.num_experts


def _arch_backends(cfg: ModelConfig) -> dict[str, str]:
    """The arch's default {family: impl} mapping: legacy per-family
    fields first, explicit ``cfg.backends`` entries win."""
    merged = {"gemm": cfg.matmul_backend, "attention": cfg.attn_backend,
              "grouped": cfg.grouped_backend}
    merged.update(dict(cfg.backends))
    return merged


def execution_policy_for(cfg: ModelConfig, *, default: str = "bf16",
                         logits: str | None = None,
                         backends=None,
                         tiles: TileConfig | None = None,
                         fallback: bool = False,
                         require=None, mesh=None) -> ExecutionPolicy:
    """The launch-script policy constructor: precision knobs from CLI
    flags, the op-family ``backends`` mapping from the repeatable
    ``--backend family=impl`` CLI overrides layered over the arch's
    defaults — validated against capability metadata at build time
    (``require`` adds feature demands, e.g. serve's attention decode;
    a non-identity ``mesh`` additionally demands Partitioning of every
    routed impl, so ``--mesh`` composes with ``--backend``)."""
    merged = _arch_backends(cfg)
    merged.update(dict(normalize_backends(backends or ())))
    return ExecutionPolicy(default=default, logits=logits, backends=merged,
                           tiles=tiles, fallback=fallback,
                           require=require or (), mesh=mesh)


def matmul_policy_for(cfg: ModelConfig, *, default: str = "bf16",
                      logits: str | None = None,
                      backend: str | None = None,
                      attn_backend: str | None = None,
                      grouped_backend: str | None = None,
                      tiles: TileConfig | None = None) -> MatmulPolicy:
    """DEPRECATED pre-registry policy constructor (one knob per kernel
    family); kept as a thin wrapper so old call sites and flags work.
    Use ``execution_policy_for(cfg, backends={family: impl})``."""
    warnings.warn(
        "matmul_policy_for is deprecated; use execution_policy_for(cfg, "
        "backends={'gemm': ..., 'attention': ..., 'grouped': ...})",
        DeprecationWarning, stacklevel=2)
    return MatmulPolicy(
        default=default, logits=logits,
        backend=backend if backend is not None else cfg.matmul_backend,
        attn_backend=(attn_backend if attn_backend is not None
                      else cfg.attn_backend),
        grouped_backend=(grouped_backend if grouped_backend is not None
                         else cfg.grouped_backend),
        tiles=tiles)


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    """One assigned input-shape cell (seq_len x global_batch + mode)."""

    name: str
    seq_len: int
    global_batch: int
    mode: str  # "train" | "prefill" | "decode"


LM_SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}
