"""Logical -> mesh-axis sharding rules (DP / FSDP / TP / EP / SP).

Mesh axes: ``data`` (FSDP + batch), ``model`` (TP), optional ``expert``
(true EP when the mesh carries one; otherwise EP rides the model axis)
and ``pod`` (pure DP across pods — reduction-only traffic, so it
tolerates the slower inter-pod fabric; parameters are NOT sharded
across pods).

Every rule is DIVISIBILITY-GUARDED: an axis is sharded only when its
size divides evenly into the mesh axis, so the same rule set compiles
for all 10 architectures (e.g. gemma3's 4 attention heads stay
replicated on a 16-way model axis while its 6912-wide FFN takes TP;
mixtral's 8 experts fall back to TP-in-expert while dbrx's 16 experts
take true EP).

Every model-axis rule is additionally CAPABILITY-GATED: given the
run's ``ExecutionPolicy``, a dim only shards when the ROUTED impl of
the op family that consumes it declares the matching role in its
``Partitioning`` capability (weights gate on the gemm impl's ``tp``,
the logits table on ``gemm@logits``, KV caches on the attention impl,
expert stacks on the grouped impl's ``ep``) — the registry's metadata
replaces the old path-matching-only heuristics.  Without a policy the
rules stay purely divisibility-guarded (the pre-registry behavior).

Batch sharding: global batch over (pod, data) when divisible; the
``long_500k`` B=1 cells switch to SEQUENCE sharding (SP) over ``data``
— activations and KV caches shard the sequence axis and XLA inserts
the partial-softmax reductions.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

__all__ = ["Sharder"]


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def _estimate_param_bytes(cfg: ModelConfig) -> int:
    """fp32 parameter bytes without allocation (eval_shape)."""
    import numpy as np

    from repro.models import api
    tree = jax.eval_shape(
        lambda: api.init_params(jax.random.PRNGKey(0), cfg))
    return int(sum(np.prod(l.shape) * 4 for l in jax.tree_util.tree_leaves(tree)))


class Sharder:
    """Builds NamedShardings for params / batch / cache of one cell.

    ``mode``: "train" (default) applies FSDP (ZeRO-3) to weight input
    dims; "serve" REPLICATES weights over the data axis when the
    TP-sharded copy fits the per-chip HBM budget — at decode, one token
    per sequence cannot amortize a per-layer FSDP all-gather, which
    otherwise makes every decode cell collective-bound (measured:
    §Perf iteration C2). Archs whose TP shard exceeds the budget
    (dbrx-132b, nemotron-340b, internvl2-76b, command-r-35b at fp32)
    keep FSDP at serve time.
    """

    # fp32 per-chip weight budget before serve-mode keeps FSDP
    SERVE_REPLICATE_BUDGET = 8 * 2 ** 30

    def __init__(self, cfg: ModelConfig, mesh: Mesh, mode: str = "train",
                 param_bytes: int | None = None, policy=None):
        self.cfg = cfg
        self.mesh = mesh
        self.mode = mode
        self.policy = policy            # ExecutionPolicy or None (legacy)
        self.d_model = _axis_size(mesh, "model")
        self.d_data = _axis_size(mesh, "data")
        self.d_expert = _axis_size(mesh, "expert")
        self.d_pod = _axis_size(mesh, "pod")
        self.dp_axes: tuple[str, ...] = tuple(
            a for a in ("pod", "data") if a in mesh.axis_names)
        self.dp_size = self.d_pod * self.d_data
        self.fsdp = True
        if mode == "serve":
            pb = param_bytes if param_bytes is not None \
                else _estimate_param_bytes(cfg)
            self.fsdp = pb / self.d_model > self.SERVE_REPLICATE_BUDGET

    # ------------------------------------------------------------ helpers

    def _m(self, dim: int) -> str | None:
        """'model' if dim divides the model axis, else replicate."""
        return "model" if dim % self.d_model == 0 else None

    def shardable(self, family: str, role: str,
                  layer: str | None = None) -> bool:
        """Does the policy's routed impl for ``family`` (optionally
        layer-scoped) declare ``role`` in its Partitioning?  True when
        no policy is attached — the legacy divisibility-only rules."""
        if self.policy is None:
            return True
        from repro.core.ops import registry
        caps = registry.get_impl(
            family, self.policy.impl_for(family, layer)).capabilities
        return (caps.partitioning is not None
                and role in caps.partitioning.roles)

    def _tp(self, dim: int, family: str = "gemm",
            layer: str | None = None) -> str | None:
        """'model' when dim divides AND the routed impl shards it."""
        if self.shardable(family, "tp", layer):
            return self._m(dim)
        return None

    def _e(self, e: int) -> str | None:
        """The axis the expert stack dim shards over: the dedicated
        'expert' axis when the mesh has one, else the legacy
        EP-on-model placement; None when EP is not routable."""
        if not self.shardable("grouped", "ep"):
            return None
        if self.d_expert > 1:
            return "expert" if e % self.d_expert == 0 else None
        return self._m(e)

    def _f(self, dim: int) -> str | None:
        """FSDP: 'data' if dim divides the data axis, else replicate."""
        if not self.fsdp:
            return None
        return "data" if dim % self.d_data == 0 else None

    def _dp(self, batch: int):
        """Batch axes: (pod,data) -> ('pod','data') / 'data' / None."""
        if batch % self.dp_size == 0:
            return self.dp_axes if len(self.dp_axes) > 1 else "data"
        if batch % self.d_data == 0:
            return "data"
        return None

    def ns(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    # ------------------------------------------------------------- params

    def _param_spec(self, path: str, shape: tuple[int, ...]) -> P:
        cfg = self.cfg
        # Embedding / unembedding tables (V, D): vocab -> model (TP of
        # the logits matmul). The embed dim is REPLICATED on purpose:
        # sharding it over 'data' makes the token-gather output carry
        # D:'data' while the activations carry B:'data' — an impossible
        # resharding that XLA SPMD resolves by involuntary full
        # rematerialization (full replication) of the hidden states.
        # Measured in EXPERIMENTS.md §Perf iteration A1.
        if path.endswith(("embed/table", "unembed/table")):
            v, d = shape
            return P(self._tp(v, "gemm", "logits"), None)
        if "pos_embed" in path:
            return P(None, self._f(shape[-1]))

        # MoE experts: (..., E, D, F)-family. True EP when E divides the
        # model axis (dbrx); otherwise TP on the ffn dim (mixtral).
        if cfg.num_experts and len(shape) == 4:  # (count, E, din, dout)
            _, e, din, dout = shape
            ep = self._e(e)
            if ep == "expert":   # true EP axis: F can still take TP
                return P(None, ep, self._f(din), self._tp(dout, "grouped"))
            if ep is not None:
                return P(None, ep, self._f(din), None)
            return P(None, None, self._f(din), self._tp(dout, "grouped"))
        if cfg.num_experts and len(shape) == 3 and shape[0] == cfg.num_experts:
            e, din, dout = shape
            ep = self._e(e)
            if ep == "expert":
                return P(ep, self._f(din), self._tp(dout, "grouped"))
            if ep is not None:
                return P(ep, self._f(din), None)
            return P(None, self._f(din), self._tp(dout, "grouped"))

        # Stacked / unstacked weight matrices: (…, d_in, d_out).
        if path.endswith("/w") and len(shape) >= 2:
            din, dout = shape[-2], shape[-1]
            lead = (None,) * (len(shape) - 2)
            # Output-projection style (wo/out_proj/ffn_v/b-of-lora): the
            # CONTRACTING dim is the sharded 'model' one.
            if any(t in path for t in ("wo/", "out_proj", "ffn_v", "/b/")):
                return P(*lead, self._tp(din), self._f(dout))
            return P(*lead, self._f(din), self._tp(dout))

        # Everything else (norm scales, biases, decay vectors, conv
        # kernels, u/w0/mu, dt_bias, ...) is small: replicate.
        return P(*((None,) * len(shape)))

    def param_specs(self, abstract_params: Any) -> Any:
        def spec(kp, leaf):
            path = "/".join(
                getattr(k, "key", getattr(k, "name", str(k))) for k in kp)
            return self.ns(self._param_spec(path, leaf.shape))
        return jax.tree_util.tree_map_with_path(spec, abstract_params)

    # -------------------------------------------------------------- batch

    def batch_specs(self, batch: dict[str, Any]) -> dict[str, Any]:
        out = {}
        for name, leaf in batch.items():
            shape = leaf.shape
            if len(shape) == 0:
                out[name] = self.ns(P())
                continue
            if name == "pos":
                # (B,) per-slot positions: sharded with the batch rows
                out[name] = self.ns(P(self._dp(shape[0])))
                continue
            b = shape[0]
            dp = self._dp(b)
            if dp is None and len(shape) >= 2 and shape[1] % self.d_data == 0:
                # SP fallback (long_500k B=1): shard sequence over data.
                out[name] = self.ns(P(None, "data", *(None,) * (len(shape) - 2)))
            else:
                out[name] = self.ns(P(dp, *(None,) * (len(shape) - 1)))
        return out

    # -------------------------------------------------------------- cache

    def _cache_spec(self, path: str, shape: tuple[int, ...]) -> P:
        # Recurrent states: rwkv wkv (count,B,H,K,V), mamba ssd
        # (count,B,H,P,N) — shard HEADS on the model axis.
        if ("wkv" in path or "ssd" in path) and len(shape) == 5:
            _, b, h, _, _ = shape
            return P(None, self._dp(b), self._m(h), None, None)
        # Latent (mla) caches: (count, B, S, rank | rope), read whole by
        # every head, so only the batch shards.
        if path.endswith(("/latent", "/k_rope")):
            return P(None, self._dp(shape[1]), None, None)
        # Stacked attn caches: (count, B, S, Kv, hd)
        if len(shape) == 5:
            _, b, s, kv, _ = shape
            kv_ax = self._tp(kv, "attention")
            dp = self._dp(b)
            if dp is None:  # B=1 long-context: sequence-shard the cache
                return P(None, None, "data" if s % self.d_data == 0 else None,
                         kv_ax, None)
            return P(None, dp, None, kv_ax, None)
        if len(shape) == 4:  # (count, B, W-1, conv_dim) mamba conv
            _, b, _, c = shape
            return P(None, self._dp(b), None, self._m(c))
        if len(shape) == 3:  # (count, B, D) rwkv shift states
            _, b, _ = shape
            return P(None, self._dp(b), None)
        return P(*((None,) * len(shape)))

    def cache_specs(self, abstract_cache: Any) -> Any:
        def spec(kp, leaf):
            path = "/".join(
                getattr(k, "key", getattr(k, "name", str(k))) for k in kp)
            return self.ns(self._cache_spec(path, leaf.shape))
        return jax.tree_util.tree_map_with_path(spec, abstract_cache)

    # ---------------------------------------------------------- optimizer

    def opt_specs(self, param_specs: Any) -> Any:
        """Adam m/v mirror the param shardings (built by optim.adamw)."""
        return param_specs
