"""One mesh surface: construction, elastic shape choice, CLI resolution.

Everything mesh-shaped lives here — the production/test constructors
that used to sit in ``launch/mesh.py``, the elastic shape chooser from
``runtime/elastic.py``, and the ``--mesh`` flag grammar shared by
train/serve/dryrun — all expressed through ``core.ops.shard.MeshSpec``
so the launcher, the op registry's ``shard_map`` variants, and the
Sharder's in_shardings agree on ONE mesh object (axis names and device
order included).

Elastic posture (unchanged from the seed): checkpoints store GLOBAL
indices per shard (checkpoint/manager.py), so restore simply targets
the new mesh's shardings — no reshard pass.  ``resharder_for`` decides
the new mesh from the surviving device count, and — new here — when
handed the run's ``ExecutionPolicy`` it re-resolves the route under the
new mesh degrees, so node failure and planned rescale re-run the same
capability validation as launch.

``choose_mesh_shape`` is config-aware: the historical default hardcoded
``model_parallel=16`` with no knowledge of the model, so gemma3's 4 KV
heads or mixtral's 8 experts on a 16-way model axis silently
replicated.  Passing the ``ModelConfig`` caps the model axis at the
largest degree that divides every TP/EP-sharded dimension.
"""

from __future__ import annotations

import dataclasses
import warnings

import jax

from repro.core.ops.shard import MeshSpec, make_mesh

__all__ = [
    "MeshSpec",
    "choose_mesh_shape",
    "make_production_mesh",
    "make_test_mesh",
    "max_parallel_degree",
    "mesh_spec_for",
    "replica_mesh_spec",
    "resharder_for",
    "resolve_mesh_flag",
    "resolve_mesh_spec",
]


# ----------------------------------------------------------- constructors

def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512; the ``pod`` axis
    carries only data-parallel gradient reductions (DESIGN.md §5), so
    it maps onto the slower inter-pod fabric.  A FUNCTION, not a
    module constant: importing never touches jax device state."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    return make_mesh(shape, axes, jax.devices()[:n])


def make_test_mesh(data: int = 2, model: int = 2, expert: int = 1):
    """Small mesh for CPU distribution tests (subprocess sets device
    count).  ``expert`` adds the EP axis only when asked, so existing
    (data, model) spec expectations are untouched."""
    if expert > 1:
        return make_mesh((data, expert, model), ("data", "expert", "model"),
                         jax.devices()[: data * expert * model])
    return make_mesh((data, model), ("data", "model"),
                     jax.devices()[: data * model])


# --------------------------------------------------------- elastic shapes

def max_parallel_degree(cfg, limit: int) -> int:
    """Largest model-axis degree <= limit every TP/EP-sharded dim of
    ``cfg`` divides into: the FFN width (TP), the expert count (EP),
    and the KV-head count (attention TP).  Dims the arch does not have
    (0) impose no constraint."""
    dims = [d for d in (cfg.d_ff, cfg.num_experts,
                        cfg.num_kv_heads or cfg.num_heads) if d]
    for deg in range(limit, 0, -1):
        if all(d % deg == 0 for d in dims):
            return deg
    return 1


def choose_mesh_shape(n_devices: int, cfg=None, model_parallel: int = 16,
                      pod_size: int = 256,
                      ) -> tuple[tuple[int, ...], tuple[str, ...]]:
    """Largest supported mesh for the surviving device count.  With a
    ``ModelConfig``, the model axis is additionally capped at the
    largest degree divisible into the model's TP/EP dims (see
    ``max_parallel_degree``) instead of silently replicating."""
    if cfg is not None:
        model_parallel = min(model_parallel,
                             max_parallel_degree(cfg, model_parallel))
    if n_devices >= 2 * pod_size and n_devices % pod_size == 0:
        pods = n_devices // pod_size
        return ((pods, pod_size // model_parallel, model_parallel),
                ("pod", "data", "model"))
    model_parallel = min(model_parallel, n_devices)
    while n_devices % model_parallel:
        model_parallel //= 2
    return ((n_devices // model_parallel, model_parallel),
            ("data", "model"))


def mesh_spec_for(n_devices: int, cfg=None) -> MeshSpec:
    """The MeshSpec ``--mesh auto`` resolves to for this device count.

    The model axis is TP; when the arch's expert count is what bounds
    the degree (it divides, the FFN alone would allow more), the axis
    still carries the experts — the Sharder and the grouped family both
    key on divisibility, not on the axis label."""
    return MeshSpec.from_shape(*choose_mesh_shape(n_devices, cfg))


def replica_mesh_spec(n_devices: int, n_active: int, cfg=None) -> MeshSpec:
    """Per-replica MeshSpec when ``n_devices`` are split evenly across
    ``n_active`` serving replicas — the single mesh surface for the
    pool's scale AND replace actions (serve.autoscale), so a repaired
    replica re-resolves its route exactly like a resized one."""
    return mesh_spec_for(max(1, n_devices // max(n_active, 1)), cfg)


# ------------------------------------------------------------ CLI surface

def resolve_mesh_flag(mesh_arg: str | None, use_mesh: bool = False,
                      ) -> str | None:
    """Merge the ``--mesh`` flag with the deprecated ``--use-mesh``
    boolean: ``--use-mesh`` is an alias for ``--mesh auto``."""
    if use_mesh:
        warnings.warn("--use-mesh is deprecated; use --mesh auto",
                      DeprecationWarning, stacklevel=2)
        if mesh_arg is None:
            mesh_arg = "auto"
    return mesh_arg


def resolve_mesh_spec(mesh_arg: str | None, cfg=None,
                      n_devices: int | None = None) -> MeshSpec | None:
    """``--mesh`` value -> MeshSpec: ``auto`` fits the device count
    (config-aware), the ``dp=2,tp=2,ep=2`` grammar is explicit, None
    stays None (single-device)."""
    if mesh_arg is None:
        return None
    if mesh_arg.strip().lower() == "auto":
        n = n_devices if n_devices is not None else jax.device_count()
        return mesh_spec_for(n, cfg)
    return MeshSpec.parse(mesh_arg)


# ---------------------------------------------------------------- elastic

def _mesh_for_spec(spec: MeshSpec, devices=None):
    """The concrete Mesh for ``spec`` — the registry's own cached mesh
    when running over the default device prefix (so shard_map bodies
    and in_shardings share one object), else an equivalent mesh over
    the given devices."""
    if devices is None:
        return spec.build()
    items = spec._axis_items()
    return make_mesh(tuple(s for _, s in items), tuple(a for a, _ in items),
                     list(devices)[: spec.size])


def resharder_for(cfg, devices=None, *, policy=None, mode: str = "train"):
    """Mesh + Sharder (+ re-routed policy) for the surviving devices.

    Without ``policy``: returns ``(mesh, sharder)`` — the historical
    elastic-restart contract.  With the run's ``ExecutionPolicy``:
    returns ``(mesh, sharder, policy)`` where the policy's ``mesh``
    field is replaced by the newly chosen MeshSpec — which re-runs
    capability validation (``Partitioning`` included), so a rescale
    that changes TP/EP degrees re-resolves the route exactly like a
    fresh launch would.
    """
    n = len(devices) if devices is not None else jax.device_count()
    spec = mesh_spec_for(n, cfg)
    mesh = _mesh_for_spec(spec, devices)
    if policy is None:
        from repro.runtime.sharding import Sharder
        return mesh, Sharder(cfg, mesh, mode=mode)
    policy = dataclasses.replace(policy, mesh=spec)
    from repro.runtime.sharding import Sharder
    return mesh, Sharder(cfg, mesh, mode=mode, policy=policy), policy
