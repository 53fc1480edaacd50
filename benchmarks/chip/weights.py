"""Random weights from ``--seed``, made on the device in one jitted call.

The benchmark makes the weights, not the program: the program is given
them, and the reference reads the same arrays or makes them again from
the same seed.  Only the layout (the tree of names and shapes the
program takes) comes from the program.  Values, by the leaf's name:

* ``w`` (a matrix ``(..., d_in, d_out)``): normal, scale ``d_in**-0.5``;
* ``table`` (embedding or head ``(V, D)``): normal, scale ``D**-0.5``,
  so that logits start near unit scale;
* ``b`` (bias): normal, scale 0.02, so that the bias paths are live;
* ``scale`` (norm gain): ``1 + 0.1 * normal``.

Weights are float32, the type the program keeps and serves them in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["key_for", "builder", "make"]


def key_for(seed: int, stream: int = 0) -> jax.Array:
    """A JAX key from any whole-number seed (more than 32 bits too)."""
    state = np.random.SeedSequence([seed % 2**64, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(state, jnp.uint32))


def _leaf(key, path, shape):
    name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "w":
        return z * shape[-2] ** -0.5
    if name == "table":
        return z * shape[-1] ** -0.5
    if name == "b":
        return 0.02 * z
    if name == "scale":
        return 1.0 + 0.1 * z
    raise ValueError(f"no weight rule for leaf {jax.tree_util.keystr(path)}")


def builder(abstract):
    """A jitted ``key -> weights`` for the ``abstract`` tree (leaves with
    ``.shape``)."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    @jax.jit
    def build(key):
        return treedef.unflatten([
            _leaf(jax.random.fold_in(key, i), path, leaf.shape)
            for i, (path, leaf) in enumerate(paths)])

    return build


def make(abstract, seed: int):
    """Weights for the ``abstract`` tree made from ``seed``."""
    return builder(abstract)(key_for(seed))
