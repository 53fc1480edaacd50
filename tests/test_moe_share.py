"""The expert share: a layer that holds some of the experts its router
scores computes the part of the result its own experts give.  On the
CPU, f32: the shares 4 + 4 of 8, with the shared experts counted once,
add up to the uncut layer, the program's and the plain reference's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_mla import hf_keys, load_reference, smoke_f32

from repro.models import layers as L
from repro.models import moe as M

E, HELD, TOP_K = 8, 4, 2


@pytest.fixture(scope="module")
def layer():
    """An uncut expert layer (router over 8, all 8 experts, shared) and
    a batch of inputs."""
    cfg = smoke_f32()
    p = M.init_moe(jax.random.PRNGKey(2), cfg.d_model, cfg.moe_d_ff, E,
                   "swiglu", shared_d_ff=cfg.shared_d_ff)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, cfg.d_model))
    return cfg, p, x


def _share(p, first):
    return {**p, **{n: {"w": p[n]["w"][first:first + HELD]}
                    for n in ("wi", "wg", "wo")}}


def _run(p, x, first=0, num_experts=E):
    out, aux = M.moe_ffn(p, x, num_experts=num_experts, top_k=TOP_K,
                         capacity_factor=1.0, mlp_kind="swiglu",
                         policy="f32", dropless=True, first_expert=first)
    return np.asarray(out), float(aux)


def test_shares_add_up_to_the_uncut_layer(layer):
    """Each share routes over all 8 router outputs; what the two give,
    less the shared experts that each computes alike, is the uncut
    layer.  Same f32 products summed in another order: 1e-5."""
    cfg, p, x = layer
    full, aux_full = _run(p, x)
    lo, aux_lo = _run(_share(p, 0), x, 0, HELD)
    hi, aux_hi = _run(_share(p, HELD), x, HELD, HELD)
    shared = np.asarray(L.mlp(p["shared"], x, "swiglu", "f32"))
    np.testing.assert_allclose(lo + hi - shared, full, rtol=0, atol=1e-5)
    # the load-balancing loss is over every expert the router scores
    assert aux_lo == pytest.approx(aux_full) and aux_hi == aux_lo


def test_a_share_is_not_the_whole(layer):
    """Half the experts give a different result: the share is real."""
    _, p, x = layer
    full, _ = _run(p, x)
    lo, _ = _run(_share(p, 0), x, 0, HELD)
    assert np.abs(lo - full).max() > 1e-2


def test_reference_shares_add_up_to_its_uncut_layer(layer):
    """The plain reference holds the same share the same way: its
    shares add up to its own uncut layer, and that equals the
    program's (both f32: 1e-5)."""
    cfg, p, x = layer
    ref = load_reference()
    keys = hf_keys(cfg)
    flat = x.reshape(-1, cfg.d_model)

    def ref_layer(params, first, held):
        c = dict(keys, num_local_experts=held, first_local_expert=first)
        return np.asarray(ref._moe(params, flat, c)[0])

    full = ref_layer(p, 0, E)
    lo = ref_layer(_share(p, 0), 0, HELD)
    hi = ref_layer(_share(p, HELD), HELD, HELD)
    shared = np.asarray(ref._swiglu(p["shared"], flat))
    np.testing.assert_allclose(lo + hi - shared, full, rtol=0, atol=1e-5)
    program, _ = _run(p, x)
    np.testing.assert_allclose(program.reshape(flat.shape), full, rtol=0,
                               atol=1e-5)


def test_decode_capacity_holds_every_assignment(layer):
    """Dropless capacity is one row per token: a token meets each expert
    at most once, so even a router that sends every token to the same
    two experts drops nothing."""
    _, p, x = layer
    x = jnp.abs(x) + 1.0          # positive rows: the rigged logits win
    rigged = {**p, "router": {"w": jnp.zeros_like(p["router"]["w"])
                              .at[:, :TOP_K].set(5.0)}}
    out, _ = _run(rigged, x)
    want = sum(
        np.asarray(L.mlp({n: {"w": p[n]["w"][e]} for n in ("wi", "wg", "wo")},
                         x, "swiglu", "f32"))
        * np.asarray(jax.nn.softmax(jnp.einsum(
            "bsd,de->bse", x, rigged["router"]["w"]), -1)[..., e:e + 1])
        for e in range(TOP_K))
    shared = np.asarray(L.mlp(p["shared"], x, "swiglu", "f32"))
    np.testing.assert_allclose(out, want + shared, rtol=0, atol=1e-4)
