"""The serve engine holds each weight in the precision its programs read
it in: a float32 leaf that every read in the tick and the prefill rounds
to bf16 is rounded once, at load.

Checked on the CPU, for a Mixtral-like model (GQA and 8 experts), a
DeepSeek-like one (latent attention, shared experts, a held share of
the routed experts) and a dense GQA one:

* under the default ``bf16`` route the engine's prefill logits equal
  ``api.prefill`` on the caller's f32 tree bit for bit, and its first 8
  greedy tokens equal those of ``api.prefill`` and ``api.decode`` there;
* the projections, experts, embedding and head are held in bf16, and
  the router, norm scales and biases stay f32;
* under ``bf16x3`` and ``PrecisionPolicy.mixed_hpc()`` the operands those
  rungs split stay f32;
* the ``engine.load`` span reports the bytes the engine holds;
* the caller's arrays are neither deleted nor changed by the load, a
  tick or a prefill;
* the lowered tick and prefill take every such weight as a bf16
  argument, never an f32 one;
* on a mesh, each rounded leaf keeps its leaf's sharding."""

import dataclasses
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.precision import PrecisionPolicy
from repro.launch.serve import Request, ServeEngine
from repro.models import api
from repro.runtime import serve_step
from repro.runtime.monitor import recent_spans, span

MAX_CTX, NEW_TOKENS = 32, 8
PROMPT = np.arange(3, 14, dtype=np.int32)
CONFIGS = {
    # one published period: GQA attention with no window, 8 experts
    "mixtral-like": lambda: dataclasses.replace(
        get_smoke("mixtral-8x7b"), num_experts=8, window=None),
    "deepseek-like": lambda: get_smoke("deepseek-v2-lite"),
    "dense-gqa": lambda: get_smoke("starcoder2-15b"),
}


def init_weights(cfg) -> dict:
    """``api.init_params`` with norm scales and biases that bf16 cannot
    hold exactly (ones and zeros it can), so rounding one shows."""
    def live(path, x):
        z = jax.random.normal(jax.random.PRNGKey(len(path) + x.size),
                              x.shape, x.dtype)
        name = _path_keys(path)[-1]
        return (1 + 0.1 * z if name == "scale" else
                0.02 * z if name == "b" else x)
    return jax.tree_util.tree_map_with_path(
        live, api.init_params(jax.random.PRNGKey(0), cfg))


def _path_keys(path) -> tuple:
    return tuple(getattr(k, "key", k) for k in path)


def read_in_bf16(keys: tuple, policy: PrecisionPolicy) -> bool:
    """The leaves a route's reads all round to bf16, by name: every
    matrix ``w`` but the router's (scored in f32) and the embedding,
    unless the route splits them; never a norm ``scale`` or a bias."""
    split = ("bf16x3", "bf16x6", "refine_ab", "fp8x3", "int8x3")
    if keys[-1] == "table":
        return keys[0] == "embed" or policy.for_("logits") not in split
    return (keys[-1] == "w" and "router" not in keys
            and policy.default not in split)


def _held(eng) -> dict:
    return {_path_keys(p): x for p, x in
            jax.tree_util.tree_flatten_with_path(eng.params)[0]}


def _load_span_attrs(mark) -> dict:
    loads = [sp for sp in recent_spans()
             if sp[0] == "engine.load" and sp[3] > mark.id]
    assert len(loads) == 1
    return loads[0][5]


def _bitwise(got, want) -> None:
    got, want = np.array(got), np.array(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got.view(np.uint8), want.view(np.uint8))


def reference_tokens(cfg, params, policy):
    """Prefill logits and greedy tokens of ``api.prefill``/``api.decode``
    on ``params``, with the cache the engine holds (activation dtype,
    ``MAX_CTX`` rows)."""
    prefill = jax.jit(lambda p, b: api.prefill(p, b, cfg, policy=policy))
    decode = jax.jit(lambda p, c, t, pos: api.decode(p, c, t, pos, cfg,
                                                     policy=policy))
    logits, cache1 = prefill(params, {"tokens": jnp.asarray(PROMPT)[None]})
    cache1 = serve_step.pad_cache(cache1, cfg, MAX_CTX)

    def splice(full, one):
        return (one.astype(full.dtype) if getattr(one, "ndim", 0) >= 2
                else full)

    cache = jax.tree.map(splice, api.init_cache(
        cfg, 1, MAX_CTX, jnp.dtype(cfg.activation_dtype)), cache1)
    toks = [int(jnp.argmax(logits[0, -1]))]
    for pos in range(len(PROMPT), len(PROMPT) + NEW_TOKENS - 1):
        lg, cache = decode(params, cache, jnp.asarray([[toks[-1]]]),
                           jnp.asarray([pos], jnp.int32))
        toks.append(int(jnp.argmax(lg[0, 0])))
    return logits, toks


@pytest.fixture(scope="module", params=list(CONFIGS))
def served(request):
    """One engine on the default route: its prefill logits and tokens
    beside the reference's, and what happened to the caller's tree."""
    cfg = CONFIGS[request.param]()
    policy = PrecisionPolicy.uniform("bf16")
    params = init_weights(cfg)
    before = jax.tree.map(np.array, params)
    eng = ServeEngine(cfg, batch_size=1, max_ctx=MAX_CTX, policy=policy,
                      eos_id=-1)
    with span("mark") as mark:
        pass
    eng.load(params)
    out = {"cfg": cfg, "policy": policy, "params": params,
           "before": before, "eng": eng, "load": _load_span_attrs(mark)}
    out["logits"], _ = eng._prefill(
        eng.params, {"tokens": jnp.asarray(PROMPT)[None]})
    req = Request(rid=0, prompt=PROMPT, max_new_tokens=NEW_TOKENS)
    eng.run([req])
    out["tokens"] = list(req.out_tokens)
    out["ref_logits"], out["ref_tokens"] = reference_tokens(
        cfg, params, policy)
    return out


def test_prefill_logits_equal_the_f32_tree_bit_for_bit(served):
    _bitwise(served["logits"], served["ref_logits"])


def test_greedy_tokens_equal_the_f32_tree(served):
    assert len(served["tokens"]) == NEW_TOKENS
    assert served["tokens"] == served["ref_tokens"]


def test_projections_experts_and_tables_are_held_in_bf16(served):
    held = _held(served["eng"])
    want = {k: jnp.bfloat16 if read_in_bf16(k, served["policy"])
            else jnp.float32 for k in held}
    assert {k: x.dtype for k, x in held.items()} == want
    assert any("router" in k for k in held) or served["cfg"].family != "moe"
    assert any(k[-1] == "scale" for k in held)


def test_caller_tree_is_untouched_by_load_tick_and_prefill(served):
    for x, old in zip(jax.tree.leaves(served["params"]),
                      jax.tree.leaves(served["before"])):
        assert not x.is_deleted()
        _bitwise(x, old)
    # the leaves kept f32 are the caller's own arrays, not copies
    held = jax.tree.leaves(served["eng"].params)
    for x, mine in zip(jax.tree.leaves(served["params"]), held):
        assert (mine is x) == (mine.dtype == x.dtype)


def test_load_span_reports_the_held_tree(served):
    held = jax.tree.leaves(served["eng"].params)
    rounded = [x for x, given in zip(held, jax.tree.leaves(served["params"]))
               if x.dtype != given.dtype]
    assert all(x.dtype == jnp.bfloat16 for x in rounded)
    assert served["load"] == {
        "rounded_leaves": len(rounded),
        "rounded_bytes": sum(x.nbytes for x in rounded),
        "kept_f32_bytes": sum(x.nbytes for x in held
                              if x.dtype == jnp.float32)}
    assert served["load"]["rounded_bytes"] > 0


@pytest.mark.parametrize("rung", ["bf16x3", "mixed_hpc"])
@pytest.mark.parametrize("name", list(CONFIGS))
def test_multi_pass_rungs_keep_the_operands_they_split(name, rung):
    cfg = CONFIGS[name]()
    policy = (PrecisionPolicy.mixed_hpc() if rung == "mixed_hpc"
              else PrecisionPolicy.uniform(rung))
    params = init_weights(cfg)
    eng = ServeEngine(cfg, batch_size=2, max_ctx=MAX_CTX, policy=policy)
    with span("mark") as mark:
        pass
    eng.load(params)
    held = _held(eng)
    assert {k: x.dtype for k, x in held.items()} == {
        k: jnp.bfloat16 if read_in_bf16(k, policy) else jnp.float32
        for k in held}
    assert held[("unembed", "table")].dtype == jnp.float32
    rounded = [x for k, x in held.items() if x.dtype == jnp.bfloat16]
    assert _load_span_attrs(mark)["rounded_bytes"] == sum(
        x.nbytes for x in rounded)


def _main_arg_types(lowered) -> list[str]:
    sig = re.search(r"func\.func public @main\((.*?)\) ->",
                    lowered.as_text(), re.S).group(1)
    return re.findall(r"%arg\d+: tensor<([^>]*)>", sig)


@pytest.mark.parametrize("name", ["mixtral-like", "deepseek-like"])
def test_lowered_programs_take_no_f32_weight_the_rule_rounds(name):
    cfg = CONFIGS[name]()
    policy = PrecisionPolicy.uniform("bf16")
    eng = ServeEngine(cfg, batch_size=2, max_ctx=MAX_CTX, policy=policy)
    eng.load(init_weights(cfg))
    keys = [_path_keys(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(eng.params)[0]]
    lowered = {
        "tick": eng._tick.lower(eng.params, eng.cache, eng.last_tok,
                                eng.pos, eng.active, eng.remaining),
        "prefill": eng._prefill.lower(
            eng.params, {"tokens": jnp.asarray(PROMPT)[None]})}
    for what, low in lowered.items():
        types = _main_arg_types(low)
        assert len(types) == len(jax.tree.leaves(low.args_info)), what
        weights = dict(zip(keys, types))    # params are the first args
        rounded = [k for k in keys if read_in_bf16(k, policy)]
        assert rounded
        assert [k for k in rounded if not weights[k].endswith("xbf16")] \
            == [], what


MESH_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses
    import jax
    from repro.configs import get_smoke
    from repro.core.precision import PrecisionPolicy
    from repro.launch.serve import ServeEngine
    from repro.models import api
    from repro.runtime import serve_step
    from repro.runtime.mesh import make_test_mesh
    from repro.runtime.sharding import Sharder

    cfg = dataclasses.replace(get_smoke("mixtral-8x7b"), num_experts=8,
                              window=None)
    specs = Sharder(cfg, make_test_mesh(data=2, model=2)).param_specs(
        serve_step.abstract_params(cfg))
    params = jax.device_put(api.init_params(jax.random.PRNGKey(0), cfg),
                            specs)
    eng = ServeEngine(cfg, batch_size=2, max_ctx=32,
                      policy=PrecisionPolicy.uniform("bf16"))
    eng.load(params)
    n = 0
    for given, held in zip(jax.tree.leaves(params),
                           jax.tree.leaves(eng.params)):
        assert held.sharding.is_equivalent_to(given.sharding, held.ndim)
        n += held.dtype != given.dtype
    assert n > 0
    assert any(len(x.sharding.device_set) == 4
               for x in jax.tree.leaves(eng.params))
    print("ALL_OK", n)
""")


def test_rounded_leaves_keep_their_sharding_on_a_mesh():
    r = subprocess.run(
        [sys.executable, "-c", MESH_PROG], capture_output=True, text=True,
        timeout=600, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                          "JAX_PLATFORMS": "cpu"})
    assert "ALL_OK" in r.stdout, f"{r.stdout[-2000:]}\n{r.stderr[-4000:]}"
