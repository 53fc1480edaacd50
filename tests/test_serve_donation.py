"""The serve engine rewrites its slot state in place: admission splices
the prefill cache and sets the slot's four vectors in one compiled
program, and the tick runs on the cache it reads.  Both donate the
cache and the vectors.

Checked on the CPU: the splice gives bitwise what the eager tree-wise
splice gave, into the first and the last slot, for an attention and a
recurrent-state configuration; the buffers passed in are deleted after
the admission and after the tick, and the ``donated`` attribute of the
``engine.splice`` and ``engine.launch`` spans says so; one splice
program serves every slot; and no cache holds one buffer twice, which a
donating program refuses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_smoke
from repro.core.precision import PrecisionPolicy
from repro.launch.serve import Request, ServeEngine
from repro.models import api
from repro.runtime import serve_step
from repro.runtime.monitor import recent_spans, span

BATCH, MAX_CTX = 4, 32
VECTORS = ("last_tok", "pos", "active", "remaining")


def tree_map_splice(cache, cache1, slot):
    """The eager splice the compiled one replaces, leaf rule and all."""
    def splice(full, one):
        if not hasattr(one, "shape") or one.ndim < 2:
            return full
        return jax.lax.dynamic_update_index_in_dim(
            full, one[:, 0].astype(full.dtype), slot, axis=1)
    return jax.tree.map(splice, cache, cache1)


def _host(tree):
    # copies: a view of a buffer (np.asarray on the CPU) keeps a later
    # call from taking it
    return jax.tree.map(np.array, tree)


def _assert_bitwise(got, want):
    got_l, got_t = jax.tree.flatten(_host(got))
    want_l, want_t = jax.tree.flatten(_host(want))
    assert got_t == want_t
    for g, w in zip(got_l, want_l):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g.view(np.uint8), w.view(np.uint8))


def _random_like(tree, key):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return treedef.unflatten([
        jax.random.normal(k, x.shape, jnp.float32).astype(x.dtype)
        for k, x in zip(keys, leaves)])


@pytest.mark.parametrize("arch", ["gemma3-1b", "rwkv6-7b"])
@pytest.mark.parametrize("slot", [0, BATCH - 1])
def test_splice_program_matches_the_tree_map_splice(arch, slot):
    cfg = get_smoke(arch)
    # a bf16 batch cache and an f32 prefill cache: the cast is checked too
    cache = _random_like(api.init_cache(cfg, BATCH, MAX_CTX, jnp.bfloat16),
                         jax.random.PRNGKey(1))
    cache1 = _random_like(api.init_cache(cfg, 1, MAX_CTX, jnp.float32),
                          jax.random.PRNGKey(2))
    vecs = (jnp.arange(BATCH, dtype=jnp.int32) + 7,
            jnp.arange(BATCH, dtype=jnp.int32) * 3,
            jnp.zeros(BATCH, bool),
            jnp.arange(BATCH, dtype=jnp.int32) + 1)
    want = (tree_map_splice(cache, cache1, slot),
            vecs[0].at[slot].set(42), vecs[1].at[slot].set(17),
            vecs[2].at[slot].set(True), vecs[3].at[slot].set(5))
    fn = jax.jit(serve_step.make_slot_splice())
    got = fn(cache, cache1, jnp.int32(slot), *vecs, jnp.int32(42),
             jnp.int32(17), jnp.int32(5))
    _assert_bitwise(got, want)


@pytest.mark.parametrize("arch", ARCHS)
def test_cache_holds_no_buffer_twice(arch):
    cfg = get_smoke(arch)
    leaves = jax.tree.leaves(api.init_cache(cfg, 2, 16, jnp.float32))
    ptrs = [x.unsafe_buffer_pointer() for x in leaves]
    assert len(set(ptrs)) == len(ptrs)


def _admit_into(eng, req, slot):
    """Admit ``req`` into ``slot`` by holding the slots before it."""
    held = [i for i in range(slot) if eng.slot_req[i] is None]
    for i in held:
        eng.slot_req[i] = Request(rid=-100 - i, prompt=req.prompt)
    try:
        assert eng.admit(req)
    finally:
        for i in held:
            eng.slot_req[i] = None
    assert eng.slot_req[slot] is req


@pytest.fixture(scope="module", params=["gemma3-1b", "rwkv6-7b"])
def served(request):
    """Three admissions (slots 0, last, 1) and one tick on one engine:
    what the cache and vectors read against the eager splice, and
    whether the buffers passed in were deleted."""
    cfg = get_smoke(request.param)
    eng = ServeEngine(cfg, batch_size=BATCH, max_ctx=MAX_CTX,
                      policy=PrecisionPolicy.uniform("f32"), eos_id=-1)
    eng.load(api.init_params(jax.random.PRNGKey(0), cfg))
    seen = {"splices": [], "deleted_after_admit": [], "slots": []}
    with span("mark") as mark:
        pass
    for rid, slot in enumerate([0, BATCH - 1, 1]):
        prompt = np.arange(2, 7 + rid, dtype=np.int32)
        before = _host((eng.cache,) + tuple(getattr(eng, v)
                                            for v in VECTORS))
        logits, cache1 = eng._prefill(
            eng.params, {"tokens": jnp.asarray(prompt)[None]})
        first = int(jnp.argmax(logits[0, -1]))
        donor = jax.tree.leaves(eng.cache)[0]
        _admit_into(eng, Request(rid=rid, prompt=prompt,
                                 max_new_tokens=6), slot)
        seen["deleted_after_admit"].append(donor.is_deleted())
        cache, last_tok, pos, active, remaining = jax.tree.map(
            jnp.asarray, before)
        want = (tree_map_splice(cache, cache1, slot),
                last_tok.at[slot].set(first),
                pos.at[slot].set(len(prompt)),
                active.at[slot].set(True),
                remaining.at[slot].set(6 - 1))
        got = (eng.cache,) + tuple(getattr(eng, v) for v in VECTORS)
        seen["splices"].append((_host(got), _host(want)))
        seen["slots"].append(slot)
    seen["splice_programs"] = eng._splice._cache_size()
    donor = jax.tree.leaves(eng.cache)[0]
    vec_donors = [getattr(eng, v) for v in VECTORS]
    assert eng.tick() == 3
    seen["deleted_after_tick"] = donor.is_deleted()
    seen["vectors_deleted_after_tick"] = [v.is_deleted() for v in vec_donors]
    spans = [sp for sp in recent_spans() if sp[3] > mark.id]
    seen["donated"] = {name: [sp[5].get("donated") for sp in spans
                              if sp[0] == name]
                       for name in ("engine.splice", "engine.launch")}
    return seen


def test_admission_splices_like_the_tree_map_splice(served):
    assert served["slots"] == [0, BATCH - 1, 1]
    for got, want in served["splices"]:
        _assert_bitwise(got, want)


def test_admission_and_tick_delete_the_buffers_passed_in(served):
    assert served["deleted_after_admit"] == [True, True, True]
    assert served["deleted_after_tick"]
    assert served["vectors_deleted_after_tick"] == [True] * 4


def test_spans_say_the_donation_took(served):
    assert served["donated"] == {"engine.splice": [True] * 3,
                                 "engine.launch": [True]}


def test_one_splice_program_serves_every_slot(served):
    assert served["splice_programs"] == 1


def test_paged_engine_donates_its_dense_state_and_the_tick():
    cfg = get_smoke("gemma3-1b")
    eng = ServeEngine(cfg, batch_size=2, max_ctx=MAX_CTX,
                      policy=PrecisionPolicy.uniform("f32"), eos_id=-1,
                      kv_layout="paged", kv_page_size=8)
    eng.load(api.init_params(jax.random.PRNGKey(0), cfg))
    leaves = jax.tree.leaves(eng.cache)
    assert len({x.unsafe_buffer_pointer() for x in leaves}) == len(leaves)
    with span("mark") as mark:
        pass
    req = Request(rid=0, prompt=np.arange(2, 9, dtype=np.int32),
                  max_new_tokens=4)
    eng.run([req])
    assert req.done and len(req.out_tokens) == 4
    assert eng.pages_outstanding() == 0
    spans = [sp for sp in recent_spans() if sp[3] > mark.id]
    assert [sp[5]["donated"] for sp in spans
            if sp[0] == "engine.splice"] == [True]
    assert [sp[5]["donated"] for sp in spans
            if sp[0] == "engine.launch"] == [True] * 3
