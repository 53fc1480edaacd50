"""The static auditor's self-tests.

Two halves, mirroring the baseline discipline of the bench suites:

* MUTATION tests — for every rule ID in the catalog, register a
  synthetic family/impl that seeds exactly that violation and assert
  the auditor fires THAT rule (a rule nobody can trip is a rule that
  silently rotted).  The registry is snapshotted/restored around each.
* CLEAN-RUN tests — the real registry and the real source tree audit
  to zero unsuppressed findings, which is precisely the contract the
  CI static-analysis lane enforces.

Plus the fp64 parity pin for the ``models/ssm.py`` einsum hygiene fix:
the chunked SSD scan must match a float64 sequential recurrence, so
adding ``preferred_element_type`` provably changed precision, not
semantics.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.analysis import auditor
from repro.analysis.rules import RULES, make_finding
from repro.analysis.source_rules import scan_source
from repro.core.ops import registry, shard
from repro.core.ops.registry import OpSpec, Partitioning

FAM = "mutantfam"


@pytest.fixture
def sandbox():
    """Snapshot/restore the registry around a synthetic-family test."""
    fams = dict(registry._FAMILIES)
    impls = {k: dict(v) for k, v in registry._IMPLS.items()}
    yield
    for k in list(registry._FAMILIES):
        if k not in fams:
            del registry._FAMILIES[k]
    registry._FAMILIES.update(fams)
    # The legacy shim modules alias the inner per-family dicts, so restore
    # them in place rather than swapping in copies.
    for k in list(registry._IMPLS):
        if k not in impls:
            del registry._IMPLS[k]
    for k, v in impls.items():
        inner = registry._IMPLS.setdefault(k, {})
        inner.clear()
        inner.update(v)


def _problem(seed: int) -> dict:
    return {"a": jnp.ones((8, 8), jnp.float32),
            "b": jnp.ones((8, 8), jnp.float32)}


def _register(run, *, policies=("bf16",), fused=(), features=(),
              partitioning=None, contractions=1, meshes=(),
              audit_runs=(), grad_args=(), pads_to_tiles=False):
    registry.register_family(OpSpec(
        family=FAM, contract="a, b -> out", reference="probe",
        make_problem=_problem, run=run, grad_args=tuple(grad_args),
        audit_contractions=contractions, audit_meshes=tuple(meshes),
        audit_runs=tuple(audit_runs)))
    registry.register_impl(
        FAM, "probe", policies=policies, fused_policies=fused,
        features=features, pads_to_tiles=pads_to_tiles,
        partitioning=partitioning)(lambda *a, **k: None)


def _audit(**kw):
    return auditor.audit_impl(FAM, "probe", **kw)


def _ids(findings):
    return {f.rule_id for f in findings}


def _f32_dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


# ============================================================== mutations

def test_mut_aud001_untraceable_surface(sandbox):
    def run(problem, route):
        raise ValueError("deliberately untraceable")
    _register(run, contractions=0)
    assert _ids(_audit()) == {"AUD001"}


def test_mut_pre001_narrow_accumulation(sandbox):
    def run(problem, route):
        return jnp.einsum("ij,jk->ik", problem["a"].astype(jnp.bfloat16),
                          problem["b"].astype(jnp.bfloat16))  # no preferred
    _register(run)
    found = _audit()
    assert _ids(found) == {"PRE001"}
    assert found[0].target == f"{FAM}/probe/bf16"


def test_mut_pre002_pass_count_drift(sandbox):
    # Declares the 3-pass bf16x3 rung but traces a single dot.
    def run(problem, route):
        return _f32_dot(problem["a"], problem["b"])
    _register(run, policies=("bf16x3",))
    assert _ids(_audit()) == {"PRE002"}


def test_mut_pre003_downcast_before_accumulate(sandbox):
    def run(problem, route):
        d = _f32_dot(problem["a"], problem["b"])
        return d.astype(jnp.bfloat16) + problem["a"].astype(jnp.bfloat16)
    _register(run)
    assert "PRE003" in _ids(_audit())


def test_mut_cap001_vjp_claim_without_backward(sandbox):
    def run(problem, route):
        a = problem["a"]
        return jax.pure_callback(          # traces fine, differentiates not
            lambda x: x, jax.ShapeDtypeStruct(a.shape, a.dtype), a)
    _register(run, features=("vjp",), grad_args=("a",), contractions=0)
    assert _ids(_audit()) == {"CAP001"}


def test_mut_cap002_decode_claim_untraceable(sandbox):
    def run(problem, route):
        return _f32_dot(problem["a"], problem["b"])

    def decode(problem, route):
        raise ValueError("no decode path")
    _register(run, features=("decode",),
              audit_runs=(("decode", 1, decode),))
    assert _ids(_audit()) == {"CAP002"}


def _pl_dot(a, b):
    def kern(a_ref, b_ref, o_ref):
        o_ref[...] = jnp.dot(a_ref[...], b_ref[...],
                             preferred_element_type=jnp.float32)
    return pl.pallas_call(
        kern, out_shape=jax.ShapeDtypeStruct(
            (a.shape[0], b.shape[1]), jnp.float32),
        interpret=True)(a, b)


def test_mut_cap003_fused_claim_decomposes_router_side(sandbox):
    # bf16x3 is DECLARED fused but the runner calls the kernel 3 times.
    def run(problem, route):
        a, b = problem["a"], problem["b"]
        if route.precision == "bf16x3":
            return _pl_dot(a, b) + _pl_dot(a, b) + _pl_dot(a, b)
        return _pl_dot(a, b)
    _register(run, policies=("bf16", "bf16x3"),
              fused=("bf16", "bf16x3"))
    found = _audit()
    assert _ids(found) == {"CAP003"}
    assert found[0].target == f"{FAM}/probe/bf16x3"


def _sharded(body_fn, in_specs, out_specs):
    def run(problem, route):
        a, b = problem["a"], problem["b"]
        if route.mesh is None or route.mesh.is_identity:
            return _f32_dot(a, b)
        mesh = shard._mesh_for(route.mesh)
        return jax.shard_map(body_fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)(a, b)
    return run


def test_mut_shd001_undeclared_collective(sandbox):
    body = lambda x, y: jax.lax.psum(_f32_dot(x, y), "model")
    run = _sharded(body, (P(None, "model"), P("model", None)),
                   P(None, None))
    _register(run, meshes=("tp=2",), partitioning=Partitioning(
        specs=(("a", (None, "tp")), ("b", ("tp", None))),
        collectives=()))
    assert _ids(_audit()) == {"SHD001"}


def test_mut_shd002_declared_collective_never_observed(sandbox):
    body = lambda x, y: _f32_dot(x, y)     # col-parallel: no reduction
    run = _sharded(body, (P(None, None), P(None, "model")),
                   P(None, "model"))
    _register(run, meshes=("tp=2",), partitioning=Partitioning(
        specs=(("a", (None, None)), ("b", (None, "tp"))),
        collectives=("psum_f32:tp",)))
    found = _audit()
    assert _ids(found) == {"SHD002"}
    assert found[0].target == f"{FAM}/probe@audit-meshes"


def test_mut_shd003_f32_collective_reduces_bf16(sandbox):
    body = lambda x, y: jax.lax.psum(
        _f32_dot(x, y).astype(jnp.bfloat16), "model")
    run = _sharded(body, (P(None, "model"), P("model", None)),
                   P(None, None))
    _register(run, meshes=("tp=2",), partitioning=Partitioning(
        specs=(("a", (None, "tp")), ("b", ("tp", None))),
        collectives=("psum_f32:tp",)))
    assert _ids(_audit()) == {"SHD003"}


def test_mut_pal001_index_map_leaves_grid(sandbox):
    def run(problem, route):
        x = problem["a"].reshape(-1)                  # (64,)
        def kern(x_ref, o_ref):
            o_ref[...] = x_ref[...]
        return pl.pallas_call(
            kern, grid=(2,),
            in_specs=[pl.BlockSpec((32,), lambda i: (i + 1,))],  # off by one
            out_specs=pl.BlockSpec((32,), lambda i: (i,)),
            out_shape=jax.ShapeDtypeStruct((64,), jnp.float32),
            interpret=True)(x)
    _register(run, contractions=0)
    assert _ids(_audit()) == {"PAL001"}


def test_mut_pal002_block_does_not_divide(sandbox):
    def run(problem, route):
        x = problem["a"].reshape(-1)[:48]             # 48 % 32 != 0
        def kern(x_ref, o_ref):
            o_ref[...] = x_ref[...]
        return pl.pallas_call(
            kern, grid=(2,),
            in_specs=[pl.BlockSpec((32,), lambda i: (i,))],
            out_specs=pl.BlockSpec((32,), lambda i: (i,)),
            out_shape=jax.ShapeDtypeStruct((48,), jnp.float32),
            interpret=True)(x)
    _register(run, contractions=0, pads_to_tiles=True)
    assert _ids(_audit()) == {"PAL002"}


def test_mut_pal003_narrow_scratch_accumulator(sandbox):
    def run(problem, route):
        x = problem["a"]
        def kern(x_ref, o_ref, acc_ref):
            acc_ref[...] = x_ref[...].astype(jnp.bfloat16)
            o_ref[...] = acc_ref[...].astype(jnp.float32)
        return pl.pallas_call(
            kern, out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
            scratch_shapes=[pltpu.VMEM((8, 8), jnp.bfloat16)],
            interpret=True)(x)
    _register(run, contractions=0)
    assert _ids(_audit()) == {"PAL003"}


def test_mut_pal004_hardcoded_interpret_flag(sandbox):
    def run(problem, route):
        x = problem["a"]
        def kern(x_ref, o_ref):
            o_ref[...] = x_ref[...]
        return pl.pallas_call(          # ignores route.resolved_interpret()
            kern, out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
            interpret=False)(x)
    _register(run, contractions=0)
    assert _ids(_audit()) == {"PAL004"}


def test_mut_src001_raw_contraction(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax.numpy as jnp\n"
                   "def f(a, b):\n"
                   "    return jnp.einsum('ij,jk->ik', a, b)\n")
    ok = tmp_path / "ok.py"
    ok.write_text("import jax.numpy as jnp\n"
                  "def f(a, b):\n"
                  "    return jnp.einsum('ij,jk->ik', a, b,\n"
                  "                      preferred_element_type=jnp.float32)\n")
    found = scan_source(str(tmp_path))
    assert _ids(found) == {"SRC001"}
    assert [f.target for f in found] == ["bad.py:3"]


def test_every_rule_has_a_mutation_test():
    """The catalog and this file move together: a new rule ID without a
    seeded violation here fails immediately."""
    import pathlib
    src = pathlib.Path(__file__).read_text()
    for rule_id in RULES:
        assert f"test_mut_{rule_id.lower()}" in src, \
            f"rule {rule_id} has no mutation self-test"


# ============================================================== clean runs

def test_real_registry_audits_clean():
    """The CI static-analysis contract: every registered (family, impl,
    policy) triple — sharded variants included — yields zero findings."""
    assert auditor.audit_all(source=False) == []


def test_source_tree_audits_clean():
    assert scan_source() == []


def test_registry_reports_audited_column():
    rows = registry.capability_rows()
    assert rows and all(r["audited"] == "yes" for r in rows)


# ============================================================== baselines

def test_baseline_round_trip(tmp_path):
    path = str(tmp_path / "baseline.json")
    f1 = make_finding("PRE001", "fam/impl/bf16", "seeded")
    f2 = make_finding("SHD002", "fam/impl@audit-meshes", "seeded")
    auditor.save_baseline(path, [f1, f2])
    baseline = auditor.load_baseline(path)
    res = auditor.apply_baseline([f1, f2], baseline)
    assert res.unsuppressed == () and len(res.suppressed) == 2
    assert res.stale_keys == ()
    # A suppression whose finding no longer fires is STALE, not silent.
    res = auditor.apply_baseline([f1], baseline)
    assert res.stale_keys == (f2.key,)
    # Unknown findings pass through regardless of the baseline.
    f3 = make_finding("PAL001", "fam/impl/bf16", "new")
    res = auditor.apply_baseline([f1, f3], baseline)
    assert res.unsuppressed == (f3,)


def test_baseline_missing_file_is_empty(tmp_path):
    baseline = auditor.load_baseline(str(tmp_path / "absent.json"))
    assert baseline["suppressions"] == []


def test_cli_list_rules_and_family(capsys):
    from repro.analysis.__main__ import main
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    assert all(rule_id in out for rule_id in RULES)
    assert main(["--family", "gemm", "--no-source", "--no-meshes"]) == 0


# ==================================================== einsum hygiene pin

def test_ssd_chunked_matches_fp64_sequential_reference():
    """The chunked SSD scan (whose einsums now pin f32 accumulation)
    against a float64 token-by-token recurrence: semantics unchanged,
    precision no worse."""
    from repro.models.ssm import _ssd_chunked
    rng = np.random.default_rng(0)
    b, s, h, p, n, chunk = 2, 12, 2, 4, 4, 4
    x = rng.standard_normal((b, s, h, p)).astype(np.float32)
    bm = rng.standard_normal((b, s, n)).astype(np.float32)
    cm = rng.standard_normal((b, s, n)).astype(np.float32)
    rel = (-np.abs(rng.standard_normal((b, s, h))) * 0.1).astype(np.float32)
    dt = np.abs(rng.standard_normal((b, s, h))).astype(np.float32)

    x64, b64, c64, rel64, dt64 = (t.astype(np.float64)
                                  for t in (x, bm, cm, rel, dt))
    st = np.zeros((b, h, p, n), np.float64)
    y = np.zeros((b, s, h, p), np.float64)
    for t in range(s):
        st = st * np.exp(rel64[:, t])[:, :, None, None] + np.einsum(
            "bh,bhp,bn->bhpn", dt64[:, t], x64[:, t], b64[:, t])
        y[:, t] = np.einsum("bn,bhpn->bhp", c64[:, t], st)

    got_y, got_st = _ssd_chunked(
        jnp.asarray(x), jnp.asarray(bm), jnp.asarray(cm),
        jnp.asarray(rel), jnp.asarray(dt), chunk, "f32")
    np.testing.assert_allclose(np.asarray(got_y), y, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(got_st), st, rtol=2e-4, atol=2e-4)
