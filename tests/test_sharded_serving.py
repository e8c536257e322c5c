"""Sharded prefused partials vs the single-device serving runtime.

The contract under test (ISSUE 3 acceptance):
  * on a forced multi-device host (CI: ``XLA_FLAGS=
    --xla_force_host_platform_device_count=8``), sharded ``compile_serving``
    output is bit-exact vs the single-device jnp reference for every
    PREDICTIVE_QUERIES entry, every bucket size, and mesh shapes (1,8),
    (2,4), (8,1),
  * no recompilation across ragged batches (trace/cache counts, same as
    test_serving.py),
  * placement: partials below the byte threshold replicate, larger ones
    row-shard, and non-divisible row counts fall back to replication via
    ``safe_spec`` (the 15-heads-on-16-way rule, applied to partials),
  * ``CompiledQuery.predict_rows`` with a mesh matches the unsharded path.

The single-device mesh tests always run, so tier-1 exercises the shard_map
program on every platform; the multi-device matrix needs 8 host devices and
skips elsewhere (the CI ``multi-device`` job provides them).
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.laq import shard_pk_index, shard_rows
from repro.core.query import (
    compile_query,
    compile_serving,
    plan_partition_spec,
    plan_query,
    requests_from_rows,
)
from repro.data import QUERY_IR, generate_ssb, predictive_query_names, ssb_catalog
from repro.launch.mesh import make_serving_mesh
from repro.launch.sharding import param_pspec, safe_spec

PRED_NAMES = predictive_query_names()
BUCKETS = (8, 32)
MESH_SHAPES = [(1, 8), (2, 4), (8, 1)]
# Sizes covering every bucket (exact + padded) plus the chunked oversize path.
BATCH_SIZES = (3, 8, 20, 32, 70)

needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8",
)


@pytest.fixture(scope="module")
def data():
    return generate_ssb(sf=1, scale=0.0005, seed=5)


@pytest.fixture(scope="module")
def catalog(data):
    return ssb_catalog(data)


@pytest.fixture(scope="module")
def plans():
    """Per-module cache: compiled plans/runtimes are reused across tests."""
    return {}


def _runtime(plans, catalog, name, **kwargs):
    kwargs.setdefault("buckets", BUCKETS)
    mesh = kwargs.pop("mesh", None)
    mesh_key = None if mesh is None else tuple(mesh.devices.shape)
    key = ("serve", name, mesh_key, tuple(sorted(kwargs.items())))
    if key not in plans:
        plans[key] = compile_serving(catalog, QUERY_IR[name](), mesh=mesh,
                                     **kwargs)
    return plans[key]


def _random_requests(q, catalog, n, rng):
    """Live dimension keys mixed with guaranteed misses (as test_serving)."""
    reqs = {}
    for arm in q.arms:
        dim = catalog[arm.table]
        live = np.asarray(dim.key(arm.pk_col))[: int(dim.nvalid)]
        keys = rng.choice(live, size=n)
        miss = rng.random(n) < 0.25
        keys = np.where(miss, rng.integers(-3, 0, size=n), keys)
        reqs[arm.fk_col] = keys.astype(np.int32)
    return reqs


# --------------------------------------------- single-device mesh (tier-1)
@pytest.mark.parametrize("backend", ["fused", "nonfused"])
def test_sharded_serving_single_device_mesh(backend, catalog, plans):
    """The shard_map program is exercised even on one device."""
    name = PRED_NAMES[0]
    q = QUERY_IR[name]()
    mesh = make_serving_mesh((1, 1))
    ref = _runtime(plans, catalog, name, backend=backend)
    sh = _runtime(plans, catalog, name, backend=backend, mesh=mesh,
                  shard_threshold_bytes=0)
    assert sh.mesh is mesh
    assert sh.sharded is not None and sh.sharded.num_sharded > 0
    rng = np.random.default_rng(3)
    for n in BATCH_SIZES:
        reqs = _random_requests(q, catalog, n, rng)
        np.testing.assert_array_equal(
            np.asarray(sh.serve(reqs)), np.asarray(ref.serve(reqs)))


def test_sharded_serving_rejects_pallas(catalog):
    q = QUERY_IR[PRED_NAMES[0]]()
    mesh = make_serving_mesh((1, 1))
    with pytest.raises(ValueError, match="pallas"):
        compile_serving(catalog, q, mesh=mesh, serve_backend="pallas")
    with pytest.raises(ValueError, match="pallas"):
        compile_query(catalog, q, mesh=mesh, serve_backend="pallas")


# ------------------------------------------------- multi-device bit-exact
@needs_8_devices
@pytest.mark.parametrize("shape", MESH_SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
@pytest.mark.parametrize("backend", ["fused", "nonfused"])
@pytest.mark.parametrize("name", PRED_NAMES)
def test_sharded_matches_single_device(name, backend, shape, catalog, plans):
    """Sharded serving ≡ single-device jnp reference, bitwise in fp32."""
    q = QUERY_IR[name]()
    mesh = make_serving_mesh(shape)
    ref = _runtime(plans, catalog, name, backend=backend)
    sh = _runtime(plans, catalog, name, backend=backend, mesh=mesh,
                  shard_threshold_bytes=0)
    rng = np.random.default_rng(11)
    for n in BATCH_SIZES:
        reqs = _random_requests(q, catalog, n, rng)
        np.testing.assert_array_equal(
            np.asarray(sh.serve(reqs)),
            np.asarray(ref.serve(reqs)),
            err_msg=f"{name} {backend} mesh={shape} n={n}",
        )


@needs_8_devices
@pytest.mark.parametrize("shape", MESH_SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
def test_sharded_no_recompile_across_ragged_batches(shape, catalog):
    """One trace per bucket for life, exactly like the unsharded runtime."""
    q = QUERY_IR["P1.linear.year"]()
    mesh = make_serving_mesh(shape)
    runtime = compile_serving(catalog, q, buckets=BUCKETS, mesh=mesh,
                              shard_threshold_bytes=0)
    rng = np.random.default_rng(0)
    sizes = [1, 3, 8, 9, 20, 31, 32, 33, 70, 100]
    for n in sizes:
        out = runtime.serve(_random_requests(q, catalog, n, rng))
        assert out.shape == (n, runtime.out_width)
    assert runtime.num_compiles == len(BUCKETS)
    assert runtime.jit_cache_size() == len(BUCKETS)
    for n in sizes:
        runtime.serve(_random_requests(q, catalog, n, rng))
    assert runtime.num_compiles == len(BUCKETS)


@needs_8_devices
@pytest.mark.parametrize("shape", MESH_SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
@pytest.mark.parametrize("backend", ["fused", "nonfused"])
def test_sharded_predict_rows_matches(backend, shape, catalog, plans):
    """compile_query(mesh=...) predict_rows ≡ the unsharded program."""
    name = "P3.tree.year" if backend == "nonfused" else "P2.linear.select.scalar"
    q = QUERY_IR[name]()
    mesh = make_serving_mesh(shape)
    ref = compile_query(catalog, q, backend=backend)
    sh = compile_query(catalog, q, backend=backend, mesh=mesh,
                       shard_threshold_bytes=0)
    assert sh.plan.partition_specs is not None
    ids = jnp.asarray([0, 1, 5, 17, 100, 2999], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(sh.predict_rows(ids)), np.asarray(ref.predict_rows(ids))
    )


@needs_8_devices
def test_sharded_predict_rows_out_of_range_nan_semantics(catalog):
    """Out-of-range row ids keep the unsharded NaN-fill contract.

    The sharded gather clips pointers into the local block, which would
    silently turn ``jnp.take``'s NaN fill into 0.0 — the forward reproduces
    the fill explicitly, even when every arm is row-sharded.
    """
    q = QUERY_IR["P1.linear.year"]()
    mesh = make_serving_mesh((1, 8))
    ref = compile_query(catalog, q, backend="fused")
    sh = compile_query(catalog, q, backend="fused", mesh=mesh,
                       shard_threshold_bytes=0)
    cap = catalog[q.fact].capacity
    ids = jnp.asarray([0, cap + 7, 10**7, -1, 5], jnp.int32)
    want = np.asarray(ref.predict_rows(ids))
    assert np.isnan(want[1]).all() and np.isnan(want[2]).all()
    np.testing.assert_array_equal(np.asarray(sh.predict_rows(ids)), want)


@needs_8_devices
def test_sharded_serving_matches_predict_rows(catalog, plans):
    """The serving ≡ predict_rows contract survives sharding end to end."""
    name = "P1.linear.year"
    q = QUERY_IR[name]()
    mesh = make_serving_mesh((2, 4))
    compiled = compile_query(catalog, q, backend="fused", mesh=mesh,
                             shard_threshold_bytes=0)
    runtime = _runtime(plans, catalog, name, backend="fused", mesh=mesh,
                       shard_threshold_bytes=0)
    fact = catalog[q.fact]
    ok = np.asarray(fact.valid_mask())
    for p in q.fact_preds:
        ok = ok & np.asarray(p.mask(fact))
    ids = np.nonzero(ok)[0][:50]
    got = np.asarray(runtime.serve(requests_from_rows(fact, q, ids)))
    want = np.asarray(compiled.predict_rows(jnp.asarray(ids, jnp.int32)))
    np.testing.assert_array_equal(got, want)


@needs_8_devices
def test_bucket_rounding_to_dp_multiples(catalog):
    """Buckets round up to DP-size multiples so padded batches divide."""
    q = QUERY_IR["P1.linear.year"]()
    mesh = make_serving_mesh((8, 1))
    runtime = compile_serving(catalog, q, buckets=(3, 9), mesh=mesh)
    assert runtime.buckets == (8, 16)
    out = runtime.serve(
        _random_requests(q, catalog, 5, np.random.default_rng(0)))
    assert out.shape == (5, runtime.out_width)


@needs_8_devices
def test_placement_threshold_and_divisibility(catalog):
    """Placement: small → replicate; large → shard; non-divisible → safe."""
    q = QUERY_IR["P1.linear.year"]()
    mesh = make_serving_mesh((2, 4))
    # Huge threshold: everything replicates, still bit-exact (covered above).
    repl = compile_serving(catalog, q, mesh=mesh,
                           shard_threshold_bytes=1 << 40)
    assert all(spec[0] is None for spec in repl.plan.partition_specs)
    assert repl.sharded.num_sharded == 0
    # Zero threshold: shard wherever rows divide the 4-way model axis; the
    # date dim (2555 rows) does not divide 4 and must fall back.
    sh = compile_serving(catalog, q, mesh=mesh, shard_threshold_bytes=0)
    rows = {a.fk_col: catalog[a.table].capacity for a in q.arms}
    for arm, spec in zip(q.arms, sh.plan.partition_specs):
        expected = "model" if rows[arm.fk_col] % 4 == 0 else None
        assert spec[0] == expected, (arm.fk_col, spec)
    assert 0 < sh.sharded.num_sharded < len(q.arms)
    assert sh.sharded.nbytes_per_device() < repl.sharded.nbytes_per_device()


# ------------------------------------------------ per-shard PKIndex slices
def test_shard_pk_index_probe_reconstructs_global():
    rng = np.random.default_rng(0)
    pk = jnp.asarray(rng.permutation(64).astype(np.int32))
    sidx = shard_pk_index(pk, 4)
    assert sidx.num_shards == 4 and sidx.rows_per_shard == 16
    queries = jnp.asarray([0, 7, 13, 63, 64, -1], jnp.int32)
    hits = np.zeros(queries.shape[0], bool)
    resolved = np.zeros(queries.shape[0], np.int64)
    for s in range(4):
        fj = sidx.shard(s).probe(queries)
        found = np.asarray(fj.found)
        # Shard-local row offsets lift to global rows by the block offset.
        resolved[found] = np.asarray(fj.ptr)[found] + s * 16
        assert not np.any(hits & found), "two shards claimed one key"
        hits |= found
    full = np.asarray(pk)
    for i, k in enumerate(np.asarray(queries)):
        if 0 <= k < 64:
            assert hits[i] and full[resolved[i]] == k
        else:
            assert not hits[i]


def test_shard_pk_index_and_shard_rows_validate():
    pk = jnp.arange(10, dtype=jnp.int32)
    with pytest.raises(ValueError, match="shard"):
        shard_pk_index(pk, 3)
    with pytest.raises(ValueError, match="shard"):
        shard_rows(jnp.zeros((10, 2)), 4)
    assert shard_rows(jnp.zeros((12, 2)), 4).shape == (4, 3, 2)


# -------------------------------- safe_spec / param_pspec fallback (15-on-16)
def _stub_mesh(**axes):
    """A mesh stand-in for divisibility logic (no devices needed)."""
    return types.SimpleNamespace(axis_names=tuple(axes), shape=dict(axes))


def test_safe_spec_divisibility_fallback():
    mesh = _stub_mesh(data=1, model=16)
    # 15 rows on a 16-way axis: the dim is left unsharded, not an error.
    assert safe_spec(mesh, (15, 64), "model", None) == P(None, None)
    assert safe_spec(mesh, (32, 64), "model", None) == P("model", None)
    # Axis tuples multiply; missing axes fall back too.
    assert safe_spec(mesh, (16, 4), ("data", "model"), None) == P(
        ("data", "model"), None)
    assert safe_spec(mesh, (8, 4), ("pod", "data"), None) == P(None, None)


def test_param_pspec_divisibility_fallback():
    mesh = _stub_mesh(pod=1, data=2, model=16)
    cfg = types.SimpleNamespace(moe=None)
    # 15 attention heads' worth of columns on a 16-way model axis.
    assert param_pspec("blocks/0/attn/wq", (4, 64, 15), mesh, cfg) == P(
        None, ("pod", "data"), None)
    assert param_pspec("blocks/0/attn/wq", (4, 64, 32), mesh, cfg) == P(
        None, ("pod", "data"), "model")


def test_plan_partition_spec_applies_fallback_to_partials():
    """The 15-on-16 rule, applied to a prefused partial's row count."""
    mesh = _stub_mesh(data=1, model=16)
    spec, why = plan_partition_spec(mesh, (15, 4), threshold=0)
    assert spec == P(None, None) and "safe_spec fallback" in why
    spec, why = plan_partition_spec(mesh, (64, 4), threshold=0)
    assert spec == P("model", None) and "row-shard" in why
    spec, why = plan_partition_spec(mesh, (64, 4), threshold=1 << 30)
    assert spec == P(None, None) and "replicate small" in why
    spec, why = plan_partition_spec(None, (64, 4), threshold=0)
    assert spec == P(None, None) and "no mesh" in why


def test_plan_query_records_partition_specs():
    from repro.core.fusion import LinearOperator

    rng = np.random.default_rng(0)
    model = LinearOperator(jnp.asarray(rng.normal(size=(6, 4)), jnp.float32))
    mesh = _stub_mesh(data=1, model=16)
    plan = plan_query(model, 1024, [64, 15], out_width=4, mesh=mesh,
                      shard_threshold_bytes=0)
    assert plan.partition_specs == (P("model", None), P(None, None))
    assert "place=" in plan.reason
    meshless = plan_query(model, 1024, [64, 15], out_width=4)
    assert meshless.partition_specs is None
