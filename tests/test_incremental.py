"""Versioned Catalog + incremental prefuse maintenance (ISSUE 5).

The contract under test:
  * ``append → refresh`` is **bit-exact** vs a cold rebuild on the updated
    catalog — property-tested across fused/nonfused × segment/matmul for
    the whole-query program, and across fused/nonfused (and a (1,8) mesh,
    when 8 host devices exist) for the serving runtime,
  * the delta path never retraces: ``ServingRuntime.num_compiles`` is
    unchanged across a same-shape refresh, and latency windows reset so
    post-refresh percentiles never mix pre-refresh samples,
  * Session caches are version-keyed: a cached plan/runtime can never serve
    pre-append partials,
  * ``DomainCache.refresh`` grows geometrically instead of silently
    truncating when the merged unique set exceeds capacity (regression),
  * ``PKIndex.extend`` is array-identical to a cold ``pk_index``,
  * capacity growth falls back to recompile/rebuild with a named
    ``explain()`` reason,
  * plain-dict catalogs auto-wrap read-only (back-compat shim).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fusion import LinearOperator, random_tree
from repro.core.laq import (PAD_KEY, Catalog, CatalogReadOnlyError,
                            DomainCache, Table, pk_index)
from repro.core.query import (PREDICTION, Aggregate, ArmSpec, GroupKey,
                              PredictiveQuery, Session, compile_query,
                              compile_serving, make_serving_mesh)
from repro.core.laq.selection import Pred

needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8",
)


# --------------------------------------------------------------------- data
def star_catalog(seed: int, n_d1: int = 24, n_d2: int = 10,
                 n_fact: int = 64, slack: int = 16) -> Catalog:
    """A 2-arm star with padded dimension capacity for appends to land in."""
    rng = np.random.default_rng(seed)
    d1 = {"pk": np.arange(n_d1) * 2,      # sparse keys: FKs can miss
          "a": rng.normal(size=n_d1), "b": rng.normal(size=n_d1)}
    d2 = {"pk2": np.arange(n_d2),
          "c": rng.normal(size=n_d2),
          "g": rng.integers(0, 4, n_d2)}
    f = {"fk1": rng.integers(0, 2 * (n_d1 + slack), n_fact),
         "fk2": rng.integers(0, n_d2 + slack // 2, n_fact),
         "val": rng.normal(size=n_fact)}
    return Catalog({
        "d1": Table.from_columns("d1", d1, key_cols=("pk",),
                                 capacity=n_d1 + slack),
        "d2": Table.from_columns("d2", d2, key_cols=("pk2", "g"),
                                 capacity=n_d2 + slack),
        "fact": Table.from_columns("fact", f, key_cols=("fk1", "fk2"),
                                   capacity=n_fact + slack),
    })


def d1_rows(rng, m, start):
    return {"pk": start * 2 + 1 + 2 * np.arange(m),   # odd keys: fresh
            "a": rng.normal(size=m), "b": rng.normal(size=m)}


def d2_rows(rng, m, start):
    return {"pk2": start + np.arange(m), "c": rng.normal(size=m),
            "g": rng.integers(0, 4, m)}


def _query(model, group: bool) -> PredictiveQuery:
    gk = (GroupKey("d2", "g", 4),) if group else ()
    return PredictiveQuery(
        fact="fact",
        arms=(ArmSpec("d1", "fk1", "pk", ("a", "b"),
                      (Pred("a", ">", -1.0),)),
              ArmSpec("d2", "fk2", "pk2", ("c",))),
        fact_preds=(Pred("val", ">", -2.0),),
        model=model,
        group_keys=gk,
        aggregates=(Aggregate(PREDICTION, "sum", "pred"),
                    Aggregate("val", "mean", "v"),
                    Aggregate("*", "count", "n")),
        num_groups=4 if group else 8192)


def _models(seed=0):
    rng = np.random.default_rng(seed)
    return [LinearOperator(jnp.asarray(
        rng.normal(size=(3, 2)).astype(np.float32))),
        random_tree(rng, 3, depth=2)]


def assert_results_equal(a, b):
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=k)


# ------------------------------------------- append → refresh ≡ cold rebuild
@pytest.mark.parametrize("backend", ["fused", "nonfused"])
@pytest.mark.parametrize("agg_backend", ["segment", "matmul"])
def test_refresh_equals_cold_rebuild_run(backend, agg_backend):
    for model in _models():
        cat = star_catalog(seed=7)
        q = _query(model, group=True)
        cq = compile_query(cat, q, backend=backend, agg_backend=agg_backend)
        rng = np.random.default_rng(11)
        cat.append("d1", d1_rows(rng, 5, start=24))
        cat.append("d2", d2_rows(rng, 3, start=10))
        cat.append("fact", {"fk1": [1, 49, 3], "fk2": [10, 12, 0],
                            "val": [0.5, -0.5, 1.5]})
        line = cq.refresh()
        assert "delta" in line
        cold = compile_query(cat, q, backend=backend,
                             agg_backend=agg_backend)
        assert_results_equal(cq.run(), cold.run())
        ids = np.arange(0, 67, 5, dtype=np.int32)
        np.testing.assert_array_equal(np.asarray(cq.predict_rows(ids)),
                                      np.asarray(cold.predict_rows(ids)))


@pytest.mark.parametrize("backend", ["fused", "nonfused"])
def test_refresh_equals_cold_rebuild_serving(backend):
    for model in _models(seed=3):
        cat = star_catalog(seed=8)
        q = _query(model, group=False)
        rt = compile_serving(cat, q, backend=backend, buckets=(8, 32))
        reqs = {"fk1": np.array([0, 2, 49, 51, 99], np.int32),
                "fk2": np.array([0, 9, 10, 12, 3], np.int32)}
        rt.serve(reqs)
        n0 = rt.num_compiles
        rng = np.random.default_rng(12)
        cat.append("d1", d1_rows(rng, 5, start=24))
        cat.append("d2", d2_rows(rng, 3, start=10))
        line = rt.refresh()
        assert "delta" in line
        assert rt.num_compiles == n0, "delta refresh must not retrace"
        cold = compile_serving(cat, q, backend=backend, buckets=(8, 32))
        np.testing.assert_array_equal(np.asarray(rt.serve(reqs)),
                                      np.asarray(cold.serve(reqs)))


@needs_8_devices
@pytest.mark.parametrize("shape", [(1, 8), (2, 4)])
def test_refresh_sharded_serving_bit_exact(shape):
    cat = star_catalog(seed=9, n_d1=32, n_d2=16)
    model = _models(seed=5)[0]
    q = _query(model, group=False)
    mesh = make_serving_mesh(shape)
    rt = compile_serving(cat, q, backend="fused", mesh=mesh,
                         shard_threshold_bytes=0, buckets=(8,))
    reqs = {"fk1": np.array([0, 2, 65, 67, 99], np.int32),
            "fk2": np.array([0, 9, 16, 18, 3], np.int32)}
    rt.serve(reqs)
    n0 = rt.num_compiles
    rng = np.random.default_rng(13)
    cat.append("d1", d1_rows(rng, 6, start=32))
    cat.append("d2", d2_rows(rng, 4, start=16))
    assert "delta" in rt.refresh()
    assert rt.num_compiles == n0
    cold_sharded = compile_serving(cat, q, backend="fused", mesh=mesh,
                                   shard_threshold_bytes=0, buckets=(8,))
    cold_single = compile_serving(cat, q, backend="fused", buckets=(8,))
    out = np.asarray(rt.serve(reqs))
    np.testing.assert_array_equal(out, np.asarray(cold_sharded.serve(reqs)))
    np.testing.assert_array_equal(out, np.asarray(cold_single.serve(reqs)))


# ------------------------------------------------------- hypothesis property
def test_property_append_refresh_equals_cold():
    """Property: build on a prefix of the dimension rows, append the rest,
    refresh — results must be bitwise the cold compile on the full catalog,
    for run(), predict_rows() AND serving, across every backend combo."""
    pytest.importorskip(
        "hypothesis",
        reason="property tests need hypothesis (requirements-dev)")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=10, deadline=None)
    @given(
        seed=st.integers(0, 2**16),
        split=st.floats(0.1, 0.9),
        backend=st.sampled_from(["fused", "nonfused"]),
        agg_backend=st.sampled_from(["segment", "matmul"]),
        tree=st.booleans(),
        group=st.booleans(),
    )
    def check(seed, split, backend, agg_backend, tree, group):
        _check_append_refresh(seed, split, backend, agg_backend, tree,
                              group)

    check()


def _check_append_refresh(seed, split, backend, agg_backend, tree, group):
    rng = np.random.default_rng(seed)
    n_d1, n_d2 = 20, 12
    m1 = max(1, min(n_d1 - 1, int(n_d1 * split)))
    m2 = max(1, min(n_d2 - 1, int(n_d2 * split)))
    d1 = {"pk": np.arange(n_d1) * 2, "a": rng.normal(size=n_d1),
          "b": rng.normal(size=n_d1)}
    d2 = {"pk2": np.arange(n_d2), "c": rng.normal(size=n_d2),
          "g": rng.integers(0, 4, n_d2)}
    f = {"fk1": rng.integers(0, 2 * n_d1 + 4, 48),
         "fk2": rng.integers(0, n_d2 + 2, 48),
         "val": rng.normal(size=48)}
    model = (random_tree(rng, 3, depth=2) if tree
             else LinearOperator(jnp.asarray(
                 rng.normal(size=(3, 2)).astype(np.float32))))

    def tables(prefix1, prefix2):
        return {
            "d1": Table.from_columns(
                "d1", {k: v[:prefix1] for k, v in d1.items()},
                key_cols=("pk",), capacity=n_d1),
            "d2": Table.from_columns(
                "d2", {k: v[:prefix2] for k, v in d2.items()},
                key_cols=("pk2", "g"), capacity=n_d2),
            "fact": Table.from_columns("fact", f, key_cols=("fk1", "fk2")),
        }

    q = _query(model, group=group)
    warm_cat = Catalog(tables(m1, m2))
    warm = compile_query(warm_cat, q, backend=backend,
                         agg_backend=agg_backend)
    warm_cat.append("d1", {k: v[m1:] for k, v in d1.items()})
    warm_cat.append("d2", {k: v[m2:] for k, v in d2.items()})
    warm.refresh()
    cold = compile_query(Catalog(tables(n_d1, n_d2)), q, backend=backend,
                         agg_backend=agg_backend)
    assert_results_equal(warm.run(), cold.run())
    ids = np.arange(48, dtype=np.int32)
    np.testing.assert_array_equal(np.asarray(warm.predict_rows(ids)),
                                  np.asarray(cold.predict_rows(ids)))

    # The serving runtime over the same split (fact-free online phase).
    warm_rt_cat = Catalog(tables(m1, m2))
    rt = compile_serving(warm_rt_cat, q, backend=backend, buckets=(16,))
    warm_rt_cat.append("d1", {k: v[m1:] for k, v in d1.items()})
    warm_rt_cat.append("d2", {k: v[m2:] for k, v in d2.items()})
    rt.refresh()
    cold_rt = compile_serving(Catalog(tables(n_d1, n_d2)), q,
                              backend=backend, buckets=(16,))
    reqs = {"fk1": f["fk1"][:16], "fk2": f["fk2"][:16]}
    np.testing.assert_array_equal(np.asarray(rt.serve(reqs)),
                                  np.asarray(cold_rt.serve(reqs)))


# ----------------------------------------------------- staleness (Session)
def test_session_cache_never_serves_stale_partials():
    cat = star_catalog(seed=21)
    model = _models(seed=2)[0]
    sess = Session(cat)
    q = _query(model, group=False)
    builder = sess.bind(q)
    r0 = builder.run()
    rt = builder.serve(buckets=(8,))
    # Keys 55 (odd d1 key) and 10/11 (d2) do not exist yet.
    reqs = {"fk1": np.array([55, 55], np.int32),
            "fk2": np.array([10, 11], np.int32)}
    assert np.all(np.asarray(rt.serve(reqs)) == 0)
    rng = np.random.default_rng(22)
    new_d1 = d1_rows(rng, 4, start=24)
    new_d1["a"] = np.abs(new_d1["a"])   # pass the d1 arm's a > -1 predicate
    cat.append("d1", new_d1)
    cat.append("d2", d2_rows(rng, 4, start=10))
    # Same cached objects come back — refreshed, never pre-append state.
    r1 = builder.run()
    assert sess.num_plans == 1
    assert float(r1["n"]) >= float(r0["n"])
    rt2 = builder.serve(buckets=(8,))
    assert rt2 is rt
    assert np.any(np.asarray(rt2.serve(reqs)) != 0), \
        "version-keyed cache served pre-append partials"
    cold = Session(cat).bind(q)
    assert_results_equal(r1, cold.run())
    np.testing.assert_array_equal(
        np.asarray(rt2.serve(reqs)),
        np.asarray(cold.serve(buckets=(8,)).serve(reqs)))


def test_session_refresh_eager():
    cat = star_catalog(seed=23)
    sess = Session(cat)
    q = _query(_models(seed=4)[0], group=False)
    sess.bind(q).run()
    sess.bind(q).serve(buckets=(8,))
    rng = np.random.default_rng(24)
    cat.append("d1", d1_rows(rng, 2, start=24))
    out = sess.refresh()
    assert len(out) == 2          # one plan + one runtime refreshed
    assert all("delta" in line for line in out.values())
    assert sess.refresh() == {}   # converged


# ------------------------------------------------- fallback + update paths
def test_capacity_growth_falls_back_with_named_reason():
    cat = star_catalog(seed=25, slack=2)
    q = _query(_models(seed=6)[0], group=True)
    cq = compile_query(cat, q)
    rt = compile_serving(cat, q, buckets=(8,))
    rt.serve({"fk1": np.zeros(3, np.int32), "fk2": np.zeros(3, np.int32)})
    rng = np.random.default_rng(26)
    cat.append("d1", d1_rows(rng, 8, start=24))   # overflows slack=2 → grow
    assert cat.deltas_since("d1", 0)[0].grew
    line = cq.refresh()
    assert "recompile(capacity-growth:d1" in line
    assert "capacity-growth" in cq.plan.reason
    line = rt.refresh()
    assert "rebuild(capacity-growth:d1" in line
    assert rt.num_compiles == 0   # fresh jit cache
    cold = compile_query(cat, q)
    assert_results_equal(cq.run(), cold.run())
    cold_rt = compile_serving(cat, q, buckets=(8,))
    reqs = {"fk1": np.array([1, 53], np.int32),
            "fk2": np.array([0, 1], np.int32)}
    np.testing.assert_array_equal(np.asarray(rt.serve(reqs)),
                                  np.asarray(cold_rt.serve(reqs)))


def test_update_column_refreshes_partials():
    cat = star_catalog(seed=27)
    q = _query(_models(seed=8)[0], group=False)
    cq = compile_query(cat, q, backend="fused")
    rt = compile_serving(cat, q, backend="fused", buckets=(8,))
    cat.update_column("d1", "a", [0, 3, 5], [2.0, -3.0, 0.25])
    assert "delta" in cq.refresh()
    assert "delta" in rt.refresh()
    cold = compile_query(cat, q, backend="fused")
    assert_results_equal(cq.run(), cold.run())
    cold_rt = compile_serving(cat, q, backend="fused", buckets=(8,))
    reqs = {"fk1": np.array([0, 6, 10], np.int32),
            "fk2": np.array([0, 1, 2], np.int32)}
    np.testing.assert_array_equal(np.asarray(rt.serve(reqs)),
                                  np.asarray(cold_rt.serve(reqs)))


def test_update_key_column_rejected():
    cat = star_catalog(seed=28)
    with pytest.raises(ValueError, match="key column"):
        cat.update_column("d1", "pk", [0], [999])


def test_append_is_transactional():
    cat = star_catalog(seed=29)
    v0 = cat.version("d1")
    t0 = cat["d1"]
    with pytest.raises(ValueError, match="missing columns"):
        cat.append("d1", {"pk": [999]})
    with pytest.raises(ValueError, match="ragged"):
        cat.append("d1", {"pk": [999], "a": [1.0, 2.0], "b": [0.0]})
    assert cat.version("d1") == v0 and cat["d1"] is t0


# ------------------------------------------------- stats reset (satellite)
def test_latency_stats_reset_across_refresh():
    cat = star_catalog(seed=31)
    q = _query(_models(seed=9)[0], group=False)
    rt = compile_serving(cat, q, buckets=(8,), sync_stats=True)
    reqs = {"fk1": np.array([0, 2], np.int32),
            "fk2": np.array([0, 1], np.int32)}
    for _ in range(3):
        rt.serve(reqs)
    stats = rt.latency_stats()
    assert stats[8]["count"] == 2 and "compile_ms" in stats[8]
    n0 = rt.num_compiles
    rng = np.random.default_rng(32)
    cat.append("d1", d1_rows(rng, 2, start=24))
    rt.refresh()
    post = rt.latency_stats()
    assert post[8]["count"] == 0 and "p50" not in post[8], \
        "post-refresh percentiles must not mix pre-refresh samples"
    # The compile record is per cache *generation*, not per window: a delta
    # refresh keeps it (no retrace happened).
    assert post[8]["compile_ms"] == stats[8]["compile_ms"]
    assert rt.num_compiles == n0, "delta refresh adds no traces"
    rt.serve(reqs)
    assert rt.num_compiles == n0, "refreshed state re-dispatches cached jit"
    assert rt.latency_stats()[8]["count"] == 1


def test_compile_records_survive_rebuild_per_generation():
    """Regression: a post-rebuild retrace of an already-seen bucket used to
    overwrite ``_compile_s[bucket]``, losing the first generation's compile
    time while ``num_compiles`` claimed a fresh generation."""
    cat = star_catalog(seed=33, slack=2)
    q = _query(_models(seed=10)[0], group=False)
    rt = compile_serving(cat, q, buckets=(8,))
    reqs = {"fk1": np.array([0, 2], np.int32),
            "fk2": np.array([0, 1], np.int32)}
    rt.serve(reqs)
    assert rt.generation == 0
    gen0 = rt.compile_history()[0][8]
    rng = np.random.default_rng(34)
    cat.append("d1", d1_rows(rng, 6, start=24))   # exceeds capacity slack
    rt.refresh()                                  # → rebuild: new generation
    rt.serve(reqs)                                # retrace of bucket 8
    assert rt.generation == 1
    hist = rt.compile_history()
    assert len(hist) == 2 and hist[0][8] == gen0, \
        "rebuild retrace must archive, not overwrite, generation-0 compiles"
    assert rt.latency_stats()[8]["compile_ms"] == hist[1][8]


# ----------------------------------------------- DomainCache capacity (bug)
def test_domain_cache_refresh_grows_instead_of_truncating():
    """Regression: the old jnp.unique(size=cap) merge silently dropped the
    largest keys once the merged unique set exceeded the cached capacity."""
    cache = DomainCache()
    keys = jnp.asarray(np.arange(8, dtype=np.int32))
    dom = cache.get_or_build([("r", "k")], [keys], size=8)
    assert dom.shape == (8,)
    new = jnp.asarray(np.arange(100, 106, dtype=np.int32))
    merged = cache.refresh([("r", "k")], new)
    live = np.asarray(merged)[np.asarray(merged) != PAD_KEY]
    assert merged.shape[0] == 16            # geometric growth, not 8
    assert set(live.tolist()) == set(range(8)) | set(range(100, 106)), \
        "refresh dropped keys"
    with pytest.raises(ValueError, match="capacity"):
        cache.refresh([("r", "k")],
                      jnp.asarray(np.arange(200, 220, dtype=np.int32)),
                      grow=False)


def test_domain_cache_refresh_table_hook():
    cache = DomainCache()
    cache.get_or_build([("d1", "pk")],
                       [jnp.asarray(np.arange(4, dtype=np.int32))], size=8)
    cat = star_catalog(seed=33)
    cat.domain_cache = cache
    rng = np.random.default_rng(34)
    cat.append("d1", d1_rows(rng, 2, start=24))
    dom = np.asarray(cache.get_or_build(
        [("d1", "pk")], [], size=8))
    assert 49 in dom.tolist()               # appended key merged in


# ------------------------------------------------------ PKIndex.extend
def test_pk_index_extend_matches_cold_rebuild():
    rng = np.random.default_rng(41)
    keys = rng.permutation(np.arange(0, 200, 3))[:40].astype(np.int32)
    cap = 64
    pk = np.full(cap, PAD_KEY, np.int32)
    pk[:30] = keys[:30]
    idx = pk_index(jnp.asarray(pk))
    pk2 = pk.copy()
    pk2[30:40] = keys[30:40]
    ext = idx.extend(keys[30:40], np.arange(30, 40))
    cold = pk_index(jnp.asarray(pk2))
    np.testing.assert_array_equal(np.asarray(ext.sorted_pk),
                                  np.asarray(cold.sorted_pk))
    np.testing.assert_array_equal(np.asarray(ext.order),
                                  np.asarray(cold.order))
    assert ext.n_live == 40
    with pytest.raises(ValueError, match="uniqueness"):
        ext.extend(keys[:1], np.array([40]))
    with pytest.raises(ValueError, match="capacity"):
        ext.extend(np.arange(1000, 1030, dtype=np.int32), np.arange(30))


# ------------------------------------------------------ back-compat shims
def test_plain_dict_catalogs_wrap_read_only():
    cat = star_catalog(seed=51)
    plain = dict(cat.snapshot())
    q = _query(_models(seed=10)[0], group=False)
    cq = compile_query(plain, q)                 # Mapping shim
    rt = compile_serving(plain, q, buckets=(8,))
    sess = Session(plain)                        # Session shim
    assert isinstance(sess.catalog, Catalog) and sess.catalog.read_only
    with pytest.raises(CatalogReadOnlyError):
        sess.catalog.append("d1", d1_rows(np.random.default_rng(0), 1,
                                          start=24))
    # Read-only catalogs never change version: refresh is a clean no-op.
    assert "no-op" in cq.refresh()
    assert "no-op" in rt.refresh()
    assert_results_equal(cq.run(), sess.bind(q).run())


def test_catalog_versions_and_deltas():
    cat = star_catalog(seed=52)
    assert cat.versions(("d1", "d2")) == (("d1", 0), ("d2", 0))
    rng = np.random.default_rng(53)
    cat.append("d1", d1_rows(rng, 2, start=24))
    cat.append("d1", d1_rows(rng, 2, start=26))
    assert cat.version("d1") == 2
    assert len(cat.deltas_since("d1", 0)) == 2
    assert len(cat.deltas_since("d1", 1)) == 1
    with pytest.raises(ValueError, match="forward"):
        cat.deltas_since("d1", 5)
    d = cat.deltas_since("d1", 0)[0]
    assert (d.kind, d.lo, d.hi) == ("append", 24, 26)


def test_zero_row_mutations_are_version_noops():
    """Regression: an empty append/update must not bump the version (there
    is nothing to refresh) nor poison later delta refreshes."""
    cat = star_catalog(seed=61)
    q = _query(_models(seed=12)[0], group=False)
    rt = compile_serving(cat, q, buckets=(8,))
    cq = compile_query(cat, q)
    empty = {c: np.empty(0) for c in cat["d1"].columns}
    assert cat.append("d1", empty) == 0 and cat.version("d1") == 0
    assert cat.update_column("d1", "a", [], []) == 0
    assert "no-op" in rt.refresh() and "no-op" in cq.refresh()
    rng = np.random.default_rng(62)
    cat.append("d1", d1_rows(rng, 2, start=24))
    assert "delta" in rt.refresh() and "delta" in cq.refresh()
    cold = compile_serving(cat, q, buckets=(8,))
    reqs = {"fk1": np.array([49, 51], np.int32),
            "fk2": np.array([0, 1], np.int32)}
    np.testing.assert_array_equal(np.asarray(rt.serve(reqs)),
                                  np.asarray(cold.serve(reqs)))


def test_delta_log_is_bounded_and_staleness_rebuilds():
    """Regression: the per-table delta log must not grow without bound; an
    artifact staler than the log's retention rebuilds instead of crashing."""
    cat = star_catalog(seed=63)
    cat.MAX_DELTA_LOG = 4
    q = _query(_models(seed=13)[0], group=False)
    rt = compile_serving(cat, q, buckets=(8,))
    cq = compile_query(cat, q)
    rng = np.random.default_rng(64)
    for i in range(6):                       # > MAX_DELTA_LOG appends
        cat.append("d1", d1_rows(rng, 1, start=24 + i))
    assert len(cat.deltas_since("d1", cat.version("d1") - 1)) == 1
    assert len(cat._deltas["d1"]) == 4      # bounded
    with pytest.raises(ValueError, match="compacted"):
        cat.deltas_since("d1", 0)
    assert "history-compacted" in rt.refresh()   # rebuild, not a crash
    assert "history-compacted" in cq.refresh()
    cold = compile_serving(cat, q, buckets=(8,))
    reqs = {"fk1": np.array([49, 59], np.int32),
            "fk2": np.array([0, 1], np.int32)}
    np.testing.assert_array_equal(np.asarray(rt.serve(reqs)),
                                  np.asarray(cold.serve(reqs)))
    assert_results_equal(cq.run(), compile_query(cat, q).run())


def test_bulk_update_logs_span_not_id_tuple():
    """Regression: huge update_column calls must not pin per-row id tuples
    in the delta log forever — they compact to one covering span."""
    cat = star_catalog(seed=65)
    cat.UPDATE_ROWS_MAX = 4
    q = _query(_models(seed=14)[0], group=False)
    cq = compile_query(cat, q, backend="fused")
    ids = np.arange(2, 10)                   # 8 > UPDATE_ROWS_MAX
    cat.update_column("d1", "a", ids, np.linspace(-1, 1, 8))
    d = cat.deltas_since("d1", 0)[0]
    assert d.rows == () and (d.lo, d.hi) == (2, 10)
    assert "delta" in cq.refresh()
    assert_results_equal(cq.run(),
                         compile_query(cat, q, backend="fused").run())


def test_duplicate_pk_append_rejected_before_commit():
    """Regression: appending a duplicate primary key must fail *at append*
    (transactionally — version unchanged, no poisoned delta), not later
    inside every artifact's refresh, forever."""
    cat = star_catalog(seed=56)
    q = _query(_models(seed=15)[0], group=False)
    rt = compile_serving(cat, q, buckets=(8,))   # teaches PK cols
    v0 = cat.version("d1")
    rng = np.random.default_rng(57)
    dup = d1_rows(rng, 2, start=24)
    dup["pk"] = np.array([0, 49])                # 0 already exists
    with pytest.raises(ValueError, match="already exist in unique key"):
        cat.append("d1", dup)
    assert cat.version("d1") == v0               # transactional: no commit
    assert "no-op" in rt.refresh()               # nothing poisoned
    dup_block = d1_rows(rng, 2, start=24)
    dup_block["pk"] = np.array([49, 49])         # dup within the block
    with pytest.raises(ValueError, match="within the appended block"):
        cat.append("d1", dup_block)
    cat.append("d1", d1_rows(rng, 2, start=24))  # clean append still works
    assert "delta" in rt.refresh()


def test_refresh_decisions_accumulate_on_explain():
    cat = star_catalog(seed=54)
    q = _query(_models(seed=11)[0], group=False)
    cq = compile_query(cat, q)
    assert "no-op" in cq.refresh()          # nothing pending
    rng = np.random.default_rng(55)
    cat.append("d1", d1_rows(rng, 1, start=24))
    cq.refresh()
    reasons = cq.plan.reason
    assert "refresh=no-op" in reasons and "refresh=delta" in reasons, \
        "every refresh decision must land on explain()"


def test_refresh_trail_on_explain_is_bounded():
    """Regression: a streaming artifact refreshed per batch must not grow
    plan.reason (and memory) without bound — only the base reason plus a
    bounded tail of recent decisions is kept."""
    cat = star_catalog(seed=58, slack=96)    # 40 appends stay in capacity
    q = _query(_models(seed=16)[0], group=False)
    cq = compile_query(cat, q)
    rt = compile_serving(cat, q, buckets=(8,))
    base_cq, base_rt = len(cq.plan.reason), len(rt.plan.reason)
    rng = np.random.default_rng(59)
    for i in range(40):
        cat.append("d1", {"pk": [101 + 2 * i], "a": rng.normal(size=1),
                          "b": rng.normal(size=1)})
        cq.refresh()
        rt.refresh()
    assert len(cq.plan.reason) < base_cq + 8 * 80
    assert len(rt.plan.reason) < base_rt + 8 * 80
    assert "refresh=delta" in cq.plan.reason
