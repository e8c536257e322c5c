"""Session query-builder API: builder ≡ hand-built IR (plan-cache hit, no
re-trace), multi-aggregate lowering vs the numpy oracle on both aggregation
backends, registry bit-exactness through the Session, ``num_groups="auto"``,
backend-keyed planner thresholds, and ``eval_value`` error reporting."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fusion import LinearOperator, random_tree
from repro.core.laq import PAD_GROUP, Pred
from repro.core.query import (COUNT_STAR, PLANNER_THRESHOLDS, PREDICTION,
                              Aggregate, ArmSpec, GroupKey, PredictiveQuery,
                              Session, compile_query, compile_serving,
                              eval_value, plan_aggregation, plan_query,
                              planner_threshold, query, query_key,
                              requests_from_rows)
from repro.data import QUERY_IR, generate_ssb, ssb_catalog, ssb_session
from helpers_relational import np_predictive_query

ALL_NAMES = sorted(QUERY_IR)


@pytest.fixture(scope="module")
def data():
    return generate_ssb(sf=1, scale=0.0005, seed=5)


@pytest.fixture(scope="module")
def catalog(data):
    return ssb_catalog(data)


def _linear(k, l, seed=0):
    rng = np.random.default_rng(seed)
    return LinearOperator(jnp.asarray(
        rng.normal(size=(k, l)).astype(np.float32) / np.sqrt(k)))


# ------------------------------------------------- builder ≡ hand-built IR
def test_builder_lowers_to_handbuilt_ir(catalog):
    model = _linear(3, 2)
    built = (query("lineorder")
             .join("date", on=("lo_orderdate", "datekey"),
                   features=["d_month", "d_weeknuminyear"],
                   where=[("d_year", "==", 1993)])
             .join("supplier", on=("lo_suppkey", "suppkey"),
                   features=["s_city"])
             .where(("lo_discount", "between", (1, 3)))
             .predict(model)
             .group_by(("date", "d_year", 8, 1992), num_groups=8)
             .agg(revenue="sum(lo_revenue)", preds=("mean", PREDICTION),
                  n="count")
             .build())
    hand = PredictiveQuery(
        fact="lineorder",
        arms=(ArmSpec("date", "lo_orderdate", "datekey",
                      ("d_month", "d_weeknuminyear"),
                      (Pred("d_year", "==", 1993),)),
              ArmSpec("supplier", "lo_suppkey", "suppkey", ("s_city",))),
        fact_preds=(Pred("lo_discount", "between", (1, 3)),),
        model=model,
        group_keys=(GroupKey("date", "d_year", 8, 1992),),
        aggregates=(Aggregate("lo_revenue", "sum", "revenue"),
                    Aggregate(PREDICTION, "mean", "preds"),
                    Aggregate(COUNT_STAR, "count", "n")),
        num_groups=8)
    for f in dataclasses.fields(PredictiveQuery):
        assert getattr(built, f.name) == getattr(hand, f.name), f.name
    assert query_key(built) == query_key(hand)


def test_registry_builders_hit_plan_cache(data):
    """Rebuilding a registry query (fresh model objects each call) must
    produce a hash-equal IR and hit the session's plan cache — the
    structural key, not object identity, owns reuse."""
    sess = ssb_session(data)
    for name in ("Q3.2", "P1.linear.year", "P4.tree.select.region"):
        q1, q2 = QUERY_IR[name](), QUERY_IR[name]()
        assert q1 is not q2
        assert query_key(q1) == query_key(q2), name
        assert sess.compile(q1) is sess.compile(q2), name


def test_property_builder_ir_hash_equal():
    """Property: any builder-constructed query is hash-equal to its
    hand-built ``PredictiveQuery`` (same plan-cache key, so no re-trace)."""
    pytest.importorskip(
        "hypothesis",
        reason="property tests need hypothesis (requirements-dev)")
    from hypothesis import given, settings, strategies as st

    arms_pool = [
        ("part", "lo_partkey", "partkey", ("p_size", "p_category"),
         (Pred("p_category", "<", 10),)),
        ("supplier", "lo_suppkey", "suppkey", ("s_city",), ()),
        ("date", "lo_orderdate", "datekey", ("d_month",),
         (Pred("d_year", "between", (1993, 1995)),)),
    ]
    fact_pool = [Pred("lo_discount", "between", (1, 3)),
                 Pred("lo_quantity", "<", 25)]
    gk_pool = [GroupKey("date", "d_year", 8, 1992),
               GroupKey("part", "p_brand1", 1000)]
    agg_pool = [("revenue", ("sum", ("mul", "lo_extendedprice",
                                     "lo_discount"))),
                ("q_mean", "mean(lo_quantity)"),
                ("n", "count"),
                ("q_min", "min(lo_quantity)"),
                ("preds", ("max", PREDICTION))]
    model = _linear(4, 2)

    @settings(max_examples=60, deadline=None)
    @given(n_arms=st.integers(1, 3),
           fact_preds=st.booleans(),
           with_model=st.booleans(),
           n_gks=st.integers(0, 2),
           aggs=st.sets(st.integers(0, 4), min_size=1, max_size=4),
           num_groups=st.sampled_from([64, 8192, "auto"]))
    def check(n_arms, fact_preds, with_model, n_gks, aggs, num_groups):
        picked = arms_pool[:n_arms]
        agg_items = [agg_pool[i] for i in sorted(aggs)
                     if with_model or agg_pool[i][0] != "preds"]
        if not agg_items:
            agg_items = [agg_pool[2]]

        b = query("lineorder")
        for table, fk, pk, feats, preds in picked:
            b = b.join(table, on=(fk, pk), features=feats, where=preds)
        if fact_preds:
            b = b.where(*fact_pool)
        if with_model:
            b = b.predict(model)
        if n_gks:
            b = b.group_by(*gk_pool[:n_gks], num_groups=num_groups)
        b = b.agg(**dict(agg_items))

        hand = PredictiveQuery(
            fact="lineorder",
            arms=tuple(ArmSpec(t, fk, pk, f, p)
                       for t, fk, pk, f, p in picked),
            fact_preds=tuple(fact_pool) if fact_preds else (),
            model=model if with_model else None,
            group_keys=tuple(gk_pool[:n_gks]),
            aggregates=tuple(
                {"revenue": Aggregate(("mul", "lo_extendedprice",
                                       "lo_discount"), "sum", "revenue"),
                 "q_mean": Aggregate("lo_quantity", "mean", "q_mean"),
                 "n": Aggregate(COUNT_STAR, "count", "n"),
                 "q_min": Aggregate("lo_quantity", "min", "q_min"),
                 "preds": Aggregate(PREDICTION, "max", "preds"),
                 }[name] for name, _ in agg_items),
            num_groups=num_groups if n_gks else 8192)
        built = b.build()
        for f in dataclasses.fields(PredictiveQuery):
            assert getattr(built, f.name) == getattr(hand, f.name), f.name
        assert query_key(built) == query_key(hand)

    check()


# ------------------------------------- registry bit-exact through Session
@pytest.mark.parametrize("name", ALL_NAMES)
def test_registry_query_session_bit_exact(name, data, catalog):
    """All 13 SSB + 4 P* queries through the Session produce bit-exact
    results vs the pre-redesign direct ``compile_query`` path."""
    sess = ssb_session(data)
    got = sess.bind(QUERY_IR[name]()).run()
    want = compile_query(catalog, QUERY_IR[name]()).run()
    assert sorted(got) == sorted(want)
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]), err_msg=k)


def test_session_rows_and_serve_match_old_entry_points(data, catalog):
    q = QUERY_IR["P1.linear.year"]()
    sess = ssb_session(data)
    ids = jnp.asarray([0, 1, 5, 17, 100, 2999], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(sess.bind(q).rows(ids)),
        np.asarray(compile_query(catalog, q).predict_rows(ids)))
    runtime = sess.bind(q).serve(buckets=(8, 64))
    old = compile_serving(catalog, q, buckets=(8, 64))
    reqs = requests_from_rows(catalog["lineorder"], q, np.arange(6))
    np.testing.assert_array_equal(np.asarray(runtime.serve(reqs)),
                                  np.asarray(old.serve(reqs)))
    assert runtime is sess.bind(QUERY_IR["P1.linear.year"]()).serve(
        buckets=(8, 64)), "serving runtimes must be structurally cached"


def test_mesh_override_does_not_collide_in_plan_cache(catalog):
    """A per-call mesh override must compile a sibling plan, not return the
    cached meshless one (and vice versa)."""
    from repro.core.query import make_serving_mesh
    sess = Session(catalog)
    q = QUERY_IR["P1.linear.year"]()
    meshless = sess.compile(q)
    sharded = sess.compile(q, mesh=make_serving_mesh((1, 1)))
    assert meshless is not sharded
    assert meshless.plan.partition_specs is None
    assert sharded.plan.partition_specs is not None
    assert sess.compile(q) is meshless


# --------------------------------------------- multi-aggregate vs oracle
def _assert_matches_oracle(compiled, q, catalog):
    res = compiled.run()
    want = np_predictive_query(q, catalog)
    assert int(res["rows"]) == want["rows"]
    if want["groups"] is None:
        for a in q.aggregates:
            got = np.atleast_1d(np.asarray(res[a.name]))
            tol = 1e-6 * max(want["abs_scale"][a.name], 1.0)
            np.testing.assert_allclose(
                got, np.atleast_1d(want["scalars"][a.name]),
                rtol=1e-4, atol=tol, err_msg=a.name)
        return
    groups = np.asarray(res["groups"])
    live = groups != PAD_GROUP
    for a in q.aggregates:
        vals = np.asarray(res[a.name])
        v2 = vals if vals.ndim > 1 else vals[:, None]
        got = {int(g): v2[i] for i, g in enumerate(groups) if live[i]}
        want_g = {c: v[a.name] for c, v in want["groups"].items()}
        assert set(got) == set(want_g), a.name
        tol = 1e-6 * max(want["abs_scale"][a.name], 1.0)
        for c, v in want_g.items():
            np.testing.assert_allclose(got[c], v, rtol=1e-4, atol=tol,
                                       err_msg=f"{a.name} group {c}")


_MULTI_AGGS = dict(
    revenue=("sum", ("mul", "lo_extendedprice", "lo_discount")),
    rev_mean=("mean", ("mul", "lo_extendedprice", "lo_discount")),
    n="count",
    q_min="min(lo_quantity)",
    q_max="max(lo_quantity)",
)


@pytest.mark.parametrize("agg_backend", ["segment", "matmul"])
@pytest.mark.parametrize("grouped", [True, False], ids=["grouped", "scalar"])
def test_relational_multi_aggregate_matches_oracle(agg_backend, grouped,
                                                   data, catalog):
    """count/mean/min/max over a fact expression, both agg backends, with
    and without group keys — vs the brute-force numpy oracle."""
    sess = ssb_session(data)
    b = (sess.query("lineorder")
         .join("date", on=("lo_orderdate", "datekey"))
         .where(("lo_discount", "between", (1, 5)))
         .agg(**_MULTI_AGGS))
    if grouped:
        b = b.group_by(("date", "d_year", 8, 1992), num_groups=8)
    q = b.build()
    compiled = b.compile(agg_backend=agg_backend)
    assert compiled.agg_backend == agg_backend or not grouped
    _assert_matches_oracle(compiled, q, catalog)


@pytest.mark.parametrize("agg_backend", ["segment", "matmul"])
@pytest.mark.parametrize("backend", ["fused", "nonfused"])
@pytest.mark.parametrize("head", ["linear", "tree"])
def test_prediction_multi_aggregate_matches_oracle(agg_backend, backend,
                                                   head, data, catalog):
    """≥2 named aggregates (mean + count + sum/min/max of PREDICTION) in one
    compiled program, across fused/nonfused × segment/matmul — vs the
    numpy oracle."""
    model = (_linear(3, 4, seed=7) if head == "linear"
             else random_tree(np.random.default_rng(7), 3, depth=2))
    sess = ssb_session(data)
    b = (sess.query("lineorder")
         .join("part", on=("lo_partkey", "partkey"),
               features=["p_size", "p_category"])
         .join("date", on=("lo_orderdate", "datekey"),
               features=["d_month"],
               where=[("d_year", "between", (1993, 1996))])
         .predict(model)
         .group_by(("date", "d_year", 8, 1992), num_groups=8)
         .agg(psum=("sum", PREDICTION), pmean=("mean", PREDICTION),
              n="count", pmax=("max", PREDICTION)))
    q = b.build()
    compiled = b.compile(backend=backend, agg_backend=agg_backend)
    assert compiled.backend == backend
    res = compiled.run()
    assert {"psum", "pmean", "n", "pmax"} <= set(res)
    _assert_matches_oracle(compiled, q, catalog)
    # mean must be exactly the fused sum/count of the same program.
    n = np.asarray(res["n"])[:, None]
    np.testing.assert_allclose(np.asarray(res["pmean"]),
                               np.asarray(res["psum"]) / np.maximum(n, 1.0),
                               rtol=1e-6, atol=1e-6)


# ----------------------------------------------------- num_groups="auto"
def test_num_groups_auto_sizes_to_measured_domain(data, catalog):
    sess = ssb_session(data)
    base = QUERY_IR["P1.linear.year"]()
    auto = sess.compile(dataclasses.replace(base, num_groups="auto"))
    assert isinstance(auto.query.num_groups, int)
    live = int(np.sum(np.asarray(auto.run()["groups"]) != PAD_GROUP))
    assert auto.query.num_groups == live
    ref = sess.compile(base).run()
    got = auto.run()
    for k in ("prediction", "groups"):
        np.testing.assert_array_equal(
            np.asarray(got[k]),
            np.asarray(ref[k])[:auto.query.num_groups], err_msg=k)


def test_num_groups_auto_raises_under_trace(data, catalog):
    import jax
    q = dataclasses.replace(QUERY_IR["Q2.1"](), num_groups="auto")
    with pytest.raises(ValueError, match="auto"):
        jax.jit(lambda: compile_query(catalog, q).run()["revenue"])()


# --------------------------------------------- eval_value error reporting
def test_eval_value_unknown_column_names_expression(catalog):
    fact = catalog["lineorder"]
    with pytest.raises(ValueError, match="no_such_col"):
        eval_value(fact, "no_such_col")
    with pytest.raises(ValueError, match="lineorder"):
        eval_value(fact, ("mul", "lo_revenue", "no_such_col"))
    with pytest.raises(ValueError, match="my query"):
        eval_value(fact, "no_such_col", query="my query")


@pytest.mark.parametrize("expr, match", [
    (("pow", "lo_revenue", "lo_discount"), "unknown op"),
    (("mul", "lo_revenue"), "takes 2 arguments"),
    (("col",), "exactly one column name"),
    ((), "malformed"),
    (123, "malformed"),
])
def test_eval_value_malformed_expression(catalog, expr, match):
    with pytest.raises(ValueError, match=match):
        eval_value(catalog["lineorder"], expr)


def test_compile_surfaces_bad_aggregate_column(data, catalog):
    sess = ssb_session(data)
    b = (sess.query("lineorder")
         .join("date", on=("lo_orderdate", "datekey"))
         .agg(bad="sum(no_such_col)"))
    with pytest.raises(ValueError, match="no_such_col"):
        b.run()


def test_compile_rejects_bad_aggregates(catalog):
    base = query("lineorder").join("date", on=("lo_orderdate", "datekey"))
    with pytest.raises(ValueError, match="not one of"):
        compile_query(catalog, dataclasses.replace(
            base.agg(x="lo_revenue").build(),
            aggregates=(Aggregate("lo_revenue", "median", "x"),)))
    with pytest.raises(ValueError, match="distinct"):
        compile_query(catalog, dataclasses.replace(
            base.build(),
            aggregates=(Aggregate("lo_revenue", "sum", "x"),
                        Aggregate("lo_quantity", "sum", "x"))))
    with pytest.raises(ValueError, match="reserved"):
        compile_query(catalog, dataclasses.replace(
            base.build(),
            aggregates=(Aggregate("lo_revenue", "sum", "rows"),)))


# ------------------------------------------ builder validation ergonomics
def test_builder_validates_catalog_names(catalog):
    sess = Session(catalog)
    with pytest.raises(KeyError, match="no_such_table"):
        sess.query("no_such_table")
    b = sess.query("lineorder")
    with pytest.raises(KeyError, match="no_such_dim"):
        b.join("no_such_dim", on=("lo_orderdate", "datekey"))
    with pytest.raises(ValueError, match="not a key column"):
        b.join("date", on=("lo_orderdate", "not_a_key"))
    with pytest.raises(ValueError, match="not a key column"):
        b.join("date", on=("lo_revenue", "datekey"))  # float, not a fact key
    with pytest.raises(ValueError, match="feature columns"):
        b.join("date", on=("lo_orderdate", "datekey"),
               features=["nope"])
    with pytest.raises(ValueError, match="detached"):
        query("lineorder").join(
            "date", on=("lo_orderdate", "datekey")).run()


def test_agg_spec_grammar():
    b = query("lineorder").join("date", on=("lo_orderdate", "datekey"))
    q = b.agg(a="lo_revenue", b="mean(lo_quantity)", c="count",
              d=("sum", ("mul", "x", "y")), e=("sub", "x", "y"),
              f=Aggregate("lo_revenue", "max", "ignored")).build()
    assert q.aggregates == (
        Aggregate("lo_revenue", "sum", "a"),
        Aggregate("lo_quantity", "mean", "b"),
        Aggregate(COUNT_STAR, "count", "c"),
        Aggregate(("mul", "x", "y"), "sum", "d"),
        Aggregate(("sub", "x", "y"), "sum", "e"),
        Aggregate("lo_revenue", "max", "f"))
    with pytest.raises(ValueError, match="unparseable"):
        b.agg(x=("median", "lo_revenue"))
    with pytest.raises(ValueError, match="unparseable"):
        b.agg(x=3.14)


# ------------------------------------- backend-keyed planner thresholds
def test_planner_threshold_backend_keyed():
    assert (planner_threshold("MXU_SEGMENT_ADVANTAGE", "cpu")
            == PLANNER_THRESHOLDS["default"]["MXU_SEGMENT_ADVANTAGE"])
    assert (planner_threshold("DENSE_JOIN_ELEMS", "weird_accel")
            == PLANNER_THRESHOLDS["default"]["DENSE_JOIN_ELEMS"])
    with pytest.raises(KeyError, match="unknown planner threshold"):
        planner_threshold("NOT_A_THRESHOLD")
    PLANNER_THRESHOLDS["faketpu"] = {"DENSE_JOIN_ELEMS": 1}
    try:
        # The calibration row flips the decision with zero refactoring:
        # tiny inputs pick the dense matmul join on cpu, gather on faketpu.
        assert plan_query(None, 64, [16, 16]).join_backend == "matmul"
        assert plan_query(None, 64, [16, 16],
                          platform="faketpu").join_backend == "gather"
    finally:
        del PLANNER_THRESHOLDS["faketpu"]


def test_plan_aggregation_costs_combined_set():
    # sum-only: unchanged crossover (compiler tests pin the boundary).
    assert plan_aggregation(100_000, 4, 4).backend == "matmul"
    assert plan_aggregation(100_000, 8192, 1).backend == "segment"
    # min/max-only sets have no matmul lowering to win with.
    assert plan_aggregation(100_000, 4, 4,
                            ops=("min", "max")).backend == "segment"
    # A count rides along without flipping a small-G matmul win …
    assert plan_aggregation(100_000, 4, 4,
                            ops=("sum", "mean", "count")).backend == "matmul"
    # … and the combined set costs more than the single sum.
    single = plan_aggregation(100_000, 4, 4)
    combo = plan_aggregation(100_000, 4, 4, ops=("sum", "mean", "count",
                                                 "min"))
    assert combo.matmul_flops > single.matmul_flops
    assert combo.segment_flops > single.segment_flops
