"""Out-of-core fact streaming + tombstone deletes (ISSUE 8).

The contract under test:
  * the streamed program is **bit-exact** vs the in-core run of the same
    fused/gather/segment program for every chunk size — 1, non-divisors of
    the fact length, larger than the fact — on grouped aggregates and
    ungrouped count/min/max (the carried segment accumulator replays the
    exact adds of the one-shot fold; ungrouped sum/mean have no segment
    structure to carry, so they are allclose),
  * both agree with a float64 numpy oracle over the live rows,
  * a refresh that keeps capacity (appends + tombstone deletes) re-chunks
    with **zero retraces** — one trace per compiled plan, ever,
  * ``delete_rows`` is a pure validity fold (shapes/keys/placement kept;
    delta refresh ≡ cold rebuild across fused/nonfused × segment/matmul),
    and ``changed_spans`` reports deletions distinct from updates,
  * ``compact`` rewrites row ids and every referencing plan recompiles
    with a named reason,
  * the planner streams exactly when the fact working set exceeds the
    memory budget (or the caller pins a chunk size) and says why,
  * streaming composes with the session: pooled dimension-side artifacts
    are shared across chunks, plans opt out of ``run_all`` stacking.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fusion import LinearOperator
from repro.core.laq import Catalog, ChangedSpans, Table, changed_spans
from repro.core.laq.selection import Pred
from repro.core.query import (PREDICTION, Aggregate, ArmSpec, GroupKey,
                              PredictiveQuery, Session, compile_query,
                              compile_serving, plan_chunk_rows,
                              plan_streaming)
from repro.core.query.multiquery import stack_key

#: The in-core baseline streaming must match bitwise.  Pinned explicitly:
#: the auto-planner may lower small-group aggregations via matmul — a
#: different (valid) program whose sums associate differently — while the
#: streamed program is always the fused/gather/segment lowering.
PINNED = dict(backend="fused", join_backend="gather", agg_backend="segment")


# --------------------------------------------------------------------- data
def star_catalog(seed: int, n_fact: int = 640, n_d1: int = 24, n_d2: int = 10,
                 slack: int = 16) -> Catalog:
    rng = np.random.default_rng(seed)
    d1 = {"pk": np.arange(n_d1) * 2,          # sparse keys: FKs can miss
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


def _model(seed: int = 1) -> LinearOperator:
    rng = np.random.default_rng(seed)
    return LinearOperator(jnp.asarray(rng.normal(size=(3, 2)), jnp.float32))


def _query(model, *, group: bool = True,
           extra_aggs: bool = False) -> PredictiveQuery:
    gk = (GroupKey("d2", "g", 4),) if group else ()
    aggs = [Aggregate(PREDICTION, "sum", "pred"),
            Aggregate(PREDICTION, "mean", "pmean"),
            Aggregate("val", "mean", "v"),
            Aggregate("*", "count", "n")]
    if extra_aggs:
        aggs += [Aggregate("val", "min", "vmin"),
                 Aggregate("val", "max", "vmax"),
                 Aggregate(("mul", "val", "val"), "sum", "v2")]
    return PredictiveQuery(
        fact="fact",
        arms=(ArmSpec("d1", "fk1", "pk", ("a", "b"),
                      (Pred("a", ">", -1.0),)),
              ArmSpec("d2", "fk2", "pk2", ("c",))),
        fact_preds=(Pred("val", ">", -2.0),),
        model=model,
        group_keys=gk,
        aggregates=tuple(aggs),
        num_groups=4 if group else 8192)


def _assert_bitwise(got, want, keys):
    for k in keys:
        assert np.array_equal(np.asarray(got[k]), np.asarray(want[k])), k


# ------------------------------------------------------------- numpy oracle
def _oracle(cat: Catalog, model: LinearOperator, *, group: bool = True):
    """Float64 row-at-a-time evaluation of ``_query`` over the live rows."""
    fact, d1, d2 = cat["fact"], cat["d1"], cat["d2"]

    def live(t):
        m = np.arange(t.capacity) < int(t.nvalid)
        if t.deleted is not None:
            m &= ~np.asarray(t.deleted)
        return m

    def lookup(t, pk_col):
        alive = live(t)
        return {int(k): i for i, k in enumerate(np.asarray(t.key(pk_col)))
                if alive[i]}

    idx1, idx2 = lookup(d1, "pk"), lookup(d2, "pk2")
    a = np.asarray(d1.col("a"), np.float64)
    b = np.asarray(d1.col("b"), np.float64)
    c = np.asarray(d2.col("c"), np.float64)
    g = np.asarray(d2.col("g"), np.int64)
    val = np.asarray(fact.col("val"), np.float64)
    fk1 = np.asarray(fact.key("fk1"))
    fk2 = np.asarray(fact.key("fk2"))
    L = np.asarray(model.L, np.float64)
    G = 4 if group else 1
    sums = {k: np.zeros((G, 2) if k in ("pred", "pmean") else (G,))
            for k in ("pred", "pmean", "v")}
    count = np.zeros((G,))
    flive = live(fact)
    for i in range(int(fact.nvalid)):
        if not flive[i] or not val[i] > -2.0:
            continue
        j1, j2 = idx1.get(int(fk1[i])), idx2.get(int(fk2[i]))
        if j1 is None or j2 is None or not a[j1] > -1.0:
            continue
        gid = int(g[j2]) if group else 0
        x = np.array([a[j1], b[j1], c[j2]])
        sums["pred"][gid] += x @ L
        sums["pmean"][gid] += x @ L
        sums["v"][gid] += val[i]
        count[gid] += 1
    cnt = np.maximum(count, 1.0)
    out = {"pred": sums["pred"], "pmean": sums["pmean"] / cnt[:, None],
           "v": sums["v"] / cnt, "n": count}
    if group:
        # The engine packs groups as sorted live codes with a zero tail, so
        # a group value no live row carries leaves no slot behind.
        present = np.nonzero(count)[0]
        out = {k: np.concatenate([v[present], np.zeros_like(v)])[:G]
               for k, v in out.items()}
    else:
        out = {k: v[0] for k, v in out.items()}
    return out


# ------------------------------------------------- streamed ≡ in-core ≡ oracle
@pytest.mark.parametrize("chunk", [1, 7, 64, 100, 999, 5000])
def test_grouped_stream_bitexact_chunk_sweep(chunk):
    """Every chunk size — 1, non-divisors, > fact rows — replays the exact
    in-core segment fold, including min/max and expression aggregates."""
    cat = star_catalog(0)
    model = _model()
    q = _query(model, extra_aggs=True)
    streamed = compile_query(cat, q, stream_chunk_rows=chunk)
    incore = compile_query(star_catalog(0), q, **PINNED)
    assert streamed._stream is not None
    _assert_bitwise(streamed.run(), incore.run(),
                    ("pred", "pmean", "v", "n", "vmin", "vmax", "v2"))


@pytest.mark.parametrize("chunk", [1, 100, 5000])
def test_ungrouped_stream(chunk):
    """Ungrouped count/min/max are bitwise; sum/mean fold per-chunk scalar
    partials (no segment structure to carry) and are allclose."""
    cat = star_catalog(3)
    model = _model()
    q = _query(model, group=False, extra_aggs=True)
    streamed = compile_query(cat, q, stream_chunk_rows=chunk).run()
    incore = compile_query(star_catalog(3), q, **PINNED).run()
    _assert_bitwise(streamed, incore, ("n", "vmin", "vmax"))
    for k in ("pred", "pmean", "v", "v2"):
        np.testing.assert_allclose(np.asarray(streamed[k]),
                                   np.asarray(incore[k]), rtol=1e-5)


@pytest.mark.parametrize("group", [True, False])
def test_stream_matches_numpy_oracle(group):
    cat = star_catalog(5)
    model = _model()
    cat.delete_rows("fact", [0, 3, 100, 639])
    cat.delete_rows("d1", [2, 9])
    got = compile_query(cat, _query(model, group=group),
                        stream_chunk_rows=97).run()
    want = _oracle(cat, model, group=group)
    for k in ("pred", "pmean", "v", "n"):
        np.testing.assert_allclose(np.asarray(got[k]), want[k],
                                   rtol=1e-5, atol=1e-6)


def test_stream_refresh_zero_retrace_and_bitexact():
    """Append + delete within capacity: the executor re-chunks the same
    shapes, so the single chunk-step trace is reused — and the refreshed
    stream equals a cold rebuild bitwise."""
    rng = np.random.default_rng(11)
    cat = star_catalog(7)
    model = _model()
    q = _query(model, extra_aggs=True)
    streamed = compile_query(cat, q, stream_chunk_rows=128)
    streamed.run()
    traces0 = streamed._stream.traces
    assert traces0 >= 1
    cat.append("fact", {"fk1": rng.integers(0, 80, 8),
                        "fk2": rng.integers(0, 18, 8),
                        "val": rng.normal(size=8)})
    cat.delete_rows("fact", [5, 77, 400, 641])
    cat.delete_rows("d1", [1, 4])
    note = streamed.refresh()
    assert "delta" in note
    cold = compile_query(cat, q, stream_chunk_rows=128)
    _assert_bitwise(streamed.run(), cold.run(),
                    ("pred", "pmean", "v", "n", "vmin", "vmax", "v2"))
    assert streamed._stream.traces == traces0, "chunk step retraced"


def test_compact_recompiles_with_named_reason():
    cat = star_catalog(9)
    model = _model()
    q = _query(model)
    streamed = compile_query(cat, q, stream_chunk_rows=64)
    streamed.run()
    cat.delete_rows("fact", np.arange(0, 400, 2))
    assert cat.compact("fact")
    note = streamed.refresh()
    assert "compaction:fact" in note
    _assert_bitwise(streamed.run(),
                    compile_query(cat, q, stream_chunk_rows=64).run(),
                    ("pred", "v", "n"))


# ----------------------------------------------------------- planner choice
def test_memory_budget_drives_streaming():
    cat = star_catalog(0)
    q = _query(_model())
    small = compile_query(cat, q, memory_budget_bytes=20_000)
    assert small._stream is not None
    assert "stream=" in small.plan.reason
    big = compile_query(cat, q, memory_budget_bytes=10**9)
    assert big._stream is None
    assert "stream=off" in big.plan.reason
    _assert_bitwise(small.run(),
                    compile_query(cat, q, **PINNED).run(),
                    ("pred", "v", "n"))


def test_plan_chunk_rows_unit():
    # pinned / auto / off
    assert plan_chunk_rows(64, 1000, 100, None) == 64
    assert plan_chunk_rows(None, 1000, 100, None) is None
    assert plan_chunk_rows(None, 1000, 100, 10**9) is None   # fits: in-core
    assert plan_chunk_rows(None, 1000, 100, 20_000) == 200   # exceeds: auto
    assert plan_chunk_rows("auto", 1000, 100, 20_000) == 200
    assert 1 <= plan_chunk_rows("auto", 1000, 100, 1) <= 1000  # clamps
    assert plan_chunk_rows(0, 1000, 100, None) is None         # 0 ≡ off
    with pytest.raises(ValueError):
        plan_chunk_rows(-1, 1000, 100, None)
    on, why = plan_streaming(64, 1000, 100, None)
    assert on == 64 and "stream=" in why


def test_stream_rejects_incompatible_backends():
    cat = star_catalog(0)
    q = _query(_model())
    for bad in (dict(backend="nonfused"), dict(join_backend="matmul"),
                dict(agg_backend="matmul")):
        with pytest.raises(ValueError, match="stream"):
            compile_query(cat, q, stream_chunk_rows=64, **bad)

    def traced(rows):
        c = star_catalog(0)
        qq = dataclasses.replace(
            q, fact_preds=(Pred("val", ">", rows),))
        return compile_query(c, qq, stream_chunk_rows=64).run()["n"]

    with pytest.raises(ValueError, match="stream"):
        jax.jit(traced)(jnp.float32(-2.0))


# -------------------------------------------------------- session composure
def test_session_stream_knob_and_explain():
    cat = star_catalog(0)
    sess = Session(cat, stream_chunk_rows=100)
    q = _query(_model())
    c = sess.compile(q)
    assert c._stream is not None
    report = c.explain().as_dict()
    assert report["extras"]["stream"].startswith("stream:")
    assert "stream=" in report["plan_reason"]
    # streaming plans never stack — run_all falls back to per-plan run()
    assert stack_key(c) is None
    base = compile_query(star_catalog(0), q, **PINNED).run()
    for out in (c.run(), sess.run_all([q])[0]):
        _assert_bitwise(out, base, ("pred", "v", "n"))


def test_pooled_artifacts_are_dimension_side_and_shared():
    """The pool invariant streaming relies on: every pooled artifact a
    streaming plan holds is dimension-sided (chunking never slices it), so
    two plans sharing arms share them across chunk loops too."""
    cat = star_catalog(0)
    sess = Session(cat, stream_chunk_rows=64)
    model = _model()
    c1 = sess.compile(_query(model))
    c2 = sess.compile(_query(model, extra_aggs=True))
    assert c1 is not c2 and c1._stream is not None
    shared = set(c1._pool_keys()) & set(c2._pool_keys())
    assert any(k[0] == "partial" for k in shared)
    assert any(k[0] == "join" for k in shared)


# --------------------------------------------- deletion as a validity fold
def test_changed_spans_reports_deletes_distinct_from_updates():
    cat = star_catalog(0)
    v0 = cat.version("fact")
    cat.update_column("fact", "val", [3, 5], [1.0, 2.0])
    cat.delete_rows("fact", [5, 9])
    cs = changed_spans(cat.deltas_since("fact", v0))
    assert isinstance(cs, ChangedSpans)
    assert cs.span is None and not cs.grew
    assert cs.dirty == (3, 5) and cs.deleted == (5, 9)
    # bulk deletes log a covering span that expands at refresh time
    big = cat.delete_rows("fact", np.arange(100, 400))
    cs2 = changed_spans(cat.deltas_since("fact", big - 1))
    assert set(cs2.deleted) == set(range(100, 400))


def test_delete_rows_semantics():
    cat = star_catalog(0)
    t0 = cat["fact"]
    v = cat.delete_rows("fact", [0, 0, 5])
    t = cat["fact"]
    assert t.num_deleted == 2 and t.num_live == int(t.nvalid) - 2
    assert not bool(t.valid_mask()[0]) and bool(t.valid_mask()[1])
    # placement/shapes/keys untouched: pure validity fold
    assert t.capacity == t0.capacity and int(t.nvalid) == int(t0.nvalid)
    assert np.array_equal(np.asarray(t.key("fk1")),
                          np.asarray(t0.key("fk1")))
    assert cat.delete_rows("fact", [5]) == v        # re-delete: version no-op
    assert cat.tombstone_fraction("fact") == 2 / 640
    for bad in ([-1], [640]):
        with pytest.raises(ValueError):
            cat.delete_rows("fact", bad)
    assert not cat.compact("fact")                  # below threshold: no-op


@pytest.mark.parametrize("backend", ["fused", "nonfused"])
@pytest.mark.parametrize("agg_backend", ["segment", "matmul"])
def test_refresh_after_delete_equals_cold_rebuild(backend, agg_backend):
    """The satellite bugfix: the delta path treats deletions as mask-only
    scatters on every backend pair, matching a cold rebuild bitwise."""
    cat = star_catalog(21, n_fact=256)
    model = _model()
    q = _query(model, extra_aggs=True)
    plan = compile_query(cat, q, backend=backend, agg_backend=agg_backend)
    plan.run()
    cat.delete_rows("fact", [0, 17, 130, 255])
    cat.delete_rows("d1", [3, 8])
    cat.delete_rows("d2", [6])
    note = plan.refresh()
    assert "delta" in note
    cold = compile_query(cat, q, backend=backend, agg_backend=agg_backend)
    _assert_bitwise(plan.run(), cold.run(),
                    ("pred", "pmean", "v", "n", "vmin", "vmax", "v2"))


def test_serving_refresh_after_delete_equals_cold():
    cat = star_catalog(13)
    q = _query(_model())
    sess = Session(cat)
    rt = sess.serving(q, buckets=(8, 32))
    rng = np.random.default_rng(2)
    batch = {"fk1": jnp.asarray(rng.integers(0, 48, 20), jnp.int32),
             "fk2": jnp.asarray(rng.integers(0, 10, 20), jnp.int32)}
    rt.serve(batch)
    n0 = rt.num_compiles
    cat.delete_rows("d1", [2, 5, 11])
    cat.delete_rows("d2", [0, 7])
    sess.refresh()
    got = rt.serve(batch)
    want = compile_serving(cat, q, buckets=(8, 32)).serve(batch)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert rt.num_compiles == n0


# ----------------------------------------------------- the property sweep
def _equivalence_case(seed: int, chunk: int, ops: list):
    """One randomized append/delete interleaving: streamed ≡ in-core
    (bitwise) ≡ numpy oracle (allclose) after every mutation batch."""
    rng = np.random.default_rng(seed)
    cat = star_catalog(seed)
    model = _model()
    q = _query(model)
    streamed = compile_query(cat, q, stream_chunk_rows=chunk)
    for kind, arg in ops:
        if kind == "append":
            cat.append("fact", {"fk1": rng.integers(0, 80, arg),
                                "fk2": rng.integers(0, 18, arg),
                                "val": rng.normal(size=arg)})
        elif kind == "delete_fact":
            ids = rng.choice(int(cat["fact"].nvalid), size=arg,
                             replace=False)
            cat.delete_rows("fact", ids)
        else:
            ids = rng.choice(int(cat[kind].nvalid),
                             size=min(arg, 3), replace=False)
            cat.delete_rows(kind, ids)
        streamed.refresh()
        got = streamed.run()
        incore = compile_query(cat, q, **PINNED).run()
        _assert_bitwise(got, incore, ("pred", "pmean", "v", "n"))
        want = _oracle(cat, model)
        for k in ("pred", "v", "n"):
            np.testing.assert_allclose(np.asarray(got[k]), want[k],
                                       rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("seed,chunk,ops", [
    (0, 1, [("delete_fact", 5), ("append", 4)]),
    (1, 93, [("append", 6), ("delete_fact", 40), ("d1", 2)]),
    (2, 640, [("d2", 1), ("delete_fact", 10), ("append", 10),
              ("delete_fact", 30)]),
    (3, 5000, [("append", 16), ("d1", 3), ("d2", 2),
               ("delete_fact", 100)]),
])
def test_append_delete_interleavings(seed, chunk, ops):
    _equivalence_case(seed, chunk, ops)


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                    # requirements-dev
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    _op = st.one_of(
        st.tuples(st.just("append"), st.integers(1, 8)),
        st.tuples(st.just("delete_fact"), st.integers(1, 60)),
        st.tuples(st.just("d1"), st.integers(1, 3)),
        st.tuples(st.just("d2"), st.integers(1, 2)))

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 2**16),
           chunk=st.one_of(st.integers(1, 700), st.just(10_000)),
           ops=st.lists(_op, min_size=1, max_size=4))
    def test_streaming_equivalence_property(seed, chunk, ops):
        """Random chunk sizes (1, non-divisors, > fact rows), random
        tombstone sets and append/delete interleavings never break the
        three-way equivalence."""
        _equivalence_case(seed, chunk, ops)
else:
    @pytest.mark.skip(reason="property tests need hypothesis "
                             "(requirements-dev)")
    def test_streaming_equivalence_property():
        pass


# ------------------------------------------------------------------- scale
@pytest.mark.slow
def test_stream_at_scale_under_budget():
    """A fact ~40x the memory budget streams in budget-sized chunks and
    still matches the pinned in-core program bitwise."""
    cat = star_catalog(0, n_fact=200_000, slack=64)
    q = _query(_model(), extra_aggs=True)
    streamed = compile_query(cat, q, memory_budget_bytes=256 * 1024)
    assert streamed._stream is not None
    assert streamed._stream.chunk_bytes() <= 256 * 1024
    incore = compile_query(cat, q, **PINNED)
    _assert_bitwise(streamed.run(), incore.run(),
                    ("pred", "pmean", "v", "n", "vmin", "vmax", "v2"))
