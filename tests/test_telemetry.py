"""Spans and compile counters of the query path (``repro.telemetry``): the
span tree of a query's first and later calls, the compile events credited
to it, the bounded ring, and the spans in a recorded profile."""
import glob
import os

import jax
import pytest

from repro import telemetry
from repro.data import QUERY_IR, generate_ssb, ssb_session

# A fact size no other test uses, so that the first call compiles its
# programs here whatever ran before in this process.
SCALE = 0.0007


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def session(no_persistent_cache):
    return ssb_session(generate_ssb(sf=1, scale=SCALE, seed=13))


def _call(sess, name):
    """Run query ``name`` once; returns the spans of its request."""
    telemetry.reset()
    jax.block_until_ready(sess.bind(QUERY_IR[name]()).run())
    return telemetry.spans()


@pytest.fixture(scope="module")
def calls(session):
    """Q2.1's first and second call, then Q2.2, whose joins Q2.1 built."""
    return {"first": _call(session, "Q2.1"), "second": _call(session, "Q2.1"),
            "reuse": _call(session, "Q2.2")}


def _tree(spans):
    """(root, {span id: [children in start order]})."""
    roots = [s for s in spans if s.parent is None]
    assert len(roots) == 1
    kids = {}
    for s in sorted(spans, key=lambda s: s.start):
        if s.parent is not None:
            kids.setdefault(s.parent, []).append(s)
    return roots[0], kids


def _subtree(span, kids):
    out = [span]
    for k in kids.get(span.id, []):
        out += _subtree(k, kids)
    return out


def test_first_call_builds_then_dispatches(calls):
    spans = calls["first"]
    root, kids = _tree(spans)
    assert root.name == "query"
    assert [s.name for s in kids[root.id]] == ["plan.build", "query.dispatch"]
    build = kids[root.id][0]
    below = [s.name for s in kids[build.id]]
    assert "plan.star" in below and "plan.groups" in below
    assert below.index("plan.star") < below.index("plan.groups")
    star = next(s for s in kids[build.id] if s.name == "plan.star")
    assert {"pool.pkindex", "pool.join", "pool.dmask"} <= {
        s.name for s in kids[star.id]}
    assert {s.request for s in spans} == {root.id}


def test_second_call_only_dispatches(calls):
    root, kids = _tree(calls["second"])
    assert root.name == "query"
    assert [s.name for s in kids[root.id]] == ["query.dispatch"]
    assert len(calls["second"]) == 2


@pytest.mark.parametrize("call", ["first", "second", "reuse"])
def test_children_lie_inside_their_parents(calls, call):
    spans = calls[call]
    by_id = {s.id: s for s in spans}
    _, kids = _tree(spans)
    for s in spans:
        assert s.start <= s.end
        if s.parent is not None:
            p = by_id[s.parent]
            assert p.start <= s.start and s.end <= p.end
        covered = sum(k.end - k.start for k in kids.get(s.id, []))
        assert (s.end - s.start) - covered >= 0     # self time


def test_compile_events_are_credited_to_the_first_call(calls):
    root, kids = _tree(calls["first"])
    build, dispatch = kids[root.id]
    assert sum(s.programs for s in _subtree(build, kids)) > 0
    assert dispatch.programs > 0 and dispatch.compile_s > 0
    assert sum(s.programs for s in calls["second"]) == 0
    assert sum(s.compile_s for s in calls["second"]) == 0


def test_totals_add_up_the_records(calls, session):
    spans = _call(session, "Q2.1")
    tot = telemetry.totals()
    assert {n: t.count for n, t in tot.items()} == {
        "query": 1, "query.dispatch": 1}
    for s in spans:
        assert tot[s.name].seconds == pytest.approx(s.end - s.start)
        assert tot[s.name].max == tot[s.name].seconds


def test_a_reused_join_builds_no_pool_join(calls):
    names = [s.name for s in calls["reuse"]]
    assert "plan.build" in names and "pool.dmask" in names
    assert "pool.join" not in names and "pool.pkindex" not in names


def test_the_ring_is_bounded():
    telemetry.reset()
    try:
        for _ in range(telemetry.RING_SIZE + 10):
            with telemetry.span("tick"):
                pass
        spans = telemetry.spans()
        assert len(spans) == telemetry.RING_SIZE
        assert spans[0].id < spans[-1].id
        assert telemetry.totals()["tick"].count == telemetry.RING_SIZE + 10
    finally:
        telemetry.reset()


def test_span_records_an_exception_and_reraises():
    telemetry.reset()
    with pytest.raises(KeyError):
        with telemetry.span("outer"):
            with telemetry.span("inner"):
                raise KeyError("x")
    inner, outer = telemetry.spans()
    assert (inner.name, outer.name) == ("inner", "outer")
    assert inner.parent == outer.id and inner.request == outer.id
    with telemetry.span("next"):
        pass
    assert telemetry.spans()[-1].parent is None     # the stack unwound


def test_spans_land_in_a_recorded_profile(session, calls, tmp_path):
    q = session.bind(QUERY_IR["Q2.1"]())
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(q.run())
    paths = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1
    pd = jax.profiler.ProfileData.from_file(paths[0])
    names = {e.name for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"repro.query", "repro.query.dispatch"} <= names


def test_a_compacted_group_by_records_one_compact_span(calls, session):
    spans = calls["first"]
    compact = [s for s in spans if s.name == "plan.compact"]
    assert len(compact) == 1
    groups = next(s for s in spans if s.name == "plan.groups")
    assert compact[0].parent == groups.id
    assert set(compact[0].attrs) == {"rows", "capacity"}
    assert compact[0].attrs["rows"] <= compact[0].attrs["capacity"]
    plan = session.compile(QUERY_IR["Q2.1"]())
    assert (f"agg=compact(rows={compact[0].attrs['rows']}, "
            f"cap={compact[0].attrs['capacity']})") in str(plan.explain())
    ungrouped = _call(session, "Q1.1")
    assert "plan.build" in {s.name for s in ungrouped}
    assert "plan.compact" not in {s.name for s in ungrouped}


def test_annotate_adds_to_the_innermost_span_only():
    telemetry.reset()

    @telemetry.span("outer", fixed=1)
    def outer(i):
        with telemetry.span("inner"):
            telemetry.annotate(step=i)
        telemetry.annotate(chosen=i)

    outer(1)
    outer(2)
    telemetry.annotate(lost=True)        # outside any span: no effect
    recs = [(s.name, s.attrs) for s in telemetry.spans()]
    assert recs == [("inner", {"step": 1}), ("outer", {"fixed": 1,
                                                       "chosen": 1}),
                    ("inner", {"step": 2}), ("outer", {"fixed": 1,
                                                       "chosen": 2})]


def test_model_plans_record_their_side_and_prefuse(no_persistent_cache):
    """A fused plan opens one ``plan.prefuse`` span inside its
    ``plan.build``, with the head's shape and the partials' size; both
    sides record their choice and fact backend on ``plan.build``."""
    from repro.core.fusion import LinearOperator
    from repro.core.query import PREDICTION, Session
    from repro.data import table4_catalog

    cat = table4_catalog(sf=1, k=6, seed=5, fact_rows=500)
    sess = Session(cat)
    w = jax.numpy.ones((6, 3), jax.numpy.float32)
    q = (sess.query("fact")
         .join("dim_b", on=("fk_b", "pk"), features=("b0", "b1"))
         .join("dim_c", on=("fk_c", "pk"), features=("c0", "c1"))
         .join("dim_d", on=("fk_d", "pk"), features=("d0", "d1"))
         .predict(LinearOperator(w))
         .group_by(("dim_d", "year", 7), num_groups=7)
         .agg(s=("sum", PREDICTION)).build())
    for backend in ("fused", "nonfused"):
        telemetry.reset()
        sess.bind(q).compile(backend=backend)
        spans = telemetry.spans()
        build = [s for s in spans if s.name == "plan.build"]
        assert len(build) == 1
        assert build[0].attrs == {"backend": backend, "fact_backend": "jnp",
                                  "fact_rows": 500, "head": "linear",
                                  "k": 6, "l": 3, "p": 0}
        pre = [s for s in spans if s.name == "plan.prefuse"]
        if backend == "nonfused":
            assert pre == []
            continue
        rows = sum(cat[t].capacity for t in ("dim_b", "dim_c", "dim_d"))
        assert len(pre) == 1 and pre[0].parent == build[0].id
        assert pre[0].attrs == {"rows": rows, "bytes": rows * 3 * 4,
                                "head": "linear", "k": 6, "l": 3, "p": 0}


def test_nonfused_tree_plan_records_its_row_block(no_persistent_cache):
    """A nonfused tree plan whose fact program runs ``tree_predict``
    records on ``plan.build`` the row block of its fact-sized call, chosen
    from the shapes; the plan's answer matches the plain jnp side."""
    import numpy as np
    from repro.core.fusion import random_tree
    from repro.core.query import PREDICTION, Session
    from repro.data import table4_catalog
    from repro.kernels.tree_predict.kernel import row_block

    cat = table4_catalog(sf=1, k=6, seed=5, fact_rows=500)
    tree = random_tree(np.random.default_rng(3), 6, 3)
    sess = Session(cat, interpret=True)
    q = (sess.query("fact")
         .join("dim_b", on=("fk_b", "pk"), features=("b0", "b1"))
         .join("dim_c", on=("fk_c", "pk"), features=("c0", "c1"))
         .join("dim_d", on=("fk_d", "pk"), features=("d0", "d1"))
         .predict(tree)
         .group_by(("dim_d", "year", 7), num_groups=7)
         .agg(s=("sum", PREDICTION)).build())
    answers = {}
    for serve in ("pallas", "jnp"):
        telemetry.reset()
        plan = sess.bind(q).compile(backend="nonfused", serve_backend=serve)
        answers[serve] = np.asarray(plan.run()["s"])
        build = [s for s in telemetry.spans() if s.name == "plan.build"]
        assert len(build) == 1
        attrs = build[0].attrs
        assert attrs["fact_backend"] == serve
        if serve == "jnp":
            assert "block_rows" not in attrs
            continue
        n = attrs["fact_rows"]
        assert attrs["block_rows"] == row_block(n, tree.k, tree.p) == 256
    np.testing.assert_array_equal(answers["pallas"], answers["jnp"])
