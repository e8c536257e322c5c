"""Grouped aggregation over the live rows only.

A selective, model-free grouped plan keeps its live rows and their group
ids (``plan.groups``) and its program aggregates over those rows, gathered
from the resident fact on every call.  Its answers equal the masked
lowering's — bit for bit on the segment backend — for every aggregate op;
unselective, ungrouped, predictive and streamed plans keep the masked
lowering; a fact append whose live rows fit the row list refreshes by
delta with no retrace, and one that outgrows it recompiles with a named
reason.
"""
import dataclasses

import numpy as np
import pytest
from numpy.testing import assert_allclose, assert_array_equal

from repro import telemetry
from repro.core.laq import Catalog, Table
from repro.core.laq.selection import Pred
from repro.core.query import (PLANNER_THRESHOLDS, Aggregate, ArmSpec,
                              GroupKey, PredictiveQuery, compile_query)
from repro.data import QUERY_IR, generate_ssb, ssb_catalog

GROUPED = sorted(n for n in QUERY_IR
                 if n.startswith("Q") and QUERY_IR[n]().group_keys)


@pytest.fixture(scope="module")
def catalog():
    return ssb_catalog(generate_ssb(sf=1, scale=0.001, seed=3))


@pytest.fixture
def masked(monkeypatch):
    """Compile with the masked lowering whatever the selectivity."""
    def compile_masked(cat, q, **kw):
        with monkeypatch.context() as m:
            m.setitem(PLANNER_THRESHOLDS["default"],
                      "COMPACT_AGG_MAX_SELECTIVITY", -1.0)
            return compile_query(cat, q, **kw)
    return compile_masked


def _all_ops(q):
    v = q.aggregates[0].value
    return dataclasses.replace(q, aggregates=(
        Aggregate(v, "sum", "s"), Aggregate("*", "count", "n"),
        Aggregate(v, "min", "lo"), Aggregate(v, "max", "hi"),
        Aggregate(v, "mean", "m")))


def _compact_spans(fn):
    telemetry.reset()
    out = fn()
    return out, [s for s in telemetry.spans() if s.name == "plan.compact"]


@pytest.mark.parametrize("agg_backend", ["segment", "matmul"])
@pytest.mark.parametrize("name", GROUPED)
def test_compacted_matches_masked(catalog, masked, name, agg_backend):
    q = _all_ops(QUERY_IR[name]())
    cq, spans = _compact_spans(
        lambda: compile_query(catalog, q, agg_backend=agg_backend))
    mq = masked(catalog, q, agg_backend=agg_backend)
    assert cq._state["rows"] is not None and cq._state["gid"] is None
    assert mq._state["rows"] is None and mq._state["gid"] is not None
    n = int(cq.run()["rows"])
    assert [s.attrs for s in spans] == [
        {"rows": n, "capacity": cq._state["rows"].shape[0]}]
    assert f"agg=compact(rows={n}, cap=" in str(cq.explain())
    assert "agg=masked(sel " in str(mq.explain())
    got, want = cq.run(), mq.run()
    assert set(got) == set(want)
    for k in want:
        if agg_backend == "segment" or k in ("lo", "hi", "n", "groups",
                                               "rows"):
            assert_array_equal(np.asarray(got[k]), np.asarray(want[k]),
                               err_msg=k)
        else:     # one-hot matmul: same terms, another summation order
            assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                            rtol=1e-6, atol=1e-6, err_msg=k)


def _unselective(q):
    return dataclasses.replace(
        q, fact_preds=(), arms=tuple(dataclasses.replace(a, preds=())
                                     for a in q.arms))


@pytest.mark.parametrize("case, build, kw, note", [
    ("ungrouped", lambda: QUERY_IR["Q1.1"](), {}, None),
    ("unselective", lambda: _unselective(QUERY_IR["Q2.1"]()), {},
     "agg=masked(sel "),
    ("model head", lambda: QUERY_IR["P1.linear.year"](), {},
     "agg=masked(model head)"),
    ("streamed", lambda: QUERY_IR["Q2.1"](), {"stream_chunk_rows": 1024},
     "agg=masked(streamed)"),
])
def test_stays_masked(catalog, case, build, kw, note):
    cq, spans = _compact_spans(lambda: compile_query(catalog, build(), **kw))
    assert spans == []
    assert cq._state["rows"] is None and cq._state["gid_live"] is None
    reason = str(cq.explain())
    if note is None:
        assert "agg=compact" not in reason and "agg=masked" not in reason
    else:
        assert note in reason


# ----------------------------------------------------------------- refresh
def _star(n_fact=3000, capacity=8192, seed=0) -> Catalog:
    rng = np.random.default_rng(seed)
    d = {"pk": np.arange(50), "g": rng.integers(0, 8, 50)}
    f = {"fk": rng.integers(0, 50, n_fact), "val": rng.normal(size=n_fact)}
    return Catalog({
        "d": Table.from_columns("d", d, key_cols=("pk", "g"), capacity=64),
        "fact": Table.from_columns("fact", f, key_cols=("fk",),
                                   capacity=capacity)})


STAR_Q = PredictiveQuery(
    fact="fact", arms=(ArmSpec("d", "fk", "pk"),),
    fact_preds=(Pred("val", ">", 1.7),),
    group_keys=(GroupKey("d", "g", 8),),
    aggregates=(Aggregate("val", "sum", "s"), Aggregate("*", "count", "n"),
                Aggregate("val", "min", "lo"), Aggregate("val", "max", "hi"),
                Aggregate("val", "mean", "m")),
    num_groups=8)


def _live_rows(m):
    return {"fk": np.arange(m) % 50, "val": np.full(m, 2.0)}


def _assert_same(a, b):
    assert set(a) == set(b)
    for k in a:
        assert_array_equal(np.asarray(a[k]), np.asarray(b[k]), err_msg=k)


@pytest.mark.parametrize("appended, line", [
    (10, "refresh=delta(fact+1"),
    (1000, "refresh=recompile(compact-overflow: "),
])
def test_refresh_of_a_compacted_plan(appended, line):
    cat = _star()
    cq = compile_query(cat, STAR_Q)
    cap = cq._state["rows"].shape[0]
    n0 = int(cq.run()["rows"])
    assert n0 <= cap < n0 + 1000
    cat.append("fact", _live_rows(appended))
    assert cq.refresh().startswith(line)
    telemetry.reset()
    got = cq.run()
    programs = sum(s.programs for s in telemetry.spans())
    cold = compile_query(cat, STAR_Q)
    _assert_same(got, cold.run())
    assert int(got["rows"]) == n0 + appended
    if appended <= cap - n0:
        # same row-list shape: the compiled program served the refresh
        assert cq._state["rows"].shape == (cap,)
        assert programs == 0
    else:
        assert "compact-overflow" in str(cq.explain())
