"""The ``predict.power`` cell: its files found by name, its reference
against the program's, the faults its comparison must catch, its control,
and the readers of its new metrics."""
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import check, reference_predict, stargen, trace
from chipbench import run as R
from chipbench.tests.conftest import SF

CELL = "predict.power"
SEED = 2 ** 33 + 15        # wider than 32 bits, as a run's seed may be
SAMPLE = Path(__file__).with_name("data") / "predict_power_tpu_v5e.xplane.pb"


def _execute(seconds=0.5):
    return R.execute(R.Cell(CELL), SEED, seconds, False, require_tpu=False,
                     sf=SF)


def test_files_are_found_by_name():
    cell = R.Cell(CELL)
    assert cell.config["name"] == "ssb_predictive"
    assert cell.traffic["kind"] == "predict"
    assert callable(R.driver("predict"))
    assert set(cell.limits) == set(check.NUMBERS)
    assert set(cell.traffic["queries"]) == set(cell.config["queries"])
    assert {m["name"] for m in cell.end_to_end} == {"queries_per_s",
                                                    "setup_s"}
    new = {"query_device_ms.predict", "device_idle_share.predict",
           "prefuse_s", "tree_predict_roofline.predict"}
    assert new <= {m["name"] for m in cell.per_layer}
    for m in new:
        assert callable(R.reader(m))


def test_generator_matches_the_programs():
    """One seed gives the program's Table 4 star (sf 0.01: 6,000 fact
    rows, no table at its floor of 8 rows)."""
    from repro.data import table4_catalog
    mine = stargen.generate(0.01, 256, 7)
    theirs = table4_catalog(sf=0.01, k=256, seed=7)
    names = {"lineorder": "fact", "part": "dim_b", "supplier": "dim_c",
             "date": "dim_d"}
    renamed = {"lo_partkey": "fk_b", "lo_suppkey": "fk_c",
               "lo_orderdate": "fk_d", "partkey": "pk", "suppkey": "pk",
               "datekey": "pk"}
    for table, cols in mine.items():
        t = theirs[names[table]]
        m = np.asarray(t.matrix)
        for c, v in cols.items():
            if c in renamed or c == "year":
                col = renamed.get(c, c)
            else:           # a feature: p<j>, s<j>, d<j> there b, c, d
                col = "bcd"["psd".index(c[0])] + c[1:]
            np.testing.assert_array_equal(m[:, t.col_index(col)],
                                          v.astype(np.float32))


@pytest.mark.parametrize("query", ["PL.k128.l2", "PL.k128.l128",
                                   "PT.k256.d7", "PT.k16.d7"])
def test_reference_matches_the_programs_oracle(query):
    """Each query through ``repro``'s brute-force float64 oracle."""
    from repro.core.laq import Catalog
    from repro.core.query.workload import np_oracle

    from chipbench.drivers.predict import program_query
    cols = stargen.generate(SF, 256, 3)
    spec = R.Cell(CELL).config["queries"][query]
    agg = reference_predict.agg_name(query)
    mine = reference_predict.answer(reference_predict.stored(cols), agg,
                                    spec, 3)
    theirs = np_oracle(dict(Catalog(stargen.to_program_tables(cols))),
                       program_query(query, spec, 3))
    assert mine["rows"] == theirs["rows"]
    assert set(mine["groups"]) == set(theirs["groups"])
    for code, aggs in theirs["groups"].items():
        np.testing.assert_allclose(mine["groups"][code][agg][0],
                                   np.ravel(aggs[agg]), rtol=1e-9,
                                   atol=1e-12)


def test_program_agrees_with_the_reference():
    res, run = _execute()
    assert res["correct"], run.tally.notes
    assert run.tally.compared["exact_mismatch"] > 0
    assert run.tally.compared["agg_rel_err"] > 0
    assert set(run.plans) == set(R.Cell(CELL).traffic["queries"])
    assert {a["backend"] for a in run.plans.values()} == {"fused",
                                                          "nonfused"}


def _alter(monkeypatch, fn):
    import jax.numpy as jnp
    from repro.core.query.compile import CompiledQuery

    run_orig = CompiledQuery.run

    def run(self):
        out = dict(run_orig(self))
        for name in ("leaves", "score"):
            if name in out:
                out[name] = jnp.asarray(fn(name, np.array(out[name])))
        return out

    monkeypatch.setattr(CompiledQuery, "run", run)


def test_an_altered_leaf_count_is_an_exact_mismatch(monkeypatch):
    def fn(name, a):
        if name == "leaves":
            a[0, int(np.argmax(a[0]))] += 1
        return a
    _alter(monkeypatch, fn)
    res, run = _execute()
    assert not res["correct"]
    assert run.tally.values["exact_mismatch"] > 0
    assert run.tally.values["agg_rel_err"] <= run.tally.limits["agg_rel_err"]


def test_a_linear_sum_altered_by_a_thousandth_is_caught(monkeypatch):
    def fn(name, a):
        if name == "score":
            a.reshape(-1)[np.argmax(np.abs(a.reshape(-1)))] *= 1.001
        return a
    _alter(monkeypatch, fn)
    res, run = _execute()
    assert not res["correct"]
    assert run.tally.values["exact_mismatch"] == 0
    assert run.tally.values["agg_rel_err"] > run.tally.limits["agg_rel_err"]


def test_the_bfloat16_control_is_not_correct():
    for seed in (1, 2, 3):
        t = reference_predict.readings(R.Cell(CELL), seed, sf=SF)
        assert not t.correct, t.lines()
        assert t.values["exact_mismatch"] > 0
        assert t.values["agg_rel_err"] > t.limits["agg_rel_err"]


def test_prefuse_s_reads_the_prefuse_spans():
    from repro import telemetry
    telemetry.reset()
    _, run = _execute()
    fused = sum(a["backend"] == "fused" for a in run.plans.values())
    spans = [s for s in telemetry.spans() if s.name == "plan.prefuse"]
    assert len(spans) == fused > 0
    assert R.reader("prefuse_s")(run) == pytest.approx(
        sum(s.end - s.start for s in spans))
    assert all(s.attrs["bytes"] == s.attrs["rows"] * s.attrs["l"] * 4
               for s in spans)


def test_prefuse_s_is_none_without_prefuse_spans(monkeypatch):
    """A program that records no ``plan.prefuse`` span (as before the
    span existed), or no spans at all."""
    from repro import telemetry
    telemetry.reset()
    run = R.Run(R.Cell(CELL), 1, 1.0, False)
    with telemetry.span("plan.build"):
        pass
    assert R.reader("prefuse_s")(run) is None
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    assert R.reader("prefuse_s")(run) is None


#: The queries of the committed v5e trace that ran ``tree_predict``
#: nonfused over SSB SF1's 6M rows: P3's depth-3 tree over 5 features and
#: P4's depth-2 tree over 3.
TRACED_TREES = {"P3.tree.year": (5, 3), "P4.tree.select.region": (3, 2)}


def _traced_run(plans):
    tr = trace.load(str(SAMPLE))
    run = R.Run(R.Cell(CELL), 1, 1.0, True)
    run.summary = trace.summarize(tr)
    run.device = type("Device", (), {"device_kind": "TPU v5 lite"})()
    win = next(s for s in tr.host_spans if s.name == trace.WINDOW_SPAN)
    run.completed = {q: sum(
        1 for s in tr.host_spans if s.name == "chipbench.query." + q
        and win.start_ns <= s.start_ns < win.end_ns) for q in TRACED_TREES}
    run.plans = plans
    return run


def test_roofline_share_on_a_chip_trace():
    plans = {q: {"head": "tree", "backend": "nonfused",
                 "fact_backend": "pallas", "fact_rows": 6_000_000, "k": k,
                 "p": 2 ** d - 1, "l": 2 ** d}
             for q, (k, d) in TRACED_TREES.items()}
    run = _traced_run(plans)
    assert all(run.completed.values())
    share = R.reader("tree_predict_roofline.predict")(run)
    assert 0 < share <= 100


@pytest.mark.parametrize("plans", [
    {}, {"P3.tree.year": {}},
    {"P3.tree.year": {"head": "tree", "backend": "fused",
                      "fact_backend": "jnp", "fact_rows": 6_000_000,
                      "k": 5, "p": 7, "l": 8}}],
    ids=["no-plans", "no-attrs", "no-kernel-plan"])
def test_roofline_share_is_none_without_a_kernel_plan(plans):
    assert R.reader("tree_predict_roofline.predict")(
        _traced_run(plans)) is None
