"""The readers of the program's spans and compile counters, on each cell at
a tiny scale factor, and on a program that records no spans."""
import json
import sys

import pytest

from chipbench import run as R
from chipbench.tests.conftest import ROOT, SF

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NEW = ("plan_build_s", "program_load_s", "programs_loaded",
       "query_host_us.latency")
SEED = 2 ** 33 + 29


def _metrics(cell):
    return [m["name"] for m in R.Cell(cell).per_layer if m["name"] in NEW]


@pytest.fixture(scope="module")
def runs():
    """Each cell run once, with the spans it recorded."""
    from repro import telemetry
    out = {}
    for cell in CELLS:
        telemetry.reset()
        _, run = R.execute(R.Cell(cell), SEED, 1.0, False,
                           require_tpu=False, sf=SF)
        out[cell] = (run, telemetry.spans(),
                     {m: R.reader(m)(run) for m in _metrics(cell)})
    return out


def test_every_new_metric_is_read_in_some_cell():
    assert {m for c in CELLS for m in _metrics(c)} == set(NEW)


@pytest.mark.parametrize("cell", CELLS)
def test_readers_give_positive_numbers(cell, runs):
    _, _, values = runs[cell]
    assert values and all(v is not None and v > 0 for v in values.values())


@pytest.mark.parametrize("cell", CELLS)
def test_plan_build_is_within_the_compile_spans(cell, runs):
    run, spans, values = runs[cell]
    build = [s for s in spans if s.name == "plan.build"]
    assert len(build) == len(run.completed)     # one plan a query of the mix
    assert values["plan_build_s"] <= run.compile_s


def test_query_host_us_reads_the_window_roots(runs):
    cell = "flight1.power"
    run, spans, values = runs[cell]
    roots = [s for s in spans if s.name == "query" and s.parent is None]
    n = sum(run.completed.values())
    # Set-up calls each query twice (its build, then the plan-cache path);
    # every other root is the window's.
    assert len(roots) == 2 * len(run.completed) + n
    window = roots[-n:]
    # Each window root lies inside the answer it belongs to.
    assert all(1e3 * (s.end - s.start) <= ms
               for s, ms in zip(window, run.latency_ms))
    assert values["query_host_us.latency"] == pytest.approx(
        1e6 * sum(s.end - s.start for s in window) / n)


@pytest.mark.parametrize("cell", CELLS)
def test_readers_return_none_without_the_span_module(cell, runs,
                                                     monkeypatch):
    run, _, _ = runs[cell]
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    for m in _metrics(cell):
        assert R.reader(m)(run) is None
