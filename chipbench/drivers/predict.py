"""The ``predict`` traffic kind: the paper's predictive pipelines, run as a
power test.

One client, closed loop.  Each pass runs the mix's queries once, in an
order shuffled from the seed, through ``Session.bind(q).run()``, blocking
on each answer, and each answer's latency is timed on the host from the
call to the answer.  A query joins every fact row of the star to its three
dimensions, scores it with the query's model head (linear: the sum of its
predictions per year; tree: its leaf histogram per year), as the
configuration's ``queries`` give it.  The window is whole passes: it closes
at the end of the first pass that ends ``run.seconds`` or more after it
opened.  After the window every answer is checked against the float64
reference of :mod:`chipbench.reference_predict`, each query's reference
computed once.

Each plan's ``plan.build`` span attributes (its fused/nonfused side, fact
backend and head shape, where the program records them) are kept in
``run.plans`` for the metrics, and each plan's ``explain()`` line goes to
standard error.
"""
from __future__ import annotations

import importlib
import sys
import time
from typing import Dict, List, Tuple

import numpy as np

from chipbench import check, program, reference_predict, stargen


def _last_build_attrs() -> dict:
    """The attributes of the program's newest ``plan.build`` span; empty
    where the program records none."""
    try:
        telemetry = importlib.import_module("repro.telemetry")
    except ImportError:
        return {}
    builds = [s for s in telemetry.spans() if s.name == "plan.build"]
    return dict(builds[-1].attrs) if builds else {}


def program_query(name: str, spec: dict, seed: int):
    """A configuration query as the program's query IR, its head built
    with the program's own constructors."""
    import jax.numpy as jnp
    from repro.core.fusion import LinearOperator
    from repro.core.fusion.operators import tree_from_arrays
    from repro.core.query import PREDICTION, query

    h = spec["head"]
    w = stargen.head(h, seed)
    k = int(h["k"])
    model = (LinearOperator(jnp.asarray(w["L"])) if h["kind"] == "linear"
             else tree_from_arrays(w["feature"], w["threshold"], k))
    b = query(stargen.FACT)
    for (table, pk, fk, _), cols in zip(stargen.DIMS,
                                        stargen.head_features(k)):
        b = b.join(table, on=(fk, pk), features=cols)
    return (b.predict(model)
            .group_by(("date", "year", stargen.YEARS),
                      num_groups=stargen.YEARS)
            .agg(**{reference_predict.agg_name(name): ("sum", PREDICTION)})
            .build())


def drive(run) -> int:
    import jax
    from repro.core.laq import Catalog
    from repro.core.query import Session

    with run.span("data"):
        cols = stargen.generate(run.sf, run.config["features"], run.seed)
        sess = Session(Catalog(stargen.to_program_tables(cols)))
    specs = run.config["queries"]
    names = list(run.traffic["queries"])
    qs = {n: program_query(n, specs[n], run.seed) for n in names}
    run.plans = {}
    for n in names:     # plan, offline build, compile, first answer
        with run.span(f"compile.{n}", compile_span=True):
            jax.block_until_ready(sess.bind(qs[n]).run())
        a = run.plans[n] = _last_build_attrs()
        print(f"plan {n} ({a.get('backend')}, fact {a.get('fact_backend')})"
              f": {sess.bind(qs[n]).explain()}", file=sys.stderr)
    for n in names:     # the plan-cache path a pass takes
        jax.block_until_ready(sess.bind(qs[n]).run())

    rng = np.random.default_rng(run.seed)
    results: List[Tuple[str, Dict]] = []
    with run.window() as t0:
        end = t0 + run.seconds
        while time.perf_counter() < end:
            for n in rng.permutation(names):
                a = time.perf_counter()
                with run.span(f"query.{n}"):
                    res = jax.block_until_ready(sess.bind(qs[n]).run())
                run.latency_ms.append(1e3 * (time.perf_counter() - a))
                results.append((str(n), res))
    peak = program.peak_bytes(run)
    run.attempted = len(results)
    for n, _ in results:
        run.completed[n] = run.completed.get(n, 0) + 1

    results = [(n, jax.device_get(res)) for n, res in results]
    del sess, qs
    tables = reference_predict.stored(cols)
    del cols
    t = run.tally = check.Tally(run.cell.limits)
    r0 = time.perf_counter()
    with run.span("reference"):
        for n in names:
            want = reference_predict.answer(
                tables, reference_predict.agg_name(n), specs[n], run.seed)
            for m, res in results:
                if m == n:
                    check.check_answer(t, n, {"group_by": ["year"]}, res,
                                       want)
    run.reference_s = time.perf_counter() - r0
    return peak
