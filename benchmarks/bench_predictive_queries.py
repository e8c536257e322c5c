"""Fused vs non-fused end-to-end latency of compiled predictive queries.

Runs representative SSB shapes through the ``Session`` query-builder — QG1
(1 join + scalar sum), QG2 (3 joins + group-by-sum) — plus the
predict-then-aggregate variants (P1 linear head, P3 GEMM tree head), each
compiled twice: the fused plan (prefused partials, gathers + segment ops)
and the non-fused reference (materialize T, model matmul).  The ratio is
the paper's §3 speedup measured on the *whole* query, aggregation included.

The ``multiagg`` rows execute one fused program computing several named
aggregates (sum + mean + count over shared join/model work) on both
aggregation backends — the multi-aggregate lowering's cost trajectory,
gated by the CI bench-regression job like every other row.

Run:  PYTHONPATH=src python -m benchmarks.bench_predictive_queries
      [--sf 1.0] [--scale 0.003] [--json BENCH_predictive_queries.json]
"""
from __future__ import annotations

import argparse

from repro.core.query import PREDICTION
from repro.data import QUERY_IR, generate_ssb, ssb_session

from .common import bench, emit, write_json

SCALE = 0.003   # shrink factor vs true SSB (CPU-sized)

#: QG1 shape (1 join, scalar), QG2 shape (3 joins, group-by), and their
#: model-headed counterparts (P2 = QG1 shape, P1/P3 = QG2 shape).
SHAPES = ["Q1.1", "Q2.1", "P2.linear.select.scalar", "P1.linear.year",
          "P3.tree.year"]

#: Shapes re-run with a multi-aggregate head: one compiled program, several
#: named aggregates (relational sum+mean+count, and mean/count over the
#: model's prediction matrix).
MULTI_AGG = ["Q2.1", "P1.linear.year"]


def _multiagg_builder(sess, name):
    b = sess.bind(QUERY_IR[name]())
    if b.model is not None:
        return b.agg(pred_mean=("mean", PREDICTION), n="count")
    return b.agg(rev_mean="mean(lo_revenue)", rev_max="max(lo_revenue)",
                 n="count")


def run(sf: float = 1.0, scale: float = SCALE):
    data = generate_ssb(sf=sf, scale=scale, seed=0)
    sess = ssb_session(data)
    for name in SHAPES:
        b = sess.bind(QUERY_IR[name]())
        fused = b.compile(backend="fused")
        us_fused = bench(fused.run)
        emit(f"predictive/{name}/fused", us_fused,
             f"rows={int(fused.run()['rows'])};"
             f"measured_sel={fused.selectivity:.3f};{fused.plan.reason}")
        if b.model is not None:
            non = b.compile(backend="nonfused")
            us_non = bench(non.run)
            emit(f"predictive/{name}/nonfused", us_non,
                 f"speedup={us_non / max(us_fused, 1e-9):.2f}x")
        matmul = b.compile(backend="fused", agg_backend="matmul")
        emit(f"predictive/{name}/agg_matmul", bench(matmul.run),
             "Fig.4 one-hot matmul aggregation")
    for name in MULTI_AGG:
        mb = _multiagg_builder(sess, name)
        n_aggs = len(mb.build().aggregates)
        for agg_backend in ("segment", "matmul"):
            compiled = mb.compile(backend="fused", agg_backend=agg_backend)
            emit(f"predictive/{name}/multiagg_{agg_backend}",
                 bench(compiled.run),
                 f"{n_aggs} named aggregates, one fused program")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=1.0)
    ap.add_argument("--scale", type=float, default=SCALE,
                    help="shrink factor vs true SSB (CI smoke uses ~0.001)")
    ap.add_argument("--json", default=None,
                    help="write rows to this JSON artifact path")
    args = ap.parse_args()
    run(sf=args.sf, scale=args.scale)
    if args.json:
        write_json(args.json, {"bench": "predictive_queries",
                               "sf": args.sf, "scale": args.scale})


if __name__ == "__main__":
    from repro.compile_cache import init_compile_cache
    init_compile_cache()
    main()
