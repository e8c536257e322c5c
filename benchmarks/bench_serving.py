"""Dynamic-batch serving latency across padding buckets and backends.

One serving runtime per backend (``Session.bind(q).serve``) serves a ragged
sweep of request batch sizes; every size lands in one of the fixed padding
buckets, so the steady state never recompiles.  Emits per-size medians plus
the runtime's own per-bucket percentiles — the serving-side counterpart of
``bench_predictive_queries`` (which measures whole-query aggregation).

Run:  PYTHONPATH=src python -m benchmarks.bench_serving
      [--scale 0.05] [--k 16] [--l 4] [--json BENCH_serving.json]
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.core.query import Session

from .common import bench, emit, random_requests, serving_star, write_json


def run(
    scale: float = 0.05,
    k: int = 16,
    l: int = 4,
    serve_backend: str = "auto",
    interpret: bool = False,
    seed: int = 0,
):
    catalog, query, dim_rows = serving_star(k, l, scale, seed)
    builder = Session(catalog, interpret=interpret).bind(query)
    runtimes = {
        name: builder.serve(backend=name, serve_backend=serve_backend)
        for name in ("fused", "nonfused")
    }
    rng = np.random.default_rng(seed + 1)
    buckets = runtimes["fused"].buckets
    sizes = sorted({max(1, b // 2) for b in buckets} | set(buckets))
    sizes.append(2 * buckets[-1] + 3)  # oversize: served in top-bucket chunks
    for name, runtime in runtimes.items():
        for n in sizes:
            reqs = random_requests(query, dim_rows, n, rng)
            us = bench(runtime.serve, reqs)
            emit(
                f"serving/{name}/n{n}",
                us,
                f"buckets={buckets};serve_backend={runtime.serve_backend}",
            )
        emit(
            f"serving/{name}/compiles",
            float(runtime.num_compiles),
            f"traces for {len(sizes)} batch sizes",
        )
    return runtimes


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--l", type=int, default=4)
    ap.add_argument("--serve-backend", default="auto")
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    runtimes = run(
        scale=args.scale,
        k=args.k,
        l=args.l,
        serve_backend=args.serve_backend,
        interpret=args.interpret,
    )
    if args.json:
        latency = {name: rt.latency_stats() for name, rt in runtimes.items()}
        write_json(args.json, {"bench": "serving", "latency": latency})


if __name__ == "__main__":
    from repro.compile_cache import init_compile_cache
    init_compile_cache()
    main()
