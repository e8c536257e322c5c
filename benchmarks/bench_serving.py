"""Dynamic-batch serving latency across padding buckets and backends.

One ``compile_serving`` plan per backend serves a ragged sweep of request
batch sizes; every size lands in one of the fixed padding buckets, so the
steady state never recompiles.  Emits per-size medians plus the runtime's
own per-bucket percentiles — the serving-side counterpart of
``bench_predictive_queries`` (which measures whole-query aggregation).

Run:  PYTHONPATH=src python -m benchmarks.bench_serving
      [--scale 0.05] [--k 16] [--l 4] [--json BENCH_serving.json]
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.launch.serve import FusedFeatureServer

from .common import bench, emit, write_json


def run(
    scale: float = 0.05,
    k: int = 16,
    l: int = 4,
    serve_backend: str = "auto",
    interpret: bool = False,
    seed: int = 0,
):
    server = FusedFeatureServer(
        setting=2,
        sf=1,
        k=k,
        l=l,
        scale=scale,
        seed=seed,
        serve_backend=serve_backend,
        interpret=interpret,
    )
    rng = np.random.default_rng(seed + 1)
    buckets = server.runtime_fused.buckets
    sizes = sorted({max(1, b // 2) for b in buckets} | set(buckets))
    sizes.append(2 * buckets[-1] + 3)  # oversize: served in top-bucket chunks
    for fused in (True, False):
        name = "fused" if fused else "nonfused"
        runtime = server.runtime(fused)
        for n in sizes:
            reqs = server.random_requests(n, rng)
            us = bench(server.serve_batch, reqs, fused)
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
    return server


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--l", type=int, default=4)
    ap.add_argument("--serve-backend", default="auto")
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    server = run(
        scale=args.scale,
        k=args.k,
        l=args.l,
        serve_backend=args.serve_backend,
        interpret=args.interpret,
    )
    if args.json:
        latency = {
            "fused": server.runtime_fused.latency_stats(),
            "nonfused": server.runtime_nonfused.latency_stats(),
        }
        write_json(args.json, {"bench": "serving", "latency": latency})


if __name__ == "__main__":
    from repro.compile_cache import init_compile_cache
    init_compile_cache()
    main()
