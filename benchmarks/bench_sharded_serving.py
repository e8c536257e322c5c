"""Sharded vs single-device serving latency across mesh shapes.

One ``Session`` per mesh shape serves identical request batches through
the single-device fused runtime and the ``shard_map`` runtime (partials
row-sharded over the model axis, batches over the data axis), emitting
per-size medians plus each runtime's per-bucket percentiles — the scaling
counterpart of ``bench_serving``.

On CPU the mesh is forced with ``--devices N`` (sets
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before jax loads),
which measures the orchestration overhead of the sharded program — the
memory-capacity win it buys is per-device bytes
(``ShardedPrefusedPartials.nbytes_per_device``), also emitted.

Run:  PYTHONPATH=src python -m benchmarks.bench_sharded_serving
      [--devices 8] [--scale 0.05] [--k 16] [--l 4]
      [--json BENCH_sharded_serving.json]
"""

from __future__ import annotations

import argparse
import os
import sys


def run(mesh_shapes, scale: float, k: int, l: int, seed: int = 0):
    import numpy as np

    from repro.core.query import Session, make_serving_mesh

    from .common import bench, emit, random_requests, serving_star

    catalog, query, dim_rows = serving_star(k, l, scale, seed)
    base = Session(catalog).bind(query).serve(backend="fused")
    rng = np.random.default_rng(seed + 1)
    buckets = base.buckets
    sizes = sorted({max(1, b // 2) for b in buckets} | set(buckets))
    sizes.append(2 * buckets[-1] + 3)   # oversize: top-bucket chunks
    requests = {n: random_requests(query, dim_rows, n, rng) for n in sizes}

    for n in sizes:
        us = bench(base.serve, requests[n])
        emit(f"sharded_serving/mesh1x1ref/n{n}", us, "single-device fused")

    runtimes = {}
    for shape in mesh_shapes:
        sess = Session(catalog, mesh=make_serving_mesh(shape),
                       shard_threshold_bytes=0)
        rt = runtimes[shape] = sess.bind(query).serve(backend="fused")
        tag = f"mesh{shape[0]}x{shape[1]}"
        for n in sizes:
            us = bench(rt.serve, requests[n])
            # Identical math: the sharded runtime must match the reference.
            np.testing.assert_array_equal(
                np.asarray(rt.serve(requests[n])),
                np.asarray(base.serve(requests[n])))
            emit(f"sharded_serving/{tag}/n{n}", us,
                 f"sharded={rt.sharded.num_sharded}/{len(rt.sharded.arms)}"
                 f";buckets={rt.buckets}")
        emit(f"sharded_serving/{tag}/bytes_per_device",
             float(rt.sharded.nbytes_per_device()),
             "quasi-static bytes resident per device")
        emit(f"sharded_serving/{tag}/compiles", float(rt.num_compiles),
             f"traces for {len(sizes)} batch sizes")
    return base, runtimes


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (must be set before jax "
                         "initializes — this flag handles it)")
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--l", type=int, default=4)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    if args.devices:
        if "jax" in sys.modules:
            raise RuntimeError("--devices must be applied before jax loads")
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")
    import jax

    n = len(jax.devices())
    shapes = [(1, n)]
    if n > 1:
        shapes += [(n, 1)]
        half = n // 2
        if half > 1:
            shapes += [(2, half)]
    base, runtimes = run(shapes, args.scale, args.k, args.l)
    if args.json:
        from .common import write_json

        latency = {"ref": base.latency_stats()}
        for shape, rt in runtimes.items():
            latency[f"mesh{shape[0]}x{shape[1]}"] = rt.latency_stats()
        write_json(args.json, {"bench": "sharded_serving",
                               "devices": n, "latency": latency})


if __name__ == "__main__":
    from repro.compile_cache import init_compile_cache
    init_compile_cache()
    main()
