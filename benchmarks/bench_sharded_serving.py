"""Sharded vs single-device serving latency across mesh shapes.

One ``FusedFeatureServer`` per mesh shape serves identical request batches
through the single-device runtime and the ``shard_map`` runtime (partials
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

    from repro.launch.mesh import make_serving_mesh
    from repro.launch.serve import FusedFeatureServer

    from .common import bench, emit

    base = FusedFeatureServer(setting=2, sf=1, k=k, l=l, scale=scale,
                              seed=seed)
    rng = np.random.default_rng(seed + 1)
    buckets = base.runtime_fused.buckets
    sizes = sorted({max(1, b // 2) for b in buckets} | set(buckets))
    sizes.append(2 * buckets[-1] + 3)   # oversize: top-bucket chunks
    requests = {n: base.random_requests(n, rng) for n in sizes}

    for n in sizes:
        us = bench(base.serve_batch, requests[n], True)
        emit(f"sharded_serving/mesh1x1ref/n{n}", us, "single-device fused")

    servers = {}
    for shape in mesh_shapes:
        mesh = make_serving_mesh(shape)
        server = FusedFeatureServer(setting=2, sf=1, k=k, l=l, scale=scale,
                                    seed=seed, mesh=mesh,
                                    shard_threshold_bytes=0)
        servers[shape] = server
        rt = server.runtime_fused
        tag = f"mesh{shape[0]}x{shape[1]}"
        for n in sizes:
            us = bench(server.serve_batch, requests[n], True)
            # Identical math: the sharded runtime must match the reference.
            np.testing.assert_array_equal(
                np.asarray(server.serve_batch(requests[n], True)),
                np.asarray(base.serve_batch(requests[n], True)))
            emit(f"sharded_serving/{tag}/n{n}", us,
                 f"sharded={rt.sharded.num_sharded}/{len(rt.sharded.arms)}"
                 f";buckets={rt.buckets}")
        emit(f"sharded_serving/{tag}/bytes_per_device",
             float(rt.sharded.nbytes_per_device()),
             "quasi-static bytes resident per device")
        emit(f"sharded_serving/{tag}/compiles", float(rt.num_compiles),
             f"traces for {len(sizes)} batch sizes")
    return base, servers


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
    base, servers = run(shapes, args.scale, args.k, args.l)
    if args.json:
        from .common import write_json

        latency = {"ref": base.runtime_fused.latency_stats()}
        for shape, server in servers.items():
            latency[f"mesh{shape[0]}x{shape[1]}"] = (
                server.runtime_fused.latency_stats())
        write_json(args.json, {"bench": "sharded_serving",
                               "devices": n, "latency": latency})


if __name__ == "__main__":
    from repro.compile_cache import init_compile_cache
    init_compile_cache()
    main()
