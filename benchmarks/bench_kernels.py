"""Kernel-level microbenchmarks: Pallas primitives vs jnp references.

Pallas interpret mode is a correctness vehicle, not a perf vehicle, so on
CPU the timed engine is the jnp reference path; the Pallas kernels are
asserted equal first (shape sweep) and their VMEM working sets reported
(derived column) — the quantity that matters for TPU block-shape choice.
"""
from __future__ import annotations

import jax
import numpy as np
import jax.numpy as jnp

from repro.kernels import (fused_star_gather, fused_star_gather_ref,
                           onehot_matmul, onehot_matmul_ref, tree_predict,
                           tree_predict_ref)
from repro.core.fusion import random_tree

from .common import bench, emit


def run():
    rng = np.random.default_rng(0)
    # onehot_matmul (join-as-matmul / MoE dispatch)
    for n, r, d in ((1024, 4096, 256), (8192, 16384, 512)):
        idx = jnp.asarray(rng.integers(0, r, n), jnp.int32)
        tbl = jnp.asarray(rng.normal(size=(r, d)), jnp.float32)
        got = onehot_matmul(idx[:128], tbl, block_n=8, block_r=128,
                            block_d=128, interpret=True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(onehot_matmul_ref(idx[:128],
                                                                tbl)),
                                   rtol=1e-5)
        fn = jax.jit(lambda i, t: onehot_matmul_ref(i, t))
        us = bench(fn, idx, tbl)
        vmem_kb = (128 * 512 + 512 * 128 + 128 * 128) * 4 / 1024
        emit(f"kernels/onehot_matmul/n{n}_r{r}_d{d}", us,
             f"vmem_tile={vmem_kb:.0f}KiB")

    # fused_star_gather (serve-time fused pipeline)
    for n, l in ((4096, 64), (16384, 256)):
        tables = [jnp.asarray(rng.normal(size=(r, l)), jnp.float32)
                  for r in (2048, 2048, 512)]
        ptrs = jnp.asarray(np.stack(
            [rng.integers(0, t.shape[0], n) for t in tables]), jnp.int32)
        found = jnp.ones((3, n), jnp.int32)
        got = fused_star_gather(ptrs[:, :64], found[:, :64], tables,
                                interpret=True)
        np.testing.assert_allclose(
            np.asarray(got),
            np.asarray(fused_star_gather_ref(ptrs[:, :64], found[:, :64],
                                             tables)), rtol=1e-5)
        fn = jax.jit(lambda p, f: fused_star_gather_ref(p, f, tables))
        us = bench(fn, ptrs, found)
        emit(f"kernels/fused_star_gather/n{n}_l{l}", us,
             f"row_dma={(3 + 1) * l * 4}B/step")

    # tree_predict (fused GEMM tree inference)
    for n, k, depth in ((4096, 128, 6), (16384, 256, 8)):
        tree = random_tree(rng, k, depth)
        x = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
        got = tree_predict(x[:128], tree.F, tree.v, tree.H, tree.h,
                           block_n=8, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(got),
            np.asarray(tree_predict_ref(x[:128], tree.F, tree.v, tree.H,
                                        tree.h)))
        fn = jax.jit(lambda a: tree_predict_ref(a, tree.F, tree.v, tree.H,
                                                tree.h))
        us = bench(fn, x)
        p, l = 2**depth - 1, 2**depth
        vmem_kb = (128 * k + k * p + 128 * p + p * 128 + 128 * 128) * 4 / 1024
        emit(f"kernels/tree_predict/n{n}_k{k}_l{l}", us,
             f"vmem_tile={vmem_kb:.0f}KiB")


if __name__ == "__main__":
    from repro.compile_cache import init_compile_cache
    init_compile_cache()
    run()
