"""Paper Figures 17–20 + Fig. 19 heatmap: decision-tree fusion speedup.

Same sweep structure as the linear case but with Hummingbird-GEMM trees:
k features / p nodes / l leaves (paper Table 5).  Includes the fused
Pallas ``tree_predict`` kernel path (interpret mode) as a third engine in
smoke sizes.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.core.fusion import predict_fused, predict_nonfused, prefuse, \
    random_tree
from repro.data import generate_star

from .common import bench, emit

SCALE = 0.05


def one(setting, sf, k, depth, tag):
    rng = np.random.default_rng(0)
    syn = generate_star(setting, sf, k, scale=SCALE)
    tree = random_tree(rng, k, depth)
    pre = prefuse(syn.star, tree)
    fused = jax.jit(lambda: predict_fused(syn.star, pre))
    nonfused = jax.jit(lambda: predict_nonfused(syn.star, tree))
    us_f = bench(fused)
    us_n = bench(nonfused)
    emit(f"fusion_tree/{tag}/fused", us_f, "")
    emit(f"fusion_tree/{tag}/nonfused", us_n,
         f"speedup={us_n / us_f:.2f}x k/l={k / 2**depth:.2f}")
    return us_n / us_f


def run():
    # Fig. 17: setting 1 across sf (k=128, depth 3 → 8 leaves).
    for sf in (1, 2, 4, 8):
        one(1, sf, 128, 3, f"set1_sf{sf}_k128_d3")
    # Fig. 18: sf=4, growing leaves.
    for depth in (1, 3, 5, 7):
        one(1, 4, 128, depth, f"set1_sf4_k128_d{depth}")
    # Fig. 20: setting 2, large trees.
    for depth in (7, 9):
        one(2, 2, 512, depth, f"set2_sf2_k512_d{depth}")
    # Fig. 19 heatmap: sf=8, k × leaves.
    ks = (16, 64, 256)
    depths = (1, 4, 7)
    for k in ks:
        row = []
        for d in depths:
            row.append(one(1, 8, k, d, f"heat_k{k}_d{d}"))
        print("heat," + ",".join(f"{v:.2f}" for v in row))


if __name__ == "__main__":
    from repro.compile_cache import init_compile_cache
    init_compile_cache()
    run()
