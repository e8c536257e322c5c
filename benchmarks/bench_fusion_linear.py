"""Paper Figures 12–15 + Fig. 14 heatmap: linear-operator fusion speedup.

Cardinality setting 1 ("large input, small model") and setting 2 ("small
input, large model") from paper Table 4/5, swept over sf and over the
model shape (k = input width, l = output width).  Emits fused and
non-fused per-batch times and their ratio — the paper's headline result
(speedup tracks k/l, Eq. 2; up to 317× on the A40).
"""
from __future__ import annotations

import jax
import numpy as np
import jax.numpy as jnp

from repro.core.fusion import (LinearOperator, predict_fused,
                               predict_nonfused, prefuse)
from repro.data import generate_star

from .common import bench, emit

SCALE = 0.05


def one(setting, sf, k, l, tag):
    rng = np.random.default_rng(0)
    syn = generate_star(setting, sf, k, scale=SCALE)
    model = LinearOperator(jnp.asarray(
        rng.normal(size=(k, l)).astype(np.float32)))
    pre = prefuse(syn.star, model)
    fused = jax.jit(lambda: predict_fused(syn.star, pre))
    nonfused = jax.jit(lambda: predict_nonfused(syn.star, model))
    us_f = bench(fused)
    us_n = bench(nonfused)
    emit(f"fusion_linear/{tag}/fused", us_f, "")
    emit(f"fusion_linear/{tag}/nonfused", us_n,
         f"speedup={us_n / us_f:.2f}x k/l={k / l:.1f}")
    return us_n / us_f


def run():
    # Fig. 12: setting 1 across sf, small model (k=128, l=2).
    for sf in (1, 2, 4, 8):
        one(1, sf, 128, 2, f"set1_sf{sf}_k128_l2")
    # Fig. 13: hold sf=4, grow l.
    for l in (2, 8, 32, 128):
        one(1, 4, 128, l, f"set1_sf4_k128_l{l}")
    # Fig. 15: setting 2 (small input), large models.
    for l in (256, 1024, 2048):
        one(2, 2, 512, l, f"set2_sf2_k512_l{l}")
    # Fig. 14 heatmap: sf=8, k × l grid.
    print("heatmap_speedup (rows k, cols l):")
    ks = (16, 32, 64, 128)
    ls = (2, 8, 32, 128)
    for k in ks:
        row = []
        for l in ls:
            row.append(one(1, 8, k, l, f"heat_k{k}_l{l}"))
        print("heat," + ",".join(f"{v:.2f}" for v in row))


if __name__ == "__main__":
    from repro.compile_cache import init_compile_cache
    init_compile_cache()
    run()
