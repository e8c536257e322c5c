"""Paper Figures 16 & 21: pre-fusion cost vs online join-computation cost.

The fusion trade-off: pre-fused partials are recomputed whenever dimension
tables change.  Measures the pre-fusion stage and the online stage
separately across output widths l (linear) and leaf counts (tree) —
reproducing the paper's observation that the linear/online stage dominates
until l grows past ~512, after which pre-fusion dominates and fusion pays
off only for slowly-changing dimensions (the planner's amortization
input).
"""
from __future__ import annotations

import jax
import numpy as np
import jax.numpy as jnp

from repro.core.fusion import (LinearOperator, predict_fused, prefuse,
                               random_tree)
from repro.data import generate_star

from .common import bench, emit

SCALE = 0.01


def run():
    rng = np.random.default_rng(0)
    for l in (64, 256, 512, 1024, 2048):
        syn = generate_star(2, 2, 512, scale=SCALE)
        model = LinearOperator(jnp.asarray(
            rng.normal(size=(512, l)).astype(np.float32)))
        pre_fn = jax.jit(lambda: prefuse(syn.star, model).partials)
        us_pre = bench(pre_fn)
        pre = prefuse(syn.star, model)
        online = jax.jit(lambda: predict_fused(syn.star, pre))
        us_on = bench(online)
        emit(f"prefusion/linear_l{l}/prefuse", us_pre, "")
        emit(f"prefusion/linear_l{l}/online", us_on,
             f"prefuse_share={us_pre / (us_pre + us_on):.2f}")
    for depth in (6, 8, 10):
        syn = generate_star(2, 2, 256, scale=SCALE)
        tree = random_tree(rng, 256, depth)
        pre_fn = jax.jit(lambda: prefuse(syn.star, tree).partials)
        us_pre = bench(pre_fn)
        pre = prefuse(syn.star, tree)
        online = jax.jit(lambda: predict_fused(syn.star, pre))
        us_on = bench(online)
        emit(f"prefusion/tree_d{depth}/prefuse", us_pre, "")
        emit(f"prefusion/tree_d{depth}/online", us_on,
             f"prefuse_share={us_pre / (us_pre + us_on):.2f}")


if __name__ == "__main__":
    from repro.compile_cache import init_compile_cache
    init_compile_cache()
    run()
