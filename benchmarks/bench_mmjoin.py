"""MM-Join physical operators vs the sort-based join (paper §2.3 analysis
+ the companion comparison in [24]).

The paper reports MM-Join's O(n²)-ish spMM cost loses to hash join as data
grows; our TPU-native factored join (searchsorted + gather) plays the hash
join role.  Sweep row counts; emit µs for
  * ``dense``    — paper-faithful one-hot matmul row-matching matrix,
  * ``bcoo``     — BCOO spMM (CSR-equivalent in JAX),
  * ``factored`` — pointer join (ours).
Derived column = slowdown vs factored.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.laq import join_factored, mmjoin_bcoo, mmjoin_dense

from .common import bench, emit


def run(sizes=(256, 1024, 4096, 16384)):
    rng = np.random.default_rng(0)
    for n in sizes:
        n_dim = max(n // 8, 8)
        pk = rng.permutation(n_dim * 2)[:n_dim].astype(np.int32)
        fk = rng.choice(pk, size=n).astype(np.int32)
        fkj, pkj = jnp.asarray(fk), jnp.asarray(pk)

        fact = jax.jit(lambda a, b: join_factored(a, b).ptr)
        us_f = bench(fact, fkj, pkj)
        emit(f"mmjoin/factored/n{n}", us_f, "1.00x")

        if n <= 4096:  # dense I is O(n·n_dim·dom): cap like the paper's OOM
            dense = jax.jit(lambda a, b: mmjoin_dense(a, b, 2 * n_dim))
            us_d = bench(dense, fkj, pkj)
            emit(f"mmjoin/dense/n{n}", us_d, f"{us_d / us_f:.2f}x")
            bcoo = jax.jit(lambda a, b: mmjoin_bcoo(a, b, 2 * n_dim))
            us_b = bench(bcoo, fkj, pkj)
            emit(f"mmjoin/bcoo/n{n}", us_b, f"{us_b / us_f:.2f}x")


if __name__ == "__main__":
    from repro.compile_cache import init_compile_cache
    init_compile_cache()
    run()
