"""jit'd public wrapper for the tree_predict Pallas kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import tree_predict_pallas


@functools.partial(jax.jit, static_argnames=("block_n", "block_l",
                                             "interpret"))
def tree_predict(x: jnp.ndarray, f: jnp.ndarray, v: jnp.ndarray,
                 h: jnp.ndarray, hsum: jnp.ndarray, *,
                 block_n: Optional[int] = None, block_l: int = 128,
                 interpret: bool = False) -> jnp.ndarray:
    """Fused ((x·F > v)·H) == hsum — one-hot leaf encoding (n × l).

    ``block_n`` None takes the row block from the shapes
    (:func:`~.kernel.row_block`); the rows are never padded."""
    l = h.shape[1]
    # Pad leaf dim with NaN counts so padded leaves never match.
    pad_l = (-l) % block_l
    h_p = jnp.pad(h.astype(jnp.float32), ((0, 0), (0, pad_l)))
    hsum_p = jnp.pad(hsum.astype(jnp.float32).reshape(1, -1),
                     ((0, 0), (0, pad_l)), constant_values=jnp.nan)
    out = tree_predict_pallas(x, f.astype(jnp.float32),
                              v.astype(jnp.float32).reshape(1, -1),
                              h_p, hsum_p, block_n=block_n, block_l=block_l,
                              interpret=interpret)
    return out[:, :l]
