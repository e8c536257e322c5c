"""jit'd public wrapper for fused_star_gather.

Clips pointers into range (liveness is carried by ``found``), pads the
output width to the fp32 lane multiple (128), and maps batches longer than
one call's SMEM-resident pointers over chunks of ``max_rows_per_call``
rows.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from .kernel import fused_star_gather_pallas, max_rows_per_call


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_star_gather(ptrs: jnp.ndarray, found: jnp.ndarray,
                      tables: Sequence[jnp.ndarray],
                      h: jnp.ndarray | None = None, *,
                      interpret: bool = False) -> jnp.ndarray:
    """Serve-time fused star pipeline: Σⱼ Pⱼ[ptrⱼ] (== h).

    Args:
      ptrs:   (J, n) int32 FK pointers into each pre-fused partial.
      found:  (J, n) int32/bool liveness per pointer.
      tables: J arrays (r_j, l) — the pre-fused partials P_j.
      h:      optional (l,) compare vector (decision-tree online phase).
    """
    l = tables[0].shape[1]
    n = ptrs.shape[1]
    if n == 0:
        # Zero-row grid: nothing to DMA, and a (0,)-sized Pallas grid is
        # rejected by the lowering — short-circuit to an empty result.
        return jnp.zeros((0, l), jnp.float32)
    pad_l = (-l) % 128
    tabs = []
    for t in tables:
        t = jnp.pad(t.astype(jnp.float32), ((0, 0), (0, pad_l)))
        tabs.append(t)
    hh = None
    if h is not None:
        # Pad h with NaN so padded output columns compare False (then sliced
        # away anyway).
        hh = jnp.pad(h.astype(jnp.float32), (0, pad_l),
                     constant_values=jnp.nan)
    clipped = []
    for j, t in enumerate(tabs):
        clipped.append(jnp.clip(ptrs[j], 0, t.shape[0] - 1))
    ptrs_c = jnp.stack(clipped).astype(jnp.int32)
    found_c = found.astype(jnp.int32)
    rows = max_rows_per_call(len(tabs))
    if n <= rows:
        out = fused_star_gather_pallas(ptrs_c, found_c, tabs, hh,
                                       interpret=interpret)
        return out[:, :l]
    # Chunks of ``rows`` rows, one kernel call each; padded tail rows are
    # dead (found = 0) and sliced away.
    pad = (-n) % rows
    n_dims = len(tabs)

    def chunked(x):
        x = jnp.pad(x, ((0, 0), (0, pad)))
        return x.reshape(n_dims, -1, rows).transpose(1, 0, 2)

    out = jax.lax.map(
        lambda pf: fused_star_gather_pallas(pf[0], pf[1], tabs, hh,
                                            interpret=interpret),
        (chunked(ptrs_c), chunked(found_c)))
    return out.reshape(-1, out.shape[-1])[:n, :l]
