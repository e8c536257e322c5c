"""Pallas TPU kernel: fused Hummingbird GEMM decision-tree inference.

``out = ((X·F > v)·H) == h`` (paper Fig. 5, steps 1–4) executed in a single
VMEM-resident pass per (row-block × leaf-block): two MXU matmuls and two
vector compares with **no HBM round-trip between steps** — the intermediate
(bn × p) predicate matrix lives only in VREGs/VMEM.  This is the fused
non-pushdown path (used when dimension tables update too often to pre-fuse;
the planner picks between this and ``fused_star_gather``).

Grid: (n/bn, l/bl).  F (k×p), v (p), H (p×bl), h (bl) are small model
constants; X row blocks stream through.  VMEM per step:
bn·k + k·p + bn·p + p·bl + bn·bl floats — for bn=128, k=p=512, bl=128 that
is ≈ 1.6 MiB.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _tree_predict_kernel(x_ref, f_ref, v_ref, h_ref, hsum_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)                    # (bn, k)
    feats = jnp.dot(x, f_ref[...].astype(jnp.float32),
                    precision="highest",
                    preferred_element_type=jnp.float32)   # (bn, p)
    preds = (feats > v_ref[...].astype(jnp.float32)).astype(jnp.float32)
    score = jnp.dot(preds, h_ref[...].astype(jnp.float32),
                    preferred_element_type=jnp.float32)   # (bn, bl)
    out_ref[...] = (score == hsum_ref[...].astype(jnp.float32)
                    ).astype(jnp.float32)


def tree_predict_pallas(x: jnp.ndarray, f: jnp.ndarray, v: jnp.ndarray,
                        h: jnp.ndarray, hsum: jnp.ndarray, *,
                        block_n: int = 128, block_l: int = 128,
                        interpret: bool = False) -> jnp.ndarray:
    """One-hot leaf predictions (n × l); inputs pre-padded to block multiples.

    x (n,k) batch; f (k,p) feature selector; v (1,p) thresholds;
    h (p,l) ±1 path matrix; hsum (1,l) per-leaf true-side counts.
    """
    n, k = x.shape
    p, l = h.shape
    assert n % block_n == 0 and l % block_l == 0, (n, l, block_n, block_l)
    grid = (n // block_n, l // block_l)
    return pl.pallas_call(
        _tree_predict_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, p), lambda i, j: (0, 0)),
            pl.BlockSpec((1, p), lambda i, j: (0, 0)),
            pl.BlockSpec((p, block_l), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_l), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_n, block_l), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, l), jnp.float32),
        interpret=interpret,
    )(x, f, v, h, hsum)
