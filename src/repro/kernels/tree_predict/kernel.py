"""Pallas TPU kernel: fused Hummingbird GEMM decision-tree inference.

``out = ((X·F > v)·H) == h`` (paper Fig. 5, steps 1–4) executed in a single
VMEM-resident pass per (row-block × leaf-block): two MXU matmuls and two
vector compares with **no HBM round-trip between steps** — the intermediate
(bn × p) predicate matrix lives only in VREGs/VMEM.  This is the fused
non-pushdown path (used when dimension tables update too often to pre-fuse;
the planner picks between this and ``fused_star_gather``).

Grid: (cdiv(n, bn), l/bl).  F (k×p), v (p), H (p×bl), h (bl) are small
model constants; X row blocks stream through, the last one ragged (rows are
independent, and the out-of-range rows' writes are dropped).  On a TPU
v5e each grid step costs about half a microsecond whatever it carries, so
the row block ``bn`` is the largest that fits a VMEM budget
(:func:`row_block`): for a depth-7 tree (p = 127, l = 128) and k ≤ 128
that is ``ROW_BLOCK_CAP`` rows.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Largest row block: the fastest of 512 / 1024 / 2048 rows over 4.8M-row
# calls of depth-7 trees on a TPU v5e (k = 16: 14.2 / 13.1 / 12.0 ms; 128
# rows took 22.7 ms), and the largest that fits scoped VMEM at k = 16.
ROW_BLOCK_CAP = 2048
# Per-step working set the row block may take: half the default 16 MiB of
# scoped VMEM, leaving the rest to the compiler's temporaries (the f32
# "highest" matmul splits x and F into bfloat16 parts).  At k = 16, p = 127,
# l = 128 it refuses 4096 rows, which run out of scoped VMEM on a v5e.
ROW_BLOCK_VMEM_BYTES = 8 * 1024 * 1024


def _up(x: int, m: int) -> int:
    return -(-x // m) * m


def _step_bytes(bn: int, k: int, p: int, bl: int) -> int:
    """VMEM bytes of one grid step at (8, 128)-padded widths, float32:
    double-buffered x, F, v, H, hsum and out blocks, plus the (bn, p)
    ``feats`` and ``preds`` and the (bn, bl) score."""
    kl, pl_ = _up(k, 128), _up(p, 128)
    blocks = (bn * kl + _up(k, 8) * pl_ + 8 * pl_ + _up(p, 8) * bl + 8 * bl
              + bn * bl)
    return 4 * (2 * blocks + bn * (2 * pl_ + bl))


def row_block(n: int, k: int, p: int, block_l: int = 128) -> int:
    """Row block of a call over ``n`` rows: the largest power of two from 8
    to ``ROW_BLOCK_CAP`` that exceeds neither ``n`` rounded up to 8 nor,
    with its (bn, k) / (bn, p) / (bn, block_l) working set, the VMEM
    budget (8 where none fits)."""
    bn = 8
    while (bn * 2 <= min(_up(max(n, 1), 8), ROW_BLOCK_CAP)
           and _step_bytes(bn * 2, k, p, block_l) <= ROW_BLOCK_VMEM_BYTES):
        bn *= 2
    return bn


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
                        block_n: Optional[int] = None, block_l: int = 128,
                        interpret: bool = False) -> jnp.ndarray:
    """One-hot leaf predictions (n × l); the leaf axis pre-padded to a
    multiple of ``block_l``, the rows any number (``block_n`` None: the
    shapes' :func:`row_block`).

    x (n,k) batch; f (k,p) feature selector; v (1,p) thresholds;
    h (p,l) ±1 path matrix; hsum (1,l) per-leaf true-side counts.
    """
    n, k = x.shape
    p, l = h.shape
    assert l % block_l == 0, (l, block_l)
    if block_n is None:
        block_n = row_block(n, k, p, block_l)
    grid = (pl.cdiv(n, block_n), l // block_l)
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
