"""Projection as matrix multiplication (paper §2.1).

``π_{cols}(S)`` is evaluated as ``S · M`` where ``M ∈ {0,1}^{c×k}`` is the
*column-mapping matrix*: ``M[i, j] = 1`` iff source column ``i`` becomes target
column ``j``.  (The paper indexes M the other way around in prose but its
Figure 2 multiplies source @ M with M of shape c×k; we follow the figure.)

Two paths:
  * ``mapping_matrix`` + matmul — the paper-faithful LA form.  This is what
    the fusion engine composes with downstream ML operators (``M·L`` etc.).
  * ``project_gather`` — the TPU-optimized path: column projection is a
    gather of columns; XLA lowers it to a zero-FLOP slice/copy.
Both are exposed; tests assert they agree.

Every f32 matmul here and in the modules that compose with it runs at
``precision="highest"``: on a TPU the default precision rounds f32 operands
to bf16, which would round every projected value to 8 mantissa bits.  Only
products whose operands are exact in bf16 by construction (one-hot by
one-hot, 0/1 by ±1) keep the default.
"""
from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp

from .table import Table


def mapping_matrix(source_cols: Sequence[str], target_cols: Sequence[str],
                   dtype=jnp.float32) -> jnp.ndarray:
    """Build M ∈ {0,1}^{c×k} mapping source columns to target columns."""
    c, k = len(source_cols), len(target_cols)
    m = jnp.zeros((c, k), dtype)
    for j, name in enumerate(target_cols):
        i = list(source_cols).index(name)
        m = m.at[i, j].set(1)
    return m


def project_columns(matrix: jnp.ndarray, source_cols: Sequence[str],
                    target_cols: Sequence[str]) -> jnp.ndarray:
    """``matrix · M`` for the mapping ``source_cols → target_cols``, in f32."""
    return jnp.matmul(matrix, mapping_matrix(source_cols, target_cols,
                                             matrix.dtype),
                      precision="highest")


def project_matmul(table: Table, target_cols: Sequence[str]) -> Table:
    """Paper-faithful projection: one (r×c)·(c×k) matmul on the MXU."""
    out = project_columns(table.matrix, table.columns, target_cols)
    keys = {c: v for c, v in table.keys.items() if c in target_cols}
    return Table(table.name, tuple(target_cols), out, keys, table.nvalid)


def project_gather(table: Table, target_cols: Sequence[str]) -> Table:
    """Optimized projection: column gather (no FLOPs)."""
    idx = jnp.asarray([table.col_index(c) for c in target_cols])
    out = jnp.take(table.matrix, idx, axis=1)
    keys = {c: v for c, v in table.keys.items() if c in target_cols}
    return Table(table.name, tuple(target_cols), out, keys, table.nvalid)
