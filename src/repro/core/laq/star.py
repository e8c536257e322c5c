"""Star join (paper §3.1): fact table ⋈ dimension tables via factored MM-Join.

``T = I₁BM₁ + I₂CM₂ + I₃DM₃`` — each dimension contributes its projected
columns to a disjoint slice of the target, selected by the row-matching
matrix I (kept factored as FK pointers).  This module materializes T either
faithfully (dense I, matmuls) or via gathers, and is the substrate the
operator-fusion engine (``repro.core.fusion``) pushes ML operators into.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import jax.numpy as jnp

from .join import FactoredJoin, join_factored
from .projection import project_columns
from .table import Table


@dataclasses.dataclass(frozen=True)
class DimSpec:
    """One arm of the star: fact.fk_col joins dim.pk_col, keep feature_cols."""

    dim: Table
    fk_col: str          # FK column on the fact table
    pk_col: str          # PK column on the dimension table
    feature_cols: tuple  # dimension columns contributing features


@dataclasses.dataclass(frozen=True)
class StarJoin:
    """Resolved star join: factored matching matrices + combined validity."""

    fact: Table
    dims: Tuple[DimSpec, ...]
    joins: Tuple[FactoredJoin, ...]
    row_valid: jnp.ndarray  # fact rows with matches in *all* dimensions

    @property
    def feature_width(self) -> int:
        return sum(len(d.feature_cols) for d in self.dims)

    def mapping_matrices(self) -> Tuple[jnp.ndarray, ...]:
        """M_j ∈ {0,1}^{c_j × k}: dim-j columns → their slice of T's columns.

        Each dimension owns a disjoint block of the k target columns, so M_j
        has zero rows outside its block (Eq. 1's `+` composition is exact).
        """
        return dim_mapping_matrices(self.dims)

    def materialize(self) -> jnp.ndarray:
        """T = Σⱼ Iⱼ (Bⱼ Mⱼ) via gathers — (fact_capacity, k) float32.

        Rows that miss any dimension are zeroed (inner-join semantics with
        fixed capacity; ``row_valid`` carries liveness).
        """
        parts = []
        for d, fj in zip(self.dims, self.joins):
            proj = project_columns(d.dim.matrix, d.dim.columns,
                                   d.feature_cols)      # Bⱼ Mⱼ
            parts.append(fj.apply(proj))                # Iⱼ (Bⱼ Mⱼ)
        t = jnp.concatenate(parts, axis=1)
        return t * self.row_valid[:, None].astype(t.dtype)

    def materialize_matmul(self) -> jnp.ndarray:
        """Paper-faithful: dense Iⱼ one-hot matmuls (small inputs only)."""
        k = self.feature_width
        out = jnp.zeros((self.fact.capacity, k), jnp.float32)
        for d, fj, m in zip(self.dims, self.joins, self.mapping_matrices()):
            i_dense = fj.dense(d.dim.capacity)          # (r_fact, r_dim)
            out = out + jnp.matmul(                     # Iⱼ Bⱼ Mⱼ
                i_dense, jnp.matmul(d.dim.matrix, m, precision="highest"),
                precision="highest")
        return out * self.row_valid[:, None]


def dim_mapping_matrices(dims: Sequence[DimSpec]) -> Tuple[jnp.ndarray, ...]:
    """M_j for a sequence of arms, independent of any fact table.

    The quasi-static half of Eq. 1 only needs the dimension tables, so the
    serving runtime can pre-fuse partials without ever resolving a join.
    """
    k = sum(len(d.feature_cols) for d in dims)
    mats = []
    offset = 0
    for d in dims:
        c = d.dim.ncols
        m = jnp.zeros((c, k), jnp.float32)
        for t, col in enumerate(d.feature_cols):
            m = m.at[d.dim.col_index(col), offset + t].set(1.0)
        mats.append(m)
        offset += len(d.feature_cols)
    return tuple(mats)


def shard_rows(x: jnp.ndarray, num_shards: int) -> jnp.ndarray:
    """Reshape ``(r, ...)`` row-wise into ``(num_shards, r/num_shards, ...)``.

    The contiguous-block layout matches ``shard_pk_index``: shard ``s`` of a
    prefused partial holds exactly the rows its PK-index slice resolves, so
    a shard-local probe + gather touches only device-local memory.
    """
    r = int(x.shape[0])
    if num_shards < 1 or r % num_shards:
        raise ValueError(
            f"cannot shard {r} rows into {num_shards} equal blocks")
    return x.reshape(num_shards, r // num_shards, *x.shape[1:])


def star_join(fact: Table, dims: Sequence[DimSpec]) -> StarJoin:
    """Resolve FK pointers for every dimension arm (multi-way join, §2.3.2).

    Following the paper, no intermediate table is materialized: each arm's
    matching matrix is computed independently against the fact table, and
    non-matching rows are dropped via the combined validity mask.
    """
    joins = []
    valid = fact.valid_mask()
    for d in dims:
        fj = join_factored(fact.key(d.fk_col), d.dim.key(d.pk_col))
        joins.append(fj)
        valid = valid & fj.found
    return StarJoin(fact=fact, dims=tuple(dims), joins=tuple(joins),
                    row_valid=valid)
