"""Operator fusion of ML predictions into star-join query processing (§3).

The predictive pipeline is ``predictions = model(star_join(fact, dims))``.
Because both the join (LAQ) and the model are linear-algebra programs,
matmul associativity/distributivity lets the model's leading linear
operators be *pushed down* into the (quasi-static) dimension tables:

  linear (Eq. 1):   T·L = I₁(B M₁ L) + I₂(C M₂ L) + I₃(D M₃ L)
  tree   (Eq. 3):   ((T F > v) H) == h
                  = (I₁((B M₁ F > v)⊙W₁)H + I₂(...) + I₃(...)) == h

``prefuse()`` computes the per-dimension partials once; ``predict_fused``
then does only |dims| gathers + adds (+ one compare for trees) per batch —
the paper's up-to-317× speedup.  ``W_j`` is the tree-node ownership mask:
every tree node reads exactly one feature column, which lives in exactly one
dimension table, so masking non-owned nodes makes the partial sums exact
(the paper's "the predicate can be partially evaluated").
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple, Union

import jax.numpy as jnp

from ..laq.star import DimSpec, StarJoin, dim_mapping_matrices
from .operators import DecisionTreeGEMM, LinearOperator

Model = Union[LinearOperator, DecisionTreeGEMM]


@dataclasses.dataclass(frozen=True)
class PrefusedStar:
    """Per-dimension pre-fused partials P_j plus the tree's compare vector."""

    partials: Tuple[jnp.ndarray, ...]  # each (r_j, l)
    h: Optional[jnp.ndarray]           # (l,) for trees, None for linear

    def nbytes(self) -> int:
        return sum(int(p.size) * p.dtype.itemsize for p in self.partials)


def _f32(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """``a @ b`` in full f32 (the TPU default would round to bf16).

    The tree's ``preds @ H`` stays at the default: 0/1 times ±1 is exact.
    """
    return jnp.matmul(a, b, precision="highest")


def _feature_slices(dims: Sequence[DimSpec]):
    """[start, stop) of each dimension's block in T's k feature columns."""
    out = []
    off = 0
    for d in dims:
        out.append((off, off + len(d.feature_cols)))
        off += len(d.feature_cols)
    return out


def prefuse_dims(dims: Sequence[DimSpec], model: Model) -> PrefusedStar:
    """Push the model's linear prefix into dimension tables (Eq. 1/3).

    Operates on bare ``DimSpec``s — no fact table or resolved joins needed,
    which is what lets the serving runtime pre-fuse once and serve arbitrary
    request batches against the partials.
    """
    mats = dim_mapping_matrices(dims)
    parts = []
    if isinstance(model, LinearOperator):
        for j, (d, m) in enumerate(zip(dims, mats)):
            part = _f32(d.dim.matrix, _f32(m, model.L))     # B M L
            if j == 0 and model.bias is not None:
                # Constant term lives in arm 0's partial: a row missing any
                # arm is invalid and zeroed after the sum, so the bias
                # reaches exactly the rows model.apply would have biased.
                part = part + model.bias[None, :].astype(part.dtype)
            parts.append(part)
        return PrefusedStar(tuple(parts), None)
    # Decision tree: per-dim node-ownership masks W_j from F's column blocks.
    slices = _feature_slices(dims)
    f_owner = jnp.argmax(model.F, axis=0)                     # feature per node
    for d, m, (lo, hi) in zip(dims, mats, slices):
        own = ((f_owner >= lo) & (f_owner < hi)).astype(jnp.float32)  # (p,)
        feats = _f32(d.dim.matrix, _f32(m, model.F))         # (r_j, p)
        preds = (feats > model.v[None, :]).astype(jnp.float32) * own[None, :]
        parts.append(preds @ model.H)                         # (r_j, l)
    return PrefusedStar(tuple(parts), model.h)


def prefuse(star: StarJoin, model: Model) -> PrefusedStar:
    """Push the model's linear prefix into each dimension table (Eq. 1/3)."""
    return prefuse_dims(star.dims, model)


def prefuse_rows(dims: Sequence[DimSpec], model: Model, j: int,
                 row_ids: jnp.ndarray) -> jnp.ndarray:
    """Partial rows for dimension ``j`` restricted to ``row_ids``.

    The delta half of incremental prefuse maintenance: Eq. 1/3 partials are
    *row-wise* in the dimension table (row r of ``B (M L)`` reads only row r
    of B), so an append/update only ever dirties the corresponding partial
    rows.  This computes exactly those — the same per-row contractions the
    cold :func:`prefuse_dims` runs over all rows, so scattering the result
    back (:func:`extend_prefused`) reproduces the cold partial bit-exactly.
    """
    mats = dim_mapping_matrices(dims)
    d, m = dims[j], mats[j]
    rows = jnp.take(d.dim.matrix, jnp.asarray(row_ids, jnp.int32), axis=0)
    if isinstance(model, LinearOperator):
        out = _f32(rows, _f32(m, model.L))
        if j == 0 and model.bias is not None:   # matches prefuse_dims
            out = out + model.bias[None, :].astype(out.dtype)
        return out
    slices = _feature_slices(dims)
    lo, hi = slices[j]
    f_owner = jnp.argmax(model.F, axis=0)
    own = ((f_owner >= lo) & (f_owner < hi)).astype(jnp.float32)
    feats = _f32(rows, _f32(m, model.F))
    preds = (feats > model.v[None, :]).astype(jnp.float32) * own[None, :]
    return preds @ model.H


def extend_prefused(pre: PrefusedStar, dims: Sequence[DimSpec],
                    model: Model,
                    dirty: Sequence[Optional[jnp.ndarray]]) -> PrefusedStar:
    """Scatter freshly-computed partial rows into the cached partials.

    ``dirty[j]`` is the array of dimension-j row ids to recompute (appended
    span ∪ updated rows), or ``None`` for untouched arms, whose partial
    arrays are reused as-is.  Shapes never change — this is the same-
    capacity delta path; capacity growth goes through a cold ``prefuse``.
    """
    parts = []
    for j, (p, ids) in enumerate(zip(pre.partials, dirty)):
        if ids is None or len(ids) == 0:
            parts.append(p)
            continue
        ids = jnp.asarray(ids, jnp.int32)
        parts.append(p.at[ids].set(prefuse_rows(dims, model, j, ids)))
    return PrefusedStar(tuple(parts), pre.h)


def predict_fused(star: StarJoin, pre: PrefusedStar) -> jnp.ndarray:
    """Online phase: Σⱼ Iⱼ Pⱼ (gathers) and, for trees, `== h`."""
    acc = None
    for fj, p in zip(star.joins, pre.partials):
        part = fj.apply(p)
        acc = part if acc is None else acc + part
    acc = acc * star.row_valid[:, None].astype(acc.dtype)
    if pre.h is None:
        return acc
    eq = (acc == pre.h[None, :].astype(acc.dtype)).astype(acc.dtype)
    return eq * star.row_valid[:, None].astype(acc.dtype)


def predict_fused_matmul(star: StarJoin, pre: PrefusedStar) -> jnp.ndarray:
    """Paper-faithful online phase: dense Iⱼ matmuls (small inputs only)."""
    acc = None
    for d, fj, p in zip(star.dims, star.joins, pre.partials):
        part = _f32(fj.dense(d.dim.capacity), p)
        acc = part if acc is None else acc + part
    acc = acc * star.row_valid[:, None]
    if pre.h is None:
        return acc
    return (acc == pre.h[None, :]).astype(acc.dtype) * star.row_valid[:, None]


def predict_nonfused(star: StarJoin, model: Model) -> jnp.ndarray:
    """Baseline: materialize T, then run the model (separate execution)."""
    t = star.materialize()
    out = model.apply(t)
    return out * star.row_valid[:, None].astype(out.dtype)


def predict_nonfused_matmul(star: StarJoin, model: Model) -> jnp.ndarray:
    """Paper-faithful baseline: dense-I materialization, then the model."""
    t = star.materialize_matmul()
    out = model.apply(t)
    return out * star.row_valid[:, None].astype(out.dtype)


def predict_fused_kernel(star: StarJoin, pre: PrefusedStar, *,
                         interpret: bool = False) -> jnp.ndarray:
    """Online phase on the ``fused_star_gather`` Pallas kernel.

    Same contraction as :func:`predict_fused` — Σⱼ Iⱼ Pⱼ (+ ``== h``) — but
    executed as one scalar-prefetch kernel pass: the FK pointers land in SMEM
    and each partial's rows are DMA'd HBM→VMEM directly, instead of XLA
    gathers.  The per-arm liveness masks are applied inside the kernel; the
    combined row validity is applied after the compare, which matches
    :func:`predict_fused` bit-exactly in fp32 (identical add order).
    """
    from repro.kernels import fused_star_gather

    ptrs = jnp.stack([fj.ptr for fj in star.joins])
    found = jnp.stack([fj.found for fj in star.joins]).astype(jnp.int32)
    out = fused_star_gather(ptrs, found, list(pre.partials), pre.h,
                            interpret=interpret)
    return out * star.row_valid[:, None].astype(out.dtype)


def predict_nonfused_kernel(star: StarJoin, model: Model, *,
                            interpret: bool = False) -> jnp.ndarray:
    """Baseline with the model step on the ``tree_predict`` Pallas kernel.

    Only decision trees have a kernel lowering on the non-fused path
    (``((T F > v) H) == h`` as one fused block); callers must gate on the
    model type — linear heads stay on the XLA matmul.
    """
    from repro.kernels import tree_predict

    t = star.materialize()
    out = tree_predict(t, model.F, model.v, model.H, model.h,
                       interpret=interpret)
    return out * star.row_valid[:, None].astype(out.dtype)
