"""Group-by aggregation in LAQ (paper §2.4).

* ``groupby_sum_matmul`` — paper-faithful single-column aggregation (Fig. 4):
  fill the aggregated values into MAT_R, groups into MAT_S, multiply, reduce
  with a ones vector.  Dense matmuls on the MXU.
* ``groupby_sum_segment`` — the optimized path: map rows to dense group ids
  (sort-unique, as TQP does for multi-column groups) and ``segment_sum``.
* ``composite_code`` — multi-column group-by via composite integer encoding
  followed by the single-column machinery (paper §2.4.2's sort-unique
  procedure).
* ``groupby_codes`` / ``segment_aggregate`` / ``matmul_aggregate`` — the
  code-level backends the predictive-query compiler (``repro.core.query``)
  chooses between: resolve composite codes to dense group ids once
  (quasi-static), then reduce values — either with ``segment_sum`` or with
  the Fig. 4 one-hot matmul.  Both accept (n,) scalars and (n, l) prediction
  matrices, so a fused model head aggregates with the same machinery.

All functions are padding-aware: rows whose group code is PAD_GROUP are
dropped from every aggregate.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .domain import key_domain, positions

PAD_GROUP = jnp.int32(2**31 - 1)


# --------------------------------------------------------------------------
# Paper-faithful matmul path (single column, Fig. 4)
# --------------------------------------------------------------------------
def groupby_sum_matmul(keys_r: jnp.ndarray, values_r: jnp.ndarray,
                       keys_s: jnp.ndarray, groups_s: jnp.ndarray,
                       domain_size: int, num_groups: int
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """SELECT SUM(R.val) FROM R JOIN S ON R.key=S.key GROUP BY S.val.

    Returns (group_values[num_groups] int32, sums[num_groups] float32);
    unused group slots hold PAD_GROUP / 0.
    """
    dom = key_domain([keys_r, keys_s], domain_size)
    n_dom = dom.shape[0]
    pos_r = positions(dom, keys_r)                     # (rR,)
    # MAT_R: values scattered to key-domain slots.
    mat_r = (pos_r[:, None] == jnp.arange(n_dom)[None, :]) * values_r[:, None]
    # Groups: unique S values.
    grp_vals = jnp.unique(groups_s.astype(jnp.int32), size=num_groups,
                          fill_value=PAD_GROUP)
    gid_s = positions(grp_vals, groups_s.astype(jnp.int32))  # (rS,)
    pos_s = positions(dom, keys_s)
    # MAT_S[g, d] = 1 iff some S row has key-slot d and group g.
    onehot_g = (gid_s[:, None] == jnp.arange(num_groups)[None, :])
    onehot_d = (pos_s[:, None] == jnp.arange(n_dom)[None, :])
    mat_s = (onehot_g.astype(jnp.float32).T @ onehot_d.astype(jnp.float32))
    mat_s = jnp.minimum(mat_s, 1.0)                    # de-duplicate keys
    # ones @ MAT_R @ MAT_Sᵀ : reduce rows, then map domain slots to groups.
    per_slot = jnp.sum(mat_r, axis=0)                  # (n_dom,)
    sums = jnp.matmul(mat_s, per_slot, precision="highest")  # (num_groups,)
    return grp_vals, sums


def groupby_sum_segment(keys_r: jnp.ndarray, values_r: jnp.ndarray,
                        keys_s: jnp.ndarray, groups_s: jnp.ndarray,
                        domain_size: int, num_groups: int
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Optimized counterpart of ``groupby_sum_matmul`` (same signature).

    Maps each R row to its S group through the key domain and reduces with
    ``segment_sum`` instead of building MAT_R / MAT_S.  Requires unique live
    S keys (the PK side of a star schema) — with duplicate S keys mapping one
    key slot to several groups, only the matmul form can multi-count.
    """
    dom = key_domain([keys_r, keys_s], domain_size)
    n_dom = dom.shape[0]
    pos_r = positions(dom, keys_r)
    pos_s = positions(dom, keys_s)
    grp_vals = jnp.unique(groups_s.astype(jnp.int32), size=num_groups,
                          fill_value=PAD_GROUP)
    gid_s = positions(grp_vals, groups_s.astype(jnp.int32))
    # slot -> group id (one writer per slot: unique S keys); missing slots and
    # padded S rows land in the overflow segment.
    slot_gid = jnp.full((n_dom + 1,), num_groups, jnp.int32)
    slot_gid = slot_gid.at[jnp.minimum(pos_s, n_dom)].set(
        jnp.minimum(gid_s, num_groups))
    slot_gid = slot_gid.at[n_dom].set(num_groups)
    gid_r = jnp.take(slot_gid, pos_r)
    sums = jax.ops.segment_sum(values_r, gid_r,
                               num_segments=num_groups + 1)[:num_groups]
    return grp_vals, sums


# --------------------------------------------------------------------------
# Optimized path: composite codes + segment reduction
# --------------------------------------------------------------------------
def composite_code(cols: Sequence[jnp.ndarray], bounds: Sequence[int],
                   valid: jnp.ndarray) -> jnp.ndarray:
    """Encode multi-column group keys into one int32 code (row-major).

    ``bounds[i]`` must exceed every value of ``cols[i]``; the product of
    bounds must stay below 2**31 (checked at trace time).
    """
    total = 1
    for b in bounds:
        total *= int(b)
    if total >= 2**31:
        raise ValueError(f"composite code space {total} overflows int32")
    code = jnp.zeros_like(cols[0], dtype=jnp.int32)
    for c, b in zip(cols, bounds):
        code = code * jnp.int32(b) + c.astype(jnp.int32)
    return jnp.where(valid, code, PAD_GROUP)


def groupby_reduce(codes: jnp.ndarray, values: Sequence[jnp.ndarray],
                   num_groups: int, ops: Sequence[str] = ("sum",)
                   ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, ...]]:
    """Sort-unique group ids + segment reductions (sum/count/min/max/mean).

    Returns (group_codes[num_groups], per-op aggregate arrays).  Group codes
    come out sorted (the paper folds ORDER BY on group keys into this —
    §2.5: sorting the key domain sorts the result).
    """
    uniq = jnp.unique(codes, size=num_groups, fill_value=PAD_GROUP)
    gid = jnp.searchsorted(uniq, codes).astype(jnp.int32)
    live = codes != PAD_GROUP
    gid = jnp.where(live, gid, num_groups)  # padding → overflow segment
    outs = []
    for v, op in zip(values, ops):
        if op == "sum":
            o = jax.ops.segment_sum(v, gid, num_segments=num_groups + 1)[:-1]
        elif op == "count":
            o = jax.ops.segment_sum(jnp.ones_like(v), gid,
                                    num_segments=num_groups + 1)[:-1]
        elif op == "min":
            o = jax.ops.segment_min(jnp.where(live, v, jnp.inf), gid,
                                    num_segments=num_groups + 1)[:-1]
        elif op == "max":
            o = jax.ops.segment_max(jnp.where(live, v, -jnp.inf), gid,
                                    num_segments=num_groups + 1)[:-1]
        elif op == "mean":
            s = jax.ops.segment_sum(v, gid, num_segments=num_groups + 1)[:-1]
            c = jax.ops.segment_sum(jnp.ones_like(v), gid,
                                    num_segments=num_groups + 1)[:-1]
            o = s / jnp.maximum(c, 1.0)
        else:
            raise ValueError(f"unknown aggregation op {op!r}")
        outs.append(o)
    return uniq, tuple(outs)


# --------------------------------------------------------------------------
# Code-level backends for the predictive-query compiler
# --------------------------------------------------------------------------
def _live_code_count(codes: jnp.ndarray) -> "int | None":
    """Distinct live (non-PAD_GROUP) codes, or None when codes are traced."""
    try:
        concrete = np.asarray(codes)
    except (jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError):
        return None
    return int(np.unique(concrete[concrete != int(PAD_GROUP)]).size)


def groupby_codes(codes: jnp.ndarray, num_groups: int, *,
                  n_live: "int | None" = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Resolve composite codes to (sorted unique codes, dense group ids).

    Padded codes (PAD_GROUP) map to the overflow segment ``num_groups``; both
    ``segment_aggregate`` and ``matmul_aggregate`` drop it.  The resolution is
    quasi-static for a fixed fact table, so the compiler runs it once offline
    — and on that concrete-array path the distinct live codes are *counted*:
    more than ``num_groups`` of them would silently collapse the overflow
    groups into the padded tail of ``unique(size=...)`` and drop them from
    every aggregate, so it raises instead.  Under an outer trace the count is
    abstract and the check is skipped (the caller owns sizing there).  A
    caller that already measured the domain (``auto_num_groups``) passes
    ``n_live`` to skip the redundant host-side count.

    Concrete codes resolve on the host in numpy: XLA's CPU sort makes the
    device ``unique``/``searchsorted`` an order of magnitude slower than
    numpy's at offline sizes, and this resolution is the per-plan floor of
    a multi-query compile sweep.  Both paths are bit-identical (same sort
    order, same 'left' searchsorted, same overflow clamp).
    """
    try:
        concrete = np.asarray(codes)
    except (jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError):
        concrete = None
    if n_live is None and concrete is not None:
        n_live = int(np.unique(concrete[concrete != int(PAD_GROUP)]).size)
    if n_live is not None and n_live > num_groups:
        raise ValueError(
            f"group-by overflow: {n_live} distinct live group codes "
            f"exceed num_groups={num_groups}; the excess groups would "
            "silently vanish from every aggregate. Raise num_groups "
            f"(>= {n_live}) or coarsen the group keys.")
    if concrete is not None:
        u = np.unique(concrete)[:num_groups]
        uniq = np.full((num_groups,), int(PAD_GROUP), dtype=concrete.dtype)
        uniq[:u.size] = u
        gid = np.searchsorted(uniq, concrete).astype(np.int32)
        gid = np.where(concrete != int(PAD_GROUP),
                       np.minimum(gid, num_groups), num_groups)
        return jnp.asarray(uniq), jnp.asarray(gid.astype(np.int32))
    uniq = jnp.unique(codes, size=num_groups, fill_value=PAD_GROUP)
    gid = jnp.searchsorted(uniq, codes).astype(jnp.int32)
    gid = jnp.where(codes != PAD_GROUP,
                    jnp.minimum(gid, num_groups), num_groups)
    return uniq, gid


def auto_num_groups(codes: jnp.ndarray) -> int:
    """Measured group-domain size: distinct live codes on the concrete path.

    The ``num_groups="auto"`` resolution: the offline compiler holds the
    composite codes as concrete arrays, so the exact live-code count is one
    host-side ``unique`` away — sizing the group dimension to precisely the
    measured domain (never overflows, never over-allocates).  Under an outer
    trace the codes are abstract and no measurement exists; that caller owns
    sizing and must pass an explicit ``num_groups``.
    """
    n_live = _live_code_count(codes)
    if n_live is None:
        raise ValueError(
            "num_groups='auto' requires concrete group codes: under an "
            "outer trace the code domain is abstract, so pass an explicit "
            "num_groups instead")
    return max(n_live, 1)


def segment_aggregate(gid: jnp.ndarray, values: jnp.ndarray,
                      num_groups: int) -> jnp.ndarray:
    """Σ values per group via ``segment_sum``; values (n,) or (n, l)."""
    return segment_reduce(gid, values, num_groups, "sum")


_SEGMENT_OPS = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
                "max": jax.ops.segment_max}


def segment_reduce(gid: jnp.ndarray, values: jnp.ndarray, num_groups: int,
                   op: str = "sum") -> jnp.ndarray:
    """Per-group sum/min/max via segment ops; values (n,) or (n, l).

    The min/max lowering used by the compiler on *both* aggregation backends
    (one-hot matmuls have no min/max form — Fig. 4 is additive).  Rows whose
    gid is the overflow segment ``num_groups`` (padding, predicate failures)
    are dropped; group slots that receive no row come back as the segment
    identity (±inf for min/max) and are zeroed so downstream consumers never
    see infinities in dead slots.
    """
    if op not in _SEGMENT_OPS:
        raise ValueError(f"segment_reduce op {op!r} not one of "
                         f"{sorted(_SEGMENT_OPS)}")
    out = _SEGMENT_OPS[op](values, gid,
                           num_segments=num_groups + 1)[:num_groups]
    if op in ("min", "max"):
        out = jnp.where(jnp.isfinite(out), out, 0.0)
    return out


def matmul_aggregate(gid: jnp.ndarray, values: jnp.ndarray,
                     num_groups: int) -> jnp.ndarray:
    """Paper-faithful Fig. 4 aggregation: onehot(gid)ᵀ @ values on the MXU.

    Overflow rows (gid == num_groups) get an all-zero one-hot row, exactly
    mirroring the padded-key handling of ``onehot_keys``.
    """
    onehot = (gid[:, None] == jnp.arange(num_groups)[None, :])
    return jnp.matmul(onehot.astype(values.dtype).T, values,
                      precision="highest")


def decode_composite(codes: jnp.ndarray, bounds: Sequence[int]
                     ) -> Tuple[jnp.ndarray, ...]:
    """Invert ``composite_code`` (for presenting results)."""
    cols = []
    rem = codes
    for b in reversed(list(bounds)):
        cols.append(rem % jnp.int32(b))
        rem = rem // jnp.int32(b)
    return tuple(reversed(cols))
