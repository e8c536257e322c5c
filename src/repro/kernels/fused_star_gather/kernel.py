"""Pallas TPU kernel: fused star-pipeline online phase.

After pre-fusion (paper Eq. 1/3) the per-batch work is
``out[i] = Σⱼ Pⱼ[ptrⱼ[i]] · foundⱼ[i]`` and, for decision trees,
``out[i] = (Σⱼ ... ) == h``.  This kernel executes the whole online phase in
one pass with **scalar-prefetched FK pointers**: the int32 pointer arrays are
prefetched into SMEM before the grid starts, and each dimension table's
BlockSpec ``index_map`` reads them to DMA exactly the needed (block of) rows
HBM→VMEM — the same indirect-DMA pattern TPU embedding lookups use.  No
row-matching matrix, no materialized join result, no intermediate HBM
round-trips.

Grid: (n,) — one fact row per grid step, J+1 row-DMAs per step, all
double-buffered by the Pallas pipeline.  VMEM per step: (J+1)·l floats —
trivially small; the kernel is DMA-latency-bound, which is exactly the
roofline position the paper's fusion puts the online phase in (it removed
all the FLOPs).

Layout: the TPU lowering requires a block's last two dimensions to be
multiples of (8, 128) or equal to the array's own.  A one-row block of an
(r, l) table meets neither, so the tables and the output are viewed as
(r, 1, l): the block (1, 1, l) then equals the array in its last two
dimensions, and the leading (row) dimension is indexed freely by the
prefetched pointer.

SMEM: the scalar-prefetched (J, n) pointer and liveness arrays live in SMEM
for the whole call, so one call takes at most :func:`max_rows_per_call`
rows; the wrapper maps longer batches over chunks of that size.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# SMEM the prefetched pointers may take: half of a v5e core's 1 MiB, leaving
# the rest to the pipeline's own scalars.
SMEM_POINTER_BYTES = 1 << 19


def max_rows_per_call(n_dims: int) -> int:
    """Rows whose (J, n) int32 pointers + liveness fit the SMEM budget.

    The compiler pads the J axis of each prefetched array; rounding J up to
    8 keeps the estimate above what it allocates.
    """
    return SMEM_POINTER_BYTES // (2 * 4 * (-(-n_dims // 8) * 8))


def _star_gather_kernel(*refs, n_dims: int, compare: bool):
    # refs: [ptrs_smem, found_smem] + n_dims table refs (+ h_ref) + out_ref
    ptrs_ref, found_ref = refs[0], refs[1]
    tbl_refs = refs[2:2 + n_dims]
    h_ref = refs[2 + n_dims] if compare else None
    out_ref = refs[-1]
    i = pl.program_id(0)
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for j, tref in enumerate(tbl_refs):
        live = (found_ref[j, i] > 0).astype(jnp.float32)
        acc = acc + tref[...].astype(jnp.float32) * live
    if compare:
        hit = (acc == h_ref[...].astype(jnp.float32))
        acc = hit.astype(jnp.float32)
    out_ref[...] = acc


def fused_star_gather_pallas(ptrs: jnp.ndarray, found: jnp.ndarray,
                             tables: Sequence[jnp.ndarray],
                             h: jnp.ndarray | None = None, *,
                             interpret: bool = False) -> jnp.ndarray:
    """out[i] = Σⱼ tables[j][ptrs[j, i]] · found[j, i]  (== h if given).

    ptrs/found: (J, n) int32; tables[j]: (r_j, l); h: (l,) or None;
    n at most ``max_rows_per_call(J)``.
    """
    n_dims, n = ptrs.shape
    l = tables[0].shape[1]
    compare = h is not None

    in_specs = [
        pl.BlockSpec((None, 1, l), functools.partial(_tbl_index, j))
        for j in range(n_dims)
    ]
    inputs = [t.reshape(t.shape[0], 1, l) for t in tables]
    if compare:
        in_specs.append(pl.BlockSpec((1, l), lambda i, ptrs, found: (0, 0)))
        inputs.append(h.reshape(1, l))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, 1, l),
                               lambda i, ptrs, found: (i, 0, 0)),
    )
    kernel = functools.partial(_star_gather_kernel, n_dims=n_dims,
                               compare=compare)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, l), jnp.float32),
        interpret=interpret,
    )(ptrs, found, *inputs).reshape(n, l)


def _tbl_index(j, i, ptrs_ref, found_ref):
    """Row block of table j for fact row i: the prefetched FK pointer."""
    return (ptrs_ref[j, i], 0, 0)
