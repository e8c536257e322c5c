"""MM-Join: equi-join as (sparse) matrix multiplication (paper §2.3, Alg. 1).

Three physical implementations of the same logical operator:

1. ``mmjoin_dense``   — paper-faithful: build one-hot key matrices MAT_R,
   MAT_S over the common key domain and compute the row-matching matrix
   ``I = MAT_R @ MAT_Sᵀ`` as a dense matmul.  On TPU this runs on the MXU;
   it is the direct analogue of the paper's cuSPARSE spMM (TPUs have no
   sparse engine — see DESIGN.md §2).  O(r_R · r_S · |dom|) FLOPs: only
   viable for small relations, exactly mirroring the paper's observation
   that MM-Join loses to hash join at scale.
2. ``mmjoin_bcoo``    — the same contraction through
   ``jax.experimental.sparse`` BCOO, the closest JAX analogue of the CSR
   spMM the paper uses.
3. ``join_factored``  — the TPU-native form used everywhere at scale: for
   PK–FK joins (the star-schema case, §3.1) the matching matrix I has at
   most one nonzero per fact row, so we store it *factored* as an int32
   pointer vector ``ptr`` with ``I = onehot(ptr)``; applying I is a gather.
   This is the paper's COO insight ("nnz = rows of the materialized table")
   pushed to its limit, and it is what operator fusion composes with.

Materialization (paper §2.3.3) is provided both as explicit row-mapping
matrices ``I_R, I_S`` (faithful) and as gathers (factored).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax.numpy as jnp
import numpy as np

from .domain import key_domain, positions
from .table import PAD_KEY, Table


# --------------------------------------------------------------------------
# Paper-faithful path: dense one-hot / BCOO row-matching matrix
# --------------------------------------------------------------------------
def onehot_keys(keys: jnp.ndarray, domain: jnp.ndarray,
                dtype=jnp.float32) -> jnp.ndarray:
    """MAT ∈ {0,1}^{rows × |domain|}; all-zero row for padded/missing keys."""
    pos = positions(domain, keys)  # == len(domain) for misses
    return (pos[:, None] == jnp.arange(domain.shape[0])[None, :]).astype(dtype)


def mmjoin_dense(keys_r: jnp.ndarray, keys_s: jnp.ndarray,
                 domain_size: int) -> jnp.ndarray:
    """Row-matching matrix I[i,j] = 1 iff keys_r[i] == keys_s[j] (Alg. 1)."""
    dom = key_domain([keys_r, keys_s], domain_size)
    mat_r = onehot_keys(keys_r, dom)
    mat_s = onehot_keys(keys_s, dom)
    return mat_r @ mat_s.T


def mmjoin_bcoo(keys_r: jnp.ndarray, keys_s: jnp.ndarray, domain_size: int):
    """Faithful sparse path via BCOO spMM (JAX's CSR-equivalent)."""
    from jax.experimental import sparse as jsparse

    dom = key_domain([keys_r, keys_s], domain_size)
    pos_r = positions(dom, keys_r)
    pos_s = positions(dom, keys_s)
    n_dom = dom.shape[0]

    def to_bcoo(pos, nrows):
        rows = jnp.arange(nrows, dtype=jnp.int32)
        vals = (pos < n_dom).astype(jnp.float32)
        idx = jnp.stack([rows, jnp.minimum(pos, n_dom - 1)], axis=1)
        return jsparse.BCOO((vals, idx), shape=(nrows, n_dom))

    mat_r = to_bcoo(pos_r, keys_r.shape[0])
    mat_s = to_bcoo(pos_s, keys_s.shape[0])
    out = jsparse.bcoo_dot_general(
        mat_r, mat_s.todense().T,
        dimension_numbers=(((1,), (0,)), ((), ())))
    return out


# --------------------------------------------------------------------------
# Factored path: PK-FK pointer join (star schema)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class FactoredJoin:
    """I = onehot(ptr) with a validity mask, never materialized.

    ptr[i]   = row of the PK-side relation matching FK row i (0 if miss —
               masked out by ``found``).
    found[i] = FK row i has a live match.
    """

    ptr: jnp.ndarray    # (r_fk,) int32
    found: jnp.ndarray  # (r_fk,) bool

    def apply(self, pk_matrix: jnp.ndarray) -> jnp.ndarray:
        """I @ pk_matrix as a gather (zero rows where no match)."""
        rows = jnp.take(pk_matrix, self.ptr, axis=0)
        return rows * self.found[:, None].astype(pk_matrix.dtype)

    def dense(self, pk_rows: int, dtype=jnp.float32) -> jnp.ndarray:
        """Materialize I (tests / faithful comparisons only)."""
        oh = (self.ptr[:, None] == jnp.arange(pk_rows)[None, :]).astype(dtype)
        return oh * self.found[:, None].astype(dtype)


@dataclasses.dataclass(frozen=True)
class PKIndex:
    """Sorted primary-key index: the quasi-static half of ``join_factored``.

    Building it costs the argsort; probing is a searchsorted + two gathers.
    The serving runtime builds one per arm at compile time and probes it
    per request batch — sharing this probe with ``join_factored`` is what
    keeps serving bit-identical to the compiled-query join.
    """

    sorted_pk: jnp.ndarray   # ascending (PAD_KEY sorts last)
    order: jnp.ndarray       # int32 argsort permutation

    def probe(self, fk: jnp.ndarray) -> FactoredJoin:
        pos = jnp.searchsorted(self.sorted_pk, fk).astype(jnp.int32)
        pos_c = jnp.clip(pos, 0, self.sorted_pk.shape[0] - 1)
        hit = (jnp.take(self.sorted_pk, pos_c) == fk) & (fk != PAD_KEY)
        ptr = jnp.take(self.order, pos_c).astype(jnp.int32)
        return FactoredJoin(ptr=jnp.where(hit, ptr, 0), found=hit)

    @property
    def n_live(self) -> int:
        """Number of live (non-PAD_KEY) keys in the index."""
        return int(np.searchsorted(np.asarray(self.sorted_pk), PAD_KEY))

    def extend(self, new_keys, new_row_ids) -> "PKIndex":
        """Sorted-merge appended ``(key, row)`` pairs into the index.

        The incremental half of the Catalog append path: instead of
        re-argsorting all ``capacity`` rows (O(r log r)), the m appended
        keys are sorted alone and merged into the live prefix via two
        searchsorteds (O(r + m log m)).  The result is *array-identical* to
        ``pk_index`` over the appended table — including the PAD_KEY tail,
        whose stable-argsort order is the remaining pad row ids ascending —
        so probes through an extended index are bitwise the cold rebuild's.
        ``new_row_ids`` must be the table's next contiguous row block (the
        Catalog append invariant; probe results are unaffected otherwise,
        but the pad tail would differ from a cold rebuild).  Runs on host:
        index maintenance is an offline, concrete operation.
        """
        sp = np.asarray(self.sorted_pk)
        od = np.asarray(self.order)
        cap = sp.shape[0]
        n_old = int(np.searchsorted(sp, PAD_KEY))
        nk = np.asarray(new_keys, np.int32).reshape(-1)
        nr = np.asarray(new_row_ids, np.int32).reshape(-1)
        if nk.shape[0] != nr.shape[0]:
            raise ValueError(
                f"extend: {nk.shape[0]} keys vs {nr.shape[0]} row ids")
        live = nk != PAD_KEY
        nk, nr = nk[live], nr[live]
        m = nk.shape[0]
        if n_old + m > cap:
            raise ValueError(
                f"extend: {n_old} live + {m} appended keys exceed index "
                f"capacity {cap} — rebuild with pk_index after growing")
        perm = np.argsort(nk, kind="stable")
        nk, nr = nk[perm], nr[perm]
        if np.any(nk[1:] == nk[:-1]):
            raise ValueError("extend: duplicate keys within the appended "
                             "block violate PK uniqueness")
        ins = np.searchsorted(sp[:n_old], nk, side="left")
        dup = np.take(sp, np.clip(ins, 0, max(n_old - 1, 0))) == nk
        if n_old and np.any(dup):
            raise ValueError(
                f"extend: appended keys {nk[dup][:8].tolist()} already "
                "exist in the index (PK uniqueness)")
        n_new = n_old + m
        out_pk = np.full((cap,), PAD_KEY, np.int32)
        out_od = np.zeros((cap,), np.int32)
        new_pos = ins + np.arange(m)
        old_pos = np.arange(n_old) + np.searchsorted(nk, sp[:n_old],
                                                     side="left")
        out_pk[old_pos] = sp[:n_old]
        out_od[old_pos] = od[:n_old]
        out_pk[new_pos] = nk
        out_od[new_pos] = nr
        # Stable-argsort pad tail: the remaining pad rows, ascending.
        out_od[n_new:] = np.arange(n_new, cap, dtype=np.int32)
        return PKIndex(sorted_pk=jnp.asarray(out_pk),
                       order=jnp.asarray(out_od))


def pk_index(pk: jnp.ndarray) -> PKIndex:
    """Sort the PK side once; ``pk`` must have unique live keys and padded
    entries (PAD_KEY) never match."""
    order = jnp.argsort(pk).astype(jnp.int32)
    return PKIndex(sorted_pk=jnp.take(pk, order), order=order)


@dataclasses.dataclass(frozen=True)
class ShardedPKIndex:
    """Row-sharded ``PKIndex``: one independent index slice per shard.

    Shard ``s`` owns the contiguous dimension rows ``[s·rps, (s+1)·rps)``
    and indexes *only* those: ``order`` holds shard-local row offsets, so a
    probe against one slice resolves to device-local rows with no global
    renumbering.  A key owned by another shard simply misses — combining the
    per-shard ``found`` masks (at most one shard can hit, live PKs being
    globally unique) reconstructs the global probe exactly.  This is what
    lets a row-sharded prefused partial be served by device-local
    searchsorted + gathers under ``shard_map``.
    """

    sorted_pk: jnp.ndarray   # (num_shards, rows_per_shard), ascending per row
    order: jnp.ndarray       # (num_shards, rows_per_shard) int32, shard-local

    @property
    def num_shards(self) -> int:
        return int(self.sorted_pk.shape[0])

    @property
    def rows_per_shard(self) -> int:
        return int(self.sorted_pk.shape[1])

    def shard(self, s: int) -> PKIndex:
        """The shard-local ``PKIndex`` slice (tests / host-side probes)."""
        return PKIndex(sorted_pk=self.sorted_pk[s], order=self.order[s])


def shard_pk_index(pk: jnp.ndarray, num_shards: int) -> ShardedPKIndex:
    """Build per-shard ``PKIndex`` slices over equal contiguous row blocks.

    The row count must divide ``num_shards`` — the placement planner's
    ``safe_spec`` fallback replicates non-divisible dimensions instead of
    ever calling this with ragged shards.
    """
    r = int(pk.shape[0])
    if num_shards < 1 or r % num_shards:
        raise ValueError(
            f"cannot shard {r} PK rows into {num_shards} equal slices")
    blocks = pk.reshape(num_shards, r // num_shards)
    order = jnp.argsort(blocks, axis=1).astype(jnp.int32)
    return ShardedPKIndex(
        sorted_pk=jnp.take_along_axis(blocks, order, axis=1), order=order)


def join_factored(fk: jnp.ndarray, pk: jnp.ndarray) -> FactoredJoin:
    """PK-FK equi-join: pointer from each FK row into the PK relation."""
    return pk_index(pk).probe(fk)


# --------------------------------------------------------------------------
# Materialization (paper §2.3.3)
# --------------------------------------------------------------------------
def matching_pairs(I: jnp.ndarray, capacity: int
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """COO of the row-matching matrix, padded to ``capacity``.

    Returns (rows_R, rows_S, nnz); padded entries point at index
    ``I.shape[*]`` so downstream `take(mode="fill")` yields zero rows.
    """
    ii, jj = jnp.nonzero(I > 0, size=capacity,
                         fill_value=max(I.shape))
    nnz = jnp.sum((I > 0).astype(jnp.int32))
    return ii.astype(jnp.int32), jj.astype(jnp.int32), nnz


def row_mapping_matrices(ii: jnp.ndarray, jj: jnp.ndarray, r_rows: int,
                         s_rows: int, dtype=jnp.float32):
    """Faithful I_R, I_S: target row m comes from R row ii[m] / S row jj[m]."""
    i_r = (ii[:, None] == jnp.arange(r_rows)[None, :]).astype(dtype)
    i_s = (jj[:, None] == jnp.arange(s_rows)[None, :]).astype(dtype)
    return i_r, i_s


def materialize_matmul(I: jnp.ndarray, r: Table, s: Table, capacity: int
                       ) -> Table:
    """Paper-faithful materialization: T = [I_R @ R.matrix | I_S @ S.matrix]."""
    ii, jj, nnz = matching_pairs(I, capacity)
    i_r, i_s = row_mapping_matrices(ii, jj, r.capacity, s.capacity)
    left = jnp.matmul(i_r, r.matrix, precision="highest")
    right = jnp.matmul(i_s, s.matrix, precision="highest")
    cols = tuple(f"{r.name}.{c}" for c in r.columns) + tuple(
        f"{s.name}.{c}" for c in s.columns)
    keys = {}
    for name, src, idx, cap in (("r", r, ii, r.capacity), ("s", s, jj, s.capacity)):
        for c, v in src.keys.items():
            keys[f"{src.name}.{c}"] = jnp.take(v, idx, mode="fill",
                                               fill_value=PAD_KEY)
    return Table(f"{r.name}_join_{s.name}", cols,
                 jnp.concatenate([left, right], axis=1), keys, nnz)


def materialize_gather(I: jnp.ndarray, r: Table, s: Table, capacity: int
                       ) -> Table:
    """Optimized materialization: gathers instead of one-hot matmuls."""
    ii, jj, nnz = matching_pairs(I, capacity)
    left = jnp.take(r.matrix, ii, axis=0, mode="fill", fill_value=0.0)
    right = jnp.take(s.matrix, jj, axis=0, mode="fill", fill_value=0.0)
    cols = tuple(f"{r.name}.{c}" for c in r.columns) + tuple(
        f"{s.name}.{c}" for c in s.columns)
    keys = {}
    for src, idx in ((r, ii), (s, jj)):
        for c, v in src.keys.items():
            keys[f"{src.name}.{c}"] = jnp.take(v, idx, mode="fill",
                                               fill_value=PAD_KEY)
    return Table(f"{r.name}_join_{s.name}", cols,
                 jnp.concatenate([left, right], axis=1), keys, nnz)
