"""Sharded prefused partials: Eq. 1's quasi-static state over a device mesh.

The paper's serving speedup rests on prefusing each dimension's partial
``P_j = B_j M_j L`` offline and serving queries as pure gathers over those
partials.  At production scale the partials (and the fact FK batches)
outgrow one device, so this module partitions the quasi-static state across
a mesh and rebuilds the online phase as one ``shard_map``-jitted program:

* **Partials row-shard** over the mesh's ``model`` axis in contiguous
  blocks, each block paired with its own ``ShardedPKIndex`` slice and
  dimension-predicate mask, so a probe + gather touches only device-local
  rows.  A key owned by another shard misses locally; one ``psum`` over the
  model axis merges the per-shard contributions (at most one shard hits per
  key — live PKs are globally unique), reconstructing the global gather.
* **Request FK batches shard** over the data-parallel axes; the model tail
  (the tree compare vector ``h``, the non-fused model head) replicates.
* **Placement is planned, not fixed** (`plan_partition_spec`): partials
  below a byte threshold replicate, larger ones shard row-wise via
  ``mesh.safe_spec`` — a row count that doesn't divide the mesh
  axis degrades to replication instead of failing.

Bit-exactness: the owning shard contributes the identical fp32 row the
single-device gather would read and every other shard contributes zeros, so
the psum, followed by the same arm-order accumulation the unsharded runtime
uses, reproduces the single-device jnp reference bitwise (the multi-device
CI job asserts this across mesh shapes).

The Pallas kernel lowerings are deliberately not composed with ``shard_map``
here — sharded serving always uses the jnp gathers (the bit-exact reference
semantics); fusing ``fused_star_gather`` into the per-shard block program is
the TPU calibration follow-up tracked in ROADMAP.md.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..fusion.operators import DecisionTreeGEMM, LinearOperator
from ..laq.join import PKIndex, pk_index, shard_pk_index
from .mesh import dp_axes


def _shard_map(f, mesh, in_specs, out_specs):
    """``shard_map`` with the replication check off.

    The forward programs end in a ``psum`` over the shard axis, which
    guarantees the out-spec's replication, but the checker cannot prove it
    through the mixed replicated/sharded arm state.
    """
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def _rep_spec(x) -> P:
    return P(*([None] * x.ndim))


@dataclasses.dataclass(frozen=True)
class ShardedArm:
    """One star arm's quasi-static serving state, placed on the mesh.

    ``table`` is the arm's prefused partial (fused backend) or projected
    feature block (non-fused backend).  When ``spec`` row-shards it, the
    probe state is sharded to match: ``sorted_pk``/``order`` hold the
    flattened per-shard ``ShardedPKIndex`` slices (shard-local row offsets)
    and ``dmask`` the per-shard dimension-predicate mask, all laid out in
    the same contiguous row blocks so ``in_specs=P(axis)`` hands each device
    exactly its slice.  Probe state is ``None`` on the global-pointer path
    (``CompiledQuery.predict_rows``), where the FK→row resolution already
    happened offline.
    """

    fk_col: str
    spec: P
    table: jnp.ndarray                    # (r, w)
    sorted_pk: Optional[jnp.ndarray]      # (r,) per-shard-sorted | None
    order: Optional[jnp.ndarray]          # (r,) shard-local offsets | None
    dmask: Optional[jnp.ndarray]          # (r,) bool | None

    @property
    def is_sharded(self) -> bool:
        return len(self.spec) > 0 and self.spec[0] is not None


@dataclasses.dataclass(frozen=True)
class ShardedPrefusedPartials:
    """All arms' prefused partials placed across ``mesh``.

    Built once per (query, catalog, mesh) by :func:`shard_prefused_partials`
    — the sharded analogue of :class:`..fusion.pipeline.PrefusedStar` plus
    the per-arm lookup state, ready for :func:`make_serving_forward` /
    :func:`make_predict_rows_forward` to close over.
    """

    mesh: object                          # jax.sharding.Mesh
    shard_axis: str
    arms: Tuple[ShardedArm, ...]
    h: Optional[jnp.ndarray]              # tree compare vector, replicated

    @property
    def placement(self) -> Tuple[P, ...]:
        return tuple(a.spec for a in self.arms)

    @property
    def num_sharded(self) -> int:
        return sum(1 for a in self.arms if a.is_sharded)

    def nbytes_per_device(self) -> int:
        """Quasi-static bytes resident per device under this placement.

        Counts the partials *and* the per-arm probe state (PK-index slices,
        predicate masks) — for narrow partials the int32 probe arrays are a
        material fraction of the footprint.
        """
        total = 0
        for a in self.arms:
            arrs = [x for x in (a.table, a.sorted_pk, a.order, a.dmask)
                    if x is not None]
            n = sum(int(x.size) * x.dtype.itemsize for x in arrs)
            if a.is_sharded:
                n //= int(self.mesh.shape[self.shard_axis])
            total += n
        if self.h is not None:
            total += int(self.h.size) * self.h.dtype.itemsize
        return total


def shard_prefused_partials(
        mesh, arms: Sequence[Tuple[str, Optional[jnp.ndarray],
                                   Optional[jnp.ndarray], jnp.ndarray]],
        h: Optional[jnp.ndarray], specs: Sequence[P], *,
        shard_axis: str = "model") -> ShardedPrefusedPartials:
    """Place each arm's ``(fk_col, pk, dmask, table)`` per its spec.

    Arms whose spec row-shards get per-shard ``ShardedPKIndex`` slices and
    contiguous-block layouts; replicated arms keep the global ``PKIndex``.
    Every array is ``device_put`` with its ``NamedSharding`` here, so the
    per-bucket jitted programs see committed inputs and never reshard the
    quasi-static state on the serving hot path.  ``pk``/``dmask`` may be
    ``None`` for the global-pointer (``predict_rows``) path.
    """
    if shard_axis in mesh.axis_names:
        num_shards = int(mesh.shape[shard_axis])
    else:
        num_shards = 1
    placed = []
    for (fk_col, pk, dmask, table), spec in zip(arms, specs):
        sharded = len(spec) > 0 and spec[0] is not None
        if pk is None:
            sorted_pk = order = None
        elif sharded:
            sidx = shard_pk_index(pk, num_shards)
            sorted_pk = sidx.sorted_pk.reshape(-1)
            order = sidx.order.reshape(-1)
        else:
            gidx = pk_index(pk)
            sorted_pk, order = gidx.sorted_pk, gidx.order
        vec_spec = P(shard_axis) if sharded else P(None)

        def put(x, s):
            return (None if x is None
                    else jax.device_put(x, NamedSharding(mesh, s)))

        placed.append(ShardedArm(
            fk_col=fk_col, spec=spec,
            table=put(table, spec),
            sorted_pk=put(sorted_pk, vec_spec),
            order=put(order, vec_spec),
            dmask=put(dmask, vec_spec)))
    if h is not None:
        h = jax.device_put(h, NamedSharding(mesh, P(None)))
    return ShardedPrefusedPartials(mesh=mesh, shard_axis=shard_axis,
                                   arms=tuple(placed), h=h)


def _model_leaves(model) -> Tuple[Tuple[jnp.ndarray, ...], str]:
    """The replicated model tail as explicit shard_map operands."""
    if isinstance(model, LinearOperator):
        return (model.L,), "linear"
    if isinstance(model, DecisionTreeGEMM):
        return (model.F, model.v, model.H, model.h), "tree"
    raise TypeError(f"no sharded lowering for model {type(model).__name__}")


def _rebuild_model(kind: str, leaves):
    return (LinearOperator(*leaves) if kind == "linear"
            else DecisionTreeGEMM(*leaves))


def _merge_sharded(parts, hits, contribs, shard_axis):
    """psum the row-sharded arm contributions back to global values.

    One collective for all sharded arms (a pytree psum); at most one shard
    hit per request key, so the summed hit counts are exactly the global
    ``found & dmask`` bits and the summed partial rows are bitwise the
    single-device gather results (zeros are exact fp32 identities).
    """
    if not contribs:
        return parts, hits
    red = jax.lax.psum(contribs, shard_axis)
    for j, (part, hit_count) in red.items():
        parts[j] = part
        hits[j] = hit_count > 0
    return parts, hits


def _accumulate(parts, hits, valid, h, model, backend):
    """The online tail, in the exact arm/op order of the unsharded runtime
    (``ServingRuntime._online_fused`` / ``_online_nonfused``) so fp32
    results stay bitwise identical."""
    if backend == "fused":
        acc = parts[0]
        for part in parts[1:]:
            acc = acc + part
        if h is not None:
            acc = acc * valid[:, None].astype(acc.dtype)
            acc = (acc == h[None, :].astype(acc.dtype)).astype(acc.dtype)
        out = acc
    else:
        t = jnp.concatenate(parts, axis=1) * valid[:, None].astype(
            jnp.float32)
        out = model.apply_rows(t)
    return out * valid[:, None].astype(out.dtype)


def serving_arm_state(sp: ShardedPrefusedPartials) -> Tuple:
    """The placed per-arm serving state as a swappable pytree.

    One tuple per arm — ``(table, sorted_pk, order, dmask)`` — passed into
    the ``shard_map`` program at call time rather than closed over, so the
    serving runtime's ``refresh`` can swap in extended arrays (same shapes,
    same shardings) and re-dispatch into the already-compiled executables.
    """
    return tuple((a.table, a.sorted_pk, a.order,
                  a.dmask.astype(jnp.bool_)) for a in sp.arms)


def extend_sharded_arm(sp: ShardedPrefusedPartials, j: int,
                       table: jnp.ndarray, pk: jnp.ndarray,
                       dmask: jnp.ndarray, lo: int, hi: int) -> ShardedArm:
    """Re-place arm ``j`` after rows ``[lo, hi)`` changed, touching only the
    shard blocks that own them.

    The contiguous-block layout means appended rows land in the tail
    block(s): only those shards' ``ShardedPKIndex`` slices are re-argsorted
    (rows_per_shard elements each) — every untouched block's index, order
    and mask bytes are reused as-is.  Replicated arms just re-place the
    whole (small) table.  Shapes and specs are unchanged, so the swapped
    arm state dispatches into the compiled ``shard_map`` program.
    """
    arm = sp.arms[j]
    mesh = sp.mesh
    num_shards = (int(mesh.shape[sp.shard_axis])
                  if sp.shard_axis in mesh.axis_names else 1)

    def put(x, s):
        return (None if x is None
                else jax.device_put(x, NamedSharding(mesh, s)))

    if not arm.is_sharded:
        idx = pk_index(pk) if pk is not None else None
        return dataclasses.replace(
            arm, table=put(table, arm.spec),
            sorted_pk=put(idx.sorted_pk if idx else None, P(None)),
            order=put(idx.order if idx else None, P(None)),
            dmask=put(dmask, P(None)))
    r = int(table.shape[0])
    rps = r // num_shards
    s_lo, s_hi = lo // rps, -(-hi // rps)   # shard blocks owning [lo, hi)
    vec_spec = P(sp.shard_axis)
    sorted_pk = order = None
    if pk is not None:
        sorted_pk = np.array(np.asarray(sp.arms[j].sorted_pk))
        order = np.array(np.asarray(sp.arms[j].order))
        blocks = np.asarray(pk).reshape(num_shards, rps)
        for s in range(s_lo, s_hi):
            o = np.argsort(blocks[s], kind="stable").astype(np.int32)
            sorted_pk[s * rps:(s + 1) * rps] = blocks[s][o]
            order[s * rps:(s + 1) * rps] = o
        sorted_pk = jnp.asarray(sorted_pk)
        order = jnp.asarray(order)
    return dataclasses.replace(
        arm, table=put(table, arm.spec), sorted_pk=put(sorted_pk, vec_spec),
        order=put(order, vec_spec),
        dmask=put(dmask.astype(jnp.bool_) if dmask is not None else None,
                  vec_spec))


def make_serving_forward(sp: ShardedPrefusedPartials, model, backend: str):
    """The sharded online phase for ``ServingRuntime``: fks → predictions.

    One ``shard_map``-wrapped program (jitted per padding bucket by the
    runtime): the FK batch shards over the DP axes, each arm probes its
    device-local ``PKIndex`` slice and gathers its local partial rows, and
    a single psum over the shard axis merges the row-sharded arms.  The
    per-arm placed state (:func:`serving_arm_state`) is a call-time
    argument: ``forward(fks, arms)``.
    """
    mesh, axis = sp.mesh, sp.shard_axis
    dp = dp_axes(mesh)
    batch_spec = P(dp) if dp else P(None)
    extras, kind = ((), None) if backend == "fused" else _model_leaves(model)
    if backend == "fused" and sp.h is not None:
        extras = (sp.h,)
    arm_specs = tuple(
        ((P(axis, None), P(axis), P(axis), P(axis)) if a.is_sharded
         else (P(None, None), P(None), P(None), P(None)))
        for a in sp.arms)
    in_specs = (tuple(batch_spec for _ in sp.arms), arm_specs,
                tuple(_rep_spec(e) for e in extras))
    out_spec = P(dp if dp else None, None)

    def body(fks, arms, extras):
        h = extras[0] if (backend == "fused" and sp.h is not None) else None
        mdl = _rebuild_model(kind, extras) if backend != "fused" else None
        parts, hits, contribs = [], [], {}
        for j, (table, sorted_pk, order, dmask) in enumerate(arms):
            fj = PKIndex(sorted_pk, order).probe(fks[j])
            hit = fj.found & jnp.take(dmask, fj.ptr)
            rows = jnp.take(table, fj.ptr, axis=0)
            part = rows * hit[:, None].astype(rows.dtype)
            if sp.arms[j].is_sharded:
                contribs[j] = (part, hit.astype(jnp.int32))
            parts.append(part)
            hits.append(hit)
        parts, hits = _merge_sharded(parts, hits, contribs, axis)
        valid = hits[0]
        for hit in hits[1:]:
            valid = valid & hit
        return _accumulate(parts, hits, valid, h, mdl, backend)

    smapped = _shard_map(body, mesh, in_specs, out_spec)

    def forward(fks, arms):
        return smapped(tuple(fks), tuple(arms), extras)

    return forward


def predict_rows_state(sp: ShardedPrefusedPartials,
                       tables: Sequence[jnp.ndarray],
                       ptrs: Sequence[jnp.ndarray],
                       founds: Sequence[jnp.ndarray],
                       row_valid: jnp.ndarray) -> dict:
    """Placed call-time state for :func:`make_predict_rows_forward`.

    Pointers/validity replicate; each arm table keeps its planned spec.
    Rebuilt wholesale on refresh (the arrays are re-``device_put`` with the
    same shardings, so the compiled program re-dispatches without retrace).
    """
    mesh = sp.mesh
    rep = NamedSharding(mesh, P(None))
    return {
        "ptrs": tuple(jax.device_put(p, rep) for p in ptrs),
        "founds": tuple(jax.device_put(f.astype(jnp.bool_), rep)
                        for f in founds),
        "valid": jax.device_put(row_valid.astype(jnp.bool_), rep),
        "tables": tuple(
            jax.device_put(t, NamedSharding(mesh, a.spec))
            for t, a in zip(tables, sp.arms)),
    }


def make_predict_rows_forward(sp: ShardedPrefusedPartials, model,
                              backend: str):
    """Sharded ``CompiledQuery.predict_rows``: fact row ids → predictions.

    Here the FK→row resolution already ran offline (``join_factored``), so
    the per-arm pointers are *global* row numbers; each shard serves the
    pointers that land in its contiguous block (``axis_index`` arithmetic)
    and the psum merges, matching the unsharded gather bitwise.  The placed
    pointer/table state (:func:`predict_rows_state`) is a call-time
    argument: ``forward(row_ids, state)``.
    """
    mesh, axis = sp.mesh, sp.shard_axis
    extras, kind = ((), None) if backend == "fused" else _model_leaves(model)
    if backend == "fused" and sp.h is not None:
        extras = (sp.h,)
    table_specs = tuple(P(axis, None) if a.is_sharded else P(None, None)
                        for a in sp.arms)
    in_specs = (P(None), tuple(P(None) for _ in sp.arms),
                tuple(P(None) for _ in sp.arms), P(None), table_specs,
                tuple(_rep_spec(e) for e in extras))

    def body(row_ids, ptrs, founds, valid_full, tables, extras):
        h = extras[0] if (backend == "fused" and sp.h is not None) else None
        mdl = _rebuild_model(kind, extras) if backend != "fused" else None
        v = jnp.take(valid_full, row_ids)
        # Out-of-range row ids follow the unsharded ``jnp.take`` fill
        # semantics (NaN rows).  The sharded gather clips pointers into the
        # local block, which would silently turn the NaN fill into 0.0, so
        # the fill is reproduced explicitly: a float gather over the fact
        # capacity is 0 in range (negative ids wrap) and NaN out of range.
        poison = jnp.take(jnp.zeros((valid_full.shape[0],), jnp.float32),
                          row_ids)
        parts, hits, contribs = [], [], {}
        for j, table in enumerate(tables):
            gptr = jnp.take(ptrs[j], row_ids)
            hit = jnp.take(founds[j], row_ids)
            if sp.arms[j].is_sharded:
                rps = table.shape[0]
                lo = jax.lax.axis_index(axis) * rps
                own = (gptr >= lo) & (gptr < lo + rps) & hit
                local = jnp.clip(gptr - lo, 0, rps - 1)
                part = (jnp.take(table, local, axis=0)
                        * own[:, None].astype(table.dtype))
                contribs[j] = (part, own.astype(jnp.int32))
            else:
                part = (jnp.take(table, gptr, axis=0)
                        * hit[:, None].astype(table.dtype))
            parts.append(part)
            hits.append(hit)
        parts, _ = _merge_sharded(parts, hits, contribs, axis)
        # predict_rows applies the *combined* offline validity (fact preds
        # folded in), not the per-arm hit conjunction — mirror it exactly.
        if backend == "fused":
            acc = parts[0]
            for part in parts[1:]:
                acc = acc + part
            acc = acc * v[:, None].astype(acc.dtype)
            if h is None:
                out = acc
            else:
                eq = (acc == h[None, :].astype(acc.dtype)).astype(acc.dtype)
                out = eq * v[:, None].astype(acc.dtype)
        else:
            t = jnp.concatenate(parts, axis=1) * v[:, None].astype(
                jnp.float32)
            out = mdl.apply_rows(t) * v[:, None].astype(jnp.float32)
        bad = jnp.isnan(poison)[:, None]
        return jnp.where(bad, poison[:, None].astype(out.dtype), out)

    smapped = _shard_map(body, mesh, in_specs, P(None, None))

    def forward(row_ids, state):
        return smapped(row_ids, state["ptrs"], state["founds"],
                       state["valid"], state["tables"], extras)

    return forward
