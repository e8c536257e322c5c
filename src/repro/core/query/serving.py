"""Dynamic-batch serving: compile the fused online phase once, serve any
request batch.

``compile_query`` binds a static fact table, so its serving entry point
(``CompiledQuery.predict_rows``) can only score *fact rows*.  This module
traces the fused online phase over a ``(batch, fk...)`` request pytree
instead: a request is one foreign key per star arm, and the compiled program
is exactly the paper's Eq. 1 online phase — per-arm PK lookups into the
quasi-static sorted key index, then Σⱼ Pⱼ[ptrⱼ] gathers into the pre-fused
partials (+ ``== h`` for trees).  One compiled plan therefore serves
arbitrary incoming batches, not just rows the fact table happened to
contain.

Bucketed padding policy
-----------------------
XLA needs static shapes, so each incoming batch is padded (with ``PAD_KEY``,
which never matches a live PK) up to the smallest configured *bucket* size
and dispatched through one jitted program per bucket.  The jit cache is
keyed on the padded shape, so after at most ``len(buckets)`` traces no
request ever recompiles; batches larger than the top bucket are served in
top-bucket chunks.  Request buffers are donated on accelerators so the
padded int32 staging arrays are recycled across calls.

Physical lowering
-----------------
The gather-sum is lowered onto the Pallas kernels when the planner says the
shapes fit their block specs (``plan_serving_backend``): the fused path onto
``kernels/fused_star_gather`` (scalar-prefetched FK pointers, one DMA pass),
the non-fused decision-tree path onto ``kernels/tree_predict``.  Everything
else uses the pure-jnp gathers, which remain the reference semantics — the
kernel backends match them bit-exactly in fp32.

Sharded serving
---------------
``compile_serving(..., mesh=...)`` partitions the quasi-static state across
a device mesh (``core.query.sharding``): large partials row-shard over the
mesh's model axis with per-shard ``PKIndex`` slices, small ones replicate
(``plan_partition_spec``), and the padded FK batch shards over the DP axes.
Each bucket's program becomes one ``shard_map``-jitted device-local
probe + gather + psum, bit-exact vs the single-device jnp path.  Buckets
are rounded up to multiples of the DP size so every padded batch divides
the mesh.  The Pallas lowering is mutually exclusive with ``mesh`` (the
sharded block kernels are the TPU calibration follow-up).

Incremental maintenance
-----------------------
The quasi-static state (PK indices, predicate masks, prefused partials) is
a *call-time pytree argument* of the bucket programs, not a closure
constant, and the runtime records the :class:`~repro.core.laq.Catalog`
versions it was built against.  :meth:`ServingRuntime.refresh` applies
pending dimension appends/updates by delta — sorted-merge
``PKIndex.extend``, ``prefuse_rows`` over only the new rows, in-place mask
scatters, and (sharded) re-indexing of only the shard blocks that own the
appended tail — so the already-traced bucket programs keep serving with
zero recompiles.  Capacity growth changes shapes and falls back to a full
rebuild + replan (divisibility boundaries re-checked), with the decision
recorded on ``plan.reason``.
"""
from __future__ import annotations

import collections
import dataclasses
import time
import warnings
from typing import Deque, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...launch.mesh import dp_size
from ..fusion.operators import DecisionTreeGEMM
from ..fusion.pipeline import prefuse_dims, prefuse_rows
from ..laq.catalog import Catalog, CatalogHistoryError, changed_spans
from ..laq.join import PKIndex, pk_index
from ..laq.projection import project_columns
from ..laq.star import DimSpec
from ..laq.table import PAD_KEY, Table
from .explain import ExplainReport
from .ir import PredictiveQuery
from .multiquery import holds_tracers
from .snowflake import CollapsedChain, chain_tables, resolve_chain
from .planner import (QueryPlan, effective_serve_backend, place_tables,
                      plan_query, resolve_mesh_serve_backend)
from .sharding import (ShardedPrefusedPartials, extend_sharded_arm,
                       make_serving_forward, serving_arm_state,
                       shard_prefused_partials)

#: Default padding buckets: small interactive batches, mid-size batches, and
#: a bulk bucket that also serves as the chunk size for oversized requests.
DEFAULT_BUCKETS = (8, 64, 512)

#: Per-bucket latency samples kept for the percentile report (a bounded
#: window, so a long-lived runtime's bookkeeping stays O(1) per bucket).
LATENCY_WINDOW = 2048


class SentinelKeyError(ValueError):
    """A request carried a key equal to the padding sentinel ``PAD_KEY``.

    Padded slots are recognized *by value* — ``PAD_KEY`` never matches a
    live PK — so a real request key equal to the sentinel would be
    indistinguishable from padding: it would silently score zero with no
    indication anything was wrong.  ``ServingRuntime._normalize`` rejects
    such keys loudly instead; re-key the dimension if ``2**31 - 1`` must be
    a servable key.
    """


@dataclasses.dataclass(frozen=True)
class _ArmIndex:
    """Quasi-static per-arm lookup state (paper's offline phase, per arm).

    ``index`` factors the PK side of ``join_factored`` out of the online
    program: the sort runs once at compile time, the online lookup is the
    shared ``PKIndex.probe`` (searchsorted + two gathers) — the *same*
    probe the compiled-query join uses, which is what keeps serving
    bit-identical to ``predict_rows``.  ``dmask`` carries the
    dimension-side predicates and row liveness, folded into the lookup's
    validity exactly like the compiler folds them into the join (§2.2).
    """

    fk_col: str
    index: Optional[PKIndex]  # None on the mesh path (per-shard slices rule)
    dmask: jnp.ndarray        # (r,) bool, in dimension-row order
    table: Optional[jnp.ndarray]  # (r, w) partial; None on the mesh path


def _serving_tables(q: PredictiveQuery) -> Tuple[str, ...]:
    """Real catalog tables whose versions gate a runtime: heads + links.

    The fact table is deliberately absent — requests are FK tuples, never
    fact rows — but every table along a snowflake chain participates: a
    sub-dimension append changes the collapsed virtual dimension.
    """
    return tuple(sorted({t for a in q.arms for t in chain_tables(a)}))


def _serving_dims(catalog: Mapping[str, Table], q: PredictiveQuery,
                  pool=None) -> Tuple[List[DimSpec],
                                      Tuple[Optional[CollapsedChain], ...],
                                      Tuple[Optional[tuple], ...]]:
    """Per-arm DimSpecs with snowflake chains collapsed offline.

    Flat arms resolve against the catalog directly; chained arms collapse
    (through the shared pool when available — the same entry compiled
    plans use) to their head-granularity virtual dimension, whose columns
    become the arm's served feature set.  Returns ``(dims, chains,
    chain_keys)`` with ``None`` chain slots for flat arms.
    """
    dims, chains, chain_keys = [], [], []
    for a in q.arms:
        if a.links:
            if pool is not None:
                cc, ckey = pool.acquire_chain(a)
            else:
                cc, ckey = resolve_chain(catalog, a), None
            dims.append(DimSpec(cc.table, a.fk_col, a.pk_col,
                                tuple(cc.table.columns)))
            chains.append(cc)
            chain_keys.append(ckey)
        else:
            dims.append(DimSpec(catalog[a.table], a.fk_col, a.pk_col,
                                a.feature_cols))
            chains.append(None)
            chain_keys.append(None)
    return dims, tuple(chains), tuple(chain_keys)


def _mask_rows(dim: Table, preds, ids: np.ndarray) -> jnp.ndarray:
    """The dim-predicate mask evaluated on just the (live) rows ``ids``."""
    sub = Table(dim.name, dim.columns,
                jnp.take(dim.matrix, jnp.asarray(ids), axis=0),
                {c: jnp.take(v, jnp.asarray(ids))
                 for c, v in dim.keys.items()},
                int(ids.shape[0]))
    # The sub-table is all-live by construction (nvalid = len(ids), no
    # tombstones), so fold the *parent's* liveness at these rows explicitly
    # — a tombstoned row must come back False no matter what the predicates
    # say, exactly as the cold build's ``valid_mask() & preds`` fold does.
    m = jnp.take(dim.valid_mask(), jnp.asarray(ids))
    for p in preds:
        m = m & p.mask(sub)
    return m


class ServingRuntime:
    """One compiled predictive pipeline serving arbitrary request batches.

    Built by :func:`compile_serving`; hold one instance per (query, catalog)
    and call :meth:`serve` with request batches of any size.  Thread-compat:
    serving is functional over quasi-static arrays; only the latency/trace
    bookkeeping is unsynchronized.
    """

    def __init__(self, query: PredictiveQuery, plan: QueryPlan, backend: str,
                 serve_backend: str, buckets: Tuple[int, ...],
                 arms: Tuple[_ArmIndex, ...], model, h: Optional[jnp.ndarray],
                 interpret: bool, donate: bool, sync_stats: bool = True,
                 sharded: Optional[ShardedPrefusedPartials] = None,
                 catalog: Optional[Catalog] = None,
                 mesh=None, shard_axis: str = "model",
                 shard_threshold_bytes: Optional[int] = None,
                 pool=None, pool_refs: Optional[Dict] = None):
        self.query = query
        self.plan = plan
        self.backend = backend                # "fused" | "nonfused"
        self.serve_backend = serve_backend    # "jnp" | "pallas"
        self.buckets = buckets
        self._model = model
        self._interpret = interpret
        self._sync_stats = sync_stats
        self._trace_count = 0
        self._lat: Dict[int, Deque[float]] = {}
        self._lat_chunked: Deque[float] = collections.deque(
            maxlen=LATENCY_WINDOW)
        # One compile record per jit-cache generation: ``_compile_s`` is the
        # live generation's {bucket: seconds}, appended to ``_compile_log``
        # by ``_install`` so a rebuild archives instead of overwriting.
        self._compile_log: List[Dict[int, float]] = []
        self._donate = donate
        self.catalog = catalog
        self.versions: Dict[str, int] = (
            {t: catalog.version(t) for t in _serving_tables(query)}
            if catalog is not None else {})
        self._mesh = mesh
        self._shard_axis = shard_axis
        self._shard_threshold_bytes = shard_threshold_bytes
        # Session-owned ArtifactPool sharing (None when compiled
        # standalone): the keys this runtime holds references to —
        # {"arms": ((pkindex, dmask, features|None) per arm),
        #  "partials": (keys,)} — released by close().
        self._pool = pool
        self._pool_refs: Dict = pool_refs or {}
        self._install(arms, h, sharded)

    def _install(self, arms: Tuple[_ArmIndex, ...],
                 h: Optional[jnp.ndarray],
                 sharded: Optional[ShardedPrefusedPartials]):
        """Bind quasi-static state + a fresh jit cache (build and rebuild).

        The per-arm state is passed into the traced program as an argument
        (see ``_forward``), so a same-shape refresh swaps ``_state`` and
        re-dispatches into the existing executables; ``_install`` itself is
        only called when the program *must* be rebuilt (first build, or a
        shape-changing refresh), which is why it resets the trace count.
        """
        self._arms = arms
        self._h = h
        self.sharded = sharded
        self._forward_impl = (
            make_serving_forward(sharded, self._model, self.backend)
            if sharded is not None else None)
        self._state = {"arms": self._arm_state(), "h": self._h}
        self._trace_count = 0
        # A fresh cache generation starts a fresh compile record; earlier
        # generations stay archived in ``_compile_log`` (compile_history).
        self._compile_s: Dict[int, float] = {}
        self._compile_log.append(self._compile_s)
        donate_argnums = (0,) if self._donate else ()
        self._jit = jax.jit(self._forward, donate_argnums=donate_argnums)

    def _arm_state(self) -> Tuple:
        if self.sharded is not None:
            return serving_arm_state(self.sharded)
        return tuple((a.index.sorted_pk, a.index.order,
                      a.dmask.astype(jnp.bool_), a.table)
                     for a in self._arms)

    # -- sharding introspection ----------------------------------------------
    @property
    def mesh(self):
        """The serving mesh, or None on the single-device path."""
        return self.sharded.mesh if self.sharded is not None else None

    # -- introspection -------------------------------------------------------
    @property
    def request_keys(self) -> Tuple[str, ...]:
        """FK column names a request must provide, in arm order."""
        return tuple(a.fk_col for a in self._arms)

    @property
    def out_width(self) -> int:
        return self._model.l

    @property
    def num_compiles(self) -> int:
        """Traces taken since the jit cache was (re)built.

        Bounded by ``len(buckets)`` per cache generation: a delta
        ``refresh`` swaps same-shape state and never adds a trace; only a
        shape-changing rebuild starts a fresh cache (count restarts at 0).
        """
        return self._trace_count

    @property
    def generation(self) -> int:
        """The jit-cache generation (0-based; rebuilds increment it)."""
        return len(self._compile_log) - 1

    def compile_history(self) -> List[Dict[int, float]]:
        """Per-generation ``{bucket: compile_ms}`` records, oldest first.

        Consistent with the ``num_compiles`` generation semantics: a delta
        refresh keeps the live generation's record (no retrace happened), a
        shape-changing rebuild archives it and starts a new one — the
        first-generation compile times survive every later retrace instead
        of being overwritten.
        """
        return [{b: s * 1e3 for b, s in gen.items()}
                for gen in self._compile_log]

    def jit_cache_size(self) -> int:
        """The number of executables in the bucket programs' jit cache."""
        return self._jit._cache_size()

    def latency_stats(self) -> Dict[object, Dict[str, float]]:
        """Per-bucket steady-state serve latency percentiles (ms).

        Each bucket's one-time trace+compile call is kept out of the
        percentiles and reported separately as ``compile_ms`` (the *live*
        cache generation's record — earlier generations survive in
        :meth:`compile_history`); a bucket that has only ever compiled
        still appears, with ``count == 0`` and no percentile keys.

        Oversized batches (``n > buckets[-1]``) are served in top-bucket
        chunks, and their wall time is attributed **per request** under the
        ``"chunked"`` key — one sample for the whole oversized call — not
        per chunk, so one analytical batch cannot skew the top bucket's
        point-lookup percentiles.  Percentiles measure wall time only when
        the runtime synchronizes per call (``sync_stats``, the default).
        """
        out: Dict[object, Dict[str, float]] = {}
        for bucket in sorted(set(self._lat) | set(self._compile_s)):
            ts = self._lat.get(bucket, ())
            out[bucket] = {"count": len(ts)}
            if ts:
                out[bucket].update(self._percentiles(ts))
            if bucket in self._compile_s:
                out[bucket]["compile_ms"] = self._compile_s[bucket] * 1e3
        if self._lat_chunked:
            out["chunked"] = {"count": len(self._lat_chunked),
                              **self._percentiles(self._lat_chunked)}
        return out

    @staticmethod
    def _percentiles(ts) -> Dict[str, float]:
        ms = np.asarray(ts) * 1e3
        return {"p50": float(np.percentile(ms, 50)),
                "p95": float(np.percentile(ms, 95)),
                "p99": float(np.percentile(ms, 99))}

    # -- the compiled program ------------------------------------------------
    def _forward(self, fks: Tuple[jnp.ndarray, ...], state) -> jnp.ndarray:
        # Python side effect: runs once per trace (i.e. once per bucket;
        # the quasi-static state is an argument, so a same-shape refresh
        # never re-enters here).
        self._trace_count += 1
        if self._forward_impl is not None:   # sharded shard_map program
            return self._forward_impl(fks, state["arms"])
        ptrs, hits = [], []
        for (sorted_pk, order, dmask, _), fk in zip(state["arms"], fks):
            fj = PKIndex(sorted_pk, order).probe(fk)
            ptrs.append(fj.ptr)
            hits.append(fj.found & jnp.take(dmask, fj.ptr))
        valid = hits[0]
        for hit in hits[1:]:
            valid = valid & hit
        tables = [t for (_, _, _, t) in state["arms"]]
        if self.backend == "fused":
            out = self._online_fused(ptrs, hits, valid, tables, state["h"])
        else:
            out = self._online_nonfused(ptrs, hits, valid, tables)
        return out * valid[:, None].astype(out.dtype)

    def _online_fused(self, ptrs, hits, valid, tables, h) -> jnp.ndarray:
        if self.serve_backend == "pallas":
            from repro.kernels import fused_star_gather
            return fused_star_gather(
                jnp.stack(ptrs), jnp.stack(hits).astype(jnp.int32),
                tables, h, interpret=self._interpret)
        acc = None
        for ptr, hit, tbl in zip(ptrs, hits, tables):
            part = jnp.take(tbl, ptr, axis=0) * hit[:, None].astype(tbl.dtype)
            acc = part if acc is None else acc + part
        if h is None:
            return acc
        acc = acc * valid[:, None].astype(acc.dtype)
        return (acc == h[None, :].astype(acc.dtype)).astype(acc.dtype)

    def _online_nonfused(self, ptrs, hits, valid, tables) -> jnp.ndarray:
        parts = []
        for tbl, ptr, hit in zip(tables, ptrs, hits):
            rows = jnp.take(tbl, ptr, axis=0)
            parts.append(rows * hit[:, None].astype(rows.dtype))
        t = jnp.concatenate(parts, axis=1) * valid[:, None].astype(jnp.float32)
        if (self.serve_backend == "pallas"
                and isinstance(self._model, DecisionTreeGEMM)):
            from repro.kernels import tree_predict
            m = self._model
            return tree_predict(t, m.F, m.v, m.H, m.h,
                                interpret=self._interpret)
        return self._model.apply_rows(t)

    # -- introspection / lifecycle -------------------------------------------
    def _pool_keys(self) -> list:
        """Every pool key this runtime references (with multiplicity)."""
        keys = [k for ref in self._pool_refs.get("arms", ()) for k in ref
                if k is not None]
        keys.extend(self._pool_refs.get("partials", ()))
        return keys

    def explain(self) -> ExplainReport:
        """Structured plan/refresh report (``str()`` gives the legacy line)."""
        return ExplainReport(
            kind="serving", backend=self.backend,
            serve_backend=self.serve_backend,
            plan_reason=getattr(self, "_base_reason", self.plan.reason),
            trail=tuple(getattr(self, "_refresh_notes", ())),
            shared_artifacts=tuple(self._pool_keys()),
            extras=(("buckets", self.buckets),
                    ("generation", self.generation)))

    def close(self) -> None:
        """Release this runtime's shared-artifact references (idempotent)."""
        if self._pool is not None and self._pool_refs:
            self._pool.release(self._pool_keys())
        self._pool_refs = {}

    # -- incremental maintenance --------------------------------------------
    def refresh(self) -> str:
        """Apply pending catalog deltas to the serving state, in place.

        Same-shape appends/updates take the delta path: per-arm
        ``PKIndex.extend`` sorted merges (sharded arms re-index only the
        shard blocks owning the appended tail), ``prefuse_rows`` over just
        the changed dimension rows, and predicate-mask scatters — the state
        pytree is swapped and the already-traced bucket programs keep
        serving with **zero new compiles** (``num_compiles`` unchanged).
        Capacity growth falls back to a full rebuild + replan (placement
        divisibility re-checked) with a fresh jit cache, so
        ``num_compiles`` restarts from 0.  Either way the latency windows
        reset: post-refresh ``latency_stats`` never mix pre-refresh
        samples.  Compile records follow the cache generation instead: the
        delta path keeps the live record, a rebuild archives it into
        :meth:`compile_history` and starts generation ``g+1``.  Returns
        the decision line (also appended to ``plan.reason``).

        Concurrency: refresh swaps the state pytree out from under the
        bucket programs and is **not** fenced against concurrent
        :meth:`serve` calls from other threads.  Serve through an
        :class:`~repro.core.query.scheduler.AdmissionScheduler` (or its
        ``refresh()``) when requests are in flight — it drains admitted
        work before swapping.
        """
        if self.catalog is None:
            return self._note("refresh=no-op(detached: no catalog)")
        cat = self.catalog
        try:
            changed = {
                t: cat.deltas_since(t, self.versions.get(t, 0))
                for t in _serving_tables(self.query)}
        except CatalogHistoryError:
            return self._rebuild("history-compacted: runtime staler than "
                                 "the delta log")
        changed = {n: d for n, d in changed.items() if d}
        if not changed:
            return self._note("refresh=no-op(versions unchanged)")
        if any(changed_spans(d)[2] for d in changed.values()):
            compacted = sorted(n for n, d in changed.items()
                               if any(t.kind == "compact" for t in d))
            if compacted:
                return self._rebuild(
                    f"compaction:{','.join(compacted)} rewrote row ids")
            grown = sorted(n for n, d in changed.items()
                           if changed_spans(d)[2])
            return self._rebuild(f"capacity-growth:{','.join(grown)}")
        chained = {t for a in self.query.arms if a.links
                   for t in chain_tables(a)}
        if chained & set(changed):
            # A delta anywhere along a chain changes the collapsed virtual
            # dimension (composed pointers, gathered features, folded
            # validity) — re-collapse and rebind through the full rebuild
            # path rather than teaching the delta scatters chain
            # composition.  Bit-exact by construction; the flat-arm delta
            # path below stays zero-recompile for non-chain appends.
            touched = ",".join(sorted(chained & set(changed)))
            return self._rebuild(
                f"chain tables changed: {touched} re-collapsed")
        line = self._refresh_delta(changed)
        self._reset_stats()
        return line

    def _note(self, line: str) -> str:
        # Bounded decision trail: base plan reason + the last few refresh
        # lines — a runtime refreshed per streaming batch must not grow
        # its explain() string (and memory) without limit.
        if not hasattr(self, "_refresh_notes"):
            self._refresh_notes = collections.deque(maxlen=8)
        if not self._refresh_notes:
            self._base_reason = self.plan.reason
        self._refresh_notes.append(line)
        self.plan = dataclasses.replace(
            self.plan, reason="; ".join([self._base_reason,
                                         *self._refresh_notes]))
        return line

    def _reset_stats(self):
        """Latency percentiles restart at a refresh boundary (pre-refresh
        samples would pollute the post-refresh distribution).  Compile
        records are *not* cleared here: they are per cache generation
        (``num_compiles`` semantics) — a delta refresh keeps the live
        generation's record, and a rebuild already archived it via
        ``_install``."""
        self._lat.clear()
        self._lat_chunked.clear()

    def _rebuild(self, why: str) -> str:
        q = self.query
        dims, chains, chain_keys = _serving_dims(self.catalog, q,
                                                 pool=self._pool)
        # Re-plan from the *base* reason (accumulated refresh notes would
        # otherwise be baked into the new plan's base and grow unbounded).
        base_plan = (dataclasses.replace(self.plan,
                                         reason=self._base_reason)
                     if getattr(self, "_refresh_notes", None)
                     else self.plan)
        # Re-acquire from the pool FIRST (fresh references keep shared
        # refcounts above zero), then release the references of the state
        # being replaced.
        old_keys = self._pool_keys()
        arms, h, sharded, plan, refs = _serving_artifacts(
            self.catalog, q, dims, self._model, self.backend, base_plan,
            mesh=self._mesh, shard_axis=self._shard_axis,
            shard_threshold_bytes=self._shard_threshold_bytes,
            pool=self._pool, chains=chains, chain_keys=chain_keys)
        self._pool_refs = refs
        if self._pool is not None and old_keys:
            self._pool.release(old_keys)
        self.plan = plan
        if hasattr(self, "_refresh_notes"):
            self._refresh_notes.clear()   # replanned: fresh decision trail
        self._install(arms, h, sharded)
        self._reset_stats()
        self.versions = {t: self.catalog.version(t)
                         for t in _serving_tables(q)}
        return self._note(f"refresh=rebuild({why}; replanned, jit cache "
                          "reset)")

    def _refresh_delta_pooled(self, changed) -> str:
        """Pool-backed delta refresh: O(distinct artifacts), not O(plans).

        Each ``pool.get`` delta-updates the shared entry at most once per
        catalog version change regardless of how many runtimes/plans
        reference it; rebinding the refreshed arrays into ``_state`` is
        all that remains per runtime.
        """
        q = self.query
        cat = self.catalog
        pool = self._pool
        pkeys = self._pool_refs.get("partials", ())
        parts = tuple(pool.get(k) for k in pkeys) if pkeys else None
        new_arms = []
        for j, (old, ref) in enumerate(
                zip(self._arms, self._pool_refs["arms"])):
            # Serving refs are (ikey, mkey, tkey[, ckey]); a chained arm
            # carries its dmask/features on the pooled chain entry.
            ikey, mkey, tkey, ckey = (tuple(ref) + (None,) * 4)[:4]
            if ckey is not None:
                cc = pool.get(ckey)
                dmask = cc.dmask
                tbl = parts[j] if parts is not None else cc.table.matrix
            else:
                dmask = pool.get(mkey)
                tbl = parts[j] if parts is not None else pool.get(tkey)
            new_arms.append(dataclasses.replace(
                old, index=pool.get(ikey), dmask=dmask, table=tbl))
        self._arms = tuple(new_arms)
        self._state = {"arms": self._arm_state(), "h": self._h}
        self.versions = {t: cat.version(t) for t in _serving_tables(q)}
        touched = ",".join(f"{n}+{len(changed[n])}" for n in sorted(changed))
        return self._note(f"refresh=delta({touched}; pooled artifacts, "
                          "0 new compiles)")

    def _refresh_delta(self, changed) -> str:
        if self._pool is not None and self._pool_refs.get("arms"):
            return self._refresh_delta_pooled(changed)
        q = self.query
        cat = self.catalog
        # Chain tables never reach this path (refresh() routes any chain
        # delta to _rebuild), but chained arms still shape the prefuse
        # feature slices — resolve them so arm j's slice offsets match the
        # build.
        dims, _, _ = _serving_dims(cat, q)
        new_arms = list(self._arms)
        new_sharded_arms = (list(self.sharded.arms)
                            if self.sharded is not None else None)
        for j, arm in enumerate(q.arms):
            if arm.table not in changed:
                continue
            dim = cat[arm.table]
            span, dirty, _, deleted = changed_spans(changed[arm.table])
            ids = set(dirty)
            if span is not None:
                ids.update(range(span[0], span[1]))
            # Tombstoned rows need only the validity scatter below: their
            # partial rows, keys and slots are untouched (deletion is a
            # pure validity fold), so they join the mask ids but not the
            # prefuse recompute.
            touched = sorted(ids | set(deleted))
            if not touched:    # e.g. history contains only no-op deltas
                continue
            old = self._arms[j]
            table = (old.table if old.table is not None
                     else new_sharded_arms[j].table)
            if ids:
                # Partial (fused) / projected-feature (nonfused) rows: only
                # the changed dimension rows are recomputed, then scattered
                # — the delta half of Eq. 1 maintenance, bit-exact vs a
                # cold prefuse.
                upd = np.asarray(sorted(ids), np.int32)
                if self.backend == "fused":
                    rows = prefuse_rows(dims, self._model, j,
                                        jnp.asarray(upd))
                else:
                    rows = project_columns(
                        jnp.take(dim.matrix, jnp.asarray(upd), axis=0),
                        dim.columns, arm.feature_cols)
                table = table.at[jnp.asarray(upd)].set(rows)
            ids = np.asarray(touched, np.int32)
            lo, hi = int(ids.min()), int(ids.max()) + 1
            dmask = old.dmask.at[jnp.asarray(ids)].set(
                _mask_rows(dim, arm.preds, ids))
            if new_sharded_arms is not None:
                new_sharded_arms[j] = extend_sharded_arm(
                    self.sharded, j, table, dim.key(arm.pk_col), dmask,
                    lo, hi)
                new_arms[j] = dataclasses.replace(old, dmask=dmask)
            else:
                index = old.index
                if span is not None:
                    index = index.extend(
                        dim.key(arm.pk_col)[span[0]:span[1]],
                        np.arange(span[0], span[1]))
                new_arms[j] = dataclasses.replace(
                    old, index=index, dmask=dmask, table=table)
        self._arms = tuple(new_arms)
        if new_sharded_arms is not None:
            self.sharded = dataclasses.replace(
                self.sharded, arms=tuple(new_sharded_arms))
        self._state = {"arms": self._arm_state(), "h": self._h}
        self.versions = {t: cat.version(t) for t in _serving_tables(q)}
        touched = ",".join(f"{n}+{len(changed[n])}" for n in sorted(changed))
        return self._note(f"refresh=delta({touched}; shapes kept, "
                          "0 new compiles)")

    # -- request entry points ------------------------------------------------
    def serve(self, requests) -> jnp.ndarray:
        """Predictions for a request batch — any size, no recompilation.

        ``requests`` is a mapping ``{fk_col: (n,) ints}`` covering
        :attr:`request_keys`, a sequence of per-arm key arrays in arm order,
        or a stacked ``(num_arms, n)`` array.  Returns ``(n, l)`` fp32
        predictions; requests whose keys miss a live (predicate-passing)
        dimension row score zero, matching inner-join semantics.
        """
        fks = self._normalize(requests)
        n = int(fks[0].shape[0])
        if n == 0:
            return jnp.zeros((0, self.out_width), jnp.float32)
        top = self.buckets[-1]
        if n > top:
            # Oversized analytical batch: top-bucket chunks, but the wall
            # time is attributed to the *request* (one "chunked" sample),
            # never per chunk into the top bucket's percentile window —
            # one big batch must not skew point-lookup p99.
            t0 = time.perf_counter()
            chunks = [self._serve_bucketed([f[i:i + top] for f in fks],
                                           record=False)
                      for i in range(0, n, top)]
            out = jnp.concatenate(chunks, axis=0)
            if self._sync_stats:
                jax.block_until_ready(out)
            self._lat_chunked.append(time.perf_counter() - t0)
            return out
        return self._serve_bucketed(fks)

    def _serve_bucketed(self, fks: List[np.ndarray], *,
                        record: bool = True) -> jnp.ndarray:
        n = int(fks[0].shape[0])
        bucket, padded = self._admit(fks)
        return self._execute(padded, bucket, record=record)[:n]

    # Admission/execution split: the async scheduler composes padded
    # sub-batches itself (coalescing several queued requests into one
    # bucket-shaped step), so padding and dispatch are separate entry
    # points rather than one opaque serve call.
    def _admit(self, fks: List[np.ndarray],
               bucket: Optional[int] = None
               ) -> Tuple[int, Tuple[jnp.ndarray, ...]]:
        """Pad normalized request columns into a bucket-shaped batch.

        Returns ``(bucket, padded)``; ``bucket`` defaults to the smallest
        configured bucket that fits the rows (callers chunk batches larger
        than ``buckets[-1]`` before admitting).
        """
        n = int(fks[0].shape[0])
        if bucket is None:
            if n > self.buckets[-1]:
                raise ValueError(
                    f"cannot admit {n} rows in one step: top bucket is "
                    f"{self.buckets[-1]} (chunk the batch first)")
            bucket = next(b for b in self.buckets if b >= n)
        elif bucket < n or bucket not in self.buckets:
            raise ValueError(f"bucket {bucket} cannot hold {n} rows "
                             f"(buckets: {self.buckets})")
        return bucket, tuple(
            jnp.asarray(np.pad(f, (0, bucket - n), constant_values=PAD_KEY))
            for f in fks)

    def _execute(self, padded: Tuple[jnp.ndarray, ...], bucket: int, *,
                 record: bool = True) -> jnp.ndarray:
        """Dispatch one bucket program; returns the full padded output.

        Owns the latency/trace bookkeeping: a first call into a bucket is
        dominated by trace + XLA compile and lands in the generation's
        compile record instead of the percentile window (where it would
        masquerade as a p99 outlier); ``record=False`` additionally keeps
        the steady-state wall time out of the bucket window — chunk
        executions of an oversized request are attributed to the whole
        request by the caller, not per chunk.
        """
        traces_before = self._trace_count
        t0 = time.perf_counter()
        out = self._jit(padded, self._state)
        if self._sync_stats:
            # Wall-clock percentiles need a device fence; latency-sensitive
            # callers pass sync_stats=False to keep async dispatch (stats
            # then record dispatch time only).
            jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        if self._trace_count > traces_before:
            self._compile_s[bucket] = dt
        elif record:
            self._lat.setdefault(
                bucket, collections.deque(maxlen=LATENCY_WINDOW)).append(dt)
        return out

    def _normalize(self, requests) -> List[np.ndarray]:
        keys = self.request_keys
        if isinstance(requests, Mapping):
            missing = [k for k in keys if k not in requests]
            if missing:
                raise KeyError(f"request batch missing fk columns {missing}")
            cols = [requests[k] for k in keys]
        else:
            arr = requests
            if isinstance(arr, (np.ndarray, jnp.ndarray)) and arr.ndim == 1:
                cols = [arr]
            else:
                cols = list(arr)
        if len(cols) != len(keys):
            raise ValueError(
                f"expected {len(keys)} fk columns {keys}, got {len(cols)}")
        out = [np.asarray(c, np.int32).reshape(-1) for c in cols]
        n = out[0].shape[0]
        if any(c.shape[0] != n for c in out):
            raise ValueError("ragged fk columns in one request batch")
        for key, c in zip(keys, out):
            if np.any(c == PAD_KEY):
                raise SentinelKeyError(
                    f"request column {key!r} contains the padding sentinel "
                    f"{int(PAD_KEY)} (PAD_KEY): sentinel-valued keys are "
                    "indistinguishable from padded slots and would "
                    "silently score zero")
        return out


def requests_from_rows(fact: Table, q: PredictiveQuery, row_ids
                       ) -> Dict[str, np.ndarray]:
    """Lift fact-row ids into the equivalent FK request batch.

    Bridges the old serving interface (``predict_rows`` on fact rows) onto
    the dynamic runtime: the request carries exactly the fact rows' foreign
    keys, so serving it reproduces ``predict_rows`` for rows that pass the
    fact-side predicates.
    """
    ids = np.asarray(row_ids, np.int64)
    return {a.fk_col: np.asarray(fact.key(a.fk_col))[ids].astype(np.int32)
            for a in q.arms}


def _serving_artifacts(catalog: Mapping[str, Table], q: PredictiveQuery,
                       dims: Sequence[DimSpec], model, backend: str,
                       plan: QueryPlan, *, mesh=None,
                       shard_axis: str = "model",
                       shard_threshold_bytes: Optional[int] = None,
                       pool=None, chains: Sequence[
                           Optional[CollapsedChain]] = (),
                       chain_keys: Sequence[Optional[tuple]] = ()):
    """The quasi-static serving state: prefused/projected tables, per-arm
    PK indices + predicate masks, and (mesh) the placed shards.

    Shared by the cold ``compile_serving`` build and the runtime's
    shape-changing ``refresh`` rebuild, so both paths place and index the
    state identically (placement replanned from the *current* table
    shapes — the divisibility boundary is re-checked on every rebuild).
    Returns ``(arms, h, sharded, plan, pool_refs)``.

    With a ``pool`` (single-device path only), the partials / projected
    feature tables / masks / PK indices are acquired from the shared
    :class:`~.multiquery.ArtifactPool` — the same entries compiled plans
    use, so a serving runtime and a fused compiled query over the same arm
    reference one physical partial.

    ``chains``/``chain_keys`` come from :func:`_serving_dims`: a chained
    arm's dmask is the collapsed chain's validity vector (head liveness,
    hop misses and every predicate along the chain already folded in),
    its nonfused feature table is the virtual matrix, and its PK index is
    built on the *real head table's* name — the virtual PK column is the
    head's, so the entry is shared with compiled plans over the head.
    """
    chains = tuple(chains) + (None,) * (len(dims) - len(chains))
    chain_keys = (tuple(chain_keys)
                  + (None,) * (len(dims) - len(chain_keys)))
    partial_keys: Tuple = ()
    if backend == "fused":
        if pool is not None:
            tables, h, partial_keys = pool.acquire_partials(
                dims, model, chains=chains)
        else:
            pre = prefuse_dims(dims, model)
            tables = pre.partials
            h = pre.h
    else:
        feat_keys = []
        if pool is not None:
            tables = []
            for d, cc in zip(dims, chains):
                if cc is not None:
                    # The virtual matrix IS the projected feature table
                    # (columns == the arm's served features); it lives in
                    # the pool under the chain key, not a features entry.
                    tables.append(cc.table.matrix)
                    feat_keys.append(None)
                    continue
                tbl, tkey = pool.acquire_features(d.dim.name,
                                                  d.feature_cols)
                tables.append(tbl)
                feat_keys.append(tkey)
            tables = tuple(tables)
        else:
            tables = tuple(
                project_columns(d.dim.matrix, d.dim.columns, d.feature_cols)
                for d in dims)
        h = None

    arms = []
    masks = []
    arm_refs = []
    for j, (arm, d, tbl, cc) in enumerate(zip(q.arms, dims, tables,
                                              chains)):
        if pool is not None:
            if cc is not None:
                dmask, mkey = cc.dmask, None
            else:
                dmask, mkey = pool.acquire_dmask(arm.table, arm.preds)
            index, ikey = pool.acquire_pkindex(arm.table, arm.pk_col)
            arm_refs.append((ikey, mkey,
                             feat_keys[j] if backend != "fused" else None,
                             chain_keys[j]))
        else:
            if cc is not None:
                dmask = cc.dmask
            else:
                dmask = d.dim.valid_mask()
                for p in arm.preds:
                    dmask = dmask & p.mask(d.dim)
            index = (None if mesh is not None
                     else pk_index(d.dim.key(arm.pk_col)))
        masks.append(dmask)
        # On the mesh path the global index/table are dead weight: the
        # shard_map forward probes the per-shard slices instead.
        arms.append(_ArmIndex(
            fk_col=arm.fk_col,
            index=index,
            dmask=dmask,
            table=None if mesh is not None else tbl))
    pool_refs = ({"arms": tuple(arm_refs), "partials": tuple(partial_keys)}
                 if pool is not None else {})

    sharded = None
    if mesh is not None:
        specs, plan = place_tables(mesh, tables, plan, axis=shard_axis,
                                   threshold_bytes=shard_threshold_bytes)
        sharded = shard_prefused_partials(
            mesh,
            [(arm.fk_col, d.dim.key(arm.pk_col), dmask, tbl)
             for arm, d, dmask, tbl in zip(q.arms, dims, masks, tables)],
            h, specs, shard_axis=shard_axis)
        if h is not None:
            h = sharded.h
    return tuple(arms), h, sharded, plan, pool_refs


def compile_serving(catalog: Mapping[str, Table], q: PredictiveQuery, *,
                    backend: str = "auto", serve_backend: str = "auto",
                    buckets: Sequence[int] = DEFAULT_BUCKETS,
                    interpret: bool = False, donate: Optional[bool] = None,
                    sync_stats: bool = True,
                    batches_per_update: float = 1000.0,
                    memory_budget_bytes: Optional[int] = None,
                    mesh=None, shard_axis: str = "model",
                    shard_threshold_bytes: Optional[int] = None,
                    pool=None) -> ServingRuntime:
    """Compile ``q``'s online phase over a (batch, fk...) request pytree.

    The quasi-static phase (PK sort, predicate masks, Eq. 1 pre-fusion) runs
    here, once; the returned :class:`ServingRuntime` then serves arbitrary
    request batches through a fixed set of shape buckets with no
    recompilation beyond one trace per bucket.

    ``backend`` picks fused/nonfused execution ("auto" → cost model, sized
    at the top bucket); ``serve_backend`` picks the jnp gathers or the
    Pallas kernel lowering ("auto" → :func:`plan_serving_backend`; pass
    ``"pallas"`` with ``interpret=True`` to exercise the kernels on CPU).
    ``donate`` donates the padded request buffers to the compiled program
    (default: only on accelerators, where donation is supported).
    ``sync_stats=False`` drops the per-call device fence used for wall-clock
    latency percentiles, preserving async dispatch on the hot path (stats
    then record dispatch time only).

    Fact-side state is deliberately absent: requests are *not* fact rows, so
    ``q.fact_preds`` (predicates over fact measures) cannot apply and are
    ignored; dimension-side predicates are folded into the lookup validity.

    ``mesh`` switches on sharded serving: per-arm placement is decided by
    :func:`plan_partition_spec` (replicate below ``shard_threshold_bytes``,
    row-shard over ``shard_axis`` with the ``safe_spec`` divisibility
    fallback above it), buckets round up to multiples of the mesh's DP size
    and each bucket's program runs as one ``shard_map`` of device-local
    probes + gathers.  ``mesh`` is incompatible with
    ``serve_backend="pallas"``.

    ``catalog`` may be a :class:`~repro.core.laq.Catalog`, whose appends
    and column updates the runtime absorbs in place via
    :meth:`ServingRuntime.refresh`; plain mappings are auto-wrapped into a
    read-only Catalog (the pre-Catalog frozen contract — such runtimes
    never have pending deltas and refresh is a no-op).
    """
    if q.model is None:
        raise ValueError("compile_serving requires a model head")
    if q.model_preds:
        raise ValueError(
            "compile_serving does not take prediction filters "
            "(model_preds): serving returns raw predictions per request "
            "row — filter in the aggregate path (compile_query) instead")
    if not q.arms:
        raise ValueError("compile_serving requires at least one star arm")
    for arg, allowed in ((backend, ("auto", "fused", "nonfused")),
                         (serve_backend, ("auto", "jnp", "pallas"))):
        if arg not in allowed:
            raise ValueError(f"backend {arg!r} not one of {allowed}")
    serve_backend = resolve_mesh_serve_backend(serve_backend, mesh)
    if not isinstance(catalog, Catalog):
        warnings.warn(
            "passing a plain mapping to compile_serving is deprecated and "
            "will require an explicit wrap in a future release; construct "
            "a repro.core.laq.Catalog (or go through Session) — see the "
            "migration table in repro.core.query",
            DeprecationWarning, stacklevel=2)
    catalog = Catalog.wrap(catalog)
    for arm in q.arms:   # teach the catalog the join contract (PK columns)
        catalog.note_unique(arm.table, arm.pk_col)
        for lk in arm.links:
            catalog.note_unique(lk.table, lk.pk_col)
    # Pool sharing engages only on the plain single-device path against
    # the pool's own catalog (mesh placement commits arrays to devices;
    # tracer-holding tables must never leak into a cross-plan cache).
    if not (pool is not None and mesh is None and pool.catalog is catalog
            and not holds_tracers(catalog, q)):
        pool = None
    buckets = tuple(sorted({int(b) for b in buckets}))
    if not buckets or buckets[0] < 1:
        raise ValueError(f"buckets must be positive ints, got {buckets!r}")
    if mesh is not None:
        dp = dp_size(mesh)
        buckets = tuple(sorted({-(-b // dp) * dp for b in buckets}))

    dims, chains, chain_keys = _serving_dims(catalog, q, pool=pool)
    dim_rows = []
    for d in dims:
        try:
            dim_rows.append(int(d.dim.nvalid))
        except jax.errors.ConcretizationTypeError:
            dim_rows.append(d.dim.capacity)
    plan = plan_query(q.model, buckets[-1], dim_rows,
                      selectivity=1.0, num_groups=0, out_width=q.model.l,
                      batches_per_update=batches_per_update,
                      memory_budget_bytes=memory_budget_bytes)
    backend = plan.backend if backend == "auto" else backend
    serve_backend = effective_serve_backend(plan, serve_backend, backend,
                                            q.model, len(dims))
    if serve_backend != plan.serve_backend:
        plan = dataclasses.replace(
            plan, serve_backend=serve_backend,
            reason=f"{plan.reason}; serve={serve_backend} (caller override)")

    arms, h, sharded, plan, pool_refs = _serving_artifacts(
        catalog, q, dims, q.model, backend, plan, mesh=mesh,
        shard_axis=shard_axis, shard_threshold_bytes=shard_threshold_bytes,
        pool=pool, chains=chains, chain_keys=chain_keys)

    if donate is None:
        donate = (mesh is None
                  and jax.default_backend() in ("tpu", "gpu"))
    return ServingRuntime(query=q, plan=plan, backend=backend,
                          serve_backend=serve_backend, buckets=buckets,
                          arms=arms, model=q.model, h=h,
                          interpret=interpret, donate=donate,
                          sync_stats=sync_stats, sharded=sharded,
                          catalog=catalog, mesh=mesh, shard_axis=shard_axis,
                          shard_threshold_bytes=shard_threshold_bytes,
                          pool=pool, pool_refs=pool_refs)
