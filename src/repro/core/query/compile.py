"""Lower a ``PredictiveQuery`` to one jitted XLA program.

Offline (quasi-static, runs once per (query, catalog version set)):
  1. selection masks on the fact table and each dimension (``Pred``, §2.2),
  2. factored matching matrices per arm (``join_factored``, Alg. 1 / §3.1),
     with dimension-side predicate masks gathered through the FK pointers —
     the selection vector *folded into* the join validity instead of being
     multiplied through the data,
  3. the model's linear prefix pushed into the dimension tables
     (``prefuse``, Eq. 1/3),
  4. composite group codes + dense group ids (§2.4.2),
  5. the whole-query cost model (``plan_query``) choosing fused/nonfused and
     gather/matmul backends from the measured selectivity.

Online (the single jitted program): Σⱼ Iⱼ Pⱼ gathers (+ ``== h`` for trees),
value expressions, and the group-by reduction composed directly on the fused
prediction output — no intermediate table ever materializes on the fused
path.  A selective model-free group-by reduces only its live rows, gathered
from the resident fact at a row list that step 4 keeps.

Incremental maintenance: every quasi-static array the online programs read
(matrices, pointers, masks, partials, group ids) is threaded through the
jitted functions as one *state pytree argument* rather than closed over —
closure capture would bake the arrays into the jaxpr as constants and force
a retrace on every append.  :meth:`CompiledQuery.refresh` applies pending
:class:`~repro.core.laq.catalog.Catalog` deltas to that state (sorted-merge
``PKIndex.extend``, delta ``prefuse_rows``, mask scatters): same shapes ⇒
the swapped state hits the same jit cache, no retrace; capacity growth (or
select-compaction / group overflow / live rows outgrowing the compacted row
list) falls back to a recompile with a named ``explain()`` reason.
"""
from __future__ import annotations

import collections
import dataclasses
import warnings
from typing import Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ... import telemetry
from ..fusion.operators import DecisionTreeGEMM
from ..fusion.pipeline import (PrefusedStar, extend_prefused, predict_fused,
                               predict_fused_kernel, predict_fused_matmul,
                               predict_nonfused, predict_nonfused_kernel,
                               predict_nonfused_matmul, prefuse)
from ..laq.aggregation import (auto_num_groups, composite_code,
                               groupby_codes, groupby_rows, matmul_aggregate,
                               segment_aggregate, segment_reduce)
from ..laq.catalog import Catalog, CatalogHistoryError, changed_spans
from ..laq.join import FactoredJoin, PKIndex, pk_index
from ..laq.projection import project_columns
from ..laq.selection import select
from ..laq.star import DimSpec, StarJoin
from ..laq.table import PAD_KEY, Table
from .explain import ExplainReport
from .ir import (AGG_OPS, PREDICTION, Aggregate, ArmSpec, PredictiveQuery,
                 eval_value)
from .multiquery import holds_tracers
from .planner import (QueryPlan, effective_serve_backend,
                      estimate_query_cost, place_tables, plan_fact_backend,
                      plan_chain_materialization, plan_compaction,
                      plan_query, plan_streaming, resolve_mesh_serve_backend)
from .rewrite import _FILTER_FNS, rewrite_query
from .snowflake import (CollapsedChain, chain_dirty_heads, chain_tables,
                        flat_arm, link_parents, participating_tables,
                        refresh_chain, resolve_chain, virtual_name)
from .sharding import (make_predict_rows_forward, predict_rows_state,
                       shard_prefused_partials)
from .streaming import StreamExecutor, assert_pool_dimension_side


@dataclasses.dataclass
class CompiledQuery:
    """An executable plan: one jitted program + its quasi-static artifacts.

    The artifacts live in ``_state`` (a pytree the jitted programs take as
    an argument); ``catalog``/``versions`` record the data they were built
    against, and :meth:`refresh` brings them up to the catalog's current
    versions in place — by delta when shapes allow, by recompile otherwise.
    """

    query: PredictiveQuery
    plan: QueryPlan
    backend: str                    # "fused" | "nonfused"
    join_backend: str               # "gather" | "matmul"
    agg_backend: str                # "segment" | "matmul"
    serve_backend: str              # "jnp" | "pallas"
    star: StarJoin
    prefused: Optional[PrefusedStar]
    selectivity: float              # measured fraction of surviving fact rows
    group_codes: Optional[jnp.ndarray]   # sorted unique composite codes
    _rows: jnp.ndarray                   # surviving-row count
    _run: callable
    _predict: Optional[callable]
    _predict_rows: Optional[callable]
    _state: Dict = dataclasses.field(default_factory=dict)
    catalog: Optional[Catalog] = None
    versions: Dict[str, int] = dataclasses.field(default_factory=dict)
    _indices: Tuple[PKIndex, ...] = ()   # per-arm PK indices (extendable)
    _source: Optional[PredictiveQuery] = None  # q as originally passed
    # Per-arm collapsed snowflake chains (None for flat arms; empty tuple
    # for all-flat queries).  ``query`` holds the *flattened* arms — the
    # chains carry the real head/link tables and the composed pointers the
    # refresh and group-by paths need.
    _chains: Tuple[Optional[CollapsedChain], ...] = ()
    _opts: Dict = dataclasses.field(default_factory=dict)
    _sp: Optional[object] = None         # ShardedPrefusedPartials (mesh path)
    # Bounded refresh-decision trail appended to plan.reason: a long-lived
    # streaming plan must not grow its explain() string without limit.
    _refresh_notes: "collections.deque" = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=8))
    # Session-owned ArtifactPool sharing: the pool this plan acquired from
    # (None when compiled standalone) and the keys it holds references to —
    # {"arms": ((pkindex, join, dmask|None) per arm), "partials": (keys,)}.
    # ``close()`` releases them; eviction is an optimization, so a compile
    # that raises mid-way leaking a reference is benign retention, never a
    # correctness hazard.
    _pool: Optional[object] = None
    _pool_refs: Dict = dataclasses.field(default_factory=dict)
    # The raw (un-jitted) online closure, kept so Session.run_all can vmap
    # structurally compatible plans into one stacked program.
    _online_fn: Optional[callable] = None
    # Out-of-core driver (streaming.StreamExecutor) when the plan streams
    # the fact axis; ``run()`` dispatches through it instead of the
    # in-core jitted program.  None on the in-core path.
    _stream: Optional[object] = None
    # Per-rule trail from core.query.rewrite ("" entries never occur; empty
    # tuple = no rule fired or rewrite="off").  ``query`` holds the
    # *rewritten* IR the plan executes; ``_source`` the query as written.
    _rewrites: Tuple[str, ...] = ()
    # Backend of the fact-sized programs (``run``/``predictions``): "pallas"
    # where they run a kernel (planner.plan_fact_backend), else "jnp".
    fact_backend: str = "jnp"

    @property
    def is_traced(self) -> bool:
        """True when compiled under an outer trace — such a plan holds
        tracers and must not be cached/reused outside that trace."""
        return isinstance(self._rows, jax.core.Tracer)

    def run(self) -> Dict[str, jnp.ndarray]:
        """Execute the query; returns aggregates (+ "groups", "rows").

        Streaming plans (``stream_chunk_rows``) fold the fact axis chunk by
        chunk through the same fused program — grouped aggregates and
        ungrouped count/min/max come back bit-exact vs the in-core path
        (see :mod:`repro.core.query.streaming`).
        """
        with telemetry.span("query.dispatch"):
            if self._stream is not None:
                out = dict(self._stream.run())
            else:
                out = dict(self._run(self._state))
            if self.group_codes is not None:
                out["groups"] = self.group_codes
            out["rows"] = self._rows
            return out

    def predictions(self) -> jnp.ndarray:
        """The (fact_capacity, l) prediction matrix (model queries only)."""
        if self._predict is None:
            raise ValueError("query has no model")
        return self._predict(self._state)

    def predict_rows(self, row_ids: jnp.ndarray) -> jnp.ndarray:
        """Batched serving: predictions for a batch of fact row ids.

        On the fused backend this is |arms| gathers into the prefused
        partials + adds — the paper's online phase, at request batch size.
        Out-of-range ids follow ``jnp.take`` fill semantics (NaN rows);
        negative ids wrap like numpy.
        """
        if self._predict_rows is None:
            raise ValueError("query has no model")
        return self._predict_rows(row_ids, self._state)

    # -- introspection / lifecycle ------------------------------------------
    def _pool_keys(self) -> list:
        """Every pool key this plan holds a reference to (with multiplicity)."""
        keys = [k for ref in self._pool_refs.get("arms", ()) for k in ref
                if k is not None]
        keys.extend(self._pool_refs.get("partials", ()))
        return keys

    def explain(self) -> ExplainReport:
        """Structured plan/refresh report (``str()`` gives the legacy line)."""
        return ExplainReport(
            kind="compiled", backend=self.backend,
            join_backend=self.join_backend, agg_backend=self.agg_backend,
            serve_backend=self.serve_backend,
            plan_reason=getattr(self, "_base_reason", self.plan.reason),
            trail=tuple(self._refresh_notes),
            shared_artifacts=tuple(self._pool_keys()),
            extras=(("selectivity", self.selectivity),
                    ("fact_backend", self.fact_backend),
                    ("rewrites", self._rewrites),
                    ("stream", self._stream.describe()
                     if self._stream is not None else None)))

    def close(self) -> None:
        """Release this plan's shared-artifact references (idempotent).

        ``Session.evict`` calls this when dropping a cached plan; the pool
        evicts an artifact only when its *last* referencing plan closes.
        """
        if self._pool is not None and self._pool_refs:
            self._pool.release(self._pool_keys())
        self._pool_refs = {}

    # -- incremental maintenance --------------------------------------------
    def _participating(self) -> Tuple[str, ...]:
        return participating_tables(self._source or self.query)

    def refresh(self) -> str:
        """Apply pending catalog deltas to the compiled artifacts, in place.

        Appends that fit the tables' existing capacity (and non-key column
        updates) take the delta path: per-arm ``PKIndex.extend`` sorted
        merges, probes of only the appended keys/rows, ``prefuse_rows``
        over only the new dimension rows, and in-place mask/group-id
        rebuilds — all shape-preserving, so the already-compiled programs
        keep serving from the jit cache with zero retraces.  Capacity
        growth, select-compaction, or group-code overflow fall back to a
        full recompile; either way the decision is appended to
        ``plan.reason`` (visible via ``explain``) and returned.
        """
        if self.catalog is None:
            return self._note("refresh=no-op(detached: no catalog)")
        if self.is_traced:
            raise ValueError("cannot refresh a traced plan: it holds "
                             "tracers from an outer jit")
        cat = self.catalog
        try:
            changed = {n: cat.deltas_since(n, self.versions.get(n, 0))
                       for n in self._participating()}
        except CatalogHistoryError:
            return self._recompile("history-compacted: plan staler than "
                                   "the delta log")
        changed = {n: d for n, d in changed.items() if d}
        if not changed:
            return self._note("refresh=no-op(versions unchanged)")
        if self._opts.get("select_capacity") is not None:
            return self._recompile("select-compaction rebinds the fact")
        if any(changed_spans(d)[2] for d in changed.values()):
            # Compaction reuses the capacity-growth contract (row ids
            # changed shape-compatibly ⇒ every pointer artifact rebuilds),
            # but the explain() reason names it distinctly.
            compacted = sorted(n for n, d in changed.items()
                               if any(t.kind == "compact" for t in d))
            if compacted:
                return self._recompile(
                    f"compaction:{','.join(compacted)} rewrote row ids")
            grown = sorted(n for n, d in changed.items()
                           if changed_spans(d)[2])
            return self._recompile(f"capacity-growth:{','.join(grown)}")
        try:
            return self._refresh_delta(changed)
        except _GroupOverflow:
            return self._recompile("group-overflow: live codes exceed the "
                                   "compiled num_groups")
        except _CompactOverflow as e:
            return self._recompile(f"compact-overflow: {e}")

    def _note(self, line: str) -> str:
        if not self._refresh_notes:
            self._base_reason = self.plan.reason
        self._refresh_notes.append(line)
        self.plan = dataclasses.replace(
            self.plan, reason="; ".join([self._base_reason,
                                         *self._refresh_notes]))
        return line

    def _recompile(self, why: str) -> str:
        # Recompile FIRST (the fresh plan re-acquires shared artifacts,
        # keeping their refcounts above zero), then release the old
        # references — releasing first would evict artifacts the fresh
        # compile is about to rebuild.
        old_pool, old_keys = self._pool, self._pool_keys()
        fresh = compile_query(self.catalog, self._source, **self._opts)
        for f in dataclasses.fields(self):
            setattr(self, f.name, getattr(fresh, f.name))
        if old_pool is not None:
            old_pool.release(old_keys)
        return self._note(f"refresh=recompile({why})")

    def _refresh_delta(self, changed) -> str:
        if self._pool is not None and self._pool_refs.get("arms"):
            return self._refresh_delta_pooled(changed)
        q = self.query
        cat = self.catalog
        fact = cat[q.fact]
        fspan, _, _, _ = (changed_spans(changed[q.fact])
                          if q.fact in changed else (None, (), False, ()))

        # Re-collapse chains whose real tables changed (cached hops on
        # unchanged tables are reused); the per-arm pointer work below then
        # runs against the *head* table — the fact joins the head's PK, at
        # head granularity, chain or no chain.
        chains = (list(self._chains) if self._chains
                  else [None] * len(q.arms))
        stale = set(changed)
        for j, ch in enumerate(chains):
            if ch is not None and stale & set(chain_tables(ch.arm)):
                chains[j] = refresh_chain(cat, ch, stale)
        overlay = cat
        if any(c is not None for c in chains):
            overlay = {**cat, **{c.table.name: c.table
                                 for c in chains if c is not None}}

        ptrs = [np.array(p) for p in self._state["ptrs"]]
        founds = [np.array(f) for f in self._state["founds"]]
        indices = list(self._indices)
        dirty_rows = []
        for j, arm in enumerate(q.arms):
            ch = chains[j]
            head = ch.arm.table if ch is not None else arm.table
            dim = cat[head]
            # Deleted ids need no pointer/index/prefuse work: a tombstone
            # keeps the row's slot, key and data, so only the validity fold
            # (recomputed below by _assemble_star) changes.
            span, dirty, _, _ = (
                changed_spans(changed[head])
                if head in changed else (None, (), False, ()))
            ids = set(dirty)
            if span is not None:
                lo, hi = span
                ids.update(range(lo, hi))
                indices[j] = indices[j].extend(
                    dim.key(arm.pk_col)[lo:hi], np.arange(lo, hi))
                # Fact rows whose FK now hits an appended PK: probe only the
                # appended key block (O(n log m)), scatter into ptr/found.
                nk = np.asarray(dim.key(arm.pk_col))[lo:hi]
                order = np.argsort(nk, kind="stable")
                snk, srow = nk[order], (lo + order).astype(np.int32)
                fk = np.asarray(fact.key(arm.fk_col))
                pos = np.searchsorted(snk, fk)
                posc = np.clip(pos, 0, len(snk) - 1)
                hit = (snk[posc] == fk) & (fk != PAD_KEY)
                ptrs[j] = np.where(hit, srow[posc], ptrs[j]).astype(np.int32)
                founds[j] = founds[j] | hit
            if fspan is not None:
                # Appended fact rows: probe their FKs against the (already
                # extended) full index, scatter into the new row span.
                flo, fhi = fspan
                fj = indices[j].probe(fact.key(arm.fk_col)[flo:fhi])
                ptrs[j][flo:fhi] = np.asarray(fj.ptr)
                founds[j][flo:fhi] = np.asarray(fj.found)
            if ch is not None:
                # Sub-dimension deltas dirty the head rows whose composed
                # pointers resolve into the touched link rows — those
                # virtual-matrix rows (and only those) differ from the old
                # collapse, so the partial scatter stays bit-exact vs cold.
                touched = {}
                for t in chain_tables(ch.arm):
                    if t in changed:
                        tspan, tdirty, _, _ = changed_spans(changed[t])
                        tids = set(tdirty)
                        if tspan is not None:
                            tids.update(range(tspan[0], tspan[1]))
                        if tids:
                            touched[t] = np.asarray(sorted(tids), np.int64)
                dh = chain_dirty_heads(ch, touched)
                if dh is not None:
                    ids.update(int(i) for i in dh)
            dirty_rows.append(
                np.asarray(sorted(ids), np.int32) if ids else None)

        # Validity, prefuse partials and group ids rebuild from the updated
        # pointers — eager element-wise work, never a retrace.  The mask
        # fold is the same _assemble_star the cold compile runs, so the
        # refreshed validity is bitwise the cold rebuild's by construction.
        joins = tuple(FactoredJoin(jnp.asarray(p), jnp.asarray(f))
                      for p, f in zip(ptrs, founds))
        dmasks = (tuple(c.dmask if c is not None else None for c in chains)
                  if any(c is not None for c in chains) else None)
        star, valid = _assemble_star(overlay, q, joins, dmasks=dmasks)

        prefused = self.prefused
        if prefused is not None:
            prefused = extend_prefused(prefused, star.dims, q.model,
                                       dirty_rows)
        self._indices = tuple(indices)
        self._chains = tuple(chains) if any(
            c is not None for c in chains) else ()
        return self._rebind(changed, star, valid, prefused,
                            "shapes kept, jit cache reused")

    def _refresh_delta_pooled(self, changed) -> str:
        """Delta refresh for pool-backed plans.

        The shared quasi-static artifacts (PK indices, join pointers,
        predicate masks, prefused partials) come from the pool, which
        delta-updates each stale entry *exactly once* no matter how many
        plans reference it — so N plans over one registry pay O(distinct
        artifacts), not O(plans), for the probe/prefuse work.  Only the
        per-plan residue — the validity fold, group codes and state-pytree
        rebuild — runs here.
        """
        q = self.query
        cat = self.catalog
        pool = self._pool
        chains = (list(self._chains) if self._chains
                  else [None] * len(q.arms))
        indices, joins, dmasks = [], [], []
        for j, (ikey, jkey, mkey) in enumerate(self._pool_refs["arms"]):
            indices.append(pool.get(ikey))
            ptr, found = pool.get(jkey)
            joins.append(FactoredJoin(ptr, found))
            mval = pool.get(mkey) if mkey is not None else None
            if isinstance(mval, CollapsedChain):
                # Chained arm: the mask slot holds the pooled collapsed
                # chain — the pool re-collapsed it at most once for every
                # plan sharing it; the dmask and virtual table fall out.
                chains[j] = mval
                mval = mval.dmask
            dmasks.append(mval)
        overlay = cat
        if any(c is not None for c in chains):
            overlay = {**cat, **{c.table.name: c.table
                                 for c in chains if c is not None}}
        self._chains = tuple(chains) if any(
            c is not None for c in chains) else ()
        star, valid = _assemble_star(overlay, q, tuple(joins),
                                     dmasks=tuple(dmasks))
        prefused = self.prefused
        pkeys = self._pool_refs.get("partials", ())
        if pkeys:
            prefused = PrefusedStar(tuple(pool.get(k) for k in pkeys),
                                    prefused.h)
        self._indices = tuple(indices)
        return self._rebind(changed, star, valid, prefused,
                            "pooled artifacts, jit cache reused")

    def _rebind(self, changed, star, valid, prefused, how: str) -> str:
        """Shared delta-refresh tail: group codes, counts, state pytree."""
        q = self.query
        cat = self.catalog
        rows = jnp.sum(valid.astype(jnp.int32))
        uniq = gid = live = gid_live = None
        if q.group_keys:
            cols, bounds = _group_columns(cat, q, star, self._chains)
            codes = composite_code(cols, bounds, valid)
            # A compacted plan keeps its row list's length, so the delta
            # path stays zero-retrace while the live rows fit it.
            cap = (None if self._state["rows"] is None
                   else int(self._state["rows"].shape[0]))
            if cap is not None and int(rows) > cap:
                raise _CompactOverflow(f"{int(rows)} live rows exceed the "
                                       f"compiled row list of {cap}")
            try:
                if cap is None:
                    uniq, gid = groupby_codes(codes, q.num_groups)
                else:
                    uniq, live, gid_live = groupby_rows(
                        codes, q.num_groups, cap)
            except ValueError as e:
                raise _GroupOverflow(str(e)) from e

        n_fact = _static_int(star.fact.nvalid, star.fact.capacity)
        self.star = star
        self.prefused = prefused
        self.group_codes = uniq
        self._rows = rows
        self.selectivity = float(rows) / max(n_fact, 1)
        state = _query_state(star, prefused, gid, live, gid_live)
        if self._sp is not None:
            tables = (list(prefused.partials) if self.backend == "fused"
                      else [project_columns(d.dim.matrix, d.dim.columns,
                                            d.feature_cols)
                            for d in star.dims])
            state["sharded"] = predict_rows_state(
                self._sp, tables, [fj.ptr for fj in star.joins],
                [fj.found for fj in star.joins], valid)
        self._state = state
        if self._stream is not None:
            # Same capacity ⇒ same chunk shapes ⇒ the executor's jit cache
            # keeps serving: a streamed refresh is zero-retrace too.
            self._stream.rebind(state)
        self.versions = {n: cat.version(n) for n in self._participating()}
        touched = ",".join(f"{n}+{len(changed[n])}"
                           for n in sorted(changed))
        return self._note(f"refresh=delta({touched}; {how})")


class _GroupOverflow(ValueError):
    """Internal: live group codes outgrew the compiled num_groups."""


class _CompactOverflow(Exception):
    """Internal: live rows outgrew a compacted plan's row list."""


def _static_int(x, default: int) -> int:
    """``int(x)`` when concrete, ``default`` when ``x`` is a tracer."""
    try:
        return int(x)
    except jax.errors.ConcretizationTypeError:
        return default


def _assemble_star(catalog: Mapping[str, Table], q: PredictiveQuery,
                   joins: Tuple[FactoredJoin, ...],
                   dmasks: Optional[Tuple] = None
                   ) -> Tuple[StarJoin, jnp.ndarray]:
    """Fold every selection mask into the combined validity, given resolved
    per-arm joins.

    The single definition of predicate semantics (fact preds AND-fold, dim
    preds gathered through the FK pointers) shared by the cold compile and
    the delta refresh — the two must agree bitwise or refresh loses its
    ≡-cold-rebuild contract.  ``dmasks`` optionally supplies precomputed
    per-arm dimension masks (pool-shared); ``Pred.mask`` folds the table's
    validity itself, so a pooled ``valid ∧ preds`` mask is boolean-equal to
    the AND-fold done here.
    """
    fact = catalog[q.fact]
    valid = fact.valid_mask()
    for p in q.fact_preds:
        valid = valid & p.mask(fact)
    dims = []
    for j, (arm, fj) in enumerate(zip(q.arms, joins)):
        dim = catalog[arm.table]
        dims.append(DimSpec(dim, arm.fk_col, arm.pk_col, arm.feature_cols))
        ok = fj.found
        dmask = dmasks[j] if dmasks is not None else None
        if dmask is None and arm.preds:
            dmask = arm.preds[0].mask(dim)
            for p in arm.preds[1:]:
                dmask = dmask & p.mask(dim)
        if dmask is None and dim.deleted is not None:
            # ``Pred.mask`` folds the dimension's validity (tombstones
            # included), but an arm with no predicates has no mask to fold
            # through — gather the live mask explicitly so fact rows joined
            # to a tombstoned dimension row drop out.
            dmask = dim.valid_mask()
        if dmask is not None:
            ok = ok & jnp.take(dmask, fj.ptr)
        valid = valid & ok
    star = StarJoin(fact=fact, dims=tuple(dims), joins=tuple(joins),
                    row_valid=valid)
    if q.model_preds:
        # Prediction filters fold into the validity like any predicate: the
        # predictions are quasi-static (functions of the joined dimension
        # rows), so the mask is offline work and both delta-refresh paths
        # inherit it by re-running this fold.  Invalid rows may see a
        # different (zeroed-features) prediction than they would if valid —
        # irrelevant under the AND: they stay invalid either way.
        preds = q.model.apply(star.materialize())
        for f in q.model_preds:
            valid = valid & _FILTER_FNS[f.op](preds[:, f.output],
                                              jnp.float32(f.value))
        star = dataclasses.replace(star, row_valid=valid)
    return star, valid


def _resolve_star(catalog: Mapping[str, Table], q: PredictiveQuery,
                  pool=None, chains: Tuple = (), chain_keys: Tuple = ()
                  ) -> Tuple[StarJoin, jnp.ndarray, Tuple[PKIndex, ...],
                             Tuple[tuple, ...]]:
    """Joins + combined validity with every selection mask folded in.

    Also returns the per-arm ``PKIndex`` — the quasi-static half of each
    join, kept for ``refresh`` to extend instead of re-sorting.  With a
    ``pool``, indices/pointers/masks are acquired from the shared
    :class:`~.multiquery.ArtifactPool` (computed once per distinct arm
    across all plans) and the per-arm reference keys are returned as the
    fourth element (empty tuple when unpooled).

    Chained arms (``chains[j]`` not None) index and probe against the
    *real* head table name, so two queries joining the same head through
    different chains still share one PK index and fact probe; their dmask
    is the chain's folded validity (the pool reference in the mask slot
    is the chain entry's key).
    """
    fact = catalog[q.fact]
    joins, indices, arm_refs, dmasks = [], [], [], []
    any_chain = any(c is not None for c in chains)
    for j, arm in enumerate(q.arms):
        ch = chains[j] if j < len(chains) else None
        head = ch.arm.table if ch is not None else arm.table
        if pool is not None:
            idx, ikey = pool.acquire_pkindex(head, arm.pk_col)
            (ptr, found), jkey = pool.acquire_join(
                q.fact, arm.fk_col, head, arm.pk_col)
            fj = FactoredJoin(ptr, found)
            if ch is not None:
                dmask, mkey = ch.dmask, chain_keys[j]
            elif arm.preds:
                dmask, mkey = pool.acquire_dmask(arm.table, arm.preds)
            else:
                dmask = mkey = None
            arm_refs.append((ikey, jkey, mkey))
            dmasks.append(dmask)
        else:
            idx = pk_index(catalog[head].key(arm.pk_col))
            fj = idx.probe(fact.key(arm.fk_col))
            dmasks.append(ch.dmask if ch is not None else None)
        joins.append(fj)
        indices.append(idx)
    star, valid = _assemble_star(
        catalog, q, tuple(joins),
        dmasks=(tuple(dmasks) if pool is not None or any_chain else None))
    return star, valid, tuple(indices), tuple(arm_refs)


def _group_columns(catalog: Mapping[str, Table], q: PredictiveQuery,
                   star: StarJoin, chains: Tuple = ()):
    """Exact int32 group-key columns, gathered through the arm pointers.

    Chained arms register their *real* head name plus every link table:
    a sub-dimension group key composes the fact→head pointers with the
    chain's head→link pointers (associativity again — the composition is
    the flat fact→link join's pointer array).  Misses gather row 0, which
    is masked by ``composite_code``'s validity fold like any flat miss.
    """
    arm_ptr = {}
    for j, (a, fj) in enumerate(zip(q.arms, star.joins)):
        ch = chains[j] if j < len(chains) else None
        if ch is None:
            arm_ptr[a.table] = fj.ptr
        else:
            arm_ptr[ch.arm.table] = fj.ptr
            for name, lptr, _found in ch.link_ptrs:
                arm_ptr[name] = jnp.take(lptr, fj.ptr)
    cols, bounds = [], []
    for gk in q.group_keys:
        if gk.table == "fact":
            c = star.fact.key(gk.col)
        else:
            c = jnp.take(catalog[gk.table].key(gk.col), arm_ptr[gk.table])
        cols.append(c - jnp.int32(gk.offset))
        bounds.append(gk.bound)
    return cols, bounds


def _head_attrs(model) -> Dict:
    """A model head's kind and shape, as span attrs (``p`` is 0 for a
    linear head, which has no tree nodes)."""
    tree = isinstance(model, DecisionTreeGEMM)
    return {"head": "tree" if tree else "linear", "k": model.k,
            "l": model.l, "p": model.p if tree else 0}


def _fact_row_bytes(fact: Table, q: PredictiveQuery, n_arms: int,
                    out_width: int) -> int:
    """Per-fact-row working-set bytes of the online program.

    State leaves (matrix columns, exact keys, per-arm pointer+found,
    validity, group id) plus the fact-sized intermediates the program
    materializes (prediction rows, per-aggregate masked value temps) — the
    quantity the streaming planner compares against the device budget.
    """
    base = fact.ncols * 4 + len(fact.keys) * 4 + n_arms * 5 + 1 + 4
    inter = ((out_width * 4 if q.model is not None else 0)
             + 4 * max(len(q.aggregates), 1))
    return base + inter


def _check_aggregates(q: PredictiveQuery):
    if not q.aggregates:
        raise ValueError("query has no aggregates")
    names = [a.name for a in q.aggregates]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate aggregate names {names}: each "
                         "aggregate needs a distinct result column name")
    reserved = {"rows", "groups"} & set(names)
    if reserved:
        raise ValueError(f"aggregate names {sorted(reserved)} collide with "
                         "the reserved result keys 'rows'/'groups'")
    for agg in q.aggregates:
        if agg.op not in AGG_OPS:
            raise ValueError(
                f"aggregate op {agg.op!r} (aggregate {agg.name!r}) not one "
                f"of {list(AGG_OPS)}")
        if agg.value == PREDICTION and q.model is None:
            raise ValueError("PREDICTION aggregate requires a model")


# --------------------------------------------------------------------------
# Quasi-static state as a pytree (the jitted programs' data argument)
# --------------------------------------------------------------------------
def _query_state(star: StarJoin, prefused: Optional[PrefusedStar],
                 gid: Optional[jnp.ndarray],
                 rows: Optional[jnp.ndarray] = None,
                 gid_live: Optional[jnp.ndarray] = None) -> Dict:
    """Every array the online programs read, as one swappable pytree.

    ``refresh`` replaces leaves with same-shape updates; because these are
    jit *arguments* (not closure constants), the swapped state re-dispatches
    into the already-compiled executables.  A grouped plan carries either
    ``gid`` (one id per fact row, the masked lowering) or ``rows`` and
    ``gid_live`` (its live rows and their ids, the compacted lowering).
    """
    return {
        "fact_matrix": star.fact.matrix,
        "valid": star.row_valid,
        "ptrs": tuple(fj.ptr for fj in star.joins),
        "founds": tuple(fj.found for fj in star.joins),
        "dim_mats": tuple(d.dim.matrix for d in star.dims),
        "partials": (tuple(prefused.partials)
                     if prefused is not None else None),
        "h": prefused.h if prefused is not None else None,
        "gid": gid,
        "rows": rows,
        "gid_live": gid_live,
        "sharded": None,
    }


def _star_view(star0: StarJoin, state: Dict) -> StarJoin:
    """The StarJoin skeleton rebound onto the state pytree's arrays."""
    fact = dataclasses.replace(star0.fact, matrix=state["fact_matrix"])
    dims = tuple(
        dataclasses.replace(d, dim=dataclasses.replace(d.dim, matrix=m))
        for d, m in zip(star0.dims, state["dim_mats"]))
    joins = tuple(FactoredJoin(p, f)
                  for p, f in zip(state["ptrs"], state["founds"]))
    return StarJoin(fact=fact, dims=dims, joins=joins,
                    row_valid=state["valid"])


def _prefused_view(state: Dict) -> Optional[PrefusedStar]:
    if state["partials"] is None:
        return None
    return PrefusedStar(tuple(state["partials"]), state["h"])


def _program_state(state: Dict) -> Dict:
    """The state subtree the single-device programs take.

    The ``"sharded"`` subtree holds mesh-committed arrays; feeding those
    into a single-device jit alongside host arrays would raise a device
    mismatch, so each program crosses the jit boundary with exactly the
    arrays it reads.
    """
    return {k: v for k, v in state.items() if k != "sharded"}


@telemetry.span("plan.build")
def compile_query(catalog: Mapping[str, Table], q: PredictiveQuery, *,
                  backend: str = "auto", join_backend: str = "auto",
                  agg_backend: str = "auto", serve_backend: str = "auto",
                  select_capacity: Optional[int] = None,
                  batches_per_update: float = 1000.0,
                  memory_budget_bytes: Optional[int] = None,
                  stream_chunk_rows=None,
                  chain_strategy: str = "auto",
                  rewrite: str = "on",
                  interpret: bool = False, mesh=None,
                  shard_axis: str = "model",
                  shard_threshold_bytes: Optional[int] = None,
                  pool=None) -> CompiledQuery:
    """Plan + lower ``q`` against ``catalog`` into one jitted program.

    ``catalog`` may be a :class:`~repro.core.laq.Catalog` — the versioned
    data surface whose appends the compiled plan can absorb via
    :meth:`CompiledQuery.refresh` — or any plain ``Mapping[str, Table]``,
    which is auto-wrapped into a *read-only* Catalog for back-compat (the
    pre-Catalog frozen-dict contract; such plans never have pending deltas).

    All of ``q.aggregates`` lower into that one program over the shared
    join/model work: ``sum``/``count``/``mean``/``min``/``max``, with mean
    as a fused sum/count (one count reduction shared across every
    count/mean aggregate) and min/max through segment ops on either
    aggregation backend.  ``q.num_groups == "auto"`` sizes the group
    dimension from the measured live code domain (offline concrete path
    only — see :func:`~repro.core.laq.aggregation.auto_num_groups`).

    ``backend`` / ``join_backend`` / ``agg_backend`` override the planner
    ("auto" defers to the cost model); explicit "matmul" backends give the
    paper-faithful reference lowering used by tests and benchmarks.
    ``serve_backend`` picks the physical kernel for the *serving* paths —
    ``predict_rows`` always, and ``predictions``/``run`` when the join
    backend is "gather" (the dense "matmul" join is its own paper-faithful
    lowering) and, for the fused gather, the fact fits one kernel call
    (:func:`~.planner.plan_fact_backend`, named in ``plan.reason``):
    "pallas" lowers the fused gather-sum onto ``fused_star_gather`` and
    non-fused trees onto ``tree_predict`` ("auto" picks it on TPU when the
    shapes fit the block specs); ``interpret=True`` runs the kernels in
    interpret mode so the lowering is testable on CPU.

    ``stream_chunk_rows`` turns ``run()`` out-of-core: the fact axis streams
    host→device in chunks of that many rows (``"auto"`` sizes chunks to
    ``memory_budget_bytes``; the default ``None`` streams only when the
    budget is set and the fact working set exceeds it) through the fused
    online program, folding per-chunk partial aggregates bit-exactly for
    grouped aggregates and ungrouped count/min/max — see
    :mod:`repro.core.query.streaming`.  The serving paths
    (``predict_rows``) are request-batched and unaffected.

    A model-free group-by whose measured selectivity is at most the
    planner's ``COMPACT_AGG_MAX_SELECTIVITY`` keeps its live rows and their
    group ids offline, and its program aggregates over those rows alone,
    read from the resident fact on every call (``agg=compact`` in
    ``explain()``; :func:`~.planner.plan_compaction`).

    ``select_capacity`` applies the fact predicates by ``mask_select``
    compaction (§2.2) *before* the joins: surviving rows are packed into a
    fixed buffer of that many rows, shrinking every online shape — the right
    call for very selective queries.  Row ids seen by ``predict_rows`` then
    index the compacted table.

    ``mesh`` shards the *serving* path: each arm's quasi-static row table
    (prefused partial / projected features) is placed per
    ``plan_partition_spec`` and ``predict_rows`` becomes one ``shard_map``
    of device-local gathers + a psum (``core.query.sharding``), bit-exact
    vs the single-device program.  The whole-query aggregate program
    (``run``/``predictions``) stays single-device — it is fact-sized, not
    partial-sized.  ``mesh`` is incompatible with ``serve_backend="pallas"``.
    """
    for name, arg, allowed in (
            ("backend", backend, ("auto", "fused", "nonfused")),
            ("join_backend", join_backend, ("auto", "gather", "matmul")),
            ("agg_backend", agg_backend, ("auto", "segment", "matmul")),
            ("serve_backend", serve_backend, ("auto", "jnp", "pallas")),
            ("chain_strategy", chain_strategy,
             ("auto", "through", "materialize")),
            ("rewrite", rewrite, ("on", "off"))):
        if arg not in allowed:
            raise ValueError(f"{name} {arg!r} not one of {allowed}")
    serve_backend = resolve_mesh_serve_backend(serve_backend, mesh)
    _check_aggregates(q)
    if not isinstance(catalog, Catalog):
        warnings.warn(
            "passing a plain mapping to compile_query is deprecated and "
            "will require an explicit wrap in a future release; construct "
            "a repro.core.laq.Catalog (or go through Session) — see the "
            "migration table in repro.core.query",
            DeprecationWarning, stacklevel=2)
    cat0 = Catalog.wrap(catalog)
    for arm in q.arms:   # teach the catalog the join contract (PK columns)
        cat0.note_unique(arm.table, arm.pk_col)
        for lk in arm.links:
            cat0.note_unique(lk.table, lk.pk_col)
    source_q = q
    opts = dict(backend=backend, join_backend=join_backend,
                agg_backend=agg_backend, serve_backend=serve_backend,
                select_capacity=select_capacity,
                batches_per_update=batches_per_update,
                memory_budget_bytes=memory_budget_bytes,
                stream_chunk_rows=stream_chunk_rows,
                chain_strategy=chain_strategy, rewrite=rewrite,
                interpret=interpret, mesh=mesh, shard_axis=shard_axis,
                shard_threshold_bytes=shard_threshold_bytes, pool=pool)
    # Query/model co-optimization (core.query.rewrite): run the exact
    # rewrite rules over the IR, then keep whichever of (original,
    # rewritten) the cost model scores cheaper.  The rules read arrays, so
    # they are skipped under an outer trace; ``_source`` stays the original
    # query, so refresh-by-recompile re-runs the rewrite from scratch.
    rewrite_trail: Tuple[str, ...] = ()
    if rewrite == "on" and not holds_tracers(cat0, q):
        with telemetry.span("plan.rewrite"):
            rw = rewrite_query(cat0, q)
            if rw.changed:
                def _cost(qq):
                    return estimate_query_cost(
                        qq.model, cat0[qq.fact].capacity,
                        [cat0[a.table].capacity for a in qq.arms],
                        out_width=qq.model.l if qq.model is not None else 1,
                        batches_per_update=batches_per_update)
                cost_orig, cost_rw = _cost(q), _cost(rw.query)
                if cost_rw <= cost_orig:
                    q = rw.query
                    rewrite_trail = rw.trail
                else:
                    rewrite_trail = (
                        f"rejected: cost {cost_rw:.3g} > {cost_orig:.3g}",)
    # Pool sharing engages only on the plain single-device path against the
    # pool's own catalog: select-compaction rebinds the fact to a local
    # table, mesh placement commits arrays to devices, and tracer-holding
    # tables must never leak into a cross-plan cache.
    use_pool = (pool is not None and select_capacity is None
                and mesh is None and pool.catalog is cat0
                and not holds_tracers(cat0, q))
    # How many plans already share these join artifacts — measured BEFORE
    # this plan acquires (its own reference must not inflate the hint).
    sharing = pool.sharing_hint(q.fact, q.arms) if use_pool else 1.0
    catalog = cat0
    if select_capacity is not None:
        fact = select(catalog[q.fact], q.fact_preds,
                      capacity=select_capacity)
        catalog = {**catalog, q.fact: fact}
        q = dataclasses.replace(q, fact_preds=())
    # Snowflake chains collapse offline to head-granularity virtual
    # dimensions (factored joins compose associatively — see
    # core.query.snowflake), overlaid on the catalog like the
    # select-compacted fact; the flattened query then lowers through the
    # unchanged star pipeline, bit-exact with materializing each chain.
    chains: Tuple = ()
    chain_keys: Tuple = ()
    notes = []
    if any(a.links for a in q.arms):
        ccs, ckeys = [], []
        for arm in q.arms:
            if not arm.links:
                ccs.append(None)
                ckeys.append(None)
                continue
            k, note = plan_chain_materialization(
                virtual_name(arm),
                [catalog[p].capacity for p in link_parents(arm)],
                strategy=chain_strategy)
            notes.append(note)
            if use_pool:
                cc, ckey = pool.acquire_chain(arm, keep_hops=k)
            else:
                cc, ckey = resolve_chain(catalog, arm, keep_hops=k), None
            ccs.append(cc)
            ckeys.append(ckey)
        chains, chain_keys = tuple(ccs), tuple(ckeys)
        catalog = {**catalog, **{c.table.name: c.table
                                 for c in chains if c is not None}}
        q = dataclasses.replace(q, arms=tuple(flat_arm(a) for a in q.arms))
    with telemetry.span("plan.star"):
        star, valid, indices, arm_refs = _resolve_star(
            catalog, q, pool=pool if use_pool else None, chains=chains,
            chain_keys=chain_keys)
    fact = star.fact
    rows = jnp.sum(valid.astype(jnp.int32))
    # Offline compilation measures selectivity from the data; when a caller
    # traces compile_query itself (whole pipeline under one outer jit), the
    # counts are abstract — plan with static shapes and selectivity 1.
    n_fact = _static_int(fact.nvalid, fact.capacity)
    traced = isinstance(rows, jax.core.Tracer) or holds_tracers(cat0,
                                                                source_q)
    try:
        sel = float(rows) / max(n_fact, 1)
    except jax.errors.ConcretizationTypeError:
        sel = 1.0
    out_width = q.model.l if q.model is not None else 1

    # Out-of-core decision: fact working-set bytes vs the device budget
    # (planner), or a caller-pinned chunk size.
    stream_rows = stream_reason = None
    if stream_chunk_rows is not None or memory_budget_bytes is not None:
        row_bytes = _fact_row_bytes(fact, q, len(star.dims), out_width)
        stream_rows, stream_reason = plan_streaming(
            stream_chunk_rows, fact.capacity, row_bytes,
            memory_budget_bytes)

    # Group codes and dense ids resolve before planning so
    # ``num_groups="auto"`` can size the group dimension from the measured
    # code domain (the codes are concrete on the offline path) and feed the
    # planner the real G.  A selective model-free plan keeps only its live
    # rows and their ids: its program aggregates over those rows alone.
    uniq = gid = live = gid_live = None
    compact_cap = None
    if q.group_keys:
        with telemetry.span("plan.groups"):
            cols, bounds = _group_columns(catalog, q, star, chains)
            codes = composite_code(cols, bounds, valid)
            n_live = None
            if q.num_groups == "auto":
                n_live = auto_num_groups(codes)
                q = dataclasses.replace(q, num_groups=n_live)
            blocker = ("model head" if q.model is not None
                       else "streamed" if stream_rows is not None
                       else "traced" if traced else None)
            n_rows = None if traced else int(rows)
            compact_cap, compact_note = plan_compaction(
                sel, n_rows, fact.capacity, blocker)
            notes.append(compact_note)
            if compact_cap is None:
                uniq, gid = groupby_codes(codes, q.num_groups, n_live=n_live)
            else:
                with telemetry.span("plan.compact", rows=n_rows,
                                    capacity=compact_cap):
                    uniq, live, gid_live = groupby_rows(
                        codes, q.num_groups, compact_cap, n_live=n_live)
    elif q.num_groups == "auto":
        q = dataclasses.replace(
            q, num_groups=PredictiveQuery.__dataclass_fields__[
                "num_groups"].default)

    # The planner's selectivity term is the share of fact rows the online
    # program touches: the compacted row list for a compacted plan, the
    # whole (possibly ``select_capacity``-compacted, §2.2) fact otherwise —
    # the masked lowering's online cost stays at full capacity.
    online_sel = (1.0 if compact_cap is None
                  else min(compact_cap / max(n_fact, 1), 1.0))
    plan = plan_query(q.model, n_fact,
                      [_static_int(d.dim.nvalid, d.dim.capacity)
                       for d in star.dims],
                      selectivity=online_sel,
                      num_groups=q.num_groups if q.group_keys else 0,
                      out_width=out_width,
                      agg_ops=tuple(a.op for a in q.aggregates),
                      batches_per_update=batches_per_update,
                      memory_budget_bytes=memory_budget_bytes,
                      sharing=sharing)
    if rewrite_trail:
        notes.insert(0, "rewrite=[" + "; ".join(rewrite_trail) + "]")
    if notes:
        plan = dataclasses.replace(
            plan, reason="; ".join([plan.reason, *notes]))
    backend = plan.backend if backend == "auto" else backend
    join_backend = plan.join_backend if join_backend == "auto" else join_backend
    agg_backend = ((plan.agg.backend if plan.agg else "segment")
                   if agg_backend == "auto" else agg_backend)

    # Streaming runs the fused gather/segment program per chunk — the one
    # lowering whose per-row bits are independent of chunking — so explicit
    # conflicting backend overrides are rejected rather than silently
    # un-streamed.
    if (stream_rows is not None and stream_chunk_rows is None
            and q.model is not None and backend == "nonfused"
            and plan.fusion is not None
            and memory_budget_bytes is not None
            and plan.fusion.prefused_bytes > memory_budget_bytes):
        # The budget already ruled out resident prefused partials
        # (plan_fusion's older contract) — chunking the fact cannot shrink
        # the dimension side, so the budget-driven path defers to that
        # choice.  A merely amortization-driven nonfused pick does NOT
        # defer: out-of-core has no nonfused lowering, and prefusing is the
        # price of exceeding memory.  An explicit chunk size always streams.
        stream_rows = None
        stream_reason = "stream=off (budget forces nonfused prefuse)"
    if stream_reason:
        plan = dataclasses.replace(
            plan, stream_chunk_rows=stream_rows,
            reason=f"{plan.reason}; {stream_reason}")
    if stream_rows is not None:
        for name, val, bad in (("backend", opts["backend"], "nonfused"),
                               ("join_backend", opts["join_backend"],
                                "matmul"),
                               ("agg_backend", opts["agg_backend"],
                                "matmul")):
            if val == bad:
                raise ValueError(
                    f"stream_chunk_rows is incompatible with {name}="
                    f"{bad!r}: chunked execution folds partial aggregates "
                    "through the fused gather/segment program (matmul "
                    "lowerings are not bitwise chunk-stable)")
        if traced:
            raise ValueError(
                "streaming is an offline host-side driver: it cannot run "
                "under an outer trace (compile without stream_chunk_rows "
                "there)")
        if q.model is not None:
            backend = "fused"
        join_backend = "gather"
        agg_backend = "segment"
    serve_backend = effective_serve_backend(plan, serve_backend, backend,
                                            q.model, len(star.dims))
    if serve_backend != plan.serve_backend:
        plan = dataclasses.replace(
            plan, serve_backend=serve_backend,
            reason=f"{plan.reason}; serve={serve_backend} (caller override)")

    fact_backend, why = plan_fact_backend(serve_backend, backend,
                                          len(star.dims), fact.capacity)
    if why:
        plan = dataclasses.replace(plan, reason=f"{plan.reason}; {why}")
    if join_backend != "gather":   # the dense join is its own lowering
        fact_backend = "jnp"

    if q.model is not None:
        telemetry.annotate(backend=backend, fact_backend=fact_backend,
                           fact_rows=fact.capacity, **_head_attrs(q.model))
        if backend != "fused" and fact_backend == "pallas":
            # tree_predict's row block over the fact-sized call
            from repro.kernels.tree_predict.kernel import row_block
            telemetry.annotate(block_rows=row_block(
                fact.capacity, q.model.k, q.model.p))

    prefused = None
    partial_keys = ()
    if q.model is not None and backend == "fused":
        rows_p = sum(d.dim.capacity for d in star.dims)
        with telemetry.span("plan.prefuse", rows=rows_p,
                            bytes=rows_p * q.model.l * 4,
                            **_head_attrs(q.model)):
            if use_pool:
                parts, h, partial_keys = pool.acquire_partials(
                    star.dims, q.model, chains=chains)
                prefused = PrefusedStar(parts, h)
            else:
                prefused = prefuse(star, q.model)
            if not traced:     # the span times the build, not its dispatch
                jax.block_until_ready(prefused.partials)

    reduce_fn = (matmul_aggregate if agg_backend == "matmul"
                 else segment_aggregate)
    model = q.model
    num_groups = q.num_groups
    aggregates = q.aggregates
    fact_desc = q.fact

    def _predictions(state):
        star_v = _star_view(star, state)
        pre_v = _prefused_view(state)
        if backend == "fused":
            if join_backend != "gather":
                return predict_fused_matmul(star_v, pre_v)
            if fact_backend == "pallas":
                return predict_fused_kernel(star_v, pre_v,
                                            interpret=interpret)
            return predict_fused(star_v, pre_v)
        if join_backend != "gather":
            return predict_nonfused_matmul(star_v, model)
        if fact_backend == "pallas":   # resolve_ guarantees a tree model
            return predict_nonfused_kernel(star_v, model,
                                           interpret=interpret)
        return predict_nonfused(star_v, model)

    def _agg_values(agg, pred, fact_v, valid_v):
        """Per-row values for one aggregate (sum-masked for additive ops)."""
        if agg.value == PREDICTION:
            return pred                          # already validity-masked
        vals = eval_value(fact_v, agg.value,
                          query=f"{agg.name!r} on {fact_desc!r}")
        if agg.op in ("min", "max"):
            return vals       # invalid rows are masked by gid / ±inf below
        return jnp.where(valid_v, vals, 0.0)

    def _online(state):
        if state["rows"] is None:
            fact_v = dataclasses.replace(fact, matrix=state["fact_matrix"])
            valid_v = state["valid"]
            gid_v = state["gid"]
        else:
            # Compacted: the live rows, gathered from the resident fact on
            # every call; padding rows carry the dropped overflow id.
            fact_v = dataclasses.replace(fact, matrix=jnp.take(
                state["fact_matrix"], state["rows"], axis=0))
            gid_v = state["gid_live"]
            valid_v = gid_v != num_groups
        pred = _predictions(state) if model is not None else None
        out = {}
        # One shared count reduction backs every count/mean aggregate.
        count = None
        if any(a.op in ("count", "mean") for a in aggregates):
            ones = valid_v.astype(jnp.float32)
            count = (reduce_fn(gid_v, ones, num_groups)
                     if gid_v is not None else jnp.sum(ones))
        for agg in aggregates:
            if agg.op == "count":
                out[agg.name] = count
                continue
            vals = _agg_values(agg, pred, fact_v, valid_v)
            if gid_v is not None:
                if agg.op in ("min", "max"):
                    # Invalid rows sit in the dropped overflow segment, so
                    # no value masking is needed; min/max lower through
                    # segment ops on both aggregation backends (Fig. 4's
                    # one-hot matmul is additive-only).
                    out[agg.name] = segment_reduce(gid_v, vals, num_groups,
                                                   agg.op)
                elif agg.op == "mean":
                    s = reduce_fn(gid_v, vals, num_groups)
                    c = jnp.maximum(count, 1.0)
                    out[agg.name] = s / (c[:, None] if s.ndim > 1 else c)
                else:
                    out[agg.name] = reduce_fn(gid_v, vals, num_groups)
            elif agg.op in ("min", "max"):
                fill = jnp.inf if agg.op == "min" else -jnp.inf
                mask = valid_v[:, None] if vals.ndim > 1 else valid_v
                r = (jnp.min if agg.op == "min" else jnp.max)(
                    jnp.where(mask, vals, fill), axis=0)
                out[agg.name] = jnp.where(jnp.isfinite(r), r, 0.0)
            elif agg.op == "mean":
                out[agg.name] = (jnp.sum(vals, axis=0)
                                 / jnp.maximum(count, 1.0))
            else:
                out[agg.name] = jnp.sum(vals, axis=0)
        return out

    state = _query_state(star, prefused, gid, live, gid_live)
    online_jit = jax.jit(_online)
    pred_jit = jax.jit(_predictions)

    def run_fn(st):
        return online_jit(_program_state(st))

    predict_jit = predict_rows_jit = None
    sp = None
    if q.model is not None:
        def predict_jit(st):
            return pred_jit(_program_state(st))

        if mesh is not None:
            fwd, plan, sharded_state, sp = _make_predict_rows_sharded(
                star, q.model, prefused, backend, plan, mesh, shard_axis,
                shard_threshold_bytes)
            state["sharded"] = sharded_state
            fwd_jit = jax.jit(fwd)

            def predict_rows_jit(row_ids, st):
                return fwd_jit(row_ids, st["sharded"])
        else:
            rows_jit = jax.jit(
                _make_predict_rows(star, q.model, backend, serve_backend,
                                   interpret))

            def predict_rows_jit(row_ids, st):
                return rows_jit(row_ids, _program_state(st))

    stream = None
    if stream_rows is not None:
        # Result widths come from the in-core program's abstract output
        # shapes — eval_shape spends no FLOPs and guarantees the chunk
        # accumulators agree with what the in-core fold produces.
        out_shapes = jax.eval_shape(_online, _program_state(state))
        stream = StreamExecutor(
            star=star, state=state, aggregates=aggregates, model=model,
            num_groups=num_groups if q.group_keys else 0,
            fact_desc=fact_desc, chunk_rows=stream_rows,
            out_shapes=out_shapes)
        if use_pool:
            # Tentpole invariant: pooled artifacts a streamed plan shares
            # are dimension-side and flow to every chunk unchanged.
            assert_pool_dimension_side(
                pool, {"arms": arm_refs, "partials": tuple(partial_keys)},
                state, star)

    return CompiledQuery(
        query=q, plan=plan, backend=backend, join_backend=join_backend,
        agg_backend=agg_backend, serve_backend=serve_backend, star=star,
        prefused=prefused, selectivity=sel, group_codes=uniq, _rows=rows, _run=run_fn, _predict=predict_jit,
        _predict_rows=predict_rows_jit, _state=state, catalog=cat0,
        versions={n: cat0.version(n)
                  for n in participating_tables(source_q)},
        _indices=indices, _source=source_q, _opts=opts, _sp=sp,
        _chains=chains,
        _pool=pool if use_pool else None,
        _pool_refs=({"arms": arm_refs, "partials": tuple(partial_keys)}
                    if use_pool else {}),
        _online_fn=_online, _stream=stream, _rewrites=rewrite_trail,
        fact_backend=fact_backend)


def _make_predict_rows_sharded(star: StarJoin, model,
                               prefused: Optional[PrefusedStar],
                               backend: str, plan: QueryPlan, mesh,
                               shard_axis: str,
                               shard_threshold_bytes: Optional[int]):
    """Sharded serving path: row tables placed on the mesh, one shard_map.

    Returns ``(forward, plan, sharded_state, sp)`` with the per-arm
    placement recorded on the plan.  The FK→row pointers were resolved
    offline (``join_factored``), so the forward uses global-pointer
    device-local gathers (see ``make_predict_rows_forward``); the placed
    arrays live in ``sharded_state`` so ``refresh`` can re-place updated
    rows and re-dispatch without retracing.
    """
    if backend == "fused":
        tables = list(prefused.partials)
        h = prefused.h
    else:
        tables = [project_columns(d.dim.matrix, d.dim.columns,
                                  d.feature_cols)
                  for d in star.dims]
        h = None
    specs, plan = place_tables(mesh, tables, plan, axis=shard_axis,
                               threshold_bytes=shard_threshold_bytes)
    sp = shard_prefused_partials(
        mesh, [(d.fk_col, None, None, tbl)
               for d, tbl in zip(star.dims, tables)],
        h, specs, shard_axis=shard_axis)
    fn = make_predict_rows_forward(sp, model, backend)
    sharded_state = predict_rows_state(
        sp, tables, [fj.ptr for fj in star.joins],
        [fj.found for fj in star.joins], star.row_valid)
    return fn, plan, sharded_state, sp


def _make_predict_rows(star: StarJoin, model, backend: str,
                       serve_backend: str = "jnp",
                       interpret: bool = False):
    """Row-batched prediction: the serving path (fact rows as requests).

    The returned function takes ``(row_ids, state)`` — the quasi-static
    pointers/partials flow from the state pytree so a refresh re-dispatches
    into the same compiled program.
    """
    if backend == "fused" and serve_backend == "pallas":
        def fn(row_ids, state):
            from repro.kernels import fused_star_gather
            v = jnp.take(state["valid"], row_ids)
            ptrs = jnp.stack([jnp.take(p, row_ids)
                              for p in state["ptrs"]])
            found = jnp.stack([jnp.take(f, row_ids)
                               for f in state["founds"]]).astype(jnp.int32)
            out = fused_star_gather(ptrs, found, list(state["partials"]),
                                    state["h"], interpret=interpret)
            return out * v[:, None].astype(out.dtype)
        return fn

    if backend == "fused":
        def fn(row_ids, state):
            v = jnp.take(state["valid"], row_ids)
            acc = None
            for ptr0, found0, part in zip(state["ptrs"], state["founds"],
                                          state["partials"]):
                ptr = jnp.take(ptr0, row_ids)
                hit = jnp.take(found0, row_ids)
                p = jnp.take(part, ptr, axis=0) * hit[:, None].astype(
                    part.dtype)
                acc = p if acc is None else acc + p
            acc = acc * v[:, None].astype(acc.dtype)
            if state["h"] is None:
                return acc
            eq = (acc == state["h"][None, :].astype(acc.dtype))
            return eq.astype(acc.dtype) * v[:, None].astype(acc.dtype)
        return fn

    def fn(row_ids, state):
        v = jnp.take(state["valid"], row_ids)
        parts = []
        for d, mat, ptr0, found0 in zip(star.dims, state["dim_mats"],
                                        state["ptrs"], state["founds"]):
            proj = project_columns(mat, d.dim.columns, d.feature_cols)
            ptr = jnp.take(ptr0, row_ids)
            hit = jnp.take(found0, row_ids)
            parts.append(jnp.take(proj, ptr, axis=0)
                         * hit[:, None].astype(proj.dtype))
        t = jnp.concatenate(parts, axis=1) * v[:, None].astype(jnp.float32)
        if serve_backend == "pallas" and isinstance(model, DecisionTreeGEMM):
            from repro.kernels import tree_predict
            out = tree_predict(t, model.F, model.v, model.H, model.h,
                               interpret=interpret)
        else:
            out = model.apply_rows(t)
        return out * v[:, None].astype(out.dtype)
    return fn


def query_from_star(star: StarJoin, fact_name: str = None, *,
                    model=None, aggregates: Tuple[Aggregate, ...] = (),
                    group_keys=(), num_groups: int = 8192
                    ) -> Tuple[Dict[str, Table], PredictiveQuery]:
    """Lift an already-resolved ``StarJoin`` into (catalog, PredictiveQuery).

    Convenience for callers holding legacy ``star_join`` outputs (synthetic
    generators, serving): the compiler re-resolves the joins, so the result
    is equivalent to having built the IR directly.
    """
    fact_name = fact_name or star.fact.name
    catalog = {fact_name: star.fact}
    arms = []
    for d in star.dims:
        catalog[d.dim.name] = d.dim
        arms.append(ArmSpec(d.dim.name, d.fk_col, d.pk_col,
                            tuple(d.feature_cols)))
    if not aggregates and model is not None:
        aggregates = (Aggregate(PREDICTION, "sum", "prediction"),)
    return catalog, PredictiveQuery(
        fact=fact_name, arms=tuple(arms), model=model,
        group_keys=tuple(group_keys), aggregates=tuple(aggregates),
        num_groups=num_groups)
