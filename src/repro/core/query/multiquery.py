"""Multi-query optimizer: shared artifacts across compiled plans (ROADMAP
"Cross-query optimization").

The registry runs 17+ queries that each independently materialize the same
quasi-static artifacts: most share star arms, so most recompute the same PK
sort, the same fact-sized FK probe, the same dimension predicate mask, and
(per model prefix) the same Eq. 1 prefused partial.  This module makes that
work shareable at plan time:

Arm-level content keys
    :func:`query_key` hashes whole queries; the functions here hash the
    *pieces a single arm contributes* — ``("pkindex", table, pk_col)``,
    ``("join", fact, fk_col, table, pk_col)``, ``("dmask", table, preds)``,
    ``("features", table, feature_cols)`` and ``("partial", ...)`` keyed by
    the model-prefix slice content — so two different queries sharing a
    (table, model-prefix, predicate) arm resolve to the same artifact keys
    even when the rest of their plans differ.

``ArtifactPool``
    A reference-counted store of those artifacts, owned by a ``Session``
    and bound to its :class:`~repro.core.laq.Catalog`.  ``acquire_*``
    computes on miss and hands back shared arrays on hit (bit-identical by
    construction: hits are the output of the very computation the cold path
    would run); ``release`` drops references and evicts at zero.  Every
    entry records the catalog versions it was built against and refreshes
    *lazily, exactly once* when fetched stale — N plans referencing one
    artifact pay one delta update between them, which is what makes
    ``Session.refresh()`` O(distinct artifacts) instead of O(plans) for the
    shared quasi-static work.  The delta math per kind mirrors the
    unpooled refresh paths (``PKIndex.extend`` sorted merges, appended-key
    block probes, ``prefuse_rows`` over dirty rows, mask scatters) so a
    pooled refresh stays bit-exact vs a cold rebuild.

Batched multi-query execution
    :func:`stack_key` classifies compiled plans into structural
    compatibility classes (same fact/arm shapes, backends, aggregate list,
    group dimension and state-pytree signature — predicates and group
    bounds live in the state, not the program); :func:`make_stacked_runner`
    vmaps one plan's online program over a leading query axis so
    ``Session.run_all`` executes a whole class as one jitted dispatch.

No compile/serving/session imports happen at module top level (those
modules receive the pool as an opaque argument), keeping the dependency
graph acyclic: ``session → {compile, serving, multiquery}``.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
from typing import (Callable, Dict, List, Mapping, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from ..fusion.operators import DecisionTreeGEMM, LinearOperator
from ..fusion.pipeline import _feature_slices, prefuse_dims, prefuse_rows
from ..laq.catalog import Catalog, CatalogHistoryError, changed_spans
from ..laq.join import FactoredJoin, PKIndex, pk_index
from ..laq.projection import project_columns
from ..laq.star import DimSpec
from ..laq.table import PAD_KEY, Table
from .ir import ArmSpec, Model, PredictiveQuery
from .snowflake import (CollapsedChain, chain_dirty_heads, chain_key,
                        chain_tables, participating_tables, qualified_cols,
                        refresh_chain, resolve_chain, virtual_name)


# --------------------------------------------------------------------------
# Content hashing (models by array bytes)
# --------------------------------------------------------------------------
def _array_key(a) -> tuple:
    arr = np.asarray(a)
    return (arr.shape, arr.dtype.str,
            hashlib.blake2b(arr.tobytes(), digest_size=16).hexdigest())


def model_key(model: Optional[Model]):
    """Content key for a model head; falls back to identity under a trace."""
    if model is None:
        return None
    try:
        if isinstance(model, LinearOperator):
            return ("linear", _array_key(model.L),
                    None if model.bias is None else _array_key(model.bias))
        if isinstance(model, DecisionTreeGEMM):
            return ("tree", _array_key(model.F), _array_key(model.v),
                    _array_key(model.H), _array_key(model.h))
    except (jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError):
        pass
    return ("id", type(model).__name__, id(model))


def _digest(a) -> str:
    arr = np.asarray(a)
    return hashlib.blake2b(
        arr.tobytes() + repr((arr.shape, arr.dtype.str)).encode(),
        digest_size=16).hexdigest()


# --------------------------------------------------------------------------
# Arm-level artifact keys
# --------------------------------------------------------------------------
def pkindex_key(table: str, pk_col: str) -> tuple:
    return ("pkindex", table, pk_col)


def join_key(fact: str, fk_col: str, table: str, pk_col: str) -> tuple:
    return ("join", fact, fk_col, table, pk_col)


def dmask_key(table: str, preds: tuple) -> tuple:
    return ("dmask", table, tuple(preds))


def features_key(table: str, feature_cols: Sequence[str]) -> tuple:
    return ("features", table, tuple(feature_cols))


def partial_key(table: str, feature_cols: Sequence[str], model: Model,
                lo: int, hi: int, j: int = 0) -> tuple:
    """Content key of one arm's Eq. 1/3 prefused partial.

    Linear heads: the partial is ``B_j @ L[lo:hi]`` (the one-hot mapping
    matmul reproduces the slice exactly in fp32), so only the *slice
    content* keys it — two queries placing the same arm at different
    feature offsets still share, as long as their L rows there agree.
    A folded constant bias (rewrite rule) is carried by arm 0's partial,
    so that arm's key pins the bias bytes too.  Tree heads additionally
    depend on the node-ownership mask, which reads the argmax over the
    **full** F, so the key pins (lo, hi) and all of F/v/H.
    """
    if isinstance(model, LinearOperator):
        bias = ()
        if j == 0 and model.bias is not None:
            bias = (("bias", _digest(model.bias)),)
        return ("partial", "linear", table, tuple(feature_cols),
                _digest(np.asarray(model.L)[lo:hi])) + bias
    return ("partial", "tree", table, tuple(feature_cols), int(lo), int(hi),
            _digest(model.F), _digest(model.v), _digest(model.H))


def arm_keys(q: PredictiveQuery) -> Tuple[Tuple[tuple, ...], ...]:
    """Per-arm artifact key sets — the common-subplan signature of ``q``.

    For each arm, the keys of every poolable artifact the arm contributes:
    PK index, FK join probe, predicate mask (when predicated) and model
    partial (when ``q`` has a model).  Two queries share offline work
    exactly where these sets intersect.
    """
    slices = [(0, 0)] * len(q.arms)
    if q.model is not None:
        off = 0
        slices = []
        for arm in q.arms:
            slices.append((off, off + arm.feature_width))
            off += arm.feature_width
    out = []
    for j, (arm, (lo, hi)) in enumerate(zip(q.arms, slices)):
        # Chained arms index/probe against the real head table (shared with
        # flat arms over the same head); the chain collapse and its partial
        # are keyed by the full chain content.
        keys = [pkindex_key(arm.table, arm.pk_col),
                join_key(q.fact, arm.fk_col, arm.table, arm.pk_col)]
        if arm.links:
            keys.append(chain_key(arm))
        elif arm.preds:
            keys.append(dmask_key(arm.table, arm.preds))
        if q.model is not None:
            if arm.links:
                keys.append(partial_key(virtual_name(arm),
                                        qualified_cols(arm), q.model,
                                        lo, hi, j) + (chain_key(arm),))
            else:
                keys.append(partial_key(arm.table, arm.feature_cols,
                                        q.model, lo, hi, j))
        out.append(tuple(keys))
    return tuple(out)


def holds_tracers(catalog, q: PredictiveQuery) -> bool:
    """True when ``q``'s tables or model hold tracers (compile under an
    outer jit).

    Pooled artifacts must be concrete — a cached tracer would leak out of
    its trace, and content keys need ``tobytes()`` — so tracing callers
    bypass the pool entirely.
    """
    tracer = jax.core.Tracer
    for name in participating_tables(q):
        t = catalog[name]
        if isinstance(t.matrix, tracer) or isinstance(t.nvalid, tracer):
            return True
        if any(isinstance(v, tracer) for v in t.keys.values()):
            return True
    if q.model is not None:
        arrays = ((q.model.F, q.model.v, q.model.H)
                  if isinstance(q.model, DecisionTreeGEMM)
                  else (q.model.L,))
        if any(isinstance(a, tracer) for a in arrays):
            return True
    return False


def _mask_rows(dim: Table, preds, ids: np.ndarray) -> jnp.ndarray:
    """Dim-predicate mask on just the (live) rows ``ids``.

    Identical math to the serving runtime's delta-mask helper — the pool's
    scatter refresh must agree bitwise with the unpooled delta path.
    """
    sub = Table(dim.name, dim.columns,
                jnp.take(dim.matrix, jnp.asarray(ids), axis=0),
                {c: jnp.take(v, jnp.asarray(ids))
                 for c, v in dim.keys.items()},
                int(ids.shape[0]))
    # Liveness comes from the *parent* table: the sub-table is fully
    # "valid" by construction, so tombstones must be gathered explicitly.
    m = jnp.take(dim.valid_mask(), jnp.asarray(ids))
    for p in preds:
        m = m & p.mask(sub)
    return m


# --------------------------------------------------------------------------
# The pool
# --------------------------------------------------------------------------
@dataclasses.dataclass
class _PoolEntry:
    """One shared artifact: value + versions + refcount + update counter."""

    key: tuple
    kind: str
    value: object
    versions: Dict[str, int]     # gating tables → catalog version at build
    spec: Dict                   # kind-specific refresh context
    refcount: int = 0
    updates: int = 0             # delta/cold refreshes applied in place

    def nbytes(self) -> int:
        return sum(int(a.size) * a.dtype.itemsize
                   for a in _entry_arrays(self.value))


def _entry_arrays(value) -> List:
    if isinstance(value, PKIndex):
        return [value.sorted_pk, value.order]
    if isinstance(value, CollapsedChain):
        arrs = [value.table.matrix, value.dmask]
        for _name, ptr, found in value.link_ptrs:
            arrs.extend([ptr, found])
        for h in value.hops:
            if h is not None:
                arrs.extend([h.ptr, h.found])
        return arrs
    if isinstance(value, tuple):
        return [v for v in value if v is not None]
    return [value] if value is not None else []


class ArtifactPool:
    """Reference-counted shared quasi-static artifacts for one catalog.

    ``acquire_*`` methods return ``(value, key)`` and take a reference;
    :meth:`get` is the non-refcounting fetch used by plan refresh paths
    (the plan already holds its reference — refetching must not leak
    counts).  Both refresh a stale entry first, exactly once per catalog
    version change no matter how many plans reference it.  :meth:`release`
    drops references and evicts entries nothing points at.
    """

    def __init__(self, catalog):
        self.catalog: Catalog = Catalog.wrap(catalog)
        self._entries: Dict[tuple, _PoolEntry] = {}
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    # -- core entry lifecycle ------------------------------------------------
    def _fresh(self, key: tuple, kind: str, tables: Tuple[str, ...],
               build: Callable[[], object], spec: Dict) -> _PoolEntry:
        entry = self._entries.get(key)
        if entry is None:
            self.misses += 1
            entry = _PoolEntry(
                key=key, kind=kind, value=build(),
                versions={n: self.catalog.version(n) for n in tables},
                spec=dict(spec))
            self._entries[key] = entry
        else:
            self.hits += 1
            self._refresh_entry(entry)
        return entry

    def get(self, key: tuple):
        """The entry's current value, refreshed if stale (no refcount)."""
        entry = self._entries[key]
        self._refresh_entry(entry)
        return entry.value

    def release(self, keys: Sequence[tuple]) -> int:
        """Drop one reference per key; evict entries reaching zero.

        ``keys`` is the exact multiset the owner acquired (duplicates drop
        multiple references).  Returns the number of evictions.
        """
        evicted = 0
        work = list(keys)
        while work:
            key = work.pop()
            entry = self._entries.get(key)
            if entry is None:
                continue
            entry.refcount -= 1
            if entry.refcount <= 0:
                del self._entries[key]
                evicted += 1
                # Chains hold one reference on each pooled hop probe;
                # evicting the chain drops those too.
                work.extend(entry.spec.get("hops", ()))
        self.evictions += evicted
        return evicted

    def refcount(self, key: tuple) -> int:
        entry = self._entries.get(key)
        return entry.refcount if entry is not None else 0

    def update_count(self, key: tuple) -> int:
        entry = self._entries.get(key)
        return entry.updates if entry is not None else 0

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: tuple) -> bool:
        return key in self._entries

    def keys(self):
        return self._entries.keys()

    def stats(self) -> Dict:
        """Pool-wide counters: entries/hits/misses/evictions/updates/bytes
        plus a per-kind entry count."""
        by_kind: Dict[str, int] = collections.Counter(
            e.kind for e in self._entries.values())
        return {
            "entries": len(self._entries),
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "updates": sum(e.updates for e in self._entries.values()),
            "bytes": sum(e.nbytes() for e in self._entries.values()),
            "by_kind": dict(by_kind),
        }

    def sharing_hint(self, fact: str, arms) -> float:
        """How many plans already share ``(fact, arms)``'s join artifacts.

        Feeds the planner's prefuse amortization: a partial referenced by N
        plans amortizes its build cost over N times the batches.  1.0 when
        nothing is shared yet.
        """
        counts = [self._entries[k].refcount for arm in arms
                  for k in (join_key(fact, arm.fk_col, arm.table,
                                     arm.pk_col),)
                  if k in self._entries]
        return 1.0 + float(max(counts)) if counts else 1.0

    # -- acquire: PK index ---------------------------------------------------
    def _pkindex_entry(self, table: str, pk_col: str) -> _PoolEntry:
        return self._fresh(
            pkindex_key(table, pk_col), "pkindex", (table,),
            lambda: pk_index(self.catalog[table].key(pk_col)),
            {"table": table, "pk_col": pk_col})

    def acquire_pkindex(self, table: str, pk_col: str
                        ) -> Tuple[PKIndex, tuple]:
        entry = self._pkindex_entry(table, pk_col)
        entry.refcount += 1
        return entry.value, entry.key

    # -- acquire: FK join probe ---------------------------------------------
    def acquire_join(self, fact: str, fk_col: str, table: str, pk_col: str
                     ) -> Tuple[Tuple[jnp.ndarray, jnp.ndarray], tuple]:
        """The fact-sized ``(ptr, found)`` probe of one arm — the dominant
        shared artifact (and offline cost) across the registry."""
        def build():
            idx = self._pkindex_entry(table, pk_col).value
            fj = idx.probe(self.catalog[fact].key(fk_col))
            return (fj.ptr, fj.found)
        entry = self._fresh(
            join_key(fact, fk_col, table, pk_col), "join", (fact, table),
            build, {"fact": fact, "fk_col": fk_col, "table": table,
                    "pk_col": pk_col})
        entry.refcount += 1
        return entry.value, entry.key

    # -- acquire: dimension predicate mask ----------------------------------
    def _build_dmask(self, table: str, preds) -> jnp.ndarray:
        dim = self.catalog[table]
        m = dim.valid_mask()
        for p in preds:
            m = m & p.mask(dim)
        return m

    def acquire_dmask(self, table: str, preds
                      ) -> Tuple[jnp.ndarray, tuple]:
        """Row liveness ∧ dimension predicates, in dimension-row order.

        ``Pred.mask`` folds the validity mask itself, so this value is
        boolean-identical on the compile path (which ANDs bare pred masks)
        and the serving path (which ANDs validity explicitly).
        """
        preds = tuple(preds)
        entry = self._fresh(
            dmask_key(table, preds), "dmask", (table,),
            lambda: self._build_dmask(table, preds),
            {"table": table, "preds": preds})
        entry.refcount += 1
        return entry.value, entry.key

    # -- acquire: projected feature tables (nonfused serving) ----------------
    def acquire_features(self, table: str, feature_cols: Sequence[str]
                         ) -> Tuple[jnp.ndarray, tuple]:
        feature_cols = tuple(feature_cols)

        def build():
            dim = self.catalog[table]
            return project_columns(dim.matrix, dim.columns, feature_cols)
        entry = self._fresh(
            features_key(table, feature_cols), "features", (table,),
            build, {"table": table, "feature_cols": feature_cols})
        entry.refcount += 1
        return entry.value, entry.key

    # -- acquire: collapsed snowflake chains ----------------------------------
    def acquire_chain(self, arm: ArmSpec, *, keep_hops: int = 0
                      ) -> Tuple[CollapsedChain, tuple]:
        """The collapsed chain of one multi-hop arm (see ``snowflake``).

        Keyed by the full chain content (head, hop keys, features, preds),
        gated on every chain table's version.  ``keep_hops`` is a
        refresh-speed hint only — it never changes the collapsed values —
        so plans that disagree on it still share one entry (first build
        wins).

        Each hop's parent→link probe is itself pooled at hop granularity
        (the ``join`` kind, parent table as the probing side): two chains
        sharing a prefix — or a flat arm probing the same link — reuse one
        probe entry instead of recomputing it per chain.  The chain holds
        a reference on each hop key (recorded in ``spec["hops"]``);
        :meth:`release` drops them when the chain is evicted.
        """
        key = chain_key(arm)
        entry = self._entries.get(key)
        if entry is None:
            self.misses += 1
            hop_keys: list = []

            def hop_source(parent, lk):
                _, ik = self.acquire_pkindex(lk.table, lk.pk_col)
                (ptr, found), k = self.acquire_join(
                    parent, lk.fk_col, lk.table, lk.pk_col)
                hop_keys.extend((k, ik))
                return FactoredJoin(ptr, found)

            value = resolve_chain(self.catalog, arm, keep_hops=keep_hops,
                                  hop_source=hop_source)
            entry = _PoolEntry(
                key=key, kind="chain", value=value,
                versions={n: self.catalog.version(n)
                          for n in chain_tables(arm)},
                spec={"arm": arm, "keep_hops": keep_hops,
                      "hops": tuple(hop_keys)})
            self._entries[key] = entry
        else:
            self.hits += 1
            self._refresh_entry(entry)
        entry.refcount += 1
        return entry.value, entry.key

    # -- acquire: prefused partials (one prefuse_dims per miss set) ----------
    def acquire_partials(self, dims: Sequence[DimSpec], model: Model,
                         chains: Sequence[Optional[CollapsedChain]] = ()
                         ) -> Tuple[Tuple[jnp.ndarray, ...],
                                    Optional[jnp.ndarray],
                                    Tuple[tuple, ...]]:
        """Eq. 1/3 partials for a whole arm list: ``(partials, h, keys)``.

        Misses are computed by ONE :func:`prefuse_dims` call over the full
        list — exactly the computation the unpooled compile runs, so hits
        handed back from the pool are bit-identical to what that call
        would have produced for them.

        ``chains`` marks which dims are collapsed snowflake chains (parallel
        to ``dims``; None entries are flat).  A chained partial's key
        carries the chain's content key — the virtual table *name* alone
        would alias chains over the same tables with different hop keys —
        and its refresh gates on every chain table.
        """
        chains = tuple(chains) + (None,) * (len(dims) - len(chains))
        slices = _feature_slices(dims)
        keys, arm_specs = [], []
        for j, (d, (lo, hi), cc) in enumerate(zip(dims, slices, chains)):
            k = partial_key(d.dim.name, d.feature_cols, model, lo, hi, j)
            if cc is not None:
                k = k + (chain_key(cc.arm),)
                arm_specs.append(cc.arm)
            else:
                arm_specs.append((d.dim.name, d.fk_col, d.pk_col,
                                  tuple(d.feature_cols)))
            keys.append(k)
        keys = tuple(keys)
        arm_specs = tuple(arm_specs)
        pre = (prefuse_dims(dims, model)
               if any(k not in self._entries for k in keys) else None)
        parts = []
        for j, (d, key, cc) in enumerate(zip(dims, keys, chains)):
            entry = self._entries.get(key)
            if entry is None:
                self.misses += 1
                gates = (chain_tables(cc.arm) if cc is not None
                         else (d.dim.name,))
                entry = _PoolEntry(
                    key=key, kind="partial", value=pre.partials[j],
                    versions={n: self.catalog.version(n) for n in gates},
                    spec={"arms": arm_specs, "j": j, "model": model})
                self._entries[key] = entry
            else:
                self.hits += 1
                self._refresh_entry(entry)
            entry.refcount += 1
            parts.append(entry.value)
        h = model.h if isinstance(model, DecisionTreeGEMM) else None
        return tuple(parts), h, keys

    # -- lazy, exactly-once refresh ------------------------------------------
    def _refresh_entry(self, entry: _PoolEntry) -> None:
        stale = self.catalog.stale_tables(entry.versions)
        if not stale:
            return
        refresh = getattr(self, f"_refresh_{entry.kind}")
        try:
            deltas = {n: self.catalog.deltas_since(n, entry.versions[n])
                      for n in stale}
            if any(d and changed_spans(d)[2] for d in deltas.values()):
                raise CatalogHistoryError("capacity growth: cold rebuild")
            refresh(entry, deltas)
        except CatalogHistoryError:
            # Staler than the delta log, or shapes changed: rebuild cold.
            # Growth-driven rebuilds change array shapes, which is safe —
            # every referencing plan recompiles on growth before reading.
            entry.value = getattr(self, f"_rebuild_{entry.kind}")(entry)
        entry.versions = {n: self.catalog.version(n)
                          for n in entry.versions}
        entry.updates += 1

    @staticmethod
    def _touched_ids(deltas) -> Optional[np.ndarray]:
        span, dirty, _, deleted = changed_spans(deltas)
        ids = set(dirty) | set(deleted)
        if span is not None:
            ids.update(range(span[0], span[1]))
        return np.asarray(sorted(ids), np.int32) if ids else None

    @staticmethod
    def _pad_ids(ids: np.ndarray) -> np.ndarray:
        """Pad a dirty-row id list up to a power-of-two length.

        Scatter refreshes (``value.at[ids].set(rows)``) specialize the
        jitted update on ``len(ids)``; successive appends rarely dirty the
        exact same number of rows, so every refresh would recompile.
        Padding repeats ``ids[0]`` — duplicate scatter indices carry
        *identical* row values, so the update stays deterministic and
        bit-exact while the shape lands in one of log₂ buckets.
        """
        n = len(ids)
        cap = 1 << max(3, int(np.ceil(np.log2(max(n, 1)))))
        if n == cap:
            return ids
        return np.concatenate(
            [ids, np.full(cap - n, ids[0], ids.dtype)])

    def _rebuild_pkindex(self, entry):
        s = entry.spec
        return pk_index(self.catalog[s["table"]].key(s["pk_col"]))

    def _refresh_pkindex(self, entry, deltas):
        s = entry.spec
        span = changed_spans(deltas[s["table"]]).span
        if span is not None:
            lo, hi = span
            entry.value = entry.value.extend(
                self.catalog[s["table"]].key(s["pk_col"])[lo:hi],
                np.arange(lo, hi))

    def _rebuild_join(self, entry):
        s = entry.spec
        idx = self._pkindex_entry(s["table"], s["pk_col"]).value
        fj = idx.probe(self.catalog[s["fact"]].key(s["fk_col"]))
        return (fj.ptr, fj.found)

    def _refresh_join(self, entry, deltas):
        # The same two-sided delta probe CompiledQuery._refresh_delta runs:
        # appended dim PKs are probed as a sorted block and scattered over
        # the whole fact; appended fact rows probe the (already extended)
        # full index.  Dirty non-key rows never move pointers.
        s = entry.spec
        cat = self.catalog
        fact, dim = cat[s["fact"]], cat[s["table"]]
        ptr = np.array(entry.value[0])
        found = np.array(entry.value[1])
        if s["table"] in deltas:
            span = changed_spans(deltas[s["table"]]).span
            if span is not None:
                lo, hi = span
                nk = np.asarray(dim.key(s["pk_col"]))[lo:hi]
                order = np.argsort(nk, kind="stable")
                snk, srow = nk[order], (lo + order).astype(np.int32)
                fk = np.asarray(fact.key(s["fk_col"]))
                pos = np.searchsorted(snk, fk)
                posc = np.clip(pos, 0, len(snk) - 1)
                hit = (snk[posc] == fk) & (fk != PAD_KEY)
                ptr = np.where(hit, srow[posc], ptr).astype(np.int32)
                found = found | hit
        if s["fact"] in deltas:
            span = changed_spans(deltas[s["fact"]]).span
            if span is not None:
                flo, fhi = span
                idx = self._pkindex_entry(s["table"], s["pk_col"]).value
                fj = idx.probe(fact.key(s["fk_col"])[flo:fhi])
                ptr[flo:fhi] = np.asarray(fj.ptr)
                found[flo:fhi] = np.asarray(fj.found)
        entry.value = (jnp.asarray(ptr), jnp.asarray(found))

    def _rebuild_dmask(self, entry):
        s = entry.spec
        return self._build_dmask(s["table"], s["preds"])

    def _refresh_dmask(self, entry, deltas):
        s = entry.spec
        ids = self._touched_ids(deltas[s["table"]])
        if ids is not None:
            ids = self._pad_ids(ids)
            entry.value = entry.value.at[jnp.asarray(ids)].set(
                _mask_rows(self.catalog[s["table"]], s["preds"], ids))

    def _rebuild_features(self, entry):
        s = entry.spec
        dim = self.catalog[s["table"]]
        return project_columns(dim.matrix, dim.columns, s["feature_cols"])

    def _refresh_features(self, entry, deltas):
        s = entry.spec
        ids = self._touched_ids(deltas[s["table"]])
        if ids is not None:
            ids = self._pad_ids(ids)
            dim = self.catalog[s["table"]]
            rows = project_columns(
                jnp.take(dim.matrix, jnp.asarray(ids), axis=0), dim.columns,
                s["feature_cols"])
            entry.value = entry.value.at[jnp.asarray(ids)].set(rows)

    def _hop_source_for(self, entry):
        """A ``resolve_chain`` hop source reading this chain's pooled hop
        probes (refreshing each at most once via :meth:`get`); ``None``
        for pre-pooling entries whose spec lacks hop keys."""
        if "hops" not in entry.spec:
            return None

        def hop_source(parent, lk):
            key = join_key(parent, lk.fk_col, lk.table, lk.pk_col)
            if key not in self._entries:
                return None
            ptr, found = self.get(key)
            return FactoredJoin(ptr, found)
        return hop_source

    def _rebuild_chain(self, entry):
        s = entry.spec
        return resolve_chain(self.catalog, s["arm"],
                             keep_hops=s["keep_hops"],
                             hop_source=self._hop_source_for(entry))

    def _refresh_chain(self, entry, deltas):
        hs = self._hop_source_for(entry)
        if hs is None:
            entry.value = refresh_chain(self.catalog, entry.value,
                                        set(deltas))
        else:
            s = entry.spec
            entry.value = resolve_chain(self.catalog, s["arm"],
                                        keep_hops=s["keep_hops"],
                                        hop_source=hs)

    def _partial_dims(self, entry, chains: Optional[Mapping[
            int, CollapsedChain]] = None) -> Tuple[DimSpec, ...]:
        # Chained arm specs are stored as the ArmSpec itself; they resolve
        # through the (possibly freshly re-collapsed) chain's virtual table.
        dims = []
        for i, a in enumerate(entry.spec["arms"]):
            if isinstance(a, ArmSpec):
                cc = (chains or {}).get(i) or resolve_chain(self.catalog, a)
                dims.append(DimSpec(cc.table, a.fk_col, a.pk_col,
                                    tuple(cc.table.columns)))
            else:
                t, fk, pk, fcols = a
                dims.append(DimSpec(self.catalog[t], fk, pk, fcols))
        return tuple(dims)

    def _rebuild_partial(self, entry):
        dims = self._partial_dims(entry)
        return prefuse_dims(dims, entry.spec["model"]).partials[
            entry.spec["j"]]

    def _refresh_partial(self, entry, deltas):
        s = entry.spec
        a = s["arms"][s["j"]]
        if isinstance(a, ArmSpec):
            # Chained partial: re-collapse (cheap dimension-sized gathers),
            # then scatter-refresh exactly the head rows whose virtual
            # matrix rows may differ — the same dirty set the unpooled
            # CompiledQuery._refresh_delta computes.
            cc = resolve_chain(self.catalog, a)
            dims = self._partial_dims(entry, chains={s["j"]: cc})
            touched = {}
            for name, d in deltas.items():
                t = self._touched_ids(d)
                if t is not None:
                    touched[name] = t
            ids = chain_dirty_heads(cc, touched)
        else:
            dims = self._partial_dims(entry)
            ids = self._touched_ids(deltas[dims[s["j"]].dim.name])
        if ids is not None:
            ids = jnp.asarray(self._pad_ids(np.asarray(ids, np.int32)))
            entry.value = entry.value.at[ids].set(
                prefuse_rows(dims, s["model"], s["j"], ids))


# --------------------------------------------------------------------------
# Batched multi-query execution
# --------------------------------------------------------------------------
def state_signature(state) -> tuple:
    """Treedef + per-leaf (shape, dtype) of a program-state pytree."""
    leaves, treedef = jax.tree_util.tree_flatten(state)
    return (str(treedef),
            tuple((tuple(np.shape(x)), str(jnp.asarray(x).dtype))
                  for x in leaves))


def stack_key(compiled) -> Optional[tuple]:
    """The structural compatibility class of one compiled plan, or ``None``
    when the plan cannot stack (traced, mesh-sharded, or no online fn).

    Two plans with equal keys run the *same* jitted program over different
    state pytrees: predicates and group assignments live in the state
    (``valid``/``gid``), so e.g. the four SSB flights each collapse their
    three variants into one class.  Everything the online closure bakes in
    as a static — backends, aggregate list, group dimension, model content,
    state pytree signature — is part of the key.
    """
    q = compiled.query
    if (getattr(compiled, "_online_fn", None) is None or compiled.is_traced
            or getattr(compiled, "_sp", None) is not None):
        return None
    if getattr(compiled, "_stream", None) is not None:
        # Streaming plans execute chunk-at-a-time with a carried
        # accumulator — there is no single whole-fact state to stack.
        return None
    if getattr(compiled, "_opts", {}).get("select_capacity") is not None:
        # Compacted plans close over a per-plan fact skeleton whose key
        # columns differ between members — not one shared program.
        return None
    sig = state_signature(
        {k: v for k, v in compiled._state.items() if k != "sharded"})
    return ("stack", q.fact,
            tuple((a.table, a.fk_col, a.pk_col, a.feature_cols)
                  for a in q.arms),
            q.aggregates,
            q.num_groups if q.group_keys else None,
            model_key(q.model),
            compiled.backend, compiled.join_backend, compiled.agg_backend,
            compiled.serve_backend, sig)


def make_stacked_runner(online_fn: Callable) -> Callable:
    """One jitted program executing N structurally compatible plans.

    ``online_fn`` is a plan's raw (un-jitted) online closure taking one
    program-state pytree; the runner takes a *stacked* pytree (every leaf
    gains a leading query axis) and vmaps the program over it — one
    dispatch for the whole class.  Gathers, element-wise masking and
    segment reductions are row-independent, so the batched program is
    bit-exact vs per-plan execution (asserted by the tier-1 tests).
    """
    return jax.jit(jax.vmap(online_fn))


def stack_states(states: Sequence) -> object:
    """Stack per-plan program states leaf-wise along a new query axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


# --------------------------------------------------------------------------
# Measurement helpers (benches/tests)
# --------------------------------------------------------------------------
def artifact_bytes(plans) -> int:
    """Resident bytes of *derived* quasi-static artifacts, deduplicated.

    Counts pointers/masks/partials/indices — the arrays compilation
    manufactures — and excludes source tables (``fact_matrix``/
    ``dim_mats``), which alias the catalog across plans whether or not a
    pool is in play and would dilute the sharing ratio.  Arrays shared
    between plans (the pool's whole point) count once, by ``id``.
    """
    seen: Dict[int, int] = {}

    def add(a):
        if a is None:
            return
        arr = a
        seen[id(arr)] = int(arr.size) * arr.dtype.itemsize

    for p in plans:
        state = getattr(p, "_state", None)
        if state is not None and "ptrs" in state:      # CompiledQuery
            for k in ("valid", "gid", "h"):
                add(state.get(k))
            for k in ("ptrs", "founds", "partials"):
                for a in (state.get(k) or ()):
                    add(a)
            for idx in getattr(p, "_indices", ()):
                add(idx.sorted_pk)
                add(idx.order)
        else:                                           # ServingRuntime
            add(getattr(p, "_h", None))
            for a in getattr(p, "_arms", ()):
                if a.index is not None:
                    add(a.index.sorted_pk)
                    add(a.index.order)
                add(a.dmask)
                add(a.table)
    return sum(seen.values())
