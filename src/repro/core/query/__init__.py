"""Predictive-query compiler: selection ⋈ star join ⋈ model ⋈ group-by,
lowered to one jitted linear-algebra program.

The paper's thesis (§3) is that relational operators and ML predictions share
a linear-algebra substrate, so a *whole* predictive query can be planned and
fused as one program.  This package is that planner/compiler, fronted by one
declarative surface: the :class:`Session` query builder.

Session API (the single entry point)
------------------------------------
A ``Session`` binds a catalog (+ optional device mesh) once; a fluent,
immutable builder then describes the pipeline and drives all three
execution modes::

    sess = Session(catalog, mesh=None)
    q = (sess.query("lineorder")
         .join("date", on=("lo_orderdate", "datekey"),
               features=["d_month"], where=[("d_year", "==", 1993)])
         .where(("lo_discount", "between", (1, 3)))
         .predict(model)
         .group_by(("date", "d_year", 8, 1992), num_groups="auto")
         .agg(revenue="sum(lo_revenue)", preds=("mean", PREDICTION),
              n="count"))

    q.run()                    # whole-query aggregates — one fused program
    q.rows(row_ids)            # row predictions for a fact-row batch
    q.serve(buckets=(8, 64))   # bucketed dynamic-batch ServingRuntime

One compiled program computes *all* named aggregates over the shared
join/model work: ``sum``/``count``/``mean``/``min``/``max``, with mean
lowered as a fused sum/count and min/max through segment ops on both
aggregation backends.  Mesh placement, sharding thresholds, interpret mode
and plan-cache keys live on the session; plans are cached structurally
(:func:`~repro.core.query.session.query_key`), so equivalent pipelines —
fluent, hand-built IR, or registry rebuilds — never re-trace.

Multi-query optimization (the shared-artifact pool + batched execution)
-----------------------------------------------------------------------
A session is a *multi-query* optimizer, not just a plan cache.  Every plan
and serving runtime compiled through it acquires its physical artifacts —
PK indices, factored join pointers, predicate dim-masks, pre-fused model
partials — from one reference-counted :class:`ArtifactPool`
(``sess.pool``) keyed by arm-level content hashes.  N plans sharing a join
arm hold ONE pkindex/pointer array; N plans pre-fusing the same model over
the same dimension hold ONE partial.  The payoffs::

    sess.pool.stats()        # entries/hits/misses/bytes, per artifact kind
    catalog.append(...)      # a refresh touches each shared artifact ONCE
    sess.run_all([q1, ...])  # structurally compatible plans stack into one
                             # jitted program (leading query axis, vmapped)
                             # — one dispatch per class, bit-exact vs run()
    sess.evict(q)            # release a query's pool references; the last
                             # holder of an artifact frees it

``plan_query`` hears about sharing too: a join arm already resident in the
pool amortizes its maintenance cost over all holders, which the planner
folds into the fusion decision (``sharing=…x`` in the plan reason).
``compiled.explain()`` / ``runtime.explain()`` / ``scheduler.explain()``
all return a unified :class:`ExplainReport` whose ``shared_artifacts``
lists the pool keys a plan holds; ``str(report)`` is the legacy one-line
trail, ``report.as_dict()`` the machine-readable form.

Migration from the deprecated pre-Session entry points (thin shims that now
raise ``DeprecationWarning`` — the ``PredictiveQuery`` IR itself is still
the stable compiler contract):

=============================================  =============================
Old call                                       Session call
=============================================  =============================
``compile_query(catalog, q, **kw)``            ``sess.compile(q, **kw)`` or
                                               ``sess.bind(q).compile(**kw)``
``compile_query(catalog, q).run()``            ``sess.bind(q).run()``
``[compile_query(c, q).run() for q in qs]``    ``sess.run_all(qs)`` (pooled
                                               artifacts + one stacked
                                               program per class)
``CompiledQuery.predict_rows(ids)``            ``builder.rows(ids)``
``compile_serving(catalog, q, buckets=b)``     ``builder.serve(buckets=b)``
``compile_query(..., mesh=m, shard_...=...)``  ``Session(catalog, mesh=m,
                                               shard_...)`` once, per-call
                                               plumbing gone
``compiled_plan(name, data)`` (SSB registry)   ``ssb_session(data).compile(
                                               QUERY_IR[name]())``
``compile_query({'t': table, ...}, q)``        ``Session(Catalog({...}))``
(plain-dict catalog, auto-wrapped read-only;   — versioned, appendable,
deprecated)                                    pool-shared
hand-built ``PredictiveQuery(...)``            ``sess.query(fact).join(...)
                                               .where(...).predict(...)
                                               .group_by(...).agg(...)``
frozen ``{name: Table}`` dict + full           ``Session(Catalog({...}))``;
rebuild after data changes                     ``catalog.append(...)`` /
                                               ``.update_column(...)``,
                                               cached plans/runtimes
                                               refresh *in place* (delta
                                               path, zero retraces while
                                               shapes hold)
=============================================  =============================

Data lifecycle
--------------
``Session(catalog)`` accepts a :class:`~repro.core.laq.Catalog` — the
versioned data surface.  Every table carries a monotone version counter;
``catalog.append(table, rows)`` / ``catalog.update_column(...)`` bump it
transactionally and log the delta.  Plan/runtime cache keys include the
participating tables' versions, so a stale artifact is impossible to serve:
the next lookup refreshes it in place — ``PKIndex.extend`` sorted merges,
``prefuse_rows`` over only the new dimension rows, mask scatters — with
zero retraces while shapes hold (appends within a table's padded capacity).
Capacity growth recompiles, with the reason on ``explain()``.  Plain dicts
auto-wrap read-only (the old frozen contract, unchanged).

The full lifecycle surface and what each mutation costs a cached plan:

========================================  ===================================
Catalog call                              Cached-plan consequence
========================================  ===================================
``append(t, rows)`` within capacity       delta refresh in place: sorted
                                          ``PKIndex.extend`` merges, block
                                          join probes, ``prefuse_rows`` over
                                          only the new rows — zero retraces
``append(t, rows)`` beyond capacity       recompile/rebuild (shapes changed),
                                          reason on ``explain()``
``update_column(t, col, ids, vals)``      delta refresh of just the dirty
                                          rows (masks/partials rescattered)
``delete_rows(t, ids)``                   tombstone: shapes, keys and row
                                          placement all kept, so the delta
                                          path applies — deleted rows drop
                                          out through the validity fold,
                                          zero retraces
``compact(t)`` (tombstone GC, fires       row ids are rewritten, so every
past ``tombstone_fraction`` threshold)    referencing plan recompiles with
                                          ``compaction:<t> rewrote row ids``
========================================  ===================================

Snowflake chains (multi-hop dimensions)
---------------------------------------
An arm generalizes past a star: :class:`ChainLink` hangs sub-dimensions off
a dimension (or off an earlier link), TPC-DS-style, to depth 3 with fanout
up to 3 per node.  Factored PK–FK joins compose associatively —
``ptr_chain = take(link_ptr, head_ptr)`` — so the compiler collapses each
chain *inner-out* into one head-granularity virtual dimension before
prefusing it into the Eq. 1 partial form; the result is bit-exact with
materializing the chain as a flat pre-joined dimension
(:func:`~repro.core.query.snowflake.materialize_chains` is the executable
statement of that identity).  Sub-dimension predicates fold into the
chain's validity vector exactly like flat dimension predicates.  The
planner costs prefuse-through vs materialize-at-hop-k per chain
(``chain[head->hop->…]: …`` in the plan reason); pooled sessions share one
collapsed chain per content key and refresh it once per sub-dimension
append; serving prefuses chains offline so the request shape is unchanged.
Build chains fluently — ``.join(..., via=[("nation", "c_nationkey",
"n_pk", ["n_gdp"])])``, or just chain ``.join`` calls whose FK lives on an
already-joined dimension — or hand ``ArmSpec(links=(...))`` to the IR.

The subsystem is fuzzed: ``core.query.workload`` generates random
snowflake schemas/queries/models/prediction filters and checks every
lowering bit-exact against a float64 numpy oracle (``python
scripts/fuzz_repro.py --seed N`` replays any failure deterministically;
``--rewrite-matrix`` re-runs a seed with the rewrite engine on and off).

Query/model co-optimization (the rewrite engine)
------------------------------------------------
Because the paper expresses query *and* model as one linear-algebra
program, optimizations can cross the boundary between them.
:mod:`~repro.core.query.rewrite` runs a deterministic rule engine over the
IR before planning (``compile_query(rewrite="on")``, the default; ``"off"``
is the escape hatch):

``distill_tree_filter``
    ``.predict(tree, where=[(leaf, "==", 1.0)])`` filters rows on a tree
    prediction (:class:`PredictionFilter`).  When the filters select
    exactly one leaf, its root-to-leaf path conditions compile into
    ordinary dimension/link predicates and the model drops out of the
    online phase entirely — predict-then-filter becomes a pure relational
    query.
``fold_constant_inputs``
    An equality predicate pinning a feature column folds ``u · L[row]``
    into a model bias (carried by arm 0's prefused partial) and removes
    the input.
``project_zero_weights``
    Features with all-zero model rows leave the arms and the model.
``prune_tree_branches``
    Range predicates that decide a tree-node comparison for every
    surviving row fold that node into the compare vector ``h``.

Every rule is exact — the rewritten plan's ``run()`` is bit-identical to
the unrewritten plan's on all lowerings (the fuzzer checks on vs off per
case) — and data-independent, so rewritten plans refresh through the same
delta paths.  The planner costs the rewritten query against the original
(:func:`~repro.core.query.planner.estimate_query_cost`) and keeps the
winner; the fired-rule trail surfaces in ``plan.reason``
(``rewrite=[...]``) and ``explain()`` extras.

Out-of-core execution (fact streaming)
--------------------------------------
When the fact table's working set exceeds device memory — or the caller
pins a chunk size — the compiled program streams: the fact axis is
block-partitioned, per-chunk partial aggregates are folded through a
carried segment accumulator (sum/count exactly; min/max as masked segment
folds), and host→device transfer of chunk *i+1* overlaps compute of chunk
*i* (double buffering with donated chunk buffers).  Results are bit-exact
vs the in-core fused/gather/segment program — same adds, same order, the
chunk boundary never splits a segment update.  Enabled per call
(``compile(q, stream_chunk_rows=..., memory_budget_bytes=...)``) or
session-wide (``Session(catalog, memory_budget_bytes=...)``); the planner
explains its in-core-vs-streaming choice in ``plan_reason`` and
``explain().extras["stream"]`` describes the chunking.  Dimension-side
artifacts (partials, pointers, masks) are built once and shared across all
chunks — streaming composes with the artifact pool unchanged.

IR node → paper construct
-------------------------
======================  =====================================================
IR node                 Paper construct
======================  =====================================================
``Pred`` (via arms /    §2.2 selection as a binary filter vector s ∈ {0,1}ⁿ —
``fact_preds``)         folded into the matching matrix's validity instead of
                        multiplied through the data (mask_select)
``ArmSpec``             §2.3/§3.1 MM-Join arm: Iⱼ = MAT_fact · MAT_dimᵀ
                        (Alg. 1), kept factored as FK pointers for PK–FK
``PredictiveQuery``     §3 predictive pipeline  γ ∘ model ∘ ⋈ ∘ σ
``model=Linear…``       Eq. 1: T·L = Σⱼ Iⱼ(Bⱼ Mⱼ L) — the linear prefix is
                        *pre-fused* into each dimension table
``model=DecisionTree…`` Eq. 3 / Fig. 5: ((T F > v) H) == h with per-dimension
                        node-ownership masks Wⱼ
``GroupKey``            §2.4.2 composite group codes (sort-unique); the radix
                        ``bound`` is one digit of the code
``Aggregate``           §2.4/Fig. 4 group-by: one-hot matmul (faithful) or
                        segment ops (optimized) — compiler-chosen per the
                        whole aggregate set (``plan_aggregation``)
======================  =====================================================

``plan_query`` extends the paper's Eq. 2/4 fusion boundary with selection
selectivity, the Fig. 4 aggregation-backend choice costed over the combined
aggregate set, and the serving-kernel choice (``plan_serving_backend``);
its thresholds are keyed by ``jax.default_backend()``
(``planner_threshold`` / ``PLANNER_THRESHOLDS``) with CPU-seeded defaults,
so TPU calibration is a table entry.  ``num_groups="auto"`` sizes the group
dimension from the measured code domain on the offline concrete-array path.

Serving
-------
``builder.serve(buckets=...)`` (→ :func:`compile_serving`) compiles the
*online phase alone* over a ``(batch, fk...)`` request pytree and returns a
:class:`ServingRuntime` — the production entry point when requests are
arbitrary incoming key tuples rather than fact rows.  Each batch is
PAD_KEY-padded up to the smallest configured bucket and dispatched through
that bucket's jitted program (one trace per bucket, ever); a session mesh
shards the quasi-static partials per ``plan_partition_spec``; the Pallas
kernels (``fused_star_gather`` / ``tree_predict``) lower the gather-sum when
shapes fit.  Request keys equal to the padding sentinel are rejected with
:class:`SentinelKeyError` — they would be indistinguishable from padding.

Async serving (the admission scheduler)
---------------------------------------
``ServingRuntime.serve`` is synchronous: one caller, one batch at a time —
right for batch scoring, wrong for concurrent open-loop traffic.
``builder.serve(async_=True)`` (or ``sess.scheduler().register(runtime)``)
puts the runtime behind an :class:`AdmissionScheduler`: a per-plan request
queue whose single drain loop coalesces arriving FK requests into
bucket-shaped batches under a latency SLO (``slo_ms``), serves oversized
analytical batches chunk-by-chunk so point lookups interleave instead of
queueing behind them (per-step admission capped at the top bucket), keeps
two priority lanes (``"interactive"`` first, ``"batch"`` with a reserved
per-step row share — starvation-free both ways), and sheds load at a
bounded row queue with :class:`SchedulerBackpressureError`.  ``submit``
returns a Future; results are bit-exact vs synchronous ``serve``.  Data
refreshes on a scheduled runtime fence first (drain-then-swap): the
session's refresh paths route through ``scheduler.refresh()`` so no request
ever spans two catalog versions.  Use the scheduler when many concurrent
callers share compiled plans; call ``serve`` directly when one caller owns
the runtime.
"""
from ..laq.catalog import (Catalog, CatalogHistoryError,
                           CatalogReadOnlyError, TableDelta, changed_spans)
from .ir import (AGG_OPS, COUNT_STAR, FILTER_OPS, PREDICTION, Aggregate,
                 ArmSpec, ChainLink, GroupKey, PredictionFilter,
                 PredictiveQuery, eval_value, query_signature)
from .compile import CompiledQuery, compile_query, query_from_star
from .rewrite import RewriteResult, rewrite_query
from .explain import ExplainReport
from .mesh import make_serving_mesh
from .snowflake import (CollapsedChain, chain_tables, materialize_chains,
                        resolve_chain, virtual_name)
from .workload import FuzzCase, FuzzReport, generate_case, np_oracle, run_fuzz
from .multiquery import (ArtifactPool, arm_keys, artifact_bytes,
                         make_stacked_runner, stack_key, stack_states)
from .planner import (AggDecision, QueryPlan, plan_aggregation,
                      plan_partition_spec, plan_placements, plan_query,
                      plan_serving_backend, plan_streaming, planner_threshold,
                      DENSE_JOIN_ELEMS, MXU_SEGMENT_ADVANTAGE,
                      PLANNER_THRESHOLDS, SERVE_KERNEL_MAX_NODES,
                      SERVE_KERNEL_MAX_WIDTH, SHARD_PARTIAL_BYTES)
from .scheduler import (DEFAULT_MAX_QUEUED_ROWS, DEFAULT_SLO_MS, LANES,
                        AdmissionScheduler, ScheduledPlan,
                        SchedulerBackpressureError, SchedulerClosedError)
from .serving import (DEFAULT_BUCKETS, SentinelKeyError, ServingRuntime,
                      compile_serving, requests_from_rows)
from .session import QueryBuilder, Session, query, query_key
from .streaming import DEFAULT_CHUNK_ROWS, StreamExecutor, plan_chunk_rows
from .sharding import (ShardedArm, ShardedPrefusedPartials,
                       shard_prefused_partials)

__all__ = [
    "AGG_OPS", "COUNT_STAR", "FILTER_OPS", "PREDICTION", "Aggregate",
    "ArmSpec", "ChainLink", "GroupKey", "PredictionFilter",
    "PredictiveQuery", "query_signature",
    "RewriteResult", "rewrite_query",
    "CollapsedChain", "chain_tables", "materialize_chains", "resolve_chain",
    "virtual_name",
    "FuzzCase", "FuzzReport", "generate_case", "np_oracle", "run_fuzz",
    "Catalog", "CatalogHistoryError", "CatalogReadOnlyError", "TableDelta",
    "changed_spans",
    "eval_value", "CompiledQuery", "compile_query", "query_from_star",
    "ExplainReport",
    "ArtifactPool", "arm_keys", "artifact_bytes", "make_stacked_runner",
    "stack_key", "stack_states",
    "AggDecision", "QueryPlan", "plan_aggregation", "plan_partition_spec",
    "plan_placements", "plan_query", "plan_serving_backend",
    "plan_streaming", "planner_threshold", "PLANNER_THRESHOLDS",
    "DEFAULT_CHUNK_ROWS", "StreamExecutor", "plan_chunk_rows",
    "DENSE_JOIN_ELEMS",
    "MXU_SEGMENT_ADVANTAGE", "SERVE_KERNEL_MAX_NODES",
    "SERVE_KERNEL_MAX_WIDTH", "SHARD_PARTIAL_BYTES",
    "DEFAULT_BUCKETS", "SentinelKeyError", "ServingRuntime",
    "compile_serving", "requests_from_rows",
    "AdmissionScheduler", "ScheduledPlan", "SchedulerBackpressureError",
    "SchedulerClosedError", "DEFAULT_MAX_QUEUED_ROWS", "DEFAULT_SLO_MS",
    "LANES",
    "QueryBuilder", "Session", "make_serving_mesh", "query", "query_key",
    "ShardedArm", "ShardedPrefusedPartials", "shard_prefused_partials",
]
