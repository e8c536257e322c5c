"""Whole-query cost model: fusion × join backend × aggregation backend.

Extends the paper's Eq. 2/4 fusion boundary (``repro.core.fusion.plan_fusion``)
to the full predictive query:

* **Selection selectivity** shrinks every online term — selection is folded
  into the factored-join validity before prediction, so only surviving rows
  flow through the model and the aggregation (§2.2 composed with §3).
* **Join backend** — factored gathers by default; the paper-faithful dense
  one-hot matmul (Alg. 1) only ever wins on tiny inputs where the MXU matmul
  amortizes gather latency, mirroring the paper's MM-Join-vs-hash-join
  crossover (§4.2).
* **Aggregation backend** — Fig. 4's one-hot matmul costs ~2·i·G·l FLOPs vs
  the segment-sum scatter's ~i·l; the matmul only pays when the group count G
  is small enough that MXU throughput covers the extra work.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import PartitionSpec as P

from ...kernels.fused_star_gather.kernel import max_rows_per_call
from ...launch.sharding import safe_spec
from ..fusion.operators import DecisionTreeGEMM
from ..fusion.planner import FusionDecision, plan_fusion
from .ir import Model

# Cost-model thresholds, keyed by ``jax.default_backend()`` with the
# CPU-bench-seeded values as the default row — making TPU calibration a
# table entry ("tpu": {...}) rather than a refactor:
#
# * DENSE_JOIN_ELEMS — dense one-hot row-matching matrices are only viable
#   when the (fact × dim) matrix is small (paper §4.2: MM-Join loses to
#   pointer joins at scale).
# * MXU_SEGMENT_ADVANTAGE — MXU matmul throughput advantage over
#   scatter-based segment_sum: the matmul aggregation is picked when its
#   FLOP overcount (≈2·G) stays under this.  Calibrated on
#   bench_predictive_queries (G=8,l=4 matmul 4× faster; G=8192 matmul 300×
#   slower — any value in [13, ~1000) separates the two regimes).
# * SHARD_PARTIAL_BYTES — below this size a prefused partial is replicated
#   rather than row-sharded: the partial fits every device comfortably and
#   replication keeps the online gather collective-free.  CPU-bench
#   calibrated (bench_sharded_serving: the psum overhead only amortizes once
#   per-device slices clear the cache-resident regime).
PLANNER_THRESHOLDS = {
    "default": {
        "DENSE_JOIN_ELEMS": 1 << 14,
        "MXU_SEGMENT_ADVANTAGE": 16.0,
        "SHARD_PARTIAL_BYTES": 1 << 20,
        # Snowflake chains: total bytes of cached hop probes (int32 ptr +
        # bool found per parent row) a chain may pin to speed refresh.
        # Hops are cached parent-first until the budget runs out —
        # materialize-at-hop-k; a zero/overflowing budget prefuses through.
        "CHAIN_CACHE_BYTES": 1 << 22,
    },
    # "tpu": {...}  ← ROADMAP "Planner calibration": re-measure there and
    # fill this row in; every decision point below reads through
    # planner_threshold(), so no other code changes.
}

# Backward-compatible module-level aliases for the CPU-seeded defaults.
DENSE_JOIN_ELEMS = PLANNER_THRESHOLDS["default"]["DENSE_JOIN_ELEMS"]
MXU_SEGMENT_ADVANTAGE = PLANNER_THRESHOLDS["default"]["MXU_SEGMENT_ADVANTAGE"]
SHARD_PARTIAL_BYTES = PLANNER_THRESHOLDS["default"]["SHARD_PARTIAL_BYTES"]


def planner_threshold(name: str, platform: Optional[str] = None):
    """The calibrated threshold ``name`` for ``platform``.

    ``platform`` defaults to ``jax.default_backend()``; platforms without a
    calibration row fall back to the CPU-seeded ``"default"`` values.
    """
    defaults = PLANNER_THRESHOLDS["default"]
    if name not in defaults:
        raise KeyError(f"unknown planner threshold {name!r}; expected one "
                       f"of {sorted(defaults)}")
    if platform is None:
        platform = jax.default_backend()
    return PLANNER_THRESHOLDS.get(platform, defaults).get(
        name, defaults[name])


# fused_star_gather holds (J+1) lane-padded (1, l) row blocks in VMEM per
# grid step; tree_predict additionally keeps the (k, p) feature-selection
# block resident.  Both are far below VMEM at these bounds, which exist to
# refuse pathological widths rather than to pack VMEM tightly.
SERVE_KERNEL_MAX_WIDTH = 8192
SERVE_KERNEL_MAX_NODES = 16384


@dataclasses.dataclass(frozen=True)
class AggDecision:
    backend: str            # "segment" | "matmul"
    matmul_flops: float
    segment_flops: float
    reason: str


@dataclasses.dataclass(frozen=True)
class QueryPlan:
    backend: str            # "fused" | "nonfused"
    join_backend: str       # "gather" | "matmul"
    agg: Optional[AggDecision]
    fusion: Optional[FusionDecision]
    selectivity: float
    reason: str
    serve_backend: str = "jnp"   # "jnp" | "pallas" — online gather-sum kernel
    # Per-arm placement of the quasi-static row tables (prefused partials /
    # projected features) over the serving mesh; None when planned meshless.
    partition_specs: Optional[Tuple[P, ...]] = None
    # Out-of-core: rows per fact chunk when the plan streams the fact axis
    # (None = in-core).  Decided by plan_streaming from the fact working-set
    # bytes vs the device-memory budget, or pinned by the caller.
    stream_chunk_rows: Optional[int] = None


def plan_partition_spec(mesh, shape: Sequence[int], *, itemsize: int = 4,
                        axis: str = "model",
                        threshold: Optional[int] = None
                        ) -> Tuple[P, str]:
    """Placement for one quasi-static row table: replicate or row-shard.

    Small tables replicate (the online gather stays collective-free); tables
    past ``threshold`` bytes (default: the backend-keyed
    ``SHARD_PARTIAL_BYTES``) row-shard over the mesh's ``axis`` — through
    ``safe_spec``, so a row count that doesn't divide the axis degrades to
    replication instead of failing (the 15-heads-on-16-way rule, applied to
    prefused partials).  Returns ``(spec, reason)``.
    """
    if threshold is None:
        threshold = planner_threshold("SHARD_PARTIAL_BYTES")
    replicated = P(*([None] * len(shape)))
    if mesh is None:
        return replicated, "no mesh: replicate"
    nbytes = itemsize
    for d in shape:
        nbytes *= int(d)
    if nbytes < threshold:
        return replicated, (f"{nbytes}B < {threshold}B: replicate small "
                            "partial")
    spec = safe_spec(mesh, shape, axis, *([None] * (len(shape) - 1)))
    if spec[0] is None:
        return spec, (f"rows={shape[0]} does not divide mesh[{axis!r}]: "
                      "replicate (safe_spec fallback)")
    return spec, f"row-shard {shape[0]} rows over {axis}={mesh.shape[axis]}"


def plan_placements(mesh, shapes: Sequence[Sequence[int]], *,
                    itemsize: int = 4, axis: str = "model",
                    threshold: Optional[int] = None
                    ) -> Tuple[Tuple[P, ...], str]:
    """Per-arm placement over the arms' row-table shapes.

    The single implementation behind ``plan_query(mesh=...)`` and the
    compile/serving paths (which re-derive from *actual* table shapes) —
    returns ``(specs, reason)`` with the reason in the plan's
    ``place=[...]`` format.
    """
    specs, whys = [], []
    for shape in shapes:
        spec, why = plan_partition_spec(mesh, shape, itemsize=itemsize,
                                        axis=axis, threshold=threshold)
        specs.append(spec)
        whys.append(why)
    return tuple(specs), "place=[" + "; ".join(whys) + "]"


def place_tables(mesh, tables, plan: "QueryPlan", *, axis: str = "model",
                 threshold_bytes: Optional[int] = None
                 ) -> Tuple[Tuple[P, ...], "QueryPlan"]:
    """Placement for *actual* arm row tables, recorded on the plan.

    The one mesh-path setup shared by ``compile_query(mesh=)`` and
    ``compile_serving(mesh=)``: fused partial widths differ from non-fused
    feature widths, so placement is re-derived from the real table shapes
    and the plan's ``partition_specs``/reason updated to match what
    executes.
    """
    specs, place = plan_placements(
        mesh, [t.shape for t in tables], itemsize=tables[0].dtype.itemsize,
        axis=axis, threshold=threshold_bytes)
    plan = dataclasses.replace(plan, partition_specs=specs,
                               reason=plan.reason + "; " + place)
    return specs, plan


def resolve_mesh_serve_backend(serve_backend: str, mesh) -> str:
    """Clamp the serve backend for mesh serving (jnp-only today).

    The Pallas kernels are not composed with ``shard_map`` yet (the sharded
    block kernels are the TPU calibration follow-up), so an explicit
    ``"pallas"`` request alongside a mesh is an error rather than a silent
    downgrade; "auto"/"jnp" resolve to the jnp gathers.
    """
    if mesh is None:
        return serve_backend
    if serve_backend == "pallas":
        raise ValueError(
            "serve_backend='pallas' does not compose with mesh serving "
            "yet (sharded block kernels are the TPU follow-up); use "
            "serve_backend='jnp' or 'auto'")
    return "jnp"


def plan_serving_backend(model: Optional[Model], num_arms: int, *,
                         backend: str = "fused",
                         platform: Optional[str] = None) -> Tuple[str, str]:
    """Physical backend for the online gather-sum: Pallas kernel or jnp.

    Returns ``(backend, reason)``.  The Pallas lowering only pays off when
    the shapes fit the kernels' block specs (SystemML's fusion-plan lesson:
    a fused operator is only a win on the right physical kernel); everything
    else falls back to the pure-jnp gathers, which XLA lowers well on every
    platform.  Pallas TPU kernels also run on CPU in interpret mode — tests
    and the CI kernels-interpret job force ``serve_backend="pallas"`` with
    ``interpret=True`` there, so the choice here is only the *default*.
    """
    if platform is None:
        platform = jax.default_backend()
    if model is None:
        return "jnp", "no model head: nothing to lower onto a kernel"
    if platform != "tpu":
        return "jnp", f"platform {platform!r}: Pallas TPU kernels need a TPU"
    if backend == "fused":
        if num_arms < 1:
            return "jnp", "no arms: no gather-sum to lower"
        if model.l > SERVE_KERNEL_MAX_WIDTH:
            return "jnp", (f"l={model.l} exceeds fused_star_gather width "
                           f"bound {SERVE_KERNEL_MAX_WIDTH}")
        return "pallas", (f"fused_star_gather fits: J={num_arms}, "
                          f"l={model.l}")
    if isinstance(model, DecisionTreeGEMM):
        if (model.p <= SERVE_KERNEL_MAX_NODES
                and model.l <= SERVE_KERNEL_MAX_WIDTH):
            return "pallas", (f"tree_predict fits: p={model.p}, l={model.l}")
        return "jnp", (f"tree p={model.p}/l={model.l} exceeds tree_predict "
                       "block bounds")
    return "jnp", "nonfused linear head: XLA matmul already optimal"


def plan_fact_backend(serve_backend: str, backend: str, num_arms: int,
                      fact_rows: int) -> Tuple[str, str]:
    """Physical backend of the fact-sized prediction program; ``(b, why)``.

    ``run()``/``predictions()`` score every fact row at once.  The fused
    ``fused_star_gather`` kernel holds one call's (J, n) pointers in SMEM,
    so it takes the fact axis only when it fits one call; a larger fact
    gathers in jnp (the kernel would run one grid step per fact row over
    hundreds of chunked calls).  The serving paths keep the kernel: their
    batches are request-sized.  ``why`` is empty when nothing changed.
    """
    if serve_backend != "pallas" or backend != "fused":
        return serve_backend, ""
    rows = max_rows_per_call(num_arms)
    if fact_rows <= rows:
        return "pallas", ""
    return "jnp", (f"run=jnp (fact pointers {num_arms}x{fact_rows} exceed "
                   f"one fused_star_gather call's SMEM, {rows} rows; "
                   "serving keeps the kernel)")


def resolve_serve_backend(serve_backend: str, backend: str, model) -> str:
    """Clamp a requested serve backend to one that actually has a kernel.

    A non-fused *linear* head has no Pallas lowering (its online step is a
    plain matmul), so a "pallas" request degrades to "jnp" there — keeping
    the recorded serve_backend an honest statement of what executes.
    """
    if serve_backend != "pallas" or backend == "fused":
        return serve_backend
    return "pallas" if isinstance(model, DecisionTreeGEMM) else "jnp"


def effective_serve_backend(plan: "QueryPlan", serve_backend: str,
                            backend: str, model, num_arms: int) -> str:
    """The serve backend that will actually execute.

    "auto" must be re-planned against the *resolved* execution backend —
    the plan's own choice was made for the planner's backend, and e.g. an
    oversized tree that fits the fused kernel's width bound does not fit
    ``tree_predict``'s node bound.  Explicit choices are clamped only where
    no kernel lowering exists (non-fused linear heads).
    """
    if serve_backend == "auto":
        if backend == plan.backend:
            return plan.serve_backend
        return plan_serving_backend(model, num_arms, backend=backend)[0]
    return resolve_serve_backend(serve_backend, backend, model)


def plan_streaming(requested, fact_rows: int, fact_row_bytes: int,
                   memory_budget_bytes: Optional[int]
                   ) -> Tuple[Optional[int], str]:
    """In-core vs out-of-core for the fact axis; returns ``(chunk, reason)``.

    The working set of the online program is ~``fact_rows × fact_row_bytes``
    (matrix columns, join pointers, validity, group ids, plus the fact-sized
    intermediates the program materializes).  When a caller pins
    ``stream_chunk_rows`` to an int the decision is theirs; ``"auto"``
    streams with budget-sized chunks; ``None`` streams only when a
    ``memory_budget_bytes`` is given and the working set exceeds it — the
    common case stays in-core with zero overhead.
    """
    from .streaming import plan_chunk_rows
    est = int(fact_rows) * max(int(fact_row_bytes), 1)
    chunk = plan_chunk_rows(requested, int(fact_rows), int(fact_row_bytes),
                            memory_budget_bytes)
    if chunk is None:
        if memory_budget_bytes is not None:
            return None, (f"stream=off (working set ~{est / 1e6:.1f}MB fits "
                          f"budget {memory_budget_bytes / 1e6:.1f}MB)")
        return None, ""
    if isinstance(requested, int) and requested > 0:
        why = "caller pinned"
    elif memory_budget_bytes is not None:
        why = (f"working set ~{est / 1e6:.1f}MB vs budget "
               f"{memory_budget_bytes / 1e6:.1f}MB")
    else:
        why = "stream_chunk_rows='auto', no budget: default chunk"
    n_chunks = -(-int(fact_rows) // chunk) if fact_rows else 1
    return chunk, (f"stream={chunk} rows/chunk x {n_chunks} ({why}; fused "
                   "segment fold, dimension-side artifacts shared)")


def plan_chain_materialization(chain_name: str, parent_rows: Sequence[int],
                               *, strategy: str = "auto",
                               platform: Optional[str] = None
                               ) -> Tuple[int, str]:
    """Where along a snowflake chain to materialize; ``(k, reason)``.

    Collapsing a chain probes each hop at its parent's granularity.  The
    probes can be *cached* on the collapsed chain (materialize-at-hop-k:
    the first ``k`` hops keep their ``FactoredJoin``), so a refresh after
    an append re-probes only hops whose tables changed — at the cost of
    ``parent_rows[i] × 5`` resident bytes per cached hop (int32 ptr +
    bool found).  Hops are admitted parent-first while the cumulative
    cost fits ``CHAIN_CACHE_BYTES``; ``strategy`` overrides: ``"through"``
    caches nothing (prefuse-through), ``"materialize"`` caches every hop.
    """
    n = len(parent_rows)
    costs = [int(r) * 5 for r in parent_rows]
    if strategy == "through":
        return 0, f"chain[{chain_name}]: prefuse-through (caller pinned)"
    if strategy == "materialize":
        return n, (f"chain[{chain_name}]: materialize@{n}/{n} "
                   f"(caller pinned; hop cache {sum(costs)}B)")
    if strategy != "auto":
        raise ValueError(f"chain_strategy {strategy!r} not one of "
                         "('auto', 'through', 'materialize')")
    budget = planner_threshold("CHAIN_CACHE_BYTES", platform)
    k, spent = 0, 0
    for c in costs:
        if spent + c > budget:
            break
        spent += c
        k += 1
    if k == 0:
        return 0, (f"chain[{chain_name}]: prefuse-through (hop cache "
                   f"{costs[0] if costs else 0}B exceeds budget {budget}B)")
    return k, (f"chain[{chain_name}]: materialize@{k}/{n} (hop cache "
               f"{spent}B fits budget {budget}B; refresh reuses unchanged "
               "hops)")


def plan_aggregation(online_rows: float, num_groups: int, out_width: int,
                     ops: Sequence[str] = ("sum",),
                     platform: Optional[str] = None) -> AggDecision:
    """Fig. 4 matmul vs segment-sum, costed over the whole aggregate set.

    Multi-aggregate queries share work: every ``mean``/``count`` aggregate
    reuses one count reduction (a width-1 one-hot matmul or ones
    segment-sum), and each ``sum``/``mean`` needs one value reduction of
    ``out_width``.  ``min``/``max`` have no one-hot matmul form (Fig. 4 is
    additive) and lower through segment ops on *both* backends, so their
    cost is shared and only the matmul-able reductions decide the backend.
    """
    i = max(online_rows, 1.0)
    g = max(num_groups, 1)
    l = max(out_width, 1)
    ops = tuple(ops) or ("sum",)
    n_sums = sum(1 for op in ops if op in ("sum", "mean"))
    needs_count = any(op in ("count", "mean") for op in ops)
    n_minmax = sum(1 for op in ops if op in ("min", "max"))
    # onehot(gid)ᵀ @ values per sum-like reduction (+ a width-1 count).
    matmul = 2.0 * i * g * l * n_sums + (2.0 * i * g if needs_count else 0.0)
    # scatter-add + id gather per reduction.
    segment = (i * l + i) * n_sums + (2.0 * i if needs_count else 0.0)
    shared = (i * l + i) * n_minmax            # segment min/max either way
    advantage = planner_threshold("MXU_SEGMENT_ADVANTAGE", platform)
    if matmul > 0 and matmul <= segment * advantage:
        return AggDecision("matmul", matmul + shared, segment + shared,
                           f"G={g} small: MXU matmul beats scatter")
    return AggDecision("segment", matmul + shared, segment + shared,
                       f"G={g}: segment ops ({segment + shared:.0f} flops) "
                       f"beat one-hot matmul ({matmul + shared:.0f} flops)")


def estimate_query_cost(model: Optional[Model], fact_rows: int,
                        dim_rows: Sequence[int], *, num_groups: int = 0,
                        out_width: int = 1, agg_ops: Sequence[str] = ("sum",),
                        batches_per_update: float = 1000.0,
                        platform: Optional[str] = None) -> float:
    """Scalar per-batch work estimate for rewrite-vs-original comparison.

    One number covering the online phase (per-arm gathers + the model's
    fused contribution + aggregation) plus the offline prefuse build
    amortized over ``batches_per_update`` — so it moves in the right
    direction for every rewrite rule: dropping the model removes the
    dominant online term (distillation), while shrinking features (k),
    tree nodes (p) or model width shrinks the amortized offline term.
    It deliberately reuses :func:`plan_aggregation`'s FLOP counts rather
    than re-deriving them.
    """
    n = float(max(fact_rows, 1))
    j = max(len(dim_rows), 1)
    r = float(sum(dim_rows)) if dim_rows else 0.0
    cost = 2.0 * n * j                         # probes + validity fold
    if model is not None:
        l = max(model.l, 1)
        cost += n * (j + 1) * l                # Σⱼ Iⱼ Pⱼ gathers + adds
        offline = 2.0 * r * max(model.k, 1) * l        # B (M L) / B (M F)
        if isinstance(model, DecisionTreeGEMM):
            # compares + ownership mask + preds @ H per dimension row
            offline += r * model.p * (l + 2.0)
            cost += n * l                      # the == h compare
        cost += offline / max(batches_per_update, 1.0)
    if num_groups > 0:
        agg = plan_aggregation(n, num_groups, out_width, ops=agg_ops,
                               platform=platform)
        cost += min(agg.matmul_flops, agg.segment_flops)
    return cost


def plan_query(model: Optional[Model], fact_rows: int,
               dim_rows: Sequence[int], *, selectivity: float = 1.0,
               num_groups: int = 0, out_width: int = 1,
               agg_ops: Sequence[str] = ("sum",),
               batches_per_update: float = 1000.0,
               memory_budget_bytes: Optional[int] = None,
               platform: Optional[str] = None, mesh=None,
               shard_axis: str = "model",
               shard_threshold_bytes: Optional[int] = None,
               sharing: float = 1.0) -> QueryPlan:
    """Pick fused/nonfused + join/agg/serving backends for one query.

    ``agg_ops`` is the query's combined aggregate set (one op per
    aggregate); the aggregation backend is costed over all of them at once
    (:func:`plan_aggregation`).  With a ``mesh``, the plan also decides
    per-arm *placement* of the quasi-static row tables
    (``partition_specs``): each arm's prefused partial is sized as
    (dim rows × out_width) fp32 and either replicated or row-sharded over
    ``shard_axis`` (see :func:`plan_partition_spec`).

    ``sharing`` (≥ 1) is the multi-query pool's hint: how many plans share
    this query's prefused partials/join artifacts.  A partial referenced by
    N plans amortizes its one-time prefuse cost over N × the batches, which
    moves the fused/nonfused break-even — modeled by scaling
    ``batches_per_update`` in the fusion decision.
    """
    sel = min(max(float(selectivity), 0.0), 1.0)
    online_rows = float(fact_rows) * sel
    sharing = max(float(sharing), 1.0)

    fusion = None
    backend = "fused"
    if model is not None:
        fusion = plan_fusion(model, fact_rows, dim_rows,
                             batches_per_update=batches_per_update * sharing,
                             memory_budget_bytes=memory_budget_bytes,
                             selectivity=sel)
        backend = "fused" if fusion.fuse else "nonfused"

    dense_elems = float(fact_rows) * float(max(dim_rows, default=1))
    join_backend = ("matmul" if dense_elems <= planner_threshold(
        "DENSE_JOIN_ELEMS", platform) else "gather")

    agg = None
    if num_groups > 0:
        agg = plan_aggregation(online_rows, num_groups, out_width,
                               ops=agg_ops, platform=platform)

    serve_backend, serve_reason = plan_serving_backend(
        model, len(dim_rows), backend=backend, platform=platform)

    partition_specs = place_reason = None
    if mesh is not None:
        partition_specs, place_reason = plan_placements(
            mesh, [(int(r), out_width) for r in dim_rows], axis=shard_axis,
            threshold=shard_threshold_bytes)

    parts = [f"sel={sel:.3f}", f"join={join_backend}"]
    if sharing > 1.0:
        parts.append(f"sharing={sharing:g}x")
    if fusion is not None:
        parts.append(f"{backend} ({fusion.reason})")
    if agg is not None:
        parts.append(f"agg={agg.backend}")
    parts.append(f"serve={serve_backend} ({serve_reason})")
    if place_reason is not None:
        parts.append(place_reason)
    return QueryPlan(backend=backend, join_backend=join_backend, agg=agg,
                     fusion=fusion, selectivity=sel,
                     reason="; ".join(parts), serve_backend=serve_backend,
                     partition_specs=partition_specs)
