"""Snowflake chains: prefuse-through vs materialize vs flat pre-joined.

Three lowerings of the same depth-3 chain query (fact → customer → nation
→ region, features on every hop, a sub-dimension predicate two hops deep):

* **through**      — ``chain_strategy="through"``: the chain collapses to
  pointer compositions each compile; nothing but the head-granularity
  virtual dimension is ever materialized.
* **materialize**  — ``chain_strategy="materialize"``: the planner pins
  hop caching at the deepest hop (costed per chain in ``plan.reason``).
* **flat**         — the schema denormalized offline by
  :func:`materialize_chains`: one real pre-joined dimension, the baseline
  a warehouse would hand-build.  The chain lowerings must match it
  bit-exactly (asserted every run) while skipping the denormalization.

Also measured: offline chain collapse time, and the sub-dimension append
refresh (cached Session plan, delta path) vs a cold recompile — the chain
maintenance win.

Run:  PYTHONPATH=src python -m benchmarks.bench_snowflake
      [--scales 0.02 0.1] [--json BENCH_snowflake.json]
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.core.fusion.operators import LinearOperator
from repro.core.laq import Catalog, Table
from repro.core.query import (Aggregate, ArmSpec, ChainLink, GroupKey,
                              PredictiveQuery, Session, compile_query,
                              materialize_chains, resolve_chain)
from repro.core.query.snowflake import chain_tables

from .common import bench, emit, write_json

BASE_FACT = 1_000_000          # rows at scale 1.0
PAD_GROUP = np.int64(2**31 - 1)


def build(scale: float, seed: int = 0):
    rng = np.random.default_rng(seed)
    n_fact = max(2_000, int(BASE_FACT * scale))
    n_cust, n_nat, n_reg = max(n_fact // 50, 64), 256, 32
    import jax.numpy as jnp

    region = Table.from_columns("region", {
        "r_pk": np.arange(n_reg), "r_g": rng.integers(0, 8, n_reg),
        "r_f0": rng.integers(-4, 5, n_reg)},
        key_cols=("r_pk", "r_g"), capacity=int(n_reg * 1.5))
    nation = Table.from_columns("nation", {
        "n_pk": np.arange(n_nat),
        "n_to_region": rng.integers(0, int(n_reg * 1.1), n_nat),
        "n_f0": rng.integers(-4, 5, n_nat)},
        key_cols=("n_pk", "n_to_region"), capacity=int(n_nat * 1.5))
    customer = Table.from_columns("customer", {
        "c_pk": np.arange(n_cust),
        "c_to_nation": rng.integers(0, int(n_nat * 1.1), n_cust),
        "c_f0": rng.integers(-4, 5, n_cust)},
        key_cols=("c_pk", "c_to_nation"), capacity=int(n_cust * 1.5))
    fact = Table.from_columns("sales", {
        "fk_cust": rng.integers(0, int(n_cust * 1.1), n_fact),
        "s_g": rng.integers(0, 8, n_fact),
        "revenue": rng.integers(-4, 5, n_fact)},
        key_cols=("fk_cust", "s_g"), capacity=int(n_fact * 1.2))
    arm = ArmSpec(
        "customer", "fk_cust", "c_pk", ("c_f0",), (),
        links=(ChainLink("nation", "c_to_nation", "n_pk", ("n_f0",),
                         preds=(("n_f0", ">=", -2),)),
               ChainLink("region", "n_to_region", "r_pk", ("r_f0",),
                         parent="nation")))
    from repro.core.query.session import _as_pred
    import dataclasses
    arm = dataclasses.replace(
        arm, links=tuple(dataclasses.replace(
            lk, preds=tuple(_as_pred(p) for p in lk.preds))
            for lk in arm.links))
    model = LinearOperator(jnp.asarray(
        rng.integers(-2, 3, (3, 2)), jnp.float32))
    q = PredictiveQuery(
        "sales", (arm,), (), model,
        (GroupKey("fact", "s_g", 8), GroupKey("region", "r_g", 8)),
        (Aggregate("revenue", "sum", "rev"),
         Aggregate("@prediction", "sum", "p"),
         Aggregate("*", "count", "n")), 64)
    tables = {"region": region, "nation": nation, "customer": customer,
              "sales": fact}
    return tables, q


def _result_map(res, names):
    groups = np.asarray(res["groups"])
    live = groups != PAD_GROUP
    out = {}
    for n in names:
        v = np.asarray(res[n], np.float64)
        v2 = v if v.ndim > 1 else v[:, None]
        out[n] = {int(g): tuple(v2[i]) for i, g in enumerate(groups)
                  if live[i]}
    return out


def run(scales, seed: int = 0, json_path: str | None = None,
        do_assert: bool = True):
    for scale in scales:
        tables, q = build(scale, seed)
        n = int(tables["sales"].nvalid)
        names = [a.name for a in q.aggregates]

        t0 = time.perf_counter()
        cc = resolve_chain(tables, q.arms[0])
        jax.block_until_ready(cc.table.matrix)
        collapse_us = (time.perf_counter() - t0) * 1e6
        emit(f"snowflake/collapse@{n}", collapse_us,
             f"hops={len(q.arms[0].links)}")

        # Apples-to-apples run comparison: the flat pre-joined schema only
        # carries the chain's PK key, so all three lowerings group on the
        # fact side here; the link-table group key is benched separately.
        qf = type(q)(q.fact, q.arms, q.fact_preds, q.model,
                     (GroupKey("fact", "s_g", 8),), q.aggregates, 8)
        results = {}
        for strategy in ("through", "materialize"):
            plan = compile_query(Catalog(dict(tables)), qf,
                                 chain_strategy=strategy)
            us = bench(plan.run)
            results[strategy] = plan.run()
            note = [r for r in plan.plan.reason.split("; ")
                    if r.startswith("chain[")]
            emit(f"snowflake/run/{strategy}@{n}", us,
                 note[0] if note else "")

        # Flat pre-joined baseline: denormalization cost paid offline.
        t0 = time.perf_counter()
        flat_tables, flat_q = materialize_chains(tables, qf)
        jax.block_until_ready(next(iter(flat_tables.values())).matrix)
        denorm_us = (time.perf_counter() - t0) * 1e6
        flat_cat = Catalog({**{k: v for k, v in tables.items()
                               if k not in chain_tables(q.arms[0])},
                            **flat_tables})
        flat_plan = compile_query(flat_cat, flat_q)
        us = bench(flat_plan.run)
        emit(f"snowflake/run/flat@{n}", us, f"denorm={denorm_us:.0f}us")

        # Grouping by a sub-dimension column (region, two hops deep) —
        # the capability the flat baseline lacks outright.
        link_plan = compile_query(Catalog(dict(tables)), q)
        emit(f"snowflake/run/linkgroup@{n}", bench(link_plan.run),
             "group by region.r_g through the chain")

        if do_assert:
            a = _result_map(results["through"], names)
            assert a == _result_map(results["materialize"], names), \
                "through != materialize"
            assert a == _result_map(flat_plan.run(), names), \
                "chain != flat baseline"

        # Sub-dimension append: cached-plan delta refresh vs cold rebuild.
        rng = np.random.default_rng(seed + 1)
        cat = Catalog(dict(tables))
        sess = Session(cat)
        sess.compile(q).run()
        m = max(1, int(tables["nation"].nvalid) // 100)

        def _append():
            cat.append("nation", {
                "n_pk": np.arange(m) + int(cat["nation"].nvalid),
                "n_to_region": rng.integers(0, 32, m),
                "n_f0": rng.integers(-4, 5, m)})

        # Warmup cycle: the first refresh jit-compiles the m-row scatter
        # updates; steady state (same append size) reuses them.
        _append()
        sess.compile(q).run()
        _append()
        t0 = time.perf_counter()
        warm = sess.compile(q)
        jax.block_until_ready(warm.run()["rows"])
        refresh_us = (time.perf_counter() - t0) * 1e6
        snap = Catalog({k: cat[k] for k in cat})
        t0 = time.perf_counter()
        cold = compile_query(snap, q)
        jax.block_until_ready(cold.run()["rows"])
        cold_us = (time.perf_counter() - t0) * 1e6
        emit(f"snowflake/refresh/delta@{n}", refresh_us,
             f"m={m};{cold_us / max(refresh_us, 1):.1f}x vs cold")
        emit(f"snowflake/refresh/cold@{n}", cold_us, f"m={m}")
        if do_assert:
            assert _result_map(warm.run(), names) == _result_map(
                cold.run(), names), "refresh != cold"

    if json_path:
        write_json(json_path, {"bench": "snowflake", "scales": list(scales)})


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scales", type=float, nargs="+", default=[0.02, 0.1])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None)
    ap.add_argument("--no-assert", action="store_true")
    args = ap.parse_args(argv)
    run(args.scales, seed=args.seed, json_path=args.json,
        do_assert=not args.no_assert)


if __name__ == "__main__":
    from repro.compile_cache import init_compile_cache
    init_compile_cache()
    main()
