"""Rewrite engine: distilled predicate plans vs predict-then-filter.

One query family, two lowerings of identical semantics:

* **off** — ``rewrite="off"``: the plan gathers every model feature, runs
  the tree as a GEMM (Fig. 5) over all fact rows, and filters on the
  prediction (``model_preds`` folded into validity).
* **on**  — the default: ``distill_tree_filter`` compiles the satisfying
  leaf's path conditions into ordinary dimension predicates and drops the
  model from the online phase entirely — the join+predict program
  degenerates to a pure relational aggregate.

Prediction filters are quasi-static — the fold runs when the star
assembles, so steady-state ``run()`` is near-identical for both plans
(emitted as a parity row).  Where dropping the model pays is the *online
maintenance cycle*: every data change re-assembles validity, and the
unrewritten plan must re-run the full fact-sized tree GEMM each time.
The bench drives append → ``refresh()`` → answer cycles through both
plans, asserts them bit-equal (the rewrite contract), and gates the
distilled cycle at ≥ 2x faster (the ISSUE 10 acceptance gate).  Also
measured: the rewrite pass itself (pure IR analysis, no data), and a
constant-input fold on a linear model (trajectory row, no gate).

Run:  PYTHONPATH=src python -m benchmarks.bench_rewrite
      [--scale 0.02] [--json BENCH_rewrite.json]
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax.numpy as jnp

from repro.core.fusion.operators import LinearOperator, tree_from_arrays
from repro.core.laq import Catalog, Table
from repro.core.laq.selection import Pred
from repro.core.query import (Aggregate, ArmSpec, GroupKey,
                              PredictionFilter, PredictiveQuery,
                              compile_query, rewrite_query)

from .common import bench, emit, write_json

BASE_FACT = 1_000_000          # rows at scale 1.0
K = 16                         # model feature width
DEPTH = 7                      # tree depth: 127 nodes / 128 leaves
PAD_GROUP = np.int64(2**31 - 1)


def _distillable_tree(rng: np.random.Generator):
    """A complete depth-``DEPTH`` tree whose all-right leaf is reachable.

    Right branches are ``feature > v``: giving the all-right path distinct
    features keeps its conjunction consistent, so filtering on that leaf
    distills to at most ``DEPTH`` ordinary predicates.  Every other node
    draws random features/thresholds — the rewrite only reads the chosen
    leaf's path.
    """
    p = 2 ** DEPTH - 1
    feature = rng.integers(0, K, p)
    threshold = rng.integers(-3, 4, p).astype(np.float32)
    node, level = 0, 0
    while node < p:
        feature[node] = level % K
        threshold[node] = np.float32(-2 + (level // K))
        node, level = 2 * node + 2, level + 1
    return tree_from_arrays(feature, threshold, K)


def build(scale: float, seed: int = 0):
    rng = np.random.default_rng(seed)
    n_fact = max(2_000, int(BASE_FACT * scale))
    n_dim = max(n_fact // 50, 64)
    dim_cols = {"d_pk": np.arange(n_dim)}
    for k in range(K):
        dim_cols[f"d_f{k}"] = rng.integers(-4, 5, n_dim)
    dim = Table.from_columns("dim", dim_cols, key_cols=("d_pk",),
                             capacity=int(n_dim * 1.5))
    fact = Table.from_columns("fact", {
        "fk": rng.integers(0, int(n_dim * 1.1), n_fact),
        "f_g": rng.integers(0, 8, n_fact),
        "revenue": rng.integers(-4, 5, n_fact)},
        key_cols=("fk", "f_g"), capacity=int(n_fact * 1.2))
    model = _distillable_tree(rng)
    arm = ArmSpec("dim", "fk", "d_pk",
                  tuple(f"d_f{k}" for k in range(K)), ())
    q = PredictiveQuery(
        "fact", (arm,), (), model, (GroupKey("fact", "f_g", 8),),
        (Aggregate("revenue", "sum", "rev"), Aggregate("*", "count", "n")),
        8, model_preds=(PredictionFilter(model.l - 1, "==", 1.0),))
    return {"dim": dim, "fact": fact}, q


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


def run(scale: float, seed: int = 0, json_path: str | None = None,
        do_assert: bool = True):
    tables, q = build(scale, seed)
    n = int(tables["fact"].nvalid)
    names = [a.name for a in q.aggregates]

    # The rewrite pass itself: pure IR/model analysis, no fact data.
    t0 = time.perf_counter()
    rw = rewrite_query(tables, q)
    rewrite_us = (time.perf_counter() - t0) * 1e6
    assert rw.changed and rw.query.model is None, rw.trail
    emit(f"rewrite/pass@{n}", rewrite_us,
         f"{len(rw.trail)} firings, {len(rw.query.arms[0].preds)} preds")

    cat_on, cat_off = Catalog(dict(tables)), Catalog(dict(tables))
    plan_on = compile_query(cat_on, q)
    plan_off = compile_query(cat_off, q, rewrite="off")
    assert any("distill" in t for t in plan_on._rewrites), plan_on._rewrites

    # Steady-state run() parity row: the prediction fold is quasi-static,
    # so both plans execute the same relational program between refreshes.
    us_run = bench(plan_on.run)
    emit(f"rewrite/run/steady@{n}", us_run,
         f"{bench(plan_off.run) / max(us_run, 1e-9):.2f}x off/on parity")

    # The gated metric: data-change → answer.  Each cycle appends m fact
    # rows and refreshes; the unrewritten plan re-runs the fact-sized tree
    # GEMM inside the validity fold, the distilled plan only probes deltas.
    m = max(1, n // 100)

    def make_cycle(cat, plan, salt):
        rng = np.random.default_rng(seed + salt)
        n_dim = int(tables["dim"].nvalid)

        def cycle():
            cat.append("fact", {
                "fk": rng.integers(0, int(n_dim * 1.1), m),
                "f_g": rng.integers(0, 8, m),
                "revenue": rng.integers(-4, 5, m)})
            plan.refresh()
            return plan.run()["rows"]
        return cycle

    us_on = bench(make_cycle(cat_on, plan_on, 2))
    us_off = bench(make_cycle(cat_off, plan_off, 2))
    speedup = us_off / max(us_on, 1e-9)
    emit(f"rewrite/cycle/on@{n}", us_on, f"m={m}; distilled: model dropped")
    emit(f"rewrite/cycle/off@{n}", us_off,
         f"m={m}; tree GEMM p={2 ** DEPTH - 1}; "
         f"distill speedup {speedup:.1f}x")

    if do_assert:
        # Same appends (same salt) on both catalogs: results must agree
        # bit-for-bit after all the refresh cycles above.
        a, b = (_result_map(plan_on.run(), names),
                _result_map(plan_off.run(), names))
        assert a == b, "rewritten != unrewritten"
        assert speedup >= 2.0, (
            f"distilled cycle only {speedup:.2f}x faster (gate: 2x)")

    # Trajectory row: constant-input folding on a linear model (no gate).
    rng = np.random.default_rng(seed + 1)
    model = LinearOperator(jnp.asarray(
        rng.integers(-2, 3, (K, 2)), jnp.float32))
    arm = q.arms[0]
    ql = PredictiveQuery(
        "fact", (ArmSpec(arm.table, arm.fk_col, arm.pk_col,
                         arm.feature_cols, (Pred("d_f0", "==", 2),)),),
        (), model, q.group_keys,
        (Aggregate("@prediction", "sum", "p"), Aggregate("*", "count", "n")),
        8)
    pl_on = compile_query(Catalog(dict(tables)), ql)
    pl_off = compile_query(Catalog(dict(tables)), ql, rewrite="off")
    us_lin = bench(pl_on.run)
    emit(f"rewrite/run/fold@{n}", us_lin,
         f"{us_lin and bench(pl_off.run) / us_lin:.2f}x vs off; "
         + ";".join(t.split("(")[0] for t in pl_on._rewrites))
    if do_assert:
        lnames = [a.name for a in ql.aggregates]
        assert _result_map(pl_on.run(), lnames) == _result_map(
            pl_off.run(), lnames), "folded != unrewritten"

    if json_path:
        write_json(json_path, {"bench": "rewrite", "scale": scale})


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None)
    ap.add_argument("--no-assert", action="store_true")
    args = ap.parse_args(argv)
    run(args.scale, args.seed, args.json, do_assert=not args.no_assert)


if __name__ == "__main__":
    from repro.compile_cache import init_compile_cache
    init_compile_cache()
    main()
