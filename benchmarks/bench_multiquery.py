"""Multi-query optimizer: pooled vs independent workload compilation.

The session's :class:`~repro.core.query.ArtifactPool` makes a *workload* —
here the full SSB registry — share one physical copy of every distinct
offline artifact (PK indices, factored join pointers, predicate dim-masks,
Eq. 1 prefused partials).  This bench measures the three payoffs:

* **compile** — total offline compile time of the registry, independent
  (``compile_query`` per query, no pool) vs pooled (one fresh
  ``ArtifactPool`` shared across the sweep).  Pool hits skip PK argsorts,
  probe passes and prefuse matmuls outright.
* **bytes**   — resident derived-artifact bytes across the compiled
  workload (:func:`~repro.core.query.artifact_bytes`, deduplicated by
  array identity): N plans sharing an arm hold ONE pointer array.
* **refresh** — a 1% ``part`` append under plans sharing that arm:
  independent plans each re-extend/re-probe their private copies; pooled
  plans refresh the shared artifact ONCE (asserted via the pool's
  per-entry update counters) and rebind.

Every pooled plan's results are asserted bit-identical to its independent
twin, and the run fails unless pooling wins ≥ ``--min-speedup`` (default
2x, the acceptance bar) on BOTH total compile time and resident bytes.

Run:  PYTHONPATH=src python -m benchmarks.bench_multiquery
      [--scale 0.02] [--reps 3] [--json BENCH_multiquery.json]
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.core.laq import Catalog
from repro.core.query import (ArtifactPool, Session, artifact_bytes,
                              compile_query)
from repro.data import QUERY_IR, generate_ssb, ssb_catalog

from .common import emit, write_json

SHARED_ARM_QUERIES = ("Q2.1", "Q2.2", "Q2.3")   # all join the part arm


def _part_block(rng, start: int, m: int):
    """``m`` fresh part rows with new keys ``start..start+m``."""
    mfgr = rng.integers(0, 5, m)
    category = mfgr * 5 + rng.integers(0, 5, m)
    return {"partkey": start + np.arange(m), "p_mfgr": mfgr,
            "p_category": category,
            "p_brand1": category * 40 + rng.integers(0, 40, m),
            "p_size": rng.integers(1, 51, m)}


def _compile_registry(catalog, names, pool=None):
    t0 = time.perf_counter()
    plans = [compile_query(catalog, QUERY_IR[n](), pool=pool)
             for n in names]
    jax.block_until_ready([p._state["valid"] for p in plans])
    return plans, (time.perf_counter() - t0) * 1e6


def run(scale: float = 0.02, reps: int = 3, seed: int = 0,
        min_speedup: float = 2.0, do_assert: bool = True):
    data = generate_ssb(sf=1, scale=scale, seed=seed, capacity_slack=1.6)
    catalog = ssb_catalog(data)
    names = sorted(QUERY_IR)
    rng = np.random.default_rng(seed + 1)

    # -- compile: whole registry, independent vs pooled ----------------------
    indep_times, pooled_times = [], []
    indep_plans = pooled_plans = None
    for _ in range(reps):
        indep_plans, us = _compile_registry(catalog, names)
        indep_times.append(us)
        pooled_plans, us = _compile_registry(catalog, names,
                                             pool=ArtifactPool(catalog))
        pooled_times.append(us)
    for n, a, b in zip(names, pooled_plans, indep_plans):
        ra, rb = a.run(), b.run()
        for k in rb:
            np.testing.assert_array_equal(
                np.asarray(ra[k]), np.asarray(rb[k]),
                err_msg=f"pooled {n}:{k} diverged from independent")
    c_us, p_us = float(np.min(indep_times)), float(np.min(pooled_times))
    compile_speedup = c_us / p_us
    emit("multiquery/compile/independent", c_us,
         f"queries={len(names)};private artifacts per plan")
    emit("multiquery/compile/pooled", p_us,
         f"queries={len(names)};{compile_speedup:.1f}x vs independent")

    # -- bytes: resident derived artifacts across the workload --------------
    indep_bytes = artifact_bytes(indep_plans)
    pooled_bytes = artifact_bytes(pooled_plans)
    bytes_ratio = indep_bytes / max(pooled_bytes, 1)
    emit("multiquery/bytes/independent", float(indep_bytes),
         "unit=bytes;sum of private derived arrays")
    emit("multiquery/bytes/pooled", float(pooled_bytes),
         f"unit=bytes;{bytes_ratio:.1f}x smaller (shared physical arrays)")

    # -- refresh: 1% part append, O(artifacts) not O(plans) ------------------
    sess = Session(Catalog({n: catalog[n] for n in catalog}))
    shared = [sess.compile(QUERY_IR[n]()) for n in SHARED_ARM_QUERIES]
    private_cat = Catalog({n: catalog[n] for n in catalog})
    private = [compile_query(private_cat, QUERY_IR[n]())
               for n in SHARED_ARM_QUERIES]
    n_part = int(np.asarray(sess.catalog["part"].nvalid))
    m = max(1, n_part // 100)
    next_key = n_part
    s_times, i_times = [], []
    for _ in range(reps):
        block = _part_block(rng, next_key, m)
        next_key += m
        sess.catalog.append("part", block)
        updates0 = sess.pool.stats()["updates"]
        t0 = time.perf_counter()
        out = sess.refresh()
        jax.block_until_ready([p._state["valid"] for p in shared])
        s_times.append((time.perf_counter() - t0) * 1e6)
        touched = sess.pool.stats()["updates"] - updates0
        stale = {k for p in shared for k in p._pool_keys() if "part" in k}
        assert all("delta" in line for line in out.values()), out
        assert touched == len(stale), \
            f"refresh touched {touched} artifacts, expected {len(stale)} " \
            f"(one per distinct stale artifact)"
        private_cat.append("part", block)
        t0 = time.perf_counter()
        for p in private:
            line = p.refresh()
            assert "delta" in line, line
        jax.block_until_ready([p._state["valid"] for p in private])
        i_times.append((time.perf_counter() - t0) * 1e6)
    for n, a, b in zip(SHARED_ARM_QUERIES, shared, private):
        ra, rb = a.run(), b.run()
        for k in rb:
            np.testing.assert_array_equal(
                np.asarray(ra[k]), np.asarray(rb[k]),
                err_msg=f"post-refresh {n}:{k} diverged")
    s_us, i_us = float(np.min(s_times)), float(np.min(i_times))
    emit("multiquery/refresh1pct/independent", i_us,
         f"plans={len(private)};each refreshes private part artifacts")
    emit("multiquery/refresh1pct/pooled", s_us,
         f"plans={len(shared)};shared part artifacts updated once "
         f"({i_us / s_us:.1f}x vs independent)")

    if do_assert:
        fails = []
        if compile_speedup < min_speedup:
            fails.append(f"registry compile only {compile_speedup:.2f}x "
                         f"faster pooled (bar: {min_speedup}x)")
        if bytes_ratio < min_speedup:
            fails.append(f"resident artifact bytes only {bytes_ratio:.2f}x "
                         f"smaller pooled (bar: {min_speedup}x)")
        if fails:
            raise SystemExit("[bench-multiquery] FAIL: " + "; ".join(fails))
    print(f"[bench-multiquery] pooled wins: compile {compile_speedup:.1f}x, "
          f"bytes {bytes_ratio:.1f}x, 1%-append refresh {i_us / s_us:.1f}x")
    return {"compile_speedup": compile_speedup, "bytes_ratio": bytes_ratio,
            "refresh_speedup": i_us / s_us}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--min-speedup", type=float, default=2.0)
    ap.add_argument("--no-assert", action="store_true",
                    help="report ratios without gating on them")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    run(scale=args.scale, reps=args.reps, seed=args.seed,
        min_speedup=args.min_speedup, do_assert=not args.no_assert)
    if args.json:
        write_json(args.json, {"bench": "multiquery",
                               "queries": sorted(QUERY_IR)})


if __name__ == "__main__":
    from repro.compile_cache import init_compile_cache
    init_compile_cache()
    main()
