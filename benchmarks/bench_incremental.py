"""Incremental refresh vs cold re-prefuse across dimension-append fractions.

The paper's §4.3 Q6/Q8 concern: prefused evaluation only amortizes if
dimension updates don't force a rebuild.  This bench appends
0.1% / 1% / 10% of the SSB ``part`` dimension to a live fused serving
runtime and measures, for each append:

* **cold**  — the pre-Catalog recourse: a fresh ``compile_serving`` on the
  updated catalog (full prefuse over every dimension row, PK re-argsort,
  and a new trace+XLA compile of the serving bucket) + one serve,
* **delta** — ``ServingRuntime.refresh()``: sorted-merge ``PKIndex.extend``,
  Eq. 1 partials prefused for ONLY the appended rows, mask scatter, zero
  retraces + the same serve.

Every serve is asserted bit-identical between the two runtimes, and the
run fails if the 1%-append delta path is not ≥ ``--min-speedup`` (default
5x, the ISSUE 5 acceptance bar) faster than cold.

Run:  PYTHONPATH=src python -m benchmarks.bench_incremental
      [--scale 0.05] [--reps 3] [--json BENCH_incremental.json]
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.core.laq import Catalog
from repro.core.query import compile_serving
from repro.data import QUERY_IR, generate_ssb, ssb_catalog

from .common import emit, write_json

FRACTIONS = (0.001, 0.01, 0.1)
QUERY = "P1.linear.year"


def _part_block(rng, start: int, m: int):
    """``m`` fresh part rows with new keys ``start..start+m``."""
    mfgr = rng.integers(0, 5, m)
    category = mfgr * 5 + rng.integers(0, 5, m)
    return {"partkey": start + np.arange(m), "p_mfgr": mfgr,
            "p_category": category,
            "p_brand1": category * 40 + rng.integers(0, 40, m),
            "p_size": rng.integers(1, 51, m)}


def _timed(fn) -> float:
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) * 1e6


def run(scale: float = 0.05, reps: int = 4, seed: int = 0,
        min_speedup: float = 5.0, do_assert: bool = True):
    # capacity_slack leaves padded rows for every appended block of the run
    # to land in without a shape change (the delta path's precondition).
    data = generate_ssb(sf=1, scale=scale, seed=seed, capacity_slack=1.6)
    catalog = ssb_catalog(data)
    q = QUERY_IR[QUERY]()
    rng = np.random.default_rng(seed + 1)
    n_part0 = int(data.part.nvalid)

    rt = compile_serving(catalog, q, backend="fused", buckets=(64,))
    reqs = {a.fk_col: rng.integers(
        0, 64, 64).astype(np.int32) for a in q.arms}
    rt.serve(reqs)                       # warm the single bucket
    next_key = n_part0

    speedups = {}
    for frac in FRACTIONS:
        m = max(1, int(n_part0 * frac))
        d_times, c_times = [], []
        for _ in range(reps):
            catalog.append("part", _part_block(rng, next_key, m))
            next_key += m

            def delta():
                line = rt.refresh()
                assert "delta" in line, f"expected delta path, got {line}"
                return rt.serve(reqs)

            d_times.append(_timed(delta))

            def cold():
                fresh = compile_serving(catalog, q, backend="fused",
                                        buckets=(64,))
                return fresh.serve(reqs), fresh

            t0 = time.perf_counter()
            out, fresh = cold()
            jax.block_until_ready(out)
            c_times.append((time.perf_counter() - t0) * 1e6)
            np.testing.assert_array_equal(
                np.asarray(rt.serve(reqs)), np.asarray(out),
                err_msg="delta refresh diverged from cold rebuild")
        # Min over reps, matching ``common.bench``: scheduler stalls on
        # shared runners are additive, the best observation is the cost.
        d_us, c_us = float(np.min(d_times)), float(np.min(c_times))
        speedups[frac] = c_us / d_us
        tag = f"append{frac:.1%}"
        emit(f"incremental/cold/{tag}", c_us,
             f"m={m};full prefuse + re-sort + retrace")
        emit(f"incremental/delta/{tag}", d_us,
             f"m={m};refresh: {speedups[frac]:.1f}x vs cold, 0 retraces")
        assert rt.num_compiles == 1, "delta path must never retrace"

    if do_assert and speedups[0.01] < min_speedup:
        raise SystemExit(
            f"[bench-incremental] FAIL: delta refresh at a 1% append is "
            f"only {speedups[0.01]:.2f}x faster than cold re-prefuse "
            f"(acceptance bar: {min_speedup}x)")
    print(f"[bench-incremental] delta vs cold speedups: "
          + ", ".join(f"{f:.1%}: {s:.1f}x" for f, s in speedups.items()))
    return speedups


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--reps", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--min-speedup", type=float, default=5.0)
    ap.add_argument("--no-assert", action="store_true",
                    help="report speedups without gating on them")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    run(scale=args.scale, reps=args.reps, seed=args.seed,
        min_speedup=args.min_speedup, do_assert=not args.no_assert)
    if args.json:
        write_json(args.json, {"bench": "incremental", "query": QUERY,
                               "fractions": list(FRACTIONS)})


if __name__ == "__main__":
    from repro.compile_cache import init_compile_cache
    init_compile_cache()
    main()
