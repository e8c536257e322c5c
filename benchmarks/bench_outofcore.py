"""Out-of-core fact streaming vs in-core, across SSB scale factors.

The ISSUE 8 concern: chunked execution must (a) stay bit-exact vs the
in-core fused/gather/segment program — the carried segment accumulator
replays the exact same adds — and (b) cost little enough that streaming is
a memory feature, not a throughput cliff.  For each scale this bench runs
the pinned in-core program and the streamed program (chunks sized to a
budget ~1/3 of the fact working set, so every run folds several chunks),
asserts bitwise equality of every output, and emits rows/s for both; the
run fails when streamed throughput at the largest scale drops below
``1 / --max-slowdown`` of in-core (default 1.3x, the acceptance bar).

A second section measures the tombstone lifecycle at the largest scale:
``delete_rows`` + zero-retrace streamed ``refresh`` (vs a cold recompile)
and the post-``compact`` rebuild.

Run:  PYTHONPATH=src python -m benchmarks.bench_outofcore
      [--scales 0.02 0.05 0.1] [--reps 9] [--json BENCH_outofcore.json]
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.core.query import compile_query
from repro.data import QUERY_IR, generate_ssb, ssb_catalog

from .common import emit, write_json

QUERY = "P1.linear.year"
#: The in-core lowering streaming is bit-exact against (the auto planner
#: may pick matmul aggregation at small group counts — a different, valid
#: program whose float adds associate differently).
PINNED = dict(backend="fused", join_backend="gather", agg_backend="segment")


def _bench_run(plan, reps: int) -> float:
    """Best wall time (µs) of ``plan.run()`` — min over reps, matching
    ``common.bench``: scheduler noise on shared runners is additive."""
    jax.block_until_ready(plan.run())          # warm the trace(s)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(plan.run())
        times.append(time.perf_counter() - t0)
    return float(np.min(times) * 1e6)


def _assert_bitexact(streamed, incore, tag: str):
    for k, v in incore.items():
        if not np.array_equal(np.asarray(streamed[k]), np.asarray(v)):
            raise SystemExit(
                f"[bench-outofcore] FAIL {tag}: streamed {k!r} diverged "
                "from the in-core fused/gather/segment program")


def run(scales=(0.02, 0.05, 0.1), reps: int = 9, seed: int = 0,
        max_slowdown: float = 1.3, do_assert: bool = True):
    q = QUERY_IR[QUERY]()
    ratios = {}
    catalog = None
    for scale in scales:
        data = generate_ssb(sf=1, scale=scale, seed=seed,
                            capacity_slack=1.3)
        catalog = ssb_catalog(data)
        fact = catalog[q.fact]
        rows = int(fact.nvalid)
        incore = compile_query(catalog, q, **PINNED)
        # A budget ~1/3 of the resident fact bytes: every scale streams in
        # several budget-sized chunks instead of degenerating to one, while
        # per-chunk dispatch overhead (fixed cost per fold on CPU) stays
        # small enough that the 1.3x throughput bar has real margin.
        fact_bytes = (fact.matrix.size * fact.matrix.dtype.itemsize
                      + sum(k.size * k.dtype.itemsize
                            for k in fact.keys.values()))
        budget = max(int(fact_bytes) // 3, 64 * 1024)
        streamed = compile_query(catalog, q, memory_budget_bytes=budget)
        if streamed._stream is None:
            raise SystemExit(f"[bench-outofcore] FAIL scale={scale}: "
                             f"budget {budget} did not trigger streaming")
        _assert_bitexact(streamed.run(), incore.run(), f"scale={scale}")

        i_us = _bench_run(incore, reps)
        s_us = _bench_run(streamed, reps)
        ratios[scale] = s_us / i_us
        n_chunks = -(-catalog[q.fact].capacity
                     // streamed.plan.stream_chunk_rows)
        emit(f"outofcore/incore/sf{scale}", i_us,
             f"rows={rows};{rows / i_us:.0f} rows/us")
        emit(f"outofcore/stream/sf{scale}", s_us,
             f"rows={rows};chunks={n_chunks};{rows / s_us:.0f} rows/us;"
             f"{ratios[scale]:.2f}x vs incore")

    # Tombstone lifecycle at the largest scale: delete + zero-retrace
    # streamed refresh (vs cold recompile), then the compaction rebuild.
    rng = np.random.default_rng(seed + 1)
    streamed = compile_query(catalog, q,
                             stream_chunk_rows=streamed.plan.stream_chunk_rows)
    streamed.run()
    traces0 = streamed._stream.traces
    n = int(catalog[q.fact].nvalid)
    catalog.delete_rows(q.fact, rng.choice(n, size=n // 100, replace=False))

    t0 = time.perf_counter()
    note = streamed.refresh()
    jax.block_until_ready(streamed.run())
    d_us = (time.perf_counter() - t0) * 1e6
    assert "delta" in note, f"expected delta path, got {note}"
    assert streamed._stream.traces == traces0, "delete refresh retraced"

    t0 = time.perf_counter()
    cold = compile_query(catalog, q,
                         stream_chunk_rows=streamed.plan.stream_chunk_rows)
    out = cold.run()
    jax.block_until_ready(out)
    c_us = (time.perf_counter() - t0) * 1e6
    _assert_bitexact(streamed.run(), out, "refresh-after-delete")
    emit("outofcore/delete_refresh", d_us,
         f"1% tombstones;{c_us / d_us:.1f}x vs cold, 0 retraces")
    emit("outofcore/delete_cold", c_us, "recompile + full rerun")

    catalog.delete_rows(q.fact,
                        rng.choice(n, size=n // 3, replace=False))
    assert catalog.compact(q.fact)
    t0 = time.perf_counter()
    note = streamed.refresh()
    jax.block_until_ready(streamed.run())
    emit("outofcore/compact_rebuild", (time.perf_counter() - t0) * 1e6,
         "tombstone GC: row ids rewrote, recompile")
    assert "compaction" in note, f"expected compaction rebuild, got {note}"

    worst = ratios[max(ratios)]
    if do_assert and worst > max_slowdown:
        raise SystemExit(
            f"[bench-outofcore] FAIL: streaming at the largest scale is "
            f"{worst:.2f}x slower than in-core (acceptance bar: "
            f"{max_slowdown}x)")
    print("[bench-outofcore] stream/incore ratios: "
          + ", ".join(f"sf{s}: {r:.2f}x" for s, r in ratios.items()))
    return ratios


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scales", type=float, nargs="+",
                    default=[0.02, 0.05, 0.1])
    ap.add_argument("--reps", type=int, default=9)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-slowdown", type=float, default=1.3)
    ap.add_argument("--no-assert", action="store_true",
                    help="report ratios without gating on them")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    run(scales=tuple(args.scales), reps=args.reps, seed=args.seed,
        max_slowdown=args.max_slowdown, do_assert=not args.no_assert)
    if args.json:
        write_json(args.json, {"bench": "outofcore", "query": QUERY,
                               "scales": list(args.scales)})


if __name__ == "__main__":
    from repro.compile_cache import init_compile_cache
    init_compile_cache()
    main()
