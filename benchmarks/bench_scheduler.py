"""Open-loop load generator for the admission scheduler (ISSUE 6).

``bench_serving`` measures the closed loop — one caller, one bucketed batch
at a time.  This bench measures what the scheduler adds under *open-loop*
traffic, where requests arrive on their own clock (uniform burst / poisson)
instead of waiting for the previous answer:

  * ``closed_loop``   — synchronous per-request ``serve`` (the baseline the
    scheduler must beat): mean us/request over sequential point lookups.
  * ``open_loop_burst`` — the same requests submitted all at once through
    the scheduler: coalescing packs them into top-bucket steps, so the
    sustained rate is dispatch-bound, not request-bound.  The bench
    *asserts* this beats the closed loop (the ISSUE acceptance criterion),
    and that every future is bit-exact vs the closed-loop outputs.
  * ``poisson``       — arrivals at 1.5x the closed-loop rate; reports the
    submit→result p99 an open-loop client actually observes.
  * ``interactive_under_batch`` — point lookups issued while an oversized
    analytical scan is in flight on the batch lane: chunked admission lets
    them ride along in top-bucket steps instead of queueing behind the
    scan; reports their p99 (the SLO-under-load number).

All rows are lower-is-better microseconds, gated by the CI bench-regression
job against ``benchmarks/baselines/BENCH_scheduler.json`` (its own
``check_regression`` invocation: latency rows are threading-jittery, so the
gate runs with a wider tolerance and no ``--min-us`` floor).

Run:  PYTHONPATH=src python -m benchmarks.bench_scheduler
      [--scale 0.05] [--requests 240] [--json BENCH_scheduler.json]
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.launch.serve import FusedFeatureServer

from .common import emit, write_json


def _pct(ts, p):
    return float(np.percentile(np.asarray(ts) * 1e6, p))


def run(scale: float = 0.05, requests: int = 240, k: int = 16, l: int = 4,
        slo_ms: float = 2.0, seed: int = 0):
    server = FusedFeatureServer(setting=2, sf=1, k=k, l=l, scale=scale,
                                seed=seed)
    rt = server.runtime_fused
    rng = np.random.default_rng(seed + 1)
    top = rt.buckets[-1]
    # Warm every bucket so neither loop ever traces mid-measurement.
    for n in [1] + list(rt.buckets):
        server.serve_batch(server.random_requests(n, rng))

    point = [server.random_requests(1, rng) for _ in range(requests)]

    # -- closed loop: sequential synchronous point lookups ------------------
    t0 = time.perf_counter()
    want = [np.asarray(server.serve_batch(r)) for r in point]
    closed_s = time.perf_counter() - t0
    qps_closed = requests / closed_s
    emit("scheduler/closed_loop/us_per_req", closed_s / requests * 1e6,
         f"qps={qps_closed:.0f};n={requests}")

    plan = server.scheduled(slo_ms=slo_ms)

    # -- open-loop burst: all requests in flight at once --------------------
    t0 = time.perf_counter()
    futs = [plan.submit(r) for r in point]
    got = [np.asarray(f.result(120)) for f in futs]
    burst_s = time.perf_counter() - t0
    qps_burst = requests / burst_s
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)   # scheduled ≡ synchronous
    assert qps_burst > qps_closed, (
        f"open-loop coalescing must beat closed-loop serving: "
        f"{qps_burst:.0f} qps <= {qps_closed:.0f} qps")
    emit("scheduler/open_loop_burst/us_per_req", burst_s / requests * 1e6,
         f"qps={qps_burst:.0f};speedup={qps_burst / qps_closed:.1f}x")

    # -- poisson arrivals at 1.5x the closed-loop rate ----------------------
    lat = []
    offered = 1.5 * qps_closed
    gaps = rng.exponential(1.0 / offered, size=requests)
    done = []
    t_start = time.perf_counter()
    next_t = t_start
    for r, gap in zip(point, gaps):
        next_t += gap
        now = time.perf_counter()
        if next_t > now:
            time.sleep(next_t - now)
        t_sub = time.perf_counter()
        done.append((t_sub, plan.submit(r)))
    for t_sub, f in done:
        f.result(120)
        lat.append(time.perf_counter() - t_sub)
    span = time.perf_counter() - t_start
    emit("scheduler/poisson/us_per_req", span / requests * 1e6,
         f"offered_qps={offered:.0f};sustained_qps={requests / span:.0f}")
    emit("scheduler/poisson/p99_us", _pct(lat, 99),
         f"p50_us={_pct(lat, 50):.0f};p95_us={_pct(lat, 95):.0f}")

    # -- point lookups while an analytical scan is in flight ----------------
    scan = server.random_requests(8 * top, rng)
    want_scan = np.asarray(server.serve_batch(scan))
    f_scan = plan.submit(scan, lane="batch")
    ilat = []
    while not f_scan.done():
        r = server.random_requests(1, rng)
        t0 = time.perf_counter()
        np.asarray(plan.submit(r).result(120))
        ilat.append(time.perf_counter() - t0)
    np.testing.assert_array_equal(np.asarray(f_scan.result(0)), want_scan)
    assert ilat, "scan completed before any interleaved lookup was served"
    emit("scheduler/interactive_under_batch/p99_us", _pct(ilat, 99),
         f"n={len(ilat)};scan_rows={8 * top};p50_us={_pct(ilat, 50):.0f}")

    st = plan.stats()
    print(f"[bench] scheduler steps={st['steps']} "
          f"admitted={st['admitted_rows']} padded={st['padded_rows']} "
          f"rejected={st['rejected']}", flush=True)
    return server, plan


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--requests", type=int, default=240)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--l", type=int, default=4)
    ap.add_argument("--slo-ms", type=float, default=2.0)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    server, plan = run(scale=args.scale, requests=args.requests, k=args.k,
                       l=args.l, slo_ms=args.slo_ms)
    stats = plan.stats()
    server.session.scheduler().close()
    if args.json:
        write_json(args.json, {"bench": "scheduler", "scheduler": stats})


if __name__ == "__main__":
    from repro.compile_cache import init_compile_cache
    init_compile_cache()
    main()
