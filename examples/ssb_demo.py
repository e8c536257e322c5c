"""SSB demo: run the Star Schema Benchmark queries through the LAQ engine.

Generates a CPU-scale SSB instance and executes all 13 queries, printing
result cardinalities and a few group-by outputs.

Run:  PYTHONPATH=src python examples/ssb_demo.py [--sf 2]
"""
import argparse
import time

import jax
import numpy as np

from repro.core.laq import PAD_GROUP, decode_composite
from repro.data import QUERIES, generate_ssb


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=1)
    ap.add_argument("--scale", type=float, default=0.003)
    args = ap.parse_args()

    data = generate_ssb(sf=args.sf, scale=args.scale, seed=0)
    print(f"SSB sf={args.sf} (scaled ×{args.scale}): "
          f"lineorder={int(data.lineorder.nvalid)} rows")

    for name, q in QUERIES.items():
        fn = jax.jit(lambda d=data, qq=q: qq(d))
        fn()  # compile
        t0 = time.perf_counter()
        res = jax.block_until_ready(fn())
        dt = (time.perf_counter() - t0) * 1e3
        key = next(k for k in ("revenue", "profit", "prediction")
                   if k in res)
        vals = np.asarray(res[key])
        if "groups" not in res:
            print(f"{name}: rows={int(res['rows']):7d} "
                  f"{key}_total={float(vals.sum()):.2f}  ({dt:.1f} ms)")
        else:
            groups = np.asarray(res["groups"])
            live = groups != PAD_GROUP
            print(f"{name}: rows={int(res['rows']):7d} "
                  f"groups={int(live.sum()):5d} "
                  f"{key}_total={vals.sum():.2f}  ({dt:.1f} ms)")
    # Show a decoded group-by result (Q2.1 = year × brand).
    res = QUERIES["Q2.1"](data)
    groups = np.asarray(res["groups"])
    rev = np.asarray(res["revenue"])
    live = groups != PAD_GROUP
    year, brand = decode_composite(groups[live][:5], [8, 1000])
    print("Q2.1 head: year", np.asarray(year) + 1992, "brand",
          np.asarray(brand), "revenue", rev[live][:5].round(1))


if __name__ == "__main__":
    from repro.compile_cache import init_compile_cache
    init_compile_cache()
    main()
