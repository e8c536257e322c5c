"""Benchmark orchestrator: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus heatmap blocks).
"""
from __future__ import annotations

import time

from . import (bench_breakdown, bench_fusion_linear, bench_fusion_tree,
               bench_kernels, bench_mmjoin, bench_prefusion, bench_ssb)
from .common import HEADER


def main() -> None:
    print(HEADER)
    t0 = time.time()
    for name, mod in [
        ("ssb (Fig.7-9)", bench_ssb),
        ("mmjoin (§2.3/[24])", bench_mmjoin),
        ("breakdown (Fig.10-11)", bench_breakdown),
        ("fusion_linear (Fig.12-15)", bench_fusion_linear),
        ("fusion_tree (Fig.17-20)", bench_fusion_tree),
        ("prefusion (Fig.16,21)", bench_prefusion),
        ("kernels", bench_kernels),
    ]:
        print(f"# --- {name} ---", flush=True)
        mod.run()
    print(f"# total {time.time() - t0:.0f}s")


if __name__ == "__main__":
    from repro.compile_cache import init_compile_cache
    init_compile_cache()
    main()
