"""Paper Figures 7–9: SSB query latency across scale factors and queries.

Runs the full 13-query SSB suite through the LAQ engine (factored MM-Join
physical operators) at several scale factors, at laptop scale
(cardinalities shrunk by ``SCALE``, selectivity structure preserved).
Per-query latencies mirror Fig. 8/9; per-sf means mirror Fig. 7.  The
join-algorithm comparison underlying the paper's analysis (MM-Join dense /
spMM vs sort-based join) is in ``bench_mmjoin.py``.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.data import QUERY_IR, generate_ssb, query_groups, ssb_session

from .common import bench, emit

SCALE = 0.003   # shrink factor vs true SSB (CPU-sized)


def run(sfs=(1, 2, 4)):
    for sf in sfs:
        data = generate_ssb(sf=sf, scale=SCALE, seed=0)
        session = ssb_session(data)
        groups = query_groups()
        total_us = 0.0
        for gname, qnames in groups.items():
            g_us = 0.0
            for qname in qnames:
                # Offline (joins/selection/codes) happens at compile; the
                # benchmarked call is the query's single jitted online plan.
                fn = session.compile(QUERY_IR[qname]()).run
                us = bench(fn)
                g_us += us
                emit(f"ssb/{qname}/sf{sf}", us,
                     f"rows={int(jnp.asarray(fn()['rows']))}")
            total_us += g_us
            emit(f"ssb/{gname}/sf{sf}", g_us / len(qnames), "group-mean")
        emit(f"ssb/all/sf{sf}", total_us / 13, "mean-13-queries")


if __name__ == "__main__":
    from repro.compile_cache import init_compile_cache
    init_compile_cache()
    run()
