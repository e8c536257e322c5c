"""Paper Figures 10–11: per-operator breakdown of query group 4.

Times the stages of Q4.2 separately: domain/pointer generation (the
paper's "domain generation"), the four join-arm resolutions, predicate
evaluation, and group-by aggregation.  The paper finds joins dominate and
domain generation takes a similar share within joins — checked here on
the factored engine, plus the effect of the paper's suggested domain
*caching* (§4.2 Q3), which we implement.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.laq import (Pred, composite_code, default_domain_cache,
                            groupby_reduce, join_factored, key_domain)
from repro.data import generate_ssb

from .common import bench, emit


def run(sf=4, scale=0.003):
    data = generate_ssb(sf=sf, scale=scale, seed=0)
    lo = data.lineorder
    arms = [(data.customer, "lo_custkey", "custkey"),
            (data.supplier, "lo_suppkey", "suppkey"),
            (data.part, "lo_partkey", "partkey"),
            (data.date, "lo_orderdate", "datekey")]

    # Stage 1: domain generation (sorted union) per join arm.
    total_dom = 0.0
    for dim, fk, pk in arms:
        fn = jax.jit(lambda a=lo.key(fk), b=dim.key(pk):
                     key_domain([a, b], size=dim.capacity * 2))
        us = bench(fn)
        total_dom += us
    emit(f"breakdown/domain_gen/sf{sf}", total_dom, "4 arms")

    # Domain caching (paper's suggested optimization — ours to measure).
    t_cold = total_dom
    cache = default_domain_cache
    for dim, fk, pk in arms:
        cache.get_or_build([(dim.name, pk)], [lo.key(fk), dim.key(pk)],
                           size=dim.capacity * 2)
    t_warm = 0.0
    for dim, fk, pk in arms:
        fn = jax.jit(lambda d=dim, f=fk, p=pk: cache._store[
            cache._key([(d.name, p)])])
        t_warm += bench(fn)
    emit(f"breakdown/domain_cached/sf{sf}", t_warm,
         f"{t_cold / max(t_warm, 1e-9):.0f}x_faster")

    # Stage 2: join-arm pointer resolution.
    total_join = 0.0
    for dim, fk, pk in arms:
        fn = jax.jit(lambda a=lo.key(fk), b=dim.key(pk):
                     join_factored(a, b).ptr)
        total_join += bench(fn)
    emit(f"breakdown/joins/sf{sf}", total_join, "4 arms")

    # Stage 3: predicates + group-by aggregation (rest of Q4.2).
    def agg():
        ok_c = join_factored(lo.key("lo_custkey"), data.customer.key("custkey"))
        ok_s = join_factored(lo.key("lo_suppkey"), data.supplier.key("suppkey"))
        ok_p = join_factored(lo.key("lo_partkey"), data.part.key("partkey"))
        ok_d = join_factored(lo.key("lo_orderdate"), data.date.key("datekey"))
        valid = (lo.valid_mask() & ok_c.found & ok_s.found & ok_p.found
                 & ok_d.found)
        valid &= jnp.take(Pred("c_region", "==", 1).mask(data.customer),
                          ok_c.ptr)
        year = jnp.take(data.date.key("d_year"), ok_d.ptr)
        nation = jnp.take(data.supplier.key("s_nation"), ok_s.ptr)
        cat = jnp.take(data.part.key("p_category"), ok_p.ptr)
        codes = composite_code([year - 1992, nation, cat], [8, 25, 25], valid)
        profit = jnp.where(valid, lo.col("lo_revenue")
                           - lo.col("lo_supplycost"), 0.0)
        return groupby_reduce(codes, [profit], 4096, ("sum",))

    us_all = bench(jax.jit(agg))
    emit(f"breakdown/q42_full/sf{sf}", us_all,
         f"joins_share={total_join / us_all:.2f}")


if __name__ == "__main__":
    from repro.compile_cache import init_compile_cache
    init_compile_cache()
    run()
