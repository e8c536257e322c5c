"""Dynamic-batch serving runtime vs the compiled-query serving path.

The contract under test (ISSUE 2 acceptance):
  * one compiled plan serves request batches of many sizes with no
    recompilation beyond the fixed bucket set (asserted via trace and jit
    cache counts),
  * the Pallas kernel backend matches the jnp gather backend bit-exactly
    in fp32 on the full predictive-query suite,
  * serving the FKs of fact rows reproduces ``CompiledQuery.predict_rows``
    bit-exactly for rows that pass the fact-side predicates.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fusion import DecisionTreeGEMM, LinearOperator
from repro.core.query import (
    compile_query,
    compile_serving,
    plan_serving_backend,
    requests_from_rows,
)
from repro.core.query.planner import plan_fact_backend, resolve_serve_backend
from repro.data import QUERY_IR, generate_ssb, predictive_query_names, ssb_catalog

PRED_NAMES = predictive_query_names()
BUCKETS = (8, 32, 128)


@pytest.fixture(scope="module")
def data():
    return generate_ssb(sf=1, scale=0.0005, seed=5)


@pytest.fixture(scope="module")
def catalog(data):
    return ssb_catalog(data)


@pytest.fixture(scope="module")
def plans():
    """Per-module cache: (name, kwargs) -> compiled plan or runtime."""
    return {}


def _compiled(plans, catalog, name, **kwargs):
    key = ("query", name, tuple(sorted(kwargs.items())))
    if key not in plans:
        plans[key] = compile_query(catalog, QUERY_IR[name](), **kwargs)
    return plans[key]


def _runtime(plans, catalog, name, **kwargs):
    kwargs.setdefault("buckets", BUCKETS)
    key = ("serve", name, tuple(sorted(kwargs.items())))
    if key not in plans:
        plans[key] = compile_serving(catalog, QUERY_IR[name](), **kwargs)
    return plans[key]


def _passing_rows(catalog, q):
    """Fact rows on which serving and predict_rows must agree exactly."""
    fact = catalog[q.fact]
    ok = np.asarray(fact.valid_mask())
    for p in q.fact_preds:
        ok = ok & np.asarray(p.mask(fact))
    return np.nonzero(ok)[0]


def _random_requests(q, catalog, n, rng):
    """Random FK batches: live dimension keys mixed with guaranteed misses."""
    reqs = {}
    for arm in q.arms:
        dim = catalog[arm.table]
        live = np.asarray(dim.key(arm.pk_col))[: int(dim.nvalid)]
        keys = rng.choice(live, size=n)
        miss = rng.random(n) < 0.25
        keys = np.where(miss, rng.integers(-3, 0, size=n), keys)
        reqs[arm.fk_col] = keys.astype(np.int32)
    return reqs


# ------------------------------------------------ serving ≡ predict_rows
@pytest.mark.parametrize("backend", ["fused", "nonfused"])
@pytest.mark.parametrize("name", PRED_NAMES)
def test_serving_matches_predict_rows(name, backend, catalog, plans):
    q = QUERY_IR[name]()
    compiled = _compiled(plans, catalog, name, backend=backend)
    runtime = _runtime(plans, catalog, name, backend=backend)
    ids = _passing_rows(catalog, q)[:50]
    got = np.asarray(runtime.serve(requests_from_rows(catalog[q.fact], q, ids)))
    want = np.asarray(compiled.predict_rows(jnp.asarray(ids, jnp.int32)))
    np.testing.assert_array_equal(got, want)


# ------------------------------------- compile once, serve any batch size
def test_one_plan_serves_ragged_batches_without_recompile(catalog, plans):
    q = QUERY_IR["P1.linear.year"]()
    runtime = compile_serving(catalog, q, buckets=BUCKETS)
    rng = np.random.default_rng(0)
    sizes = [1, 3, 8, 9, 31, 32, 33, 100, 128]
    for n in sizes:
        out = runtime.serve(_random_requests(q, catalog, n, rng))
        assert out.shape == (n, runtime.out_width)
    assert runtime.num_compiles == len(BUCKETS)
    assert runtime.jit_cache_size() == len(BUCKETS)
    # A second ragged sweep plus oversized (chunked) batches: still no
    # recompilation beyond the fixed bucket set.
    for n in sizes + [129, 300, 1000]:
        runtime.serve(_random_requests(q, catalog, n, rng))
    assert runtime.num_compiles == len(BUCKETS)
    stats = runtime.latency_stats()
    # Chunked oversized calls report under their own key: their wall time
    # covers the whole request, not one top-bucket dispatch, so mixing it
    # into the top bucket's window would corrupt point-lookup percentiles.
    assert set(stats) == set(BUCKETS) | {"chunked"}
    assert all(s["count"] > 0 for s in stats.values())
    assert all(s["p50"] <= s["p99"] for s in stats.values())
    assert all("compile_ms" in s for b, s in stats.items() if b != "chunked")
    assert stats["chunked"]["count"] == 3          # 129, 300, 1000
    assert all(s["count"] == 5 for b, s in stats.items() if b != "chunked"), \
        "per-chunk dispatches must not inflate the top bucket's window"


def test_empty_batch_and_request_validation(catalog, plans):
    q = QUERY_IR["P1.linear.year"]()
    runtime = _runtime(plans, catalog, "P1.linear.year", backend="fused")
    empty = runtime.serve({k: np.zeros(0, np.int32) for k in runtime.request_keys})
    assert empty.shape == (0, runtime.out_width)
    with pytest.raises(KeyError):
        runtime.serve({"nope": np.zeros(4, np.int32)})
    ragged = [np.zeros(4, np.int32), np.zeros(5, np.int32), np.zeros(4, np.int32)]
    with pytest.raises(ValueError):
        runtime.serve(ragged)
    with pytest.raises(ValueError):
        compile_serving(catalog, QUERY_IR["Q1.1"]())
    with pytest.raises(ValueError):
        compile_serving(catalog, q, serve_backend="bogus")
    with pytest.raises(ValueError):
        compile_serving(catalog, q, buckets=())


# ------------------------------------------- Pallas kernel ≡ jnp gathers
@pytest.mark.parametrize("name", PRED_NAMES)
def test_kernel_backend_bitexact_full_pred_suite(name, catalog, plans):
    """fused_star_gather lowering ≡ jnp gather backend, bitwise in fp32."""
    q = QUERY_IR[name]()
    rng = np.random.default_rng(7)
    ref = _runtime(plans, catalog, name, backend="fused", serve_backend="jnp")
    ker = _runtime(
        plans,
        catalog,
        name,
        backend="fused",
        serve_backend="pallas",
        interpret=True,
    )
    assert ker.serve_backend == "pallas"
    for n in (5, 32, 64):
        reqs = _random_requests(q, catalog, n, rng)
        np.testing.assert_array_equal(
            np.asarray(ker.serve(reqs)),
            np.asarray(ref.serve(reqs)),
        )


@pytest.mark.parametrize("name", ["P3.tree.year", "P4.tree.select.region"])
def test_tree_predict_kernel_bitexact_nonfused(name, catalog, plans):
    """Non-fused tree serving lowers onto tree_predict, bit-exactly."""
    q = QUERY_IR[name]()
    rng = np.random.default_rng(8)
    ref = _runtime(plans, catalog, name, backend="nonfused", serve_backend="jnp")
    ker = _runtime(
        plans,
        catalog,
        name,
        backend="nonfused",
        serve_backend="pallas",
        interpret=True,
    )
    reqs = _random_requests(q, catalog, 40, rng)
    np.testing.assert_array_equal(
        np.asarray(ker.serve(reqs)),
        np.asarray(ref.serve(reqs)),
    )


def test_compile_query_pallas_serve_backend(catalog, plans):
    """compile_query's own serving path accepts the kernel lowering too."""
    name = "P2.linear.select.scalar"
    jnp_plan = _compiled(plans, catalog, name, backend="fused")
    ker_plan = _compiled(
        plans,
        catalog,
        name,
        backend="fused",
        serve_backend="pallas",
        interpret=True,
    )
    assert ker_plan.serve_backend == "pallas"
    assert ker_plan.plan.serve_backend == "pallas"
    ids = jnp.asarray([0, 1, 5, 17, 100, 2999], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(ker_plan.predict_rows(ids)),
        np.asarray(jnp_plan.predict_rows(ids)),
    )


def test_compile_query_pallas_nonfused_tree(catalog, plans):
    """Non-fused trees lower onto tree_predict; non-fused linear clamps to
    jnp so serve_backend always names the kernel that actually runs."""
    jnp_plan = _compiled(plans, catalog, "P3.tree.year", backend="nonfused")
    ker_plan = _compiled(
        plans,
        catalog,
        "P3.tree.year",
        backend="nonfused",
        serve_backend="pallas",
        interpret=True,
    )
    assert ker_plan.serve_backend == "pallas"
    assert ker_plan.plan.serve_backend == "pallas"
    ids = jnp.asarray([0, 2, 9, 41, 333], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(ker_plan.predict_rows(ids)),
        np.asarray(jnp_plan.predict_rows(ids)),
    )
    clamped = _compiled(
        plans,
        catalog,
        "P1.linear.year",
        backend="nonfused",
        serve_backend="pallas",
        interpret=True,
    )
    assert clamped.serve_backend == "jnp"
    assert clamped.plan.serve_backend == "jnp"


# ----------------------------------------------------- planner choices
def test_plan_serving_backend_rules():
    rng = np.random.default_rng(0)
    linear = LinearOperator(jnp.asarray(rng.normal(size=(6, 4)), jnp.float32))
    assert plan_serving_backend(linear, 3, platform="cpu")[0] == "jnp"
    assert plan_serving_backend(linear, 3, platform="tpu")[0] == "pallas"
    assert plan_serving_backend(None, 3, platform="tpu")[0] == "jnp"
    got = plan_serving_backend(linear, 3, backend="nonfused", platform="tpu")
    assert got[0] == "jnp"
    from repro.core.fusion import random_tree

    tree = random_tree(rng, 6, 2)
    assert isinstance(tree, DecisionTreeGEMM)
    got = plan_serving_backend(tree, 3, backend="nonfused", platform="tpu")
    assert got[0] == "pallas"
    # resolve_serve_backend: only nonfused linear lacks a kernel lowering.
    assert resolve_serve_backend("pallas", "fused", linear) == "pallas"
    assert resolve_serve_backend("pallas", "nonfused", linear) == "jnp"
    assert resolve_serve_backend("pallas", "nonfused", tree) == "pallas"
    assert resolve_serve_backend("jnp", "fused", linear) == "jnp"


def test_plan_fact_backend_keeps_gather_kernel_off_large_facts():
    """run() scores the whole fact axis: fused_star_gather takes it only
    when one call's SMEM holds the pointers, and the plan says why not."""
    from repro.kernels.fused_star_gather.kernel import max_rows_per_call

    rows = max_rows_per_call(3)
    assert plan_fact_backend("pallas", "fused", 3, rows) == ("pallas", "")
    backend, why = plan_fact_backend("pallas", "fused", 3, 6_000_000)
    assert backend == "jnp" and "SMEM" in why
    # tree_predict streams row blocks, and jnp stays jnp.
    assert plan_fact_backend("pallas", "nonfused", 3, 6_000_000)[0] == "pallas"
    assert plan_fact_backend("jnp", "fused", 3, 6_000_000) == ("jnp", "")
