"""Admission scheduler over ``ServingRuntime`` (ISSUE 6).

The contract under test:
  * scheduled results are **bit-exact** vs synchronous ``serve`` of the
    same requests — for coalesced small batches, chunked oversized batches,
    multiple plans on one drain loop, and across a mid-stream ``refresh()``
    (fence: a started request completes entirely on its data generation),
  * SLO flush: a lone request is served within the deadline without
    waiting for a full bucket (auto drain thread),
  * priority lanes are starvation-free both ways — point lookups interleave
    with an in-flight analytical batch, and the batch lane's reserved share
    guarantees progress under an interactive flood,
  * bounded queues reject with ``SchedulerBackpressureError``; closed
    schedulers reject with ``SchedulerClosedError`` (default ``close``
    drains, ``cancel=True`` fails pending futures),
  * normalization errors (ragged / missing / sentinel-valued keys) raise
    synchronously in the submitting caller, not inside the drain loop,
  * the sharded runtime serves through the scheduler bit-exact (8 host
    devices; multi-device CI job).

Deterministic tests drive ``auto_start=False`` schedulers via ``step()``;
only the SLO test relies on the drain thread and wall-clock.
"""
import concurrent.futures
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fusion import LinearOperator
from repro.core.laq import PAD_KEY, Catalog, Table
from repro.core.laq.selection import Pred
from repro.core.query import (Aggregate, AdmissionScheduler, ArmSpec,
                              PREDICTION, PredictiveQuery, ScheduledPlan,
                              SchedulerBackpressureError,
                              SchedulerClosedError, SentinelKeyError,
                              Session, compile_serving, make_serving_mesh)

needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8",
)

BUCKETS = (4, 16)   # top bucket 16 → default batch reserve 4


# --------------------------------------------------------------------- data
def star_catalog(seed: int = 3, n_d1: int = 40, n_d2: int = 12,
                 slack: int = 16) -> Catalog:
    rng = np.random.default_rng(seed)
    d1 = {"pk": np.arange(n_d1) * 2,          # even keys; odd keys = appends
          "a": rng.normal(size=n_d1), "b": rng.normal(size=n_d1)}
    d2 = {"pk2": np.arange(n_d2), "c": rng.normal(size=n_d2)}
    f = {"fk1": rng.integers(0, 2 * n_d1, 8),
         "fk2": rng.integers(0, n_d2, 8), "val": rng.normal(size=8)}
    return Catalog({
        "d1": Table.from_columns("d1", d1, key_cols=("pk",),
                                 capacity=n_d1 + slack),
        "d2": Table.from_columns("d2", d2, key_cols=("pk2",),
                                 capacity=n_d2 + slack),
        "fact": Table.from_columns("fact", f, key_cols=("fk1", "fk2")),
    })


def _query(seed: int = 0) -> PredictiveQuery:
    rng = np.random.default_rng(seed)
    model = LinearOperator(jnp.asarray(
        rng.normal(size=(3, 2)).astype(np.float32)))
    return PredictiveQuery(
        fact="fact",
        arms=(ArmSpec("d1", "fk1", "pk", ("a", "b"),
                      (Pred("a", ">", -1.0),)),
              ArmSpec("d2", "fk2", "pk2", ("c",))),
        model=model,
        aggregates=(Aggregate(PREDICTION, "sum", "pred"),))


def _requests(rng, n, n_d1=40, n_d2=12):
    """Random per-arm FK batch; ~1/8 of keys miss (not-found masking)."""
    return {"fk1": rng.integers(0, int(2 * n_d1 * 9 / 8), n).astype(np.int32),
            "fk2": rng.integers(0, int(n_d2 * 9 / 8), n).astype(np.int32)}


@pytest.fixture()
def rt():
    return compile_serving(star_catalog(), _query(), buckets=BUCKETS)


@pytest.fixture()
def sched():
    s = AdmissionScheduler(auto_start=False)
    yield s
    s.close(cancel=True)


# ----------------------------------------------------------- bit-exactness
def test_coalesced_step_bit_exact_and_counted(rt, sched):
    plan = sched.register(rt, "p")
    rng = np.random.default_rng(0)
    reqs = [_requests(rng, n) for n in (2, 3, 4)]
    futs = [plan.submit(r) for r in reqs]
    assert sched.step() == 9          # one coalesced admission step
    for f, r in zip(futs, reqs):
        np.testing.assert_array_equal(np.asarray(f.result(0)),
                                      np.asarray(rt.serve(r)))
    st = plan.stats()
    assert st["steps"] == 1 and st["admitted_rows"] == 9
    assert st["padded_rows"] == 16 - 9     # padded into the top bucket
    assert st["lanes"]["interactive"]["count"] == 3


def test_oversized_batch_chunks_bit_exact(rt, sched):
    plan = sched.register(rt)
    rng = np.random.default_rng(1)
    reqs = _requests(rng, 3 * BUCKETS[-1] + 5)      # 53 rows → 4 chunks
    fut = plan.submit(reqs, lane="batch")
    steps = 0
    while not fut.done():
        assert sched.step() > 0
        steps += 1
    assert steps == 4
    np.testing.assert_array_equal(np.asarray(fut.result(0)),
                                  np.asarray(rt.serve(reqs)))


def test_multiple_plans_one_drain_loop(sched):
    cat = star_catalog()
    rt_a = compile_serving(cat, _query(0), buckets=BUCKETS)
    rt_b = compile_serving(cat, _query(1), buckets=BUCKETS)
    pa, pb = sched.register(rt_a, "a"), sched.register(rt_b, "b")
    assert sched.plan_names == ("a", "b")
    # Re-registering a runtime is idempotent (same plan handle).
    assert sched.register(rt_a).name == "a"
    rng = np.random.default_rng(2)
    ra, rb = _requests(rng, 7), _requests(rng, 11)
    fa, fb = pa.submit(ra), pb.submit(rb)
    assert sched.step() == 18          # one step per plan, same call
    np.testing.assert_array_equal(np.asarray(fa.result(0)),
                                  np.asarray(rt_a.serve(ra)))
    np.testing.assert_array_equal(np.asarray(fb.result(0)),
                                  np.asarray(rt_b.serve(rb)))


def test_zero_row_submission_resolves_immediately(rt, sched):
    plan = sched.register(rt)
    fut = plan.submit({"fk1": np.zeros(0, np.int32),
                       "fk2": np.zeros(0, np.int32)})
    assert np.asarray(fut.result(0)).shape == (0, rt.out_width)


# ------------------------------------------------------------------- lanes
def test_point_lookups_interleave_with_inflight_analytical(rt, sched):
    plan = sched.register(rt)
    rng = np.random.default_rng(3)
    big = _requests(rng, 4 * BUCKETS[-1])           # 4-step analytical scan
    small = _requests(rng, 2)
    fb = plan.submit(big, lane="batch")
    assert sched.step() == BUCKETS[-1]              # scan starts alone
    fi = plan.submit(small)                         # point lookup arrives
    sched.step()
    # The lookup rode along with the scan's next chunk instead of queueing
    # behind the whole scan.
    assert fi.done() and not fb.done()
    while not fb.done():
        sched.step()
    np.testing.assert_array_equal(np.asarray(fi.result(0)),
                                  np.asarray(rt.serve(small)))
    np.testing.assert_array_equal(np.asarray(fb.result(0)),
                                  np.asarray(rt.serve(big)))


def test_batch_reserve_prevents_interactive_starvation(rt, sched):
    plan = sched.register(rt)
    rng = np.random.default_rng(4)
    scan = _requests(rng, 2 * BUCKETS[-1])          # needs 32 admitted rows
    fb = plan.submit(scan, lane="batch")
    reserve = max(1, BUCKETS[-1] // 4)
    flood_budget = BUCKETS[-1] - reserve
    steps = 0
    while not fb.done():
        # Fill the whole interactive budget before every step: without the
        # reserve the scan would never be admitted a single row.
        flood = plan.submit(_requests(rng, flood_budget))
        sched.step()
        steps += 1
        assert flood.done()                         # interactive first...
        assert steps <= int(np.ceil(2 * BUCKETS[-1] / reserve))
    # ...but the scan still progressed ≥ reserve rows per step.
    np.testing.assert_array_equal(np.asarray(fb.result(0)),
                                  np.asarray(rt.serve(scan)))


def test_unknown_lane_and_plan_are_named_errors(rt, sched):
    plan = sched.register(rt)
    with pytest.raises(ValueError, match="unknown lane"):
        plan.submit(_requests(np.random.default_rng(0), 1), lane="bulk")
    with pytest.raises(KeyError, match="unknown plan"):
        sched.submit("nope", _requests(np.random.default_rng(0), 1))
    with pytest.raises(ValueError, match="already registered"):
        sched.register(compile_serving(star_catalog(), _query(1),
                                       buckets=BUCKETS), plan.name)


# ---------------------------------------------------- backpressure / close
def test_backpressure_rejects_with_named_error(rt):
    s = AdmissionScheduler(auto_start=False, max_queued_rows=8)
    plan = s.register(rt)
    rng = np.random.default_rng(5)
    plan.submit(_requests(rng, 6))
    with pytest.raises(SchedulerBackpressureError, match="at capacity"):
        plan.submit(_requests(rng, 6))
    plan.submit(_requests(rng, 2))                  # exactly at the bound
    assert plan.stats()["rejected"] == 1
    s.step()                                        # admission frees the lane
    plan.submit(_requests(rng, 8))
    s.close()


def test_close_drains_by_default_and_rejects_new_work(rt):
    s = AdmissionScheduler(auto_start=False)
    plan = s.register(rt)
    rng = np.random.default_rng(6)
    reqs = _requests(rng, 3)
    fut = plan.submit(reqs)
    s.close()                                       # drains queued work
    np.testing.assert_array_equal(np.asarray(fut.result(0)),
                                  np.asarray(rt.serve(reqs)))
    with pytest.raises(SchedulerClosedError):
        plan.submit(reqs)
    with pytest.raises(SchedulerClosedError):
        s.register(compile_serving(star_catalog(), _query(1),
                                   buckets=BUCKETS))


def test_close_cancel_fails_pending_futures(rt):
    s = AdmissionScheduler(auto_start=False)
    plan = s.register(rt)
    fut = plan.submit(_requests(np.random.default_rng(7), 3))
    s.close(cancel=True)
    with pytest.raises(SchedulerClosedError):
        fut.result(0)


def test_cancelled_future_is_dropped_at_admission(rt, sched):
    plan = sched.register(rt)
    rng = np.random.default_rng(8)
    f1, keep = plan.submit(_requests(rng, 3)), _requests(rng, 2)
    f2 = plan.submit(keep)
    assert f1.cancel()
    assert sched.step() == 2                        # only the live request
    np.testing.assert_array_equal(np.asarray(f2.result(0)),
                                  np.asarray(rt.serve(keep)))


# ------------------------------------------------- synchronous validation
def test_normalization_errors_raise_in_submitting_caller(rt, sched):
    plan = sched.register(rt)
    with pytest.raises(SentinelKeyError, match="padding sentinel"):
        plan.submit({"fk1": np.array([3, PAD_KEY], np.int32),
                     "fk2": np.array([1, 2], np.int32)})
    with pytest.raises(ValueError, match="ragged"):
        plan.submit({"fk1": np.array([3, 4], np.int32),
                     "fk2": np.array([1], np.int32)})
    with pytest.raises(KeyError):
        plan.submit({"fk1": np.array([3], np.int32)})
    assert sched.step() == 0                        # nothing was enqueued


def test_step_requires_manual_mode(rt):
    s = AdmissionScheduler()
    try:
        with pytest.raises(RuntimeError, match="auto_start=False"):
            s.step()
    finally:
        s.close()


# ------------------------------------------------------------ SLO (timed)
def test_slo_flushes_lone_request_without_full_bucket(rt):
    with AdmissionScheduler(slo_ms=5.0) as s:
        plan = s.register(rt)
        rng = np.random.default_rng(9)
        reqs = _requests(rng, 2)                    # far below the bucket
        t0 = time.perf_counter()
        fut = plan.submit(reqs)
        out = np.asarray(fut.result(timeout=30))
        waited = time.perf_counter() - t0
        np.testing.assert_array_equal(out, np.asarray(rt.serve(reqs)))
        # Generous bound (CI wall-clock): flushed by the deadline, not
        # held forever waiting for 16 rows.
        assert waited < 10.0
        st = plan.stats()["lanes"]["interactive"]
        assert st["count"] == 1 and st["p50"] >= 0.0


# -------------------------------------------------------- refresh fencing
def test_refresh_fence_keeps_request_on_one_generation():
    cat = star_catalog()
    q = _query()
    rt = compile_serving(cat, q, buckets=BUCKETS)
    twin = compile_serving(cat, q, buckets=BUCKETS)
    rng = np.random.default_rng(10)
    # Batch whose keys include rows that only exist AFTER the append (odd
    # d1 keys): old and new generations give different answers for it.
    reqs = {"fk1": np.concatenate([
                rng.integers(0, 80, 40), 81 + 2 * np.arange(8)]
            ).astype(np.int32),
            "fk2": rng.integers(0, 12, 48).astype(np.int32)}
    want_old = np.asarray(twin.serve(reqs))

    s = AdmissionScheduler(auto_start=False)
    plan = s.register(rt)
    fut = plan.submit(reqs, lane="batch")
    assert s.step() == BUCKETS[-1]                  # mid-flight: 16/48 rows
    cat.append("d1", {"pk": 81 + 2 * np.arange(8),
                      "a": rng.normal(size=8), "b": rng.normal(size=8)})
    # Drain-then-swap: the started request finishes on the old state.
    decisions = s.refresh(rt)
    assert fut.done()
    np.testing.assert_array_equal(np.asarray(fut.result(0)), want_old)
    assert "delta" in decisions[plan.name] or "no-op" in decisions[plan.name]

    # Post-swap requests see the new generation (== refreshed twin).
    twin.refresh()
    want_new = np.asarray(twin.serve(reqs))
    assert not np.array_equal(want_old, want_new)   # the append matters
    f2 = plan.submit(reqs)
    while not f2.done():
        s.step()
    np.testing.assert_array_equal(np.asarray(f2.result(0)), want_new)
    s.close()


def test_session_routes_cached_runtime_refresh_through_fence():
    cat = star_catalog()
    q = _query()
    sess = Session(cat)
    plan = sess.bind(q).serve(buckets=BUCKETS, async_=True)
    assert isinstance(plan, ScheduledPlan)
    assert sess.bind(q).serve(buckets=BUCKETS, async_=True).name == plan.name
    rng = np.random.default_rng(11)
    reqs = _requests(rng, 6)
    np.testing.assert_array_equal(
        np.asarray(plan.submit(reqs).result(30)),
        np.asarray(compile_serving(cat, q, buckets=BUCKETS).serve(reqs)))
    cat.append("d1", {"pk": 81 + 2 * np.arange(4),
                      "a": rng.normal(size=4), "b": rng.normal(size=4)})
    # The cached-runtime hit path must fence through the scheduler, not
    # call runtime.refresh() under the drain thread.
    rt2 = sess.bind(q).serve(buckets=BUCKETS)
    assert rt2 is plan.runtime
    new_keys = {"fk1": (81 + 2 * np.arange(4)).astype(np.int32),
                "fk2": np.arange(4).astype(np.int32)}
    got = np.asarray(plan.submit(new_keys).result(30))
    twin = compile_serving(cat, q, buckets=BUCKETS)
    np.testing.assert_array_equal(got, np.asarray(twin.serve(new_keys)))
    with pytest.raises(ValueError, match="already running"):
        sess.scheduler(slo_ms=1.0)
    sess.scheduler().close()
    # A closed session scheduler is replaced lazily on next use.
    assert sess.scheduler(slo_ms=1.0).slo_ms == 1.0
    sess.scheduler().close()


# ------------------------------------------------------- concurrent load
def test_concurrent_submitters_all_bit_exact(rt):
    """Many threads submit through the drain thread; every result exact."""
    rng = np.random.default_rng(12)
    batches = [_requests(rng, int(n)) for n in rng.integers(1, 40, 24)]
    want = [np.asarray(rt.serve(b)) for b in batches]
    with AdmissionScheduler(slo_ms=1.0) as s:
        plan = s.register(rt)
        with concurrent.futures.ThreadPoolExecutor(4) as pool:
            futs = list(pool.map(
                lambda b: plan.submit(b, lane="batch"
                                      if b["fk1"].size > 20 else
                                      "interactive"),
                batches))
            got = [np.asarray(f.result(60)) for f in futs]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


# ------------------------------------------------------------ sharded (CI)
@needs_8_devices
def test_sharded_runtime_through_scheduler_bit_exact():
    mesh = make_serving_mesh((1, 8))
    cat = star_catalog()
    q = _query()
    ref = compile_serving(cat, q, buckets=BUCKETS)
    rt = compile_serving(cat, q, buckets=BUCKETS, mesh=mesh,
                         shard_threshold_bytes=0)
    assert rt.sharded
    s = AdmissionScheduler(auto_start=False)
    plan = s.register(rt)
    rng = np.random.default_rng(13)
    reqs = [_requests(rng, n) for n in (3, 16, 40)]   # incl. chunked
    futs = [plan.submit(r, lane="batch" if r["fk1"].size > 16 else
                        "interactive") for r in reqs]
    while not all(f.done() for f in futs):
        s.step()
    for f, r in zip(futs, reqs):
        np.testing.assert_array_equal(np.asarray(f.result(0)),
                                      np.asarray(ref.serve(r)))
    s.close()
