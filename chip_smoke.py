"""Bring-up check on the chip: the SSB query engine and its serving path.

Runs the system's main path once, through the entry points a user calls, at
SSB scale factor 1 (6M ``lineorder`` rows, made from ``--seed``), and checks
every answer against the float64 numpy oracle of
``repro.core.query.workload``:

* analytical phase: the 13 SSB queries and P1-P4 through
  ``ssb_session(data).bind(QUERY_IR[name]()).run()``;
* serving phase: P1-P4 through ``.serve()`` (default buckets) on request
  batches of 1, 8, 64, 512 and 1000 rows made from sampled fact rows, and
  through ``CompiledQuery.predict_rows`` on sampled row ids;
* ``--chips 4`` runs only the sharded serving phase: P1-P4 served from a
  ``Session(mesh=...)`` on (1, 4) and (4, 1) meshes, compared row by row
  with the single-device runtime.

Usage::

    python chip_smoke.py                # one chip
    python chip_smoke.py --chips 4      # four chips: sharded serving only
    python chip_smoke.py --sf 0.1       # a smaller scale factor

Each query and runtime prints one line: the planner's choices, whether a
Pallas kernel is in the program that ran, ``setup_s`` (planning, offline
build and XLA compile up to the first result), one warm call's
milliseconds (informational), and the device's ``peak_bytes_in_use``.
The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every check passed.
Any mismatch or exception exits non-zero; the script exits non-zero without
a result when JAX finds no TPU.  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

# Aggregates: a float sum over up to N = 6M rows accumulates in f32.  In
# the order XLA's reductions and scatters add, the rounding error of a sum
# of same-sign terms is a random walk of size ~ 2^-24 * sqrt(N) / 3
# relative, ~5e-5 at N = 6M (a pairwise reduction gives ~log2(N) * 2^-24,
# ~1.4e-6).  2^-12 (~2.4e-4) is five such deviations at the largest group
# and still 8x below bf16's unit roundoff (2^-9), so a head or partial
# rounded to bf16 fails it.  Counts and tree-leaf histograms are integers
# below 2^24 and must match exactly.
AGG_RTOL = 2.0 ** -12
# Per served row: a linear prediction is a few f32 products and adds
# (k <= 5 features over J <= 3 arms), so its error is at most ~10 * 2^-24
# of sum_i |x_i * L_i|; 2^-18 of that sum leaves 4x margin and is 2^9 times
# tighter than bf16 rounding (2^-9).  Tree heads give exact 0/1 leaves.
ROW_RTOL = 2.0 ** -18
SERVE_SIZES = (1, 8, 64, 512, 1000)   # 1000 > the top bucket: chunked
PREDICT_ROWS = 1000
PREDICTIVE = ("P1.linear.year", "P2.linear.select.scalar", "P3.tree.year",
              "P4.tree.select.region")


class CheckFailed(AssertionError):
    """An answer disagreed with the oracle or a placement was wrong."""


def _log(msg: str) -> None:
    print(msg, flush=True)


def _peak_bytes(jax) -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def _kernels(text: str) -> int:
    """Pallas TPU kernels in a lowered program (its tpu_custom_calls)."""
    return text.count("tpu_custom_call")


def _compile_s(rt) -> float:
    """A serving runtime's first-call (trace + XLA compile) seconds."""
    stats = rt.latency_stats()
    return sum(stats.get(b, {}).get("compile_ms", 0.0)
               for b in rt.buckets) / 1e3


def _block(tree):
    import jax
    return jax.block_until_ready(tree)


# ---------------------------------------------------------------- checks
def _check_close(label, got, exp, exact, rtol, scale=None):
    got = np.asarray(got, np.float64)
    exp = np.asarray(exp, np.float64)
    if got.shape != exp.shape:
        raise CheckFailed(f"{label}: shape {got.shape} != {exp.shape}")
    if not np.all(np.isfinite(got)):
        raise CheckFailed(f"{label}: non-finite values")
    if exact:
        bad = got != exp
    else:
        ref = np.abs(exp) if scale is None else scale
        bad = np.abs(got - exp) > rtol * ref
    if np.any(bad):
        i = np.argwhere(bad)[0]
        raise CheckFailed(f"{label}: {int(bad.sum())} of {bad.size} values "
                          f"off; first at {tuple(int(x) for x in i)}: "
                          f"got {got[tuple(i)]!r} want {exp[tuple(i)]!r}")


def check_aggregates(name, q, res, want) -> None:
    """Rows and group codes exactly; values per AGG_RTOL (exact for ints)."""
    from repro.core.fusion import DecisionTreeGEMM
    from repro.core.query import PREDICTION
    from repro.core.query.workload import PAD_GROUP

    if int(res["rows"]) != want["rows"]:
        raise CheckFailed(f"{name}: rows {int(res['rows'])} != "
                          f"{want['rows']}")

    def exact(agg):
        return agg.op == "count" or (agg.value == PREDICTION and isinstance(
            q.model, DecisionTreeGEMM) and agg.op == "sum")

    if want["groups"] is None:
        for a in q.aggregates:
            exp = want["scalars"][a.name]
            if exp is None:
                exp = np.zeros_like(np.asarray(res[a.name], np.float64))
            _check_close(f"{name}.{a.name}",
                         np.atleast_1d(np.asarray(res[a.name])).ravel(),
                         np.atleast_1d(exp).ravel(), exact(a), AGG_RTOL)
        return
    codes = np.asarray(res["groups"])
    live = np.nonzero(codes != PAD_GROUP)[0]
    got_codes = sorted(int(c) for c in codes[live])
    if got_codes != sorted(want["groups"]):
        raise CheckFailed(f"{name}: group codes differ: {len(got_codes)} "
                          f"live vs {len(want['groups'])} expected")
    for a in q.aggregates if len(live) else ():
        vals = np.asarray(res[a.name], np.float64).reshape(len(codes), -1)
        exp = np.stack([np.asarray(want["groups"][int(codes[i])][a.name],
                                   np.float64).ravel() for i in live])
        _check_close(f"{name}.{a.name}", vals[live], exp, exact(a),
                     AGG_RTOL)


def _row_scale(tables, q, oracle):
    """Per-row sum_i |x_i L_i| for a linear head (features are >= 0 in SSB,
    so the oracle on |L| gives it); None for tree heads (checked exactly)."""
    from repro.core.fusion import LinearOperator
    if not isinstance(q.model, LinearOperator):
        return None
    absq = dataclasses.replace(q, model=LinearOperator(
        np.abs(np.asarray(q.model.L)), None if q.model.bias is None
        else np.abs(np.asarray(q.model.bias))))
    return oracle(tables, absq)


def check_rows(label, q, got, exp, scale) -> None:
    if scale is None:
        _check_close(label, got, exp, True, 0.0)
    else:
        _check_close(label, got, exp, False, ROW_RTOL, scale=scale)


# ---------------------------------------------------------------- phases
def analytical_phase(jax, data, names) -> None:
    from repro.core.query.workload import np_oracle
    from repro.data import QUERY_IR, ssb_session

    sess = ssb_session(data)
    tables = dict(sess.catalog)
    for name in names:
        q = QUERY_IR[name]()
        t0 = time.perf_counter()
        res = _block(sess.bind(q).run())
        setup_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        _block(sess.bind(q).run())
        warm_ms = (time.perf_counter() - t0) * 1e3
        cq = sess.bind(q).compile()
        ran = _kernels(jax.jit(cq._run).lower(cq._state).as_text())
        check_aggregates(name, q, res, np_oracle(tables, q))
        _log(f"[run] {name}: ok backend={cq.backend} join={cq.join_backend} "
             f"agg={cq.agg_backend} serve={cq.serve_backend} "
             f"pallas_in_run={ran} setup_s={setup_s:.3f} "
             f"warm_ms={warm_ms:.3f} peak_bytes={_peak_bytes(jax)}")
        if "run=jnp" in cq.plan.reason:
            _log(f"[run] {name}: planner: {cq.plan.reason}")


def _sample_requests(rng, n_fact):
    return [rng.integers(0, n_fact, size) for size in SERVE_SIZES]


def serving_phase(jax, data, seed) -> None:
    import jax.numpy as jnp
    from repro.core.query import requests_from_rows
    from repro.core.query.workload import np_row_oracle, np_serving_oracle
    from repro.data import QUERY_IR, ssb_session

    sess = ssb_session(data)
    tables = dict(sess.catalog)
    rng = np.random.default_rng(seed)
    fact = sess.catalog["lineorder"]
    n_fact = int(fact.nvalid)
    for name in PREDICTIVE:
        q = QUERY_IR[name]()
        exp_all = np_serving_oracle(tables, q)
        scale_all = _row_scale(tables, q, np_serving_oracle)
        t0 = time.perf_counter()
        rt = sess.bind(q).serve()
        build_s = time.perf_counter() - t0
        for ids in _sample_requests(rng, n_fact):
            got = _block(rt.serve(requests_from_rows(fact, q, ids)))
            check_rows(f"{name}.serve[{len(ids)}]", q, got, exp_all[ids],
                       None if scale_all is None else scale_all[ids])
        setup_s = build_s + _compile_s(rt)
        ids = rng.integers(0, n_fact, 64)
        reqs = requests_from_rows(fact, q, ids)
        t0 = time.perf_counter()
        _block(rt.serve(reqs))
        warm_ms = (time.perf_counter() - t0) * 1e3
        _, padded = rt._admit(rt._normalize(reqs))
        ran = _kernels(rt._jit.lower(padded, rt._state).as_text())
        _log(f"[serve] {name}: ok backend={rt.backend} "
             f"serve={rt.serve_backend} pallas_in_program={ran} "
             f"buckets={rt.buckets} setup_s={setup_s:.3f} "
             f"warm_ms[64]={warm_ms:.3f} peak_bytes={_peak_bytes(jax)}")

        # predict_rows: the compiled plan scores fact row ids, with the
        # whole query's validity (fact predicates included).
        cq = sess.bind(q).compile()
        exp_rows = np_row_oracle(tables, q)
        scale_rows = _row_scale(tables, q, np_row_oracle)
        ids = jnp.asarray(rng.integers(0, n_fact, PREDICT_ROWS), jnp.int32)
        t0 = time.perf_counter()
        got = _block(cq.predict_rows(ids))
        setup_s = time.perf_counter() - t0
        idx = np.asarray(ids)
        check_rows(f"{name}.predict_rows", q, got, exp_rows[idx],
                   None if scale_rows is None else scale_rows[idx])
        t0 = time.perf_counter()
        _block(cq.predict_rows(ids))
        warm_ms = (time.perf_counter() - t0) * 1e3
        ran = _kernels(jax.jit(cq._predict_rows).lower(ids, cq._state)
                       .as_text())
        _log(f"[predict_rows] {name}: ok backend={cq.backend} "
             f"serve={cq.serve_backend} pallas_in_program={ran} "
             f"setup_s={setup_s:.3f} warm_ms[{PREDICT_ROWS}]={warm_ms:.3f} "
             f"peak_bytes={_peak_bytes(jax)}")


def sharded_phase(jax, data, seed, chips) -> None:
    from repro.core.query import Session, requests_from_rows
    from repro.data import QUERY_IR, ssb_catalog, ssb_session
    from repro.launch.mesh import make_serving_mesh

    single = ssb_session(data)
    fact = single.catalog["lineorder"]
    n_fact = int(fact.nvalid)
    rng = np.random.default_rng(seed)
    batches = _sample_requests(rng, n_fact)
    row_sharded = 0
    for shape in ((1, chips), (chips, 1)):
        sess = Session(ssb_catalog(data), mesh=make_serving_mesh(shape))
        for name in PREDICTIVE:
            q = QUERY_IR[name]()
            ref = single.bind(q).serve()
            t0 = time.perf_counter()
            rt = sess.bind(q).serve()
            build_s = time.perf_counter() - t0
            for arm in rt.sharded.arms:
                devs = len(arm.table.sharding.device_set)
                if devs != chips:
                    raise CheckFailed(f"{name} on {shape}: arm {arm.fk_col} "
                                      f"spans {devs} devices, not {chips}")
                starts = {s.index[0].start for s in
                          arm.table.addressable_shards}
                if arm.is_sharded and shape[1] == chips:
                    if len(starts) != chips:
                        raise CheckFailed(f"{name} on {shape}: arm "
                                          f"{arm.fk_col} is not row-sharded "
                                          f"{chips} ways")
                    row_sharded += 1
            for ids in batches:
                reqs = requests_from_rows(fact, q, ids)
                got = np.asarray(rt.serve(reqs))
                want = np.asarray(ref.serve(reqs))
                if not np.array_equal(got, want):
                    bad = np.argwhere(got != want)[0]
                    raise CheckFailed(
                        f"{name} on {shape}: batch {len(ids)} differs from "
                        f"one device at {tuple(int(x) for x in bad)}")
            setup_s = build_s + _compile_s(rt)
            _log(f"[sharded] {name} mesh={shape}: ok backend={rt.backend} "
                 f"placement={[str(s) for s in rt.plan.partition_specs]} "
                 f"per_device_bytes={rt.sharded.nbytes_per_device()} "
                 f"setup_s={setup_s:.3f} peak_bytes={_peak_bytes(jax)}")
    if row_sharded == 0:
        raise CheckFailed("no prefused partial was row-sharded over the "
                          f"{chips}-way model axis")


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=1.0,
                    help="SSB scale factor (default 1: 6M lineorder rows)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded serving phase")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import init_compile_cache
    from repro.core.query.planner import PLANNER_THRESHOLDS
    from repro.data import QUERY_IR, generate_ssb

    cache = init_compile_cache()
    _log(f"device: {devices[0].device_kind} x{len(devices)}; "
         f"jax {jax.__version__}; compile cache {cache}")
    platform = jax.default_backend()
    if platform not in PLANNER_THRESHOLDS:
        _log(f"planner thresholds: no {platform!r} row, so the CPU-seeded "
             f"'default' row decides on this chip: "
             f"{PLANNER_THRESHOLDS['default']}")

    t0 = time.perf_counter()
    data = generate_ssb(sf=args.sf, seed=args.seed)
    _block([t.matrix for t in (data.lineorder, data.part, data.supplier,
                               data.customer, data.date)])
    _log(f"data: SSB sf={args.sf} seed={args.seed}, "
         f"{int(data.lineorder.nvalid)} lineorder rows, "
         f"generated and placed in {time.perf_counter() - t0:.1f}s; "
         f"peak_bytes={_peak_bytes(jax)}")

    if args.chips == 1:
        analytical_phase(jax, data, list(QUERY_IR))
        serving_phase(jax, data, args.seed)
    else:
        sharded_phase(jax, data, args.seed, args.chips)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
