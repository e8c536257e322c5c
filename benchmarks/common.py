"""Benchmark harness utilities: timing, CSV/JSON output, serving workload."""
from __future__ import annotations

import json
import time
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fusion import LinearOperator
from repro.core.query import Catalog, query_from_star
from repro.data import generate_star

HEADER = "name,us_per_call,derived"
_rows: List[str] = []


def emit(name: str, us: float, derived: str = ""):
    row = f"{name},{us:.1f},{derived}"
    _rows.append(row)
    print(row, flush=True)


def rows():
    return list(_rows)


def write_json(path: str, meta: Optional[dict] = None):
    """Dump every row emitted so far as a JSON benchmark artifact.

    The CI bench-smoke job uploads these (``BENCH_*.json``) on every PR —
    a crash gate plus a perf trajectory, not a regression gate.
    """
    recs = []
    for row in _rows:
        name, us, derived = row.split(",", 2)
        recs.append({"name": name, "us_per_call": float(us),
                     "derived": derived})
    payload = {"backend": jax.default_backend(), "rows": recs}
    if meta:
        payload.update(meta)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"[bench] wrote {path} ({len(recs)} rows)", flush=True)


def bench(fn: Callable, *args, warmup: int = 2, iters: int = 7) -> float:
    """Best wall time (µs) of a jitted callable (block_until_ready).

    The *minimum* over ``iters`` timed calls, not the median: scheduler
    noise on shared runners is strictly additive (multi-ms stalls land on
    random iterations), so the min is the stable estimator of the true
    cost — a real slowdown raises every observation including the best one,
    while a noisy neighbour can no longer flip the regression gate.
    """
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.min(times) * 1e6)


def serving_star(k: int, l: int, scale: float, seed: int):
    """The serving benches' workload: Table 4 setting 2 at sf 1 with a
    random ``(k, l)`` linear head.

    Returns the versioned catalog, the star query over it, and the
    dimension row counts :func:`random_requests` draws keys from.
    """
    rng = np.random.default_rng(seed)
    syn = generate_star(2, 1, k, seed=seed, scale=scale)
    model = LinearOperator(
        jnp.asarray(rng.normal(size=(k, l)).astype(np.float32)))
    tables, query = query_from_star(syn.star, model=model)
    return Catalog(tables), query, syn.dim_rows


def random_requests(query, dim_rows, n: int, rng: np.random.Generator):
    """A batch of ``n`` per-arm FK requests drawn from the key ranges."""
    reqs = {}
    for arm, rows in zip(query.arms, dim_rows):
        # ~1/16 of keys miss the dimension: exercises not-found masking.
        keys = rng.integers(0, max(int(rows * 17 / 16), 1), size=n)
        reqs[arm.fk_col] = keys.astype(np.int32)
    return reqs
