"""HLO analyzer: trip-count-aware FLOPs/collective accounting vs ground truth."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_analysis import HloAnalyzer, xla_cost_analysis


def test_scan_flops_multiplied_by_trip_count():
    n_iter, b, d = 7, 32, 64

    def scanned(ws, x):
        def step(c, w):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(step, x, ws)
        return y

    ws = jax.ShapeDtypeStruct((n_iter, d, d), jnp.float32)
    x = jax.ShapeDtypeStruct((b, d), jnp.float32)
    compiled = jax.jit(scanned).lower(ws, x).compile()
    costs = HloAnalyzer(compiled.as_text()).analyze()
    want = 2.0 * b * d * d * n_iter
    assert costs.flops == pytest.approx(want, rel=0.05)
    # XLA's own cost_analysis undercounts by ~n_iter (the bug we fix).
    xla_flops = xla_cost_analysis(compiled)["flops"]
    assert xla_flops < want / 2


def test_xla_cost_analysis_normalizes_shapes():
    class ReturnsNone:           # backends where cost_analysis is unavailable
        def cost_analysis(self):
            return None

    class ReturnsDict:
        def cost_analysis(self):
            return {"flops": 3.0}

    assert xla_cost_analysis(ReturnsNone()) == {}
    assert xla_cost_analysis(ReturnsDict()) == {"flops": 3.0}


def test_nested_scan_flops():
    def nested(ws, x):
        def outer(c, w):
            def inner(ci, _):
                return jnp.tanh(ci @ w), None
            ci, _ = jax.lax.scan(inner, c, None, length=3)
            return ci, None
        y, _ = jax.lax.scan(outer, x, ws)
        return y

    ws = jax.ShapeDtypeStruct((5, 16, 16), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    compiled = jax.jit(nested).lower(ws, x).compile()
    costs = HloAnalyzer(compiled.as_text()).analyze()
    want = 2.0 * 8 * 16 * 16 * 5 * 3
    assert costs.flops == pytest.approx(want, rel=0.1)


def test_collective_bytes_with_groups(monkeypatch):
    import subprocess, sys, json, textwrap
    # Run in a subprocess with 4 fake devices so this test doesn't disturb
    # the process-wide device count.
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, json, sys
        sys.path.insert(0, "src")
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.hlo_analysis import HloAnalyzer
        mesh = jax.make_mesh((4,), ("model",))
        def f(w, x):
            return x @ w
        with mesh:
            ws = NamedSharding(mesh, P(None, "model"))
            w = jax.ShapeDtypeStruct((64, 64), jnp.float32, sharding=ws)
            x = jax.ShapeDtypeStruct((8, 64), jnp.float32,
                                     sharding=NamedSharding(mesh, P(None, None)))
            compiled = jax.jit(f, out_shardings=NamedSharding(
                mesh, P(None, None))).lower(w, x).compile()
        c = HloAnalyzer(compiled.as_text()).analyze()
        print(json.dumps({"coll": c.total_coll_bytes,
                          "n": c.n_collectives,
                          "flops": c.flops}))
    """)
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, cwd="/root/repo")
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    # Output (8,64) f32 must be all-gathered from 4-way shards (or the
    # compiler's equivalent): some collective traffic, correct flops.
    assert res["n"] >= 1
    assert res["coll"] > 0
    assert res["flops"] == pytest.approx(2 * 8 * 64 * 16, rel=0.05)


def test_memory_bytes_reasonable():
    def f(x):
        return jnp.tanh(x) * 2.0

    x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    compiled = jax.jit(f).lower(x).compile()
    costs = HloAnalyzer(compiled.as_text()).analyze()
    nbytes = 1024 * 1024 * 4
    # Read + write ≈ 2 buffers; allow fusion bookkeeping slack.
    assert nbytes <= costs.mem_bytes <= 6 * nbytes
