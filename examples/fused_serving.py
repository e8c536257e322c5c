"""End-to-end serving driver (the paper's kind of system, as deployed).

Batched requests → pre-fused star pipeline (paper Eq. 1) for per-request
features → LM decode conditioned on those features, with KV caches.
Reports latency percentiles fused vs non-fused and verifies the outputs
are identical (fusion is exact).

Run:  PYTHONPATH=src python examples/fused_serving.py
"""
from repro.launch.serve import run_serving

if __name__ == "__main__":
    from repro.compile_cache import init_compile_cache
    init_compile_cache()
    run_serving(arch="smollm-360m", batch=4, decode_steps=8, k=96, l=8,
                repeats=10)
