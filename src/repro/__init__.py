"""repro: a predictive database in JAX after LAQ (arXiv 2306.08367).

Queries join a fact table to dimension tables, apply a model head (a linear
operator or a GEMM decision tree) and aggregate the predictions, lowered into
one jitted program per query; the same plans serve per-row predictions.

* ``core.query`` — the ``Session`` front end, IR, planner, compiler, serving
  runtime, scheduler, streaming and mesh sharding;
* ``core.fusion`` and ``core.laq`` — ML operator fusion and the linear-algebra
  relational operators beneath it;
* ``kernels`` — the Pallas kernels those operators lower to;
* ``data`` — seeded SSB and synthetic star generators; ``telemetry`` — spans
  and compile counters.
"""
__version__ = "1.0.0"
