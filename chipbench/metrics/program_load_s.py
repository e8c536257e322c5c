"""Seconds JAX spent over the run tracing, lowering and compiling (or
loading from the persistent cache) the programs that the program's spans
caused.  None where the program records no spans."""
import importlib


def read(run):
    try:
        telemetry = importlib.import_module("repro.telemetry")
    except ImportError:
        return None
    return sum(t.compile_s for t in telemetry.totals().values())
