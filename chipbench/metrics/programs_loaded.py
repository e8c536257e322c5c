"""Programs compiled or loaded from the persistent cache over the run
inside the program's spans (JAX backend-compile events).  None where the
program records no spans."""
import importlib


def read(run):
    try:
        telemetry = importlib.import_module("repro.telemetry")
    except ImportError:
        return None
    return sum(t.programs for t in telemetry.totals().values())
