"""Seconds of the program's ``plan.prefuse`` spans over the run: building
each fused plan's per-dimension partials (Eq. 1 / Eq. 3), pool misses
included.  Plans are built in set-up, so these are set-up seconds.  None
where the program records no such span."""
import importlib


def read(run):
    try:
        telemetry = importlib.import_module("repro.telemetry")
    except ImportError:
        return None
    t = telemetry.totals().get("plan.prefuse")
    return t.seconds if t is not None else None
