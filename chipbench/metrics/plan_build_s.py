"""Seconds of the program's ``plan.build`` spans over the run: every
offline step of each plan the run built (rewrite, joins and selections,
group ids, planning).  None where the program records no spans."""
import importlib


def read(run):
    try:
        telemetry = importlib.import_module("repro.telemetry")
    except ImportError:
        return None
    t = telemetry.totals().get("plan.build")
    return t.seconds if t is not None else None
