"""Mean host microseconds of the program's ``query`` spans in the window:
the plan lookup and the dispatch of each answered query, not the wait for
its answer.  The window's queries are the run's last ``query`` roots, since
nothing calls the program after the window.  None where the program records
no spans, or fewer than the window answered."""
import importlib


def read(run):
    try:
        telemetry = importlib.import_module("repro.telemetry")
    except ImportError:
        return None
    n = sum(run.completed.values())
    roots = [s for s in telemetry.spans()
             if s.name == "query" and s.parent is None]
    if n == 0 or len(roots) < n:
        return None
    return 1e6 * sum(s.end - s.start for s in roots[-n:]) / n
