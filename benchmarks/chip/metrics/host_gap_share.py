"""Scheduler: share of the window in which the device waited on the host,
from the engine's dispatch log (``repro.serving.telemetry``): from each
device program's results being ready (end of ``engine.wait``) to the next
program's enqueue (end of ``engine.dispatch``), over the window from the
first step's start to the last step's end, in %.  ``note`` splits the
seconds into readback, drain, caller, schedule, upload and dispatch."""
try:
    from repro.serving import telemetry
except ImportError:  # a program without the dispatch log
    telemetry = None


def _report(v):
    if telemetry is None or not v.steps:
        return None
    return telemetry.report(v.steps[0].t0, v.steps[-1].t1)


def read(v):
    s = _report(v)
    return None if s is None else 100.0 * s["host_gap_s"] / s["window_s"]


def note(v):
    s = _report(v)
    return None if s is None else dict(s["host_gap_split"], host_gap_s=s["host_gap_s"],
                                       dispatches=s["dispatches"])
