"""Device: share of the window the engine spent copying device programs'
results to the host (``engine.readback`` in the dispatch log,
``repro.serving.telemetry``), over the window from the first step's start
to the last step's end, in %.  ``note`` gives the longest single readback
in seconds and its program."""
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
    return None if s is None else 100.0 * s["phase_s"]["readback"] / s["window_s"]


def note(v):
    s = _report(v)
    return None if s is None else s["longest_readback"]
