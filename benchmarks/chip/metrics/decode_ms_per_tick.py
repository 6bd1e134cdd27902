"""Model step: milliseconds from the enqueue of a decode program
(``decode_step`` or ``decode_window_<n>``) to its results being ready,
summed over the window's decode programs and divided by the decode ticks
they ran, from the engine's dispatch log (``repro.serving.telemetry``)."""
try:
    from repro.serving import telemetry
except ImportError:  # a program without the dispatch log
    telemetry = None


def read(v):
    if telemetry is None or not v.steps:
        return None
    s = telemetry.report(v.steps[0].t0, v.steps[-1].t1)
    return None if s is None else s["decode_ms_per_tick"]
