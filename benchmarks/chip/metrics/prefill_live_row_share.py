"""Scheduler: rows of the window's ``prefill_step`` programs that held
prompt tokens (``live_rows``, the granted chunk lengths) over the rows
they ran (``rows``, slots x prefill chunk), from the engine's dispatch log
(``repro.serving.telemetry``), in %.  ``note`` gives both counts."""
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
    if s is None or not s["prefill_rows"]:
        return None
    return 100.0 * s["prefill_live_rows"] / s["prefill_rows"]


def note(v):
    s = _report(v)
    return None if s is None else {"live_rows": s["prefill_live_rows"], "rows": s["prefill_rows"]}
