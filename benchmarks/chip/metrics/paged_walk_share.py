"""Kernels: KV pages the window's decode programs' attention walked
(``pages_walked``) over the pages their block tables held
(``pages_table``), from the engine's dispatch log
(``repro.serving.telemetry``), in %.  A walk bounded by each slot's live
length reads about ``kv_pages_used_share``; a walk over the whole table
reads 100.  None on a program whose log has no such counts."""
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
    if s is None or not s.get("pages_table"):
        return None
    return 100.0 * s["pages_walked"] / s["pages_table"]


def note(v):
    s = _report(v)
    if s is None or "pages_table" not in s:
        return None
    return {"pages_walked": s["pages_walked"], "pages_table": s["pages_table"]}
