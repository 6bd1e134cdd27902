"""Kernels: prior KV pages the window's ``prefill_step`` programs'
attention walked (``prior_pages_walked``) over the pages their grid cells'
block tables held (``prior_pages_table``), from the engine's dispatch log
(``repro.serving.telemetry``), in %.  A walk bounded by each chunk's prior
pages reads near 0 on fresh prompts; a walk over the whole table in every
grid cell reads 100.  None on a program whose log has no such counts."""
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
    if s is None or not s.get("prior_pages_table"):
        return None
    return 100.0 * s["prior_pages_walked"] / s["prior_pages_table"]


def note(v):
    s = _report(v)
    if s is None or "prior_pages_table" not in s:
        return None
    return {"prior_pages_walked": s["prior_pages_walked"],
            "prior_pages_table": s["prior_pages_table"]}
