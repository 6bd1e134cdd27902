"""Experts: rows of the held experts' FFNs that the window's programs
computed for a live token that picked that expert (``expert_rows_routed``)
over the rows they computed (``expert_rows``: every held expert runs every
token row), from the engine's dispatch log (``repro.serving.telemetry``),
in %.  Dense compute over the held experts reads about top-k / experts of
the decode rows.  ``note`` gives both counts, and the decode programs'
routed rows.  None on a program whose log has no such counts."""
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
    if s is None or not s.get("expert_rows"):
        return None
    return 100.0 * s["expert_rows_routed"] / s["expert_rows"]


def note(v):
    s = _report(v)
    if s is None or "expert_rows" not in s:
        return None
    return {k: s[k] for k in ("expert_rows", "expert_rows_routed", "decode_expert_rows_routed")}
