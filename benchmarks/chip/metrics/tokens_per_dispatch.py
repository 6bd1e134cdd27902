"""Scheduler: output tokens emitted in the window per engine dispatch
(``ServingEngine.dispatches``): 1 per slot for a per-tick step, up to
``sync_every`` per slot for a device-resident window."""


def read(v):
    n = v.counters["dispatches"]
    return sum(r.emitted for r in v.steps) / n if n else None
