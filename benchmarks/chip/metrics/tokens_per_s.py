"""Output tokens emitted in the window over the window's seconds (one chip)."""


def read(v):
    return sum(r.emitted for r in v.steps) / v.window_s
