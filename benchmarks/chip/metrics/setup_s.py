"""Seconds from process start to the start of the window, compiles included."""


def read(v):
    return v.setup_s
