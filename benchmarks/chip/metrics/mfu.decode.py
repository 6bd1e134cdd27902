"""Model step: model FLOPs of the useful tokens of the window (each prompt
and output position once, live tokens only) over the summed wall time of
the window's dispatching steps times the chip's peak, in %."""
import costs


def read(v):
    return costs.window_mfu(v)
