"""Kernels: the least time the chip could take for the live work of every
``PagedAttn`` call in the traced window (FLOPs over peak or bytes over
bandwidth, whichever is larger, per call) over the summed device time of
the ``PagedAttn`` operations there, in %."""
import costs

KERNEL = "PagedAttn"


def read(v):
    if v.trace is None or not v.trace["op_s"].get(KERNEL):
        return None
    work = costs.kernel_work(v.traced_steps, v.dims, KERNEL, v.peak)
    if not work["least_s"]:
        return None
    return 100.0 * work["least_s"] / v.trace["op_s"][KERNEL]


def note(v):
    if v.trace is None:
        return None
    work = costs.kernel_work(v.traced_steps, v.dims, KERNEL, v.peak)
    return dict(work, device_s=v.trace["op_s"].get(KERNEL))
