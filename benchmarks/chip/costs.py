"""Operations and bytes that the algorithm needs, computed from shapes.

Only live work counts: the tokens a request really has, never the rows a
program pads a batch or a chunk to, nor the dead pages of a block table.
So the same work is counted however it is implemented.  Sizes come from
``reference.<architecture>.Dims``.
"""
from __future__ import annotations

from typing import Iterable, Tuple


def paged_attn(lens: Iterable[int], d, itemsize: int = 2) -> Tuple[float, float]:
    """One decode-attention call of one layer over live slots whose
    sequence lengths (the new token included) are ``lens``: Q.K and P.V
    for every query head, reading each slot's K and V once and its query,
    writing its output."""
    flops = 0.0
    nbytes = 0.0
    hd, kvd = d.heads * d.head_dim, d.kv_heads * d.head_dim
    for n in lens:
        flops += 4.0 * hd * n
        nbytes += itemsize * (2.0 * kvd * n + 2.0 * hd)
    return flops, nbytes


def prefill_attn(chunks: Iterable[Tuple[int, int]], d, itemsize: int = 2) -> Tuple[float, float]:
    """One chunked-prefill attention call of one layer over live
    ``(start, n)`` chunks: each of the n queries attends the ``start`` prior
    positions and, causally, the chunk; prior K/V are read once, the
    chunk's Q/K/V read, its output and its K/V pages written."""
    flops = 0.0
    nbytes = 0.0
    hd, kvd = d.heads * d.head_dim, d.kv_heads * d.head_dim
    for start, n in chunks:
        keys = n * start + n * (n + 1) / 2.0
        flops += 4.0 * hd * keys
        nbytes += itemsize * (2.0 * kvd * start + 2.0 * hd * n + 4.0 * kvd * n)
    return flops, nbytes


def matmul_flops_per_token(d) -> float:
    """The linear layers of every block, for one token."""
    hd, kvd = d.heads * d.head_dim, d.kv_heads * d.head_dim
    per_layer = d.d_model * (2 * hd + 2 * kvd) + 3 * d.d_model * d.d_ff
    return 2.0 * d.layers * per_layer


def token_flops(d, position: int) -> float:
    """One token at ``position`` through the model: linear layers plus
    attention over ``position + 1`` keys in every layer."""
    return matmul_flops_per_token(d) + 4.0 * d.layers * d.heads * d.head_dim * (position + 1)


def head_flops(d) -> float:
    """The output head for one emitted token."""
    return 2.0 * d.d_model * d.vocab


def min_seconds(flops: float, nbytes: float, peak: dict) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    tc = flops / peak["flops_per_s"]
    tb = nbytes / peak["bytes_per_s"]
    return (tc, "compute") if tc >= tb else (tb, "bandwidth")


def step_flops(steps, d) -> float:
    """Model FLOPs of the useful work in ``steps`` (harness StepRecords):
    every position a request reaches for the first time, prompt or output,
    plus the output head for every emitted token.  Positions replayed after
    a preemption are recomputation and do not count."""
    reached = {}
    flops = 0.0
    for rec in steps:
        spans = [(uid, start, n) for uid, start, n in rec.prefill]
        spans += [(uid, p0, k) for uid, p0, k in rec.decode]
        for uid, start, n in spans:
            lo = max(start, reached.get(uid, 0))
            hi = start + n
            if hi > lo:
                # sum of token_flops over positions lo..hi-1, in closed form
                m = hi - lo
                flops += m * matmul_flops_per_token(d)
                flops += 4.0 * d.layers * d.heads * d.head_dim * (m * lo + m * (m + 1) / 2.0)
                reached[uid] = hi
        flops += rec.emitted * head_flops(d)
    return flops


def kernel_work(steps, d, kernel: str, peak: dict, itemsize: int = 2):
    """Summed FLOPs, bytes and least time of every call of ``kernel``
    ("PagedAttn" or "PrefillAttn") that ``steps`` dispatched with live
    work: one call per layer per decode tick, or per prefill step."""
    flops = nbytes = least = 0.0
    bounds = {"compute": 0, "bandwidth": 0}
    for rec in steps:
        calls = []
        if kernel == "PagedAttn":
            ticks = max((k for _, _, k in rec.decode), default=0)
            for t in range(ticks):
                lens = [p0 + t + 1 for _, p0, k in rec.decode if k > t]
                calls.append(paged_attn(lens, d, itemsize))
        elif kernel == "PrefillAttn":
            if rec.prefill:
                calls.append(prefill_attn([(s, n) for _, s, n in rec.prefill], d, itemsize))
        else:
            raise ValueError(f"no cost function for kernel {kernel!r}")
        for f, b in calls:
            t, bound = min_seconds(f, b, peak)
            flops += d.layers * f
            nbytes += d.layers * b
            least += d.layers * t
            bounds[bound] += d.layers
    return {"flops": flops, "bytes": nbytes, "least_s": least, "calls_by_bound": bounds}


def window_mfu(v) -> "float | None":
    """Useful model FLOPs of a run's window over the summed wall time of its
    dispatching steps times the chip's peak, in %."""
    busy = sum(r.t1 - r.t0 for r in v.steps if r.dispatched)
    if not busy:
        return None
    return 100.0 * step_flops(v.steps, v.dims) / (busy * v.peak["flops_per_s"])
