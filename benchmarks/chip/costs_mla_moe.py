"""Operations and bytes that a DeepSeek-V2 (MLA + MoE) model's serving
needs, computed from shapes (``reference.mla_moe.Dims``).

As in ``costs.py`` only live work counts: the tokens a request really has,
never padded rows or dead table pages.  Decode attends in latent space (the
absorbed form the program and FlashMLA run): each query head scores a key
of ``kv_lora_rank + qk_rope`` values and reads back the latent as its value.
Routed experts count by the (token, held expert) pairs that routing picked,
not by the rows a dense compute over the held experts runs.
"""
from __future__ import annotations

from typing import Iterable, Tuple

try:
    from repro.serving import telemetry
except ImportError:  # a program without the dispatch log
    telemetry = None


def _latent(d) -> int:
    return d.kv_lora_rank + d.qk_rope


def paged_mla(lens: Iterable[int], d, itemsize: int = 2) -> Tuple[float, float]:
    """One ``PagedMLA`` call (one layer, one decode tick) over live slots
    whose lengths (the new token included) are ``lens``: scores over the
    latent and rope key, values over the latent, for every query head;
    each slot's latent pages read once, its queries read, its output
    written."""
    flops = nbytes = 0.0
    for n in lens:
        flops += 2.0 * d.heads * (_latent(d) + d.kv_lora_rank) * n
        nbytes += itemsize * (_latent(d) * n + d.heads * (_latent(d) + d.kv_lora_rank))
    return flops, nbytes


def prefill_mla(chunks: Iterable[Tuple[int, int]], d, itemsize: int = 2) -> Tuple[float, float]:
    """One ``PrefillMLA`` call (one layer) over live ``(start, n)`` chunks:
    each of the n queries attends the ``start`` prior positions and,
    causally, the chunk; prior latents read once, the chunk's queries and
    latents read, its output and its latent pages written."""
    flops = nbytes = 0.0
    for start, n in chunks:
        keys = n * start + n * (n + 1) / 2.0
        flops += 2.0 * d.heads * (_latent(d) + d.kv_lora_rank) * keys
        nbytes += itemsize * (_latent(d) * start + d.heads * (_latent(d) + d.kv_lora_rank) * n
                              + 2.0 * _latent(d) * n)
    return flops, nbytes


def _swiglu(d, width: int) -> float:
    return 2.0 * 3 * d.d_model * width


def attention_projection_flops(d) -> float:
    """One token through one layer's MLA projections (absorbed): the query,
    the joint latent and rope-key projection, the queries into latent space,
    the latent outputs back to values, and the output projection."""
    qk = d.qk_nope + d.qk_rope
    return 2.0 * (d.d_model * d.heads * qk + d.d_model * _latent(d)
                  + d.heads * d.qk_nope * d.kv_lora_rank + d.heads * d.kv_lora_rank * d.v_head
                  + d.heads * d.v_head * d.d_model)


def token_flops(d, position: int) -> float:
    """One decode token at ``position`` through every layer and the head,
    routed experts left out (``expert_pair_flops``): projections and
    latent attention over ``position + 1`` keys in every layer; the dense
    layers' FFN; the router and the shared experts in every MoE layer."""
    moe = d.layers - d.dense_layers
    attend = 2.0 * d.heads * (_latent(d) + d.kv_lora_rank) * (position + 1)
    return (d.layers * (attention_projection_flops(d) + attend)
            + d.dense_layers * _swiglu(d, d.d_ff)
            + moe * (2.0 * d.d_model * d.experts + _swiglu(d, d.shared * d.d_ff_expert))
            + 2.0 * d.d_model * d.vocab)


def expert_pair_flops(d) -> float:
    """One (token, expert) pair through the expert's SwiGLU FFN."""
    return _swiglu(d, d.d_ff_expert)


def decode_mfu(v) -> "float | None":
    """Useful FLOPs of the window's decode ticks over the summed
    enqueue-to-ready seconds of its decode programs (the dispatch log)
    times the chip's peak, in %.  A tick of a slot at position p is one
    token at p; routed experts count by the pairs the decode programs'
    live tokens picked among the held experts (``expert_rows_routed``).
    None for a run of another architecture (its ``dims`` lack a latent)."""
    if telemetry is None or not v.steps or not hasattr(v.dims, "kv_lora_rank"):
        return None
    s = telemetry.report(v.steps[0].t0, v.steps[-1].t1)
    if s is None or not s.get("decode_s"):
        return None
    d = v.dims
    flops = sum(token_flops(d, p0 + t) for r in v.steps for _, p0, k in r.decode for t in range(k))
    flops += s["decode_expert_rows_routed"] * expert_pair_flops(d)
    return 100.0 * flops / (s["decode_s"] * v.peak["flops_per_s"])
