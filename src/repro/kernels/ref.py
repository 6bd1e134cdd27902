"""Pure-jnp oracles for every tile-DSL kernel (paper §5 workloads).

These are the ground truth the Pallas lowerings are validated against
(``interpret=True`` on CPU), and double as the XLA execution path used by
the model layer when ``kernel_backend="xla"`` (the dry-run path).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# GEMM
# ---------------------------------------------------------------------------


def matmul(a: jax.Array, b: jax.Array, out_dtype=jnp.float32) -> jax.Array:
    return jax.lax.dot_general(
        a, b, (((a.ndim - 1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(out_dtype)


# ---------------------------------------------------------------------------
# Weight dequantization (paper Fig. 15/17): packed sub-byte -> compute dtype.
# Weights are packed along the last axis: int4 -> 2 values/byte,
# int2 -> 4 values/byte.  NF4 uses the bitsandbytes codebook.
# ---------------------------------------------------------------------------

NF4_CODEBOOK = np.array(
    [
        -1.0, -0.6961928009986877, -0.5250730514526367, -0.39491748809814453,
        -0.28444138169288635, -0.18477343022823334, -0.09105003625154495, 0.0,
        0.07958029955625534, 0.16093020141124725, 0.24611230194568634, 0.33791524171829224,
        0.44070982933044434, 0.5626170039176941, 0.7229568362236023, 1.0,
    ],
    dtype=np.float32,
)


def unpack_int4(packed: jax.Array, signed: bool = True) -> jax.Array:
    """(..., K//2) int8 -> (..., K) int8 values in [-8, 7] (or [0, 15])."""
    lo = packed & 0xF
    hi = (packed >> 4) & 0xF
    vals = jnp.stack([lo, hi], axis=-1).reshape(*packed.shape[:-1], -1)
    if signed:
        vals = jnp.where(vals >= 8, vals - 16, vals)
    return vals.astype(jnp.int8)


def unpack_int2(packed: jax.Array, signed: bool = True) -> jax.Array:
    """(..., K//4) int8 -> (..., K) int8 values in [-2, 1] (or [0, 3])."""
    parts = [(packed >> (2 * i)) & 0x3 for i in range(4)]
    vals = jnp.stack(parts, axis=-1).reshape(*packed.shape[:-1], -1)
    if signed:
        vals = jnp.where(vals >= 2, vals - 4, vals)
    return vals.astype(jnp.int8)


def unpack_nf4(packed: jax.Array) -> jax.Array:
    """(..., K//2) uint8-packed NF4 -> (..., K) float32 codebook values."""
    lo = packed & 0xF
    hi = (packed >> 4) & 0xF
    idx = jnp.stack([lo, hi], axis=-1).reshape(*packed.shape[:-1], -1)
    return jnp.asarray(NF4_CODEBOOK)[idx]


def dequant_matmul(
    a: jax.Array,
    b_packed: jax.Array,
    fmt: str = "int4",
    scales: Optional[jax.Array] = None,
    group_size: int = 128,
    out_dtype=jnp.float32,
) -> jax.Array:
    """A[M,K] @ dequant(B_packed)[N,K]^T -> [M,N].

    B is stored N-major with the K axis packed (weight-only quantization,
    the W_{INTx}A_{FP16} layout of the paper).  ``scales`` is (N, K//group)
    per-group scaling.
    """
    if fmt == "int4":
        w = unpack_int4(b_packed).astype(jnp.float32)
    elif fmt == "int2":
        w = unpack_int2(b_packed).astype(jnp.float32)
    elif fmt == "nf4":
        w = unpack_nf4(b_packed)
    elif fmt == "int8":
        w = b_packed.astype(jnp.float32)
    else:
        raise ValueError(f"unknown dequant format {fmt}")
    if scales is not None:
        n, k = w.shape
        w = w.reshape(n, k // group_size, group_size) * scales[..., None].astype(
            jnp.float32
        )
        w = w.reshape(n, k)
    acc = jax.lax.dot_general(
        a.astype(jnp.float32),
        w,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return acc.astype(out_dtype)


# ---------------------------------------------------------------------------
# KV-cache quantization: symmetric per-row (per-token) scales, packed along
# the feature axis.  The storage layout of the quantized paged pools
# (DESIGN.md §5.6): packed int8 data + a (rows, 1) scale column per page.
# ---------------------------------------------------------------------------

KV_QMAX = {"int8": 127.0, "int4": 7.0}
KV_PACK = {"int8": 1, "int4": 2}


def pack_int4(vals: jax.Array) -> jax.Array:
    """(..., K) int8 in [-8, 7] -> (..., K//2) int8, low nibble first
    (the byte order unpack_int4 and the kernel unpack loop expect)."""
    lo = vals[..., 0::2].astype(jnp.int32) & 0xF
    hi = vals[..., 1::2].astype(jnp.int32) & 0xF
    return jax.lax.bitcast_convert_type((lo | (hi << 4)).astype(jnp.uint8), jnp.int8)


def quantize_rows(x: jax.Array, fmt: str = "int8"):
    """Symmetric per-row quantization over the last axis.

    Returns ``(packed, scales)``: packed int8 data (last axis divided by the
    pack factor) and (..., 1) scales in ``x``'s dtype.  All-zero rows get
    scale 1 so dequantization stays exact (0 * 1 = 0).
    """
    qmax = KV_QMAX[fmt]
    xf = jnp.asarray(x).astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    q = jnp.clip(jnp.round(xf / scale), -qmax, qmax).astype(jnp.int8)
    if fmt == "int4":
        q = pack_int4(q)
    return q, scale.astype(jnp.asarray(x).dtype)


def dequantize_rows(packed: jax.Array, scales: jax.Array, fmt: str = "int8") -> jax.Array:
    """Inverse of :func:`quantize_rows` -> float32."""
    vals = unpack_int4(packed) if fmt == "int4" else packed
    return vals.astype(jnp.float32) * scales.astype(jnp.float32)


def paged_attention_quant(
    q: jax.Array,  # (B, Hq, D)
    k_pages: jax.Array,  # (Hkv, P, page_size, D//pack) packed int8
    v_pages: jax.Array,
    k_scales: jax.Array,  # (Hkv, P, page_size, 1)
    v_scales: jax.Array,
    block_tables: jax.Array,
    seq_lens: jax.Array,
    fmt: str = "int8",
    sm_scale: Optional[float] = None,
    window: Optional[int] = None,
    logit_soft_cap: Optional[float] = None,
    out_dtype=None,
) -> jax.Array:
    """Quantized paged-decode oracle: dequantize the pools, then the fp
    oracle — the composition the tile kernel performs page-at-a-time."""
    kf = dequantize_rows(k_pages, k_scales, fmt).astype(q.dtype)
    vf = dequantize_rows(v_pages, v_scales, fmt).astype(q.dtype)
    return paged_attention(q, kf, vf, block_tables, seq_lens, sm_scale=sm_scale,
                           window=window, logit_soft_cap=logit_soft_cap,
                           out_dtype=out_dtype)


def mla_paged_quant(
    q_lat: jax.Array,  # (B, H, R)
    q_pe: jax.Array,
    ckv_pages: jax.Array,  # (P, page_size, R//pack) packed int8
    kpe_pages: jax.Array,  # (P, page_size, Dpe//pack) packed int8
    ckv_scales: jax.Array,  # (P, page_size, 1)
    kpe_scales: jax.Array,
    block_tables: jax.Array,
    seq_lens: jax.Array,
    fmt: str = "int8",
    sm_scale: Optional[float] = None,
    window: Optional[int] = None,
    logit_soft_cap: Optional[float] = None,
    out_dtype=None,
) -> jax.Array:
    """Quantized paged MLA decode oracle (latent + rope pools both packed):
    dequantize, join the two pools into :func:`mla_rows`, then the fp
    oracle."""
    ckv = dequantize_rows(ckv_pages, ckv_scales, fmt).astype(q_lat.dtype)
    kpe = dequantize_rows(kpe_pages, kpe_scales, fmt).astype(q_lat.dtype)
    return mla_paged(q_lat, q_pe, mla_rows(ckv, kpe), block_tables, seq_lens,
                     sm_scale=sm_scale, window=window,
                     logit_soft_cap=logit_soft_cap, out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# FlashAttention (MHA/GQA, optional causal) — paper Table 3
# ---------------------------------------------------------------------------


def _attn_block(q, k, v, q_offset, sk_total, causal, sm_scale, logit_soft_cap,
                kv_len, window):
    """Attention for a block of queries at absolute offset ``q_offset``."""
    sq = q.shape[2]
    sk = k.shape[2]
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * sm_scale
    if logit_soft_cap is not None:
        s = logit_soft_cap * jnp.tanh(s / logit_soft_cap)
    mask = None
    qi = jnp.arange(sq)[:, None] + q_offset
    ki = jnp.arange(sk)[None, :]
    if causal:
        mask = qi >= ki
    if window is not None:
        wmask = (qi - ki) < window
        mask = wmask if mask is None else (mask & wmask)
    if kv_len is not None:
        lmask = (ki < kv_len[:, None])[:, None, None, :]
        s = jnp.where(lmask, s, -jnp.inf)
    if mask is not None:
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


# query-chunk size above which the S^2 logits tensor is streamed through a
# lax.map (bounds peak memory for long-context prefill)
CHUNKED_THRESHOLD = 8192
Q_CHUNK = 512


def attention(
    q: jax.Array,  # (B, Hq, Sq, D)
    k: jax.Array,  # (B, Hkv, Sk, D)
    v: jax.Array,  # (B, Hkv, Sk, D)
    causal: bool = False,
    sm_scale: Optional[float] = None,
    logit_soft_cap: Optional[float] = None,
    kv_len: Optional[jax.Array] = None,
    window: Optional[int] = None,
    out_dtype=None,
    q_chunk: Optional[int] = None,
) -> jax.Array:
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    sk = k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(d)
    if hq != hkv:
        assert hq % hkv == 0
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    off = sk - sq  # query absolute offset (suffix convention)
    chunk = q_chunk or (Q_CHUNK if sq >= CHUNKED_THRESHOLD else None)
    if chunk is not None and sq % chunk == 0 and sq > chunk:
        nq = sq // chunk

        def chunk_fn(i):
            qs = jax.lax.dynamic_slice_in_dim(q, i * chunk, chunk, axis=2)
            return _attn_block(
                qs, k, v, i * chunk + off, sk, causal, sm_scale,
                logit_soft_cap, kv_len, window,
            )

        out = jax.lax.map(chunk_fn, jnp.arange(nq))  # (nq, b, h, chunk, dv)
        # note: dv (v head dim) can differ from d (q/k dim), e.g. MLA
        out = jnp.moveaxis(out, 0, 2).reshape(b, hq, sq, v.shape[-1])
    else:
        out = _attn_block(
            q, k, v, off, sk, causal, sm_scale, logit_soft_cap, kv_len, window
        )
    return out.astype(out_dtype or q.dtype)


# ---------------------------------------------------------------------------
# Paged attention (vLLM-style): single-token decode over a paged KV pool.
# The oracle for kernels/paged_attention.py and the XLA execution path the
# serving engine uses on CPU hosts.
# ---------------------------------------------------------------------------


def paged_attention(
    q: jax.Array,  # (B, Hq, D) one query token per slot
    k_pages: jax.Array,  # (Hkv, P, page_size, D) physical page pool
    v_pages: jax.Array,  # (Hkv, P, page_size, D)
    block_tables: jax.Array,  # (B, max_pages) int32 physical page ids
    seq_lens: jax.Array,  # (B,) int32 live length per slot (0 = empty)
    sm_scale: Optional[float] = None,
    window: Optional[int] = None,
    logit_soft_cap: Optional[float] = None,
    out_dtype=None,
) -> jax.Array:
    b, hq, d = q.shape
    hkv, _, page_size, _ = k_pages.shape
    assert hq % hkv == 0
    group = hq // hkv
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(d)
    # gather each slot's pages: (Hkv, B, max_pages, page_size, D) -> (B, Hkv, S, D)
    def gathered(pages):
        g = pages[:, block_tables]
        g = jnp.moveaxis(g, 0, 1)
        return g.reshape(b, hkv, -1, d)

    k = gathered(k_pages).astype(jnp.float32)
    v = gathered(v_pages).astype(jnp.float32)
    s_total = k.shape[2]
    qg = q.reshape(b, hkv, group, d).astype(jnp.float32)
    # scale first, then cap — the same order as attention()'s _attn_block,
    # so paged and contiguous decode stay token-identical for capped models
    scores = jnp.einsum("bhgd,bhsd->bhgs", qg, k) * sm_scale
    if logit_soft_cap is not None:
        scores = logit_soft_cap * jnp.tanh(scores / logit_soft_cap)
    ki = jnp.arange(s_total, dtype=jnp.int32)
    lens = jnp.asarray(seq_lens, jnp.int32)
    mask = ki[None, :] < lens[:, None]  # (B, S)
    if window is not None:
        mask = mask & (ki[None, :] >= (lens[:, None] - window))
    mask4 = mask[:, None, None, :]
    # masked, empty-row-safe softmax (slots with len 0 emit zeros)
    neg = jnp.finfo(jnp.float32).min
    scores = jnp.where(mask4, scores, neg)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - m) * mask4
    den = jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    p = e / den
    out = jnp.einsum("bhgs,bhsd->bhgd", p, v)
    return out.reshape(b, hq, d).astype(out_dtype or q.dtype)


# ---------------------------------------------------------------------------
# Chunked-prefill attention: a block of C new tokens per slot attends prior
# context (gathered pages or a contiguous/ring strip) plus itself causally.
# The oracle for kernels/prefill_attention.py and the XLA execution path the
# serving engine's chunked-prefill fast path uses on CPU hosts.
# ---------------------------------------------------------------------------


def prefill_attention(
    q: jax.Array,  # (B, Hq, C, D) chunk queries
    k_new: jax.Array,  # (B, Hkv, C, D) the chunk's own keys
    v_new: jax.Array,  # (B, Hkv, C, D)
    k_ctx: jax.Array,  # (B, Hkv, S, D) prior context keys
    v_ctx: jax.Array,  # (B, Hkv, S, D)
    ctx_pos: jax.Array,  # (B, S) int32 absolute position per ctx entry; -1 = dead
    q_pos: jax.Array,  # (B, C) int32 absolute position per query
    chunk_lens: jax.Array,  # (B,) live tokens in the chunk (0 = inactive slot)
    sm_scale: Optional[float] = None,
    window: Optional[int] = None,
    logit_soft_cap: Optional[float] = None,
    out_dtype=None,
) -> jax.Array:
    """Masked two-part attention: ``softmax([scores_ctx ; scores_new])``.

    Context validity/causality/windowing all derive from ``ctx_pos`` so one
    oracle serves every prior-KV layout: gathered pages (position = linear
    gather index), contiguous strips (position = index) and ring buffers
    (position from the ring decode formula).  Query rows past
    ``chunk_lens`` are *not* zeroed — they still attend whatever keys their
    causal window allows (garbage the callers discard; the kernel behaves
    identically) — but a row with no valid key at all (an inactive slot
    with empty context) emits zeros, not nan.
    """
    b, hq, c, d = q.shape
    hkv = k_new.shape[1]
    assert hq % hkv == 0
    group = hq // hkv
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(d)
    qg = q.reshape(b, hkv, group, c, d).astype(jnp.float32)

    def scores_of(k):
        s = jnp.einsum("bhgcd,bhsd->bhgcs", qg, k.astype(jnp.float32)) * sm_scale
        if logit_soft_cap is not None:
            s = logit_soft_cap * jnp.tanh(s / logit_soft_cap)
        return s

    s_ctx = scores_of(k_ctx)  # (B, Hkv, G, C, S)
    s_new = scores_of(k_new)  # (B, Hkv, G, C, C)
    qp = jnp.asarray(q_pos, jnp.int32)
    cp = jnp.asarray(ctx_pos, jnp.int32)
    lens = jnp.asarray(chunk_lens, jnp.int32)
    m_ctx = (cp[:, None, :] >= 0) & (cp[:, None, :] <= qp[:, :, None])
    ci = jnp.arange(c, dtype=jnp.int32)
    m_new = (ci[None, None, :] <= ci[None, :, None]) & (
        ci[None, None, :] < lens[:, None, None]
    )
    if window is not None:
        m_ctx = m_ctx & ((qp[:, :, None] - cp[:, None, :]) < window)
        m_new = m_new & ((ci[None, :, None] - ci[None, None, :]) < window)
    mask = jnp.concatenate(
        [
            jnp.broadcast_to(m_ctx, (b, c, s_ctx.shape[-1])),
            jnp.broadcast_to(m_new, (b, c, c)),
        ],
        axis=-1,
    )[:, None, None]  # (B, 1, 1, C, S+C)
    scores = jnp.concatenate([s_ctx, s_new], axis=-1)
    neg = jnp.finfo(jnp.float32).min
    scores = jnp.where(mask, scores, neg)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - m) * mask
    den = jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    p = e / den
    v_all = jnp.concatenate(
        [v_ctx.astype(jnp.float32), v_new.astype(jnp.float32)], axis=2
    )
    out = jnp.einsum("bhgcs,bhsd->bhgcd", p, v_all)
    return out.reshape(b, hq, c, d).astype(out_dtype or q.dtype)


# ---------------------------------------------------------------------------
# Multi-head Latent Attention (paper Fig. 14/18): queries attend to a shared
# latent KV (dim) + rotary part (pe_dim); V is the latent itself.
# ---------------------------------------------------------------------------


def mla_masked(
    q_lat: jax.Array,  # (B, H, R) absorbed latent queries
    q_pe: jax.Array,  # (B, H, Dpe)
    c_kv: jax.Array,  # (B, S, R) latent cache
    k_pe: jax.Array,  # (B, S, Dpe)
    kv_len: jax.Array,  # (B,) or scalar live length per slot
    sm_scale: float,
    window: Optional[int] = None,
    logit_soft_cap: Optional[float] = None,
) -> jax.Array:
    """Latent-space MLA decode attention with a length mask — the single
    oracle both latent layouts share: the contiguous decode path feeds the
    per-slot strip, :func:`mla_paged` the page gather.  Returns the float32
    latent output (B, H, R) (callers expand through W_uv)."""
    scores = (
        jnp.einsum("bhr,bsr->bhs", q_lat.astype(jnp.float32), c_kv.astype(jnp.float32))
        + jnp.einsum("bhp,bsp->bhs", q_pe.astype(jnp.float32), k_pe.astype(jnp.float32))
    ) * sm_scale
    # scale first, then cap — the same order as attention()'s _attn_block,
    # so latent and standard attention stay token-identical for capped models
    if logit_soft_cap is not None:
        scores = logit_soft_cap * jnp.tanh(scores / logit_soft_cap)
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (scores.shape[0],))
    ki = jnp.arange(c_kv.shape[1], dtype=jnp.int32)
    mask = ki[None, None, :] < kv_len[:, None, None]
    if window is not None:
        mask = mask & (ki[None, None, :] >= (kv_len[:, None, None] - window))
    scores = jnp.where(mask, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhs,bsr->bhr", p, c_kv.astype(jnp.float32))


def mla_rows(latent: jax.Array, rope: jax.Array, width: Optional[int] = None) -> jax.Array:
    """Joint MLA rows ``[latent | rope | 0]`` along the last axis, ``width``
    lanes in all (default: no zero tail) — the layout of the paged MLA
    kernels' pages and queries (kernels/mla.py)."""
    width = width or latent.shape[-1] + rope.shape[-1]
    tail = width - latent.shape[-1] - rope.shape[-1]
    zeros = jnp.zeros(latent.shape[:-1] + (tail,), latent.dtype)
    return jnp.concatenate([latent, rope.astype(latent.dtype), zeros], axis=-1)


def mla_paged(
    q_lat: jax.Array,  # (B, H, R)
    q_pe: jax.Array,  # (B, H, Dpe)
    pages: jax.Array,  # (P, page_size, W) joint pool [latent | rope | 0]
    block_tables: jax.Array,  # (B, max_pages) int32 physical page ids
    seq_lens: jax.Array,  # (B,) int32 live length per slot
    sm_scale: Optional[float] = None,
    window: Optional[int] = None,
    logit_soft_cap: Optional[float] = None,
    out_dtype=None,
) -> jax.Array:
    """Paged MLA decode oracle: gather each slot's joint pages through its
    block table, split the latent and rope lanes, then the shared masked
    latent attention.  Because the gather reconstructs logical token
    order, outputs are identical to the contiguous strip path — the
    property the serving equivalence tests pin."""
    b, h, r = q_lat.shape
    pe = q_pe.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(r + pe)
    rows = pages[block_tables].reshape(b, -1, pages.shape[-1])
    out = mla_masked(q_lat, q_pe, rows[..., :r], rows[..., r : r + pe], seq_lens,
                     sm_scale, window=window, logit_soft_cap=logit_soft_cap)
    return out.astype(out_dtype or q_lat.dtype)


def mla_prefill(
    q_lat: jax.Array,  # (B, H, C, R) absorbed chunk queries
    q_pe: jax.Array,  # (B, H, C, Dpe)
    ckv_new: jax.Array,  # (B, C, R) the chunk's own latents
    kpe_new: jax.Array,  # (B, C, Dpe)
    ckv_ctx: jax.Array,  # (B, S, R) prior latent context
    kpe_ctx: jax.Array,  # (B, S, Dpe)
    ctx_pos: jax.Array,  # (B, S) int32 absolute position per ctx entry; -1 = dead
    q_pos: jax.Array,  # (B, C) int32 absolute position per query
    chunk_lens: jax.Array,  # (B,) live tokens in the chunk (0 = inactive slot)
    sm_scale: Optional[float] = None,
    window: Optional[int] = None,
    logit_soft_cap: Optional[float] = None,
    out_dtype=None,
) -> jax.Array:
    """MLA chunked-prefill oracle: masked two-part latent attention
    ``softmax([scores_ctx ; scores_new])`` — prefill_attention's structure
    with the latent+rope score split and the latent as V.  Same row
    semantics: rows past ``chunk_lens`` attend what causality allows
    (garbage the callers discard); rows with no valid key emit zeros."""
    b, h, c, r = q_lat.shape
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(r + q_pe.shape[-1])
    qf = q_lat.astype(jnp.float32)
    qpef = q_pe.astype(jnp.float32)

    def scores_of(kv, pe):
        s = (
            jnp.einsum("bhcr,bsr->bhcs", qf, kv.astype(jnp.float32))
            + jnp.einsum("bhcp,bsp->bhcs", qpef, pe.astype(jnp.float32))
        ) * sm_scale
        if logit_soft_cap is not None:
            s = logit_soft_cap * jnp.tanh(s / logit_soft_cap)
        return s

    s_ctx = scores_of(ckv_ctx, kpe_ctx)  # (B, H, C, S)
    s_new = scores_of(ckv_new, kpe_new)  # (B, H, C, C)
    qp = jnp.asarray(q_pos, jnp.int32)
    cp = jnp.asarray(ctx_pos, jnp.int32)
    lens = jnp.asarray(chunk_lens, jnp.int32)
    m_ctx = (cp[:, None, :] >= 0) & (cp[:, None, :] <= qp[:, :, None])
    ci = jnp.arange(c, dtype=jnp.int32)
    m_new = (ci[None, None, :] <= ci[None, :, None]) & (
        ci[None, None, :] < lens[:, None, None]
    )
    if window is not None:
        m_ctx = m_ctx & ((qp[:, :, None] - cp[:, None, :]) < window)
        m_new = m_new & ((ci[None, :, None] - ci[None, None, :]) < window)
    mask = jnp.concatenate(
        [
            jnp.broadcast_to(m_ctx, (b, c, s_ctx.shape[-1])),
            jnp.broadcast_to(m_new, (b, c, c)),
        ],
        axis=-1,
    )[:, None]  # (B, 1, C, S+C)
    scores = jnp.concatenate([s_ctx, s_new], axis=-1)
    neg = jnp.finfo(jnp.float32).min
    scores = jnp.where(mask, scores, neg)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - m) * mask
    den = jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    p = e / den
    v_all = jnp.concatenate(
        [ckv_ctx.astype(jnp.float32), ckv_new.astype(jnp.float32)], axis=1
    )
    out = jnp.einsum("bhcs,bsr->bhcr", p, v_all)
    return out.astype(out_dtype or q_lat.dtype)


def mla(
    q: jax.Array,  # (B, Hq, D)
    q_pe: jax.Array,  # (B, Hq, Dpe)
    kv: jax.Array,  # (B, S, Hkv, D)
    k_pe: jax.Array,  # (B, S, Hkv, Dpe)
    sm_scale: Optional[float] = None,
    out_dtype=None,
) -> jax.Array:
    b, hq, d = q.shape
    s_len = kv.shape[1]
    hkv = kv.shape[2]
    group = hq // hkv
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(d + q_pe.shape[-1])
    qg = q.reshape(b, hkv, group, d).astype(jnp.float32)
    qpeg = q_pe.reshape(b, hkv, group, -1).astype(jnp.float32)
    kvf = kv.astype(jnp.float32)
    kpef = k_pe.astype(jnp.float32)
    scores = jnp.einsum("bhgd,bshd->bhgs", qg, kvf)
    scores += jnp.einsum("bhgp,bshp->bhgs", qpeg, kpef)
    p = jax.nn.softmax(scores * sm_scale, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p, kvf)
    return out.reshape(b, hq, d).astype(out_dtype or q.dtype)


# ---------------------------------------------------------------------------
# Mamba-2 SSD chunked linear attention (paper Table 4: chunk_state/chunk_scan)
# ---------------------------------------------------------------------------


def chunk_cumsum(dt: jax.Array, a_log: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """dt (B,H,L), a_log (H,) -> per-chunk cumulative decay dA_cum (B,H,L)."""
    da = dt * (-jnp.exp(a_log))[None, :, None]
    return jnp.cumsum(da, axis=-1), da


def chunk_state(
    b_mat: jax.Array,  # (B, C, L, N)   "B" projections per chunk
    x: jax.Array,  # (B, C, L, P)   values
    da_cum: jax.Array,  # (B, C, L)      cumulative decay within chunk
) -> jax.Array:
    """Per-chunk state: S = sum_l exp(dA_last - dA_l) * B_l^T x_l  -> (B,C,N,P)."""
    decay = jnp.exp(da_cum[..., -1:] - da_cum)  # (B,C,L)
    bw = b_mat.astype(jnp.float32) * decay[..., None]
    return jnp.einsum("bcln,bclp->bcnp", bw, x.astype(jnp.float32))


def chunk_scan(
    c_mat: jax.Array,  # (B, C, L, N)   "C" projections
    b_mat: jax.Array,  # (B, C, L, N)
    x: jax.Array,  # (B, C, L, P)
    da_cum: jax.Array,  # (B, C, L)
    prev_states: jax.Array,  # (B, C, N, P)  inter-chunk states (already recurred)
) -> jax.Array:
    """Within-chunk scan + contribution of the carried state -> (B,C,L,P)."""
    cf = c_mat.astype(jnp.float32)
    bf = b_mat.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    l = x.shape[2]
    # inter-chunk: y_inter[l] = exp(dA_l) * C_l . S_prev
    y_inter = jnp.einsum("bcln,bcnp->bclp", cf, prev_states) * jnp.exp(da_cum)[..., None]
    # intra-chunk: masked decay attention
    seg = da_cum[..., :, None] - da_cum[..., None, :]  # (B,C,L,L) dA_l - dA_m
    mask = jnp.tril(jnp.ones((l, l), bool))
    att = jnp.einsum("bcln,bcmn->bclm", cf, bf) * jnp.exp(jnp.where(mask, seg, 0.0))
    att = jnp.where(mask, att, 0.0)
    y_intra = jnp.einsum("bclm,bcmp->bclp", att, xf)
    return (y_inter + y_intra).astype(x.dtype)


def state_recurrence(states: jax.Array, da_chunk: jax.Array) -> jax.Array:
    """Carry states across chunks: S'_c = exp(dA_chunk_c) S'_{c-1} + S_c.

    ``states`` (B,C,N,P) are per-chunk local states; ``da_chunk`` (B,C) is the
    total decay of each chunk.  Returns the *incoming* state for each chunk.
    """

    def step(carry, inp):
        s_local, decay = inp
        new = carry * jnp.exp(decay)[..., None, None] + s_local
        return new, carry  # emit the incoming state

    b, c, n, p = states.shape
    xs = (jnp.moveaxis(states, 1, 0), jnp.moveaxis(da_chunk, 1, 0))
    init = jnp.zeros((b, n, p), jnp.float32)
    _, incoming = jax.lax.scan(step, init, xs)
    return jnp.moveaxis(incoming, 0, 1)


def ssd(
    c_mat: jax.Array,  # (B, S, N) shared across heads here; callers vmap heads
    b_mat: jax.Array,
    x: jax.Array,  # (B, S, P)
    dt: jax.Array,  # (B, S)
    a_log: jax.Array,  # scalar per head
    chunk: int = 64,
) -> jax.Array:
    """Full SSD pass (reference composition of the two kernels)."""
    bsz, s, n = c_mat.shape
    p = x.shape[-1]
    nc = s // chunk
    rs = lambda t: t.reshape(bsz, nc, chunk, *t.shape[2:])
    da = dt * (-jnp.exp(a_log))
    da_cum = jnp.cumsum(da.reshape(bsz, nc, chunk), axis=-1)
    states = chunk_state(rs(b_mat), rs(x), da_cum)
    incoming = state_recurrence(states, da_cum[..., -1])
    y = chunk_scan(rs(c_mat), rs(b_mat), rs(x), da_cum, incoming)
    return y.reshape(bsz, s, p)


# ---------------------------------------------------------------------------
# Fused RMSNorm (bonus beyond-paper kernel used by the model layer)
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)).astype(x.dtype)
