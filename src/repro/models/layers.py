"""Composable model layers (pure functions over param pytrees).

Every block exists in two execution modes:

* full-sequence (training / prefill) — uses the tile-DSL kernels through
  ``repro.kernels.ops`` when ``kernel_backend`` allows, else the XLA path;
* single-token decode — operates against static-shape caches (contiguous KV,
  ring-buffer KV for sliding windows, paged KV/latent pools behind block
  tables, SSM state for Mamba).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.mla import page_width

from .config import ModelConfig, YarnScaling

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Sharding hints: step builders install activation constraints that apply
# while their step function traces (GSPMD needs interior hints when the
# natural propagation would replicate — e.g. attention with head counts not
# divisible by the TP degree, or MoE expert buffers).
# ---------------------------------------------------------------------------

import contextlib

_HINT_STACK: list = []


@contextlib.contextmanager
def shard_hints(**hooks):
    """hooks: name -> fn(x) -> x (usually with_sharding_constraint)."""
    _HINT_STACK.append(hooks)
    try:
        yield
    finally:
        _HINT_STACK.pop()


def _hint(name: str, x):
    for h in reversed(_HINT_STACK):
        if name in h and h[name] is not None:
            return h[name](x)
    return x


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def _dense_init(key, d_in, d_out, dtype, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def _split(key, n):
    return list(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# Norm / embeddings / rope
# ---------------------------------------------------------------------------


def rmsnorm(x, weight, eps=1e-6):
    return ops.rmsnorm(x, weight, eps)


def init_embedding(key, cfg: ModelConfig) -> Params:
    k1, k2 = _split(key, 2)
    p = {"embedding": _dense_init(k1, cfg.vocab_size, cfg.d_model, cfg.dtype, 1.0)}
    if not cfg.tie_embeddings:
        p["unembed"] = _dense_init(k2, cfg.d_model, cfg.vocab_size, cfg.dtype)
    return p


def embed(params: Params, tokens):
    return jnp.take(params["embedding"], tokens, axis=0)


def unembed(params: Params, x, cfg: ModelConfig):
    w = params.get("unembed")
    if w is None:
        w = params["embedding"].T
    return jnp.einsum("...d,dv->...v", x.astype(jnp.float32), w.astype(jnp.float32))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor for a context stretched ``factor`` times
    (``yarn_get_mscale`` of the published DeepSeek-V2 modeling code)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_freqs(head_dim: int, theta: float, fraction: float = 1.0,
               yarn: Optional[YarnScaling] = None):
    """Inverse frequencies of the rotated pairs, and how many dims rotate.
    With ``yarn`` (arXiv:2309.00071): pairs that turn fewer than
    ``beta_slow`` times over the original context are interpolated by
    ``factor``, pairs that turn more than ``beta_fast`` times keep their
    frequency, and a linear ramp blends the pairs between."""
    rot = int(head_dim * fraction) // 2 * 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    if yarn is not None:
        def dim_of(turns):  # the pair index that turns ``turns`` times
            ctx = yarn.original_max_position_embeddings
            return rot * math.log(ctx / (turns * 2 * math.pi)) / (2 * math.log(theta))

        low = max(math.floor(dim_of(yarn.beta_fast)), 0)
        high = min(math.ceil(dim_of(yarn.beta_slow)), rot - 1)
        ramp = jnp.clip((jnp.arange(rot // 2, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        inv = inv / yarn.factor * ramp + inv * (1.0 - ramp)
    return inv, rot


def apply_rope(x, positions, theta: float, fraction: float = 1.0,
               yarn: Optional[YarnScaling] = None):
    """x: (..., S, H, D) or (..., S, D); positions: (..., S)."""
    d = x.shape[-1]
    inv, rot = rope_freqs(d, theta, fraction, yarn)
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., S, rot/2)
    if x.ndim == ang.ndim + 1:  # head axis present
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xr = x[..., :rot].astype(jnp.float32)
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x1 * sin + x2 * cos
    out = jnp.stack([r1, r2], axis=-1).reshape(*x1.shape[:-1], rot)
    if yarn is not None and yarn.mscale != yarn.mscale_all_dim:
        # the published cos/sin carry mscale / mscale_all_dim; 1 where equal
        out = out * (yarn_mscale(yarn.factor, yarn.mscale)
                     / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    if rot < d:
        out = jnp.concatenate([out, x[..., rot:].astype(jnp.float32)], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig) -> Params:
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = _split(key, 4)
    p = {
        "wq": _dense_init(ks[0], d, h * hd, cfg.dtype),
        "wk": _dense_init(ks[1], d, hkv * hd, cfg.dtype),
        "wv": _dense_init(ks[2], d, hkv * hd, cfg.dtype),
        "wo": _dense_init(ks[3], h * hd, d, cfg.dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), cfg.dtype)
        p["bk"] = jnp.zeros((hkv * hd,), cfg.dtype)
        p["bv"] = jnp.zeros((hkv * hd,), cfg.dtype)
    return p


def _qkv(params, x, cfg: ModelConfig):
    b, s, _ = x.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,de->bse", x, params["wq"])
    k = jnp.einsum("bsd,de->bse", x, params["wk"])
    v = jnp.einsum("bsd,de->bse", x, params["wv"])
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    return (
        q.reshape(b, s, h, hd),
        k.reshape(b, s, hkv, hd),
        v.reshape(b, s, hkv, hd),
    )


def attention_full(params, x, cfg: ModelConfig, positions, window=None,
                   rope_fraction=1.0):
    """Full-sequence causal attention (training / prefill)."""
    b, s, _ = x.shape
    q, k, v = _qkv(params, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta, rope_fraction)
    k = apply_rope(k, positions, cfg.rope_theta, rope_fraction)
    qt = _hint("attn_q", q.transpose(0, 2, 1, 3))
    kt = _hint("attn_kv", k.transpose(0, 2, 1, 3))
    vt = _hint("attn_kv", v.transpose(0, 2, 1, 3))
    out = ops.attention(
        qt, kt, vt, causal=True, window=window,
        backend=cfg.kernel_backend if cfg.kernel_backend != "auto" else None,
        logit_soft_cap=cfg.logit_soft_cap,
    )
    out = _hint("attn_q", out)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
    return jnp.einsum("bse,ed->bsd", out.astype(x.dtype), params["wo"])


def _require_fp_cache(cfg: ModelConfig, layout: str):
    if cfg.kv_dtype is not None:
        raise ValueError(
            f"kv_dtype={cfg.kv_dtype!r} requires a paged cache layout; "
            f"the {layout} cache stores {cfg.dtype} only"
        )


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, window=None):
    _require_fp_cache(cfg, "contiguous")
    size = min(max_len, window) if window else max_len
    return {
        "k": jnp.zeros((batch, cfg.num_kv_heads, size, cfg.head_dim), cfg.dtype),
        "v": jnp.zeros((batch, cfg.num_kv_heads, size, cfg.head_dim), cfg.dtype),
    }


def init_paged_kv_cache(cfg: ModelConfig, num_blocks: int, page_size: int):
    """Page pool for one layer: ``(kv_heads, num_blocks, page_size, head_dim)``.

    Unlike the contiguous cache there is no per-layer ring sizing — sliding
    windows are enforced by the attention mask over gathered pages, so every
    layer shares one pool geometry.  (Layer *stacking* still requires
    uniform windows: the scanned decode body bakes the window statically.)

    With ``cfg.kv_dtype`` set ("int8"/"int4") the pools store packed int8
    bytes plus per-row scales (``*_scale_pages``, fp, shape ``(..., ps, 1)``).
    Scale leaves keep the page axis at ``ndim - 3`` so the serving layer's
    ``copy_pages`` COW treats them like any other ``*_pages`` leaf."""
    if cfg.kv_dtype is not None:
        pack = ref.KV_PACK[cfg.kv_dtype]
        pshape = (cfg.num_kv_heads, num_blocks, page_size, cfg.head_dim // pack)
        sshape = (cfg.num_kv_heads, num_blocks, page_size, 1)
        return {
            "k_pages": jnp.zeros(pshape, jnp.int8),
            "v_pages": jnp.zeros(pshape, jnp.int8),
            "k_scale_pages": jnp.zeros(sshape, cfg.dtype),
            "v_scale_pages": jnp.zeros(sshape, cfg.dtype),
        }
    shape = (cfg.num_kv_heads, num_blocks, page_size, cfg.head_dim)
    return {
        "k_pages": jnp.zeros(shape, cfg.dtype),
        "v_pages": jnp.zeros(shape, cfg.dtype),
    }


def attention_decode_paged(params, x, cfg: ModelConfig, cache, pos, tables,
                           window=None, rope_fraction=1.0):
    """One-token decode against a paged KV pool.

    ``tables`` is the (B, max_pages) int32 block table (padded with page 0);
    ``pos`` is the absolute position per slot.  The new K/V land in the page
    holding position ``pos`` (scattered per slot through the table), then the
    query attends over the gathered pages with a ragged length mask."""
    b = x.shape[0]
    h, hd = cfg.num_heads, cfg.head_dim
    q, k, v = _qkv(params, x, cfg)  # (b, 1, ...)
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    posv = posb[:, None]
    q = apply_rope(q, posv, cfg.rope_theta, rope_fraction)
    k = apply_rope(k, posv, cfg.rope_theta, rope_fraction)
    page_size = cache["k_pages"].shape[2]
    logical = posb // page_size
    offset = posb % page_size
    phys = jnp.take_along_axis(tables, logical[:, None], axis=1)[:, 0]
    backend = cfg.kernel_backend if cfg.kernel_backend != "auto" else None
    if cfg.kv_dtype is not None:
        # Quantize the appended row per (head, slot) and scatter packed bytes
        # plus the per-token scale; attention dequantizes inline at gather.
        sdt = cache["k_scale_pages"].dtype
        kq, ks = ref.quantize_rows(k[:, 0].transpose(1, 0, 2), cfg.kv_dtype)
        vq, vs = ref.quantize_rows(v[:, 0].transpose(1, 0, 2), cfg.kv_dtype)
        knew = cache["k_pages"].at[:, phys, offset].set(kq)
        vnew = cache["v_pages"].at[:, phys, offset].set(vq)
        ksnew = cache["k_scale_pages"].at[:, phys, offset].set(ks.astype(sdt))
        vsnew = cache["v_scale_pages"].at[:, phys, offset].set(vs.astype(sdt))
        out = ops.paged_attention_quant(
            q[:, 0], knew, vnew, ksnew, vsnew, tables, posb + 1,
            fmt=cfg.kv_dtype, window=window,
            logit_soft_cap=cfg.logit_soft_cap, backend=backend,
        )
        out = out.reshape(b, 1, h * hd)
        proj = jnp.einsum("bse,ed->bsd", out.astype(x.dtype), params["wo"])
        return proj, {"k_pages": knew, "v_pages": vnew,
                      "k_scale_pages": ksnew, "v_scale_pages": vsnew}
    # (b, 1, hkv, hd) -> (hkv, b, hd) scatter rows into their pages
    kdt = cache["k_pages"].dtype
    knew = cache["k_pages"].at[:, phys, offset].set(
        k[:, 0].transpose(1, 0, 2).astype(kdt)
    )
    vnew = cache["v_pages"].at[:, phys, offset].set(
        v[:, 0].transpose(1, 0, 2).astype(kdt)
    )
    out = ops.paged_attention(
        q[:, 0], knew, vnew, tables, posb + 1, window=window,
        logit_soft_cap=cfg.logit_soft_cap, backend=backend,
    )
    out = out.reshape(b, 1, h * hd)
    proj = jnp.einsum("bse,ed->bsd", out.astype(x.dtype), params["wo"])
    return proj, {"k_pages": knew, "v_pages": vnew}


def attention_prefill_paged(params, x, cfg: ModelConfig, cache, pos, tables,
                            lens, window=None, rope_fraction=1.0):
    """Chunk-wide prefill against a paged KV pool.

    ``x`` is a (B, C, d) block of prompt tokens per slot; ``pos`` (B,) is
    each slot's chunk start (its prior resident length), ``lens`` (B,) the
    live tokens within the chunk (0 = slot not prefilling this tick).  The
    chunk's K/V land in the pages holding positions [pos, pos+lens) through
    the block table (inside the tile kernel on the Pallas path; a masked
    scatter on XLA), and every chunk query attends prior pages plus the
    chunk causally."""
    b, c, _ = x.shape
    h, hd = cfg.num_heads, cfg.head_dim
    q, k, v = _qkv(params, x, cfg)  # (b, c, ...)
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    posmat = posb[:, None] + jnp.arange(c, dtype=jnp.int32)
    q = apply_rope(q, posmat, cfg.rope_theta, rope_fraction)
    k = apply_rope(k, posmat, cfg.rope_theta, rope_fraction)
    backend = cfg.kernel_backend if cfg.kernel_backend != "auto" else None
    if cfg.kv_dtype is not None:
        out, kp, vp, ksp, vsp = ops.prefill_attention_quant(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), cache["k_pages"], cache["v_pages"],
            cache["k_scale_pages"], cache["v_scale_pages"],
            tables, posb, jnp.asarray(lens, jnp.int32), fmt=cfg.kv_dtype,
            window=window, logit_soft_cap=cfg.logit_soft_cap, backend=backend,
        )
        out = out.transpose(0, 2, 1, 3).reshape(b, c, h * hd)
        proj = jnp.einsum("bse,ed->bsd", out.astype(x.dtype), params["wo"])
        return proj, {"k_pages": kp, "v_pages": vp,
                      "k_scale_pages": ksp, "v_scale_pages": vsp}
    out, kp, vp = ops.prefill_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), cache["k_pages"], cache["v_pages"],
        tables, posb, jnp.asarray(lens, jnp.int32), window=window,
        logit_soft_cap=cfg.logit_soft_cap, backend=backend,
    )
    out = out.transpose(0, 2, 1, 3).reshape(b, c, h * hd)
    proj = jnp.einsum("bse,ed->bsd", out.astype(x.dtype), params["wo"])
    return proj, {"k_pages": kp, "v_pages": vp}


def attention_prefill(params, x, cfg: ModelConfig, cache, pos, lens,
                      window=None, rope_fraction=1.0):
    """Chunk-wide prefill against the contiguous cache (ring buffers for
    sliding-window layers).  Same contract as :func:`attention_prefill_paged`
    with the prior context read from the per-slot strip: attention runs
    against the strip *before* the chunk overwrites any ring entries, so
    queries early in the chunk still see context the chunk's own tail would
    evict."""
    b, c, _ = x.shape
    h, hd = cfg.num_heads, cfg.head_dim
    q, k, v = _qkv(params, x, cfg)
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    lens = jnp.asarray(lens, jnp.int32)
    posmat = posb[:, None] + jnp.arange(c, dtype=jnp.int32)
    q = apply_rope(q, posmat, cfg.rope_theta, rope_fraction)
    k = apply_rope(k, posmat, cfg.rope_theta, rope_fraction)
    size = cache["k"].shape[2]
    r = jnp.arange(size, dtype=jnp.int32)[None, :]  # (1, S)
    if window:
        # ring entry r holds the latest position p < pos with p % size == r
        sm1 = posb[:, None] - 1
        p = sm1 - ((sm1 - r) % size)
        ctx_pos = jnp.where((posb[:, None] > 0) & (p >= 0), p, -1)
    else:
        ctx_pos = jnp.where(r < posb[:, None], r, -1)
    out = ref.prefill_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), cache["k"], cache["v"], ctx_pos, posmat,
        lens, window=window, logit_soft_cap=cfg.logit_soft_cap,
    )
    out = out.transpose(0, 2, 1, 3).reshape(b, c, h * hd)
    proj = jnp.einsum("bse,ed->bsd", out.astype(x.dtype), params["wo"])
    # Write the chunk into the strip/ring as a gather-select over cache
    # entries (no scatter): entry r takes chunk token c(r) when live.  For
    # rings c(r) is the *latest* chunk index mapping to r, so a chunk longer
    # than the ring correctly keeps only its last `size` tokens.
    rel = jnp.arange(size, dtype=jnp.int32)[None, :] - posb[:, None]  # (B,S)
    if window:
        base = rel % size  # ring: c == base (mod size)
        cidx = base + ((lens[:, None] - 1 - base) // size) * size
    else:
        cidx = rel
    live = (cidx >= 0) & (cidx < lens[:, None])
    cg = jnp.clip(cidx, 0, c - 1)[:, None, :, None]  # (B,1,S,1)
    cdt = cache["k"].dtype
    kt = k.transpose(0, 2, 1, 3).astype(cdt)  # (B, Hkv, C, hd)
    vt = v.transpose(0, 2, 1, 3).astype(cdt)
    sel = live[:, None, :, None]
    knew = jnp.where(sel, jnp.take_along_axis(kt, cg, axis=2), cache["k"])
    vnew = jnp.where(sel, jnp.take_along_axis(vt, cg, axis=2), cache["v"])
    return proj, {"k": knew, "v": vnew}


def attention_decode(params, x, cfg: ModelConfig, cache, pos, window=None,
                     rope_fraction=1.0):
    """One-token decode.  ``pos`` is the absolute position — a scalar (lockstep
    batch) or an (B,) vector (continuous batching: every slot at its own
    position).  The cache is contiguous, or a ring buffer when ``window``."""
    b = x.shape[0]
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q, k, v = _qkv(params, x, cfg)  # (b, 1, ...)
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    posv = posb[:, None]
    q = apply_rope(q, posv, cfg.rope_theta, rope_fraction)
    k = apply_rope(k, posv, cfg.rope_theta, rope_fraction)
    size = cache["k"].shape[2]
    slot = (posb % size) if window else jnp.minimum(posb, size - 1)

    def upd(c, u, s):  # per-batch-row dynamic update at its own slot
        return jax.lax.dynamic_update_slice(c, u, (0, s, 0))

    knew = jax.vmap(upd)(cache["k"], k.transpose(0, 2, 1, 3), slot)
    vnew = jax.vmap(upd)(cache["v"], v.transpose(0, 2, 1, 3), slot)
    kv_len = jnp.minimum(posb + 1, size)
    qt = q.transpose(0, 2, 1, 3)
    out = ref.attention(
        qt, knew, vnew, causal=False, kv_len=kv_len,
        logit_soft_cap=cfg.logit_soft_cap,
    )
    out = out.transpose(0, 2, 1, 3).reshape(b, 1, h * hd)
    proj = jnp.einsum("bse,ed->bsd", out.astype(x.dtype), params["wo"])
    return proj, {"k": knew, "v": vnew}


# ---------------------------------------------------------------------------
# MLA attention (DeepSeek-V2)
# ---------------------------------------------------------------------------


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """``q_head_dim ** -0.5``, times ``yarn_mscale(factor,
    mscale_all_dim) ** 2`` under YaRN, as DeepSeek-V2 publishes it."""
    m = cfg.mla
    sm = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    y = m.rope_scaling
    if y is not None and y.mscale_all_dim:
        sm *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return sm


def _mla_rope(x, positions, cfg: ModelConfig):
    return apply_rope(x, positions, cfg.rope_theta, yarn=cfg.mla.rope_scaling)


def init_mla(key, cfg: ModelConfig) -> Params:
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    ks = _split(key, 6)
    qd = h * (m.qk_nope_head_dim + m.qk_rope_head_dim)
    p = {
        "w_dkv": _dense_init(ks[0], d, m.kv_lora_rank, cfg.dtype),
        "w_kpe": _dense_init(ks[1], d, m.qk_rope_head_dim, cfg.dtype),
        "w_uk": _dense_init(ks[2], m.kv_lora_rank, h * m.qk_nope_head_dim, cfg.dtype),
        "w_uv": _dense_init(ks[3], m.kv_lora_rank, h * m.v_head_dim, cfg.dtype),
        "w_o": _dense_init(ks[4], h * m.v_head_dim, d, cfg.dtype),
        "w_q": _dense_init(ks[5], d, qd, cfg.dtype),
        "kv_norm": jnp.ones((m.kv_lora_rank,), cfg.dtype),
    }
    return p


def mla_full(params, x, cfg: ModelConfig, positions):
    """Training/prefill MLA: expand the latent into per-head K/V."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    q = jnp.einsum("bsd,de->bse", x, params["w_q"]).reshape(
        b, s, h, m.qk_nope_head_dim + m.qk_rope_head_dim
    )
    q_nope, q_pe = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim :]
    q_pe = _mla_rope(q_pe, positions, cfg)
    c_kv = rmsnorm(jnp.einsum("bsd,de->bse", x, params["w_dkv"]), params["kv_norm"], cfg.norm_eps)
    k_pe = _mla_rope(jnp.einsum("bsd,de->bse", x, params["w_kpe"]), positions, cfg)
    k_nope = jnp.einsum("bsr,re->bse", c_kv, params["w_uk"]).reshape(
        b, s, h, m.qk_nope_head_dim
    )
    v = jnp.einsum("bsr,re->bse", c_kv, params["w_uv"]).reshape(b, s, h, m.v_head_dim)
    k_pe_h = jnp.broadcast_to(k_pe[:, :, None, :], (b, s, h, m.qk_rope_head_dim))
    qfull = jnp.concatenate([q_nope, q_pe], axis=-1).transpose(0, 2, 1, 3)
    kfull = jnp.concatenate([k_nope, k_pe_h], axis=-1).transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    with jax.named_scope("mla.attend"):
        out = ops.attention(
            qfull, kfull, vt, causal=True, sm_scale=mla_softmax_scale(cfg),
            backend=cfg.kernel_backend if cfg.kernel_backend != "auto" else None,
        )
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * m.v_head_dim)
    return jnp.einsum("bse,ed->bsd", out.astype(x.dtype), params["w_o"])


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int):
    _require_fp_cache(cfg, "contiguous latent")
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, max_len, 1, m.kv_lora_rank), cfg.dtype),
        "k_pe": jnp.zeros((batch, max_len, 1, m.qk_rope_head_dim), cfg.dtype),
    }


def init_mla_paged_cache(cfg: ModelConfig, num_blocks: int, page_size: int):
    """Latent page pool for one layer: the latent is shared by every query
    head, so pages carry no head axis.  Each token is one joint row
    ``[latent | rope | 0]`` of ``page_width(rank, rope)`` lanes (640 at
    DeepSeek-V2's widths), the page the paged MLA kernels walk
    (kernels/mla.py) — about ``rank + rope_dim`` values per token instead
    of ``2 * heads * head_dim``: latent paging keeps MLA's KV compression
    through the block pool.

    With ``cfg.kv_dtype`` set the latent and rope pools store packed int8
    plus per-row scale pools, same contract as :func:`init_paged_kv_cache`."""
    m = cfg.mla
    if cfg.kv_dtype is not None:
        pack = ref.KV_PACK[cfg.kv_dtype]
        return {
            "ckv_pages": jnp.zeros(
                (num_blocks, page_size, m.kv_lora_rank // pack), jnp.int8),
            "kpe_pages": jnp.zeros(
                (num_blocks, page_size, m.qk_rope_head_dim // pack), jnp.int8),
            "ckv_scale_pages": jnp.zeros((num_blocks, page_size, 1), cfg.dtype),
            "kpe_scale_pages": jnp.zeros((num_blocks, page_size, 1), cfg.dtype),
        }
    width = page_width(m.kv_lora_rank, m.qk_rope_head_dim)
    return {"latent_pages": jnp.zeros((num_blocks, page_size, width), cfg.dtype)}


def _mla_absorbed_q(params, q_nope, cfg: ModelConfig):
    """Absorb W_uk into the queries: latent-space scoring (Fig. 18)."""
    m = cfg.mla
    w_uk = params["w_uk"].reshape(m.kv_lora_rank, cfg.num_heads, m.qk_nope_head_dim)
    return jnp.einsum(
        "...hn,rhn->...hr", q_nope.astype(jnp.float32), w_uk.astype(jnp.float32)
    )


def _mla_out_proj(params, out_lat, x_dtype, cfg: ModelConfig):
    """Expand latent outputs through W_uv and project with W_o."""
    m = cfg.mla
    w_uv = params["w_uv"].reshape(m.kv_lora_rank, cfg.num_heads, m.v_head_dim)
    out = jnp.einsum(
        "...hr,rhv->...hv", out_lat.astype(jnp.float32), w_uv.astype(jnp.float32)
    )
    out = out.reshape(*out.shape[:-2], cfg.num_heads * m.v_head_dim).astype(x_dtype)
    return jnp.einsum("...e,ed->...d", out, params["w_o"])


def mla_decode(params, x, cfg: ModelConfig, cache, pos, window=None):
    """Latent-cache decode: absorb W_uk into q and attend in latent space —
    the FlashMLA serving path (paper Fig. 18), backed by our MLA kernel."""
    b = x.shape[0]
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    q_nope, q_pe, c_kv, k_pe = _mla_decode_qkv(params, x, cfg, posb[:, None])

    def upd(c, u, s):  # per-row write at its own position
        return jax.lax.dynamic_update_slice(c, u, (s, 0, 0))

    cache_ckv = jax.vmap(upd)(cache["c_kv"], c_kv[:, None, None, :], posb)
    cache_kpe = jax.vmap(upd)(cache["k_pe"], k_pe[:, :, None, :], posb)
    q_lat = _mla_absorbed_q(params, q_nope, cfg)
    # attend over the latent cache (mask positions beyond pos via kv_len)
    with jax.named_scope("mla.attend"):
        out_lat = ref.mla_masked(
            q_lat.astype(cfg.dtype), q_pe.astype(cfg.dtype),
            cache_ckv[:, :, 0], cache_kpe[:, :, 0], pos + 1,
            mla_softmax_scale(cfg), window=window, logit_soft_cap=cfg.logit_soft_cap,
        )
    proj = _mla_out_proj(params, out_lat, x.dtype, cfg)[:, None]
    return proj, {"c_kv": cache_ckv, "k_pe": cache_kpe}


def _mla_decode_qkv(params, x, cfg: ModelConfig, posv):
    """Shared single-token MLA projections: absorbed latent queries, rotated
    rope queries, and the token's latent/rope cache entries."""
    m = cfg.mla
    b = x.shape[0]
    h = cfg.num_heads
    q = jnp.einsum("bsd,de->bse", x, params["w_q"]).reshape(
        b, h, m.qk_nope_head_dim + m.qk_rope_head_dim
    )
    q_nope, q_pe = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim :]
    q_pe = _mla_rope(
        q_pe.reshape(b, 1, h, m.qk_rope_head_dim), posv, cfg
    ).reshape(b, h, m.qk_rope_head_dim)
    c_kv = rmsnorm(
        jnp.einsum("bd,de->be", x[:, 0], params["w_dkv"]), params["kv_norm"], cfg.norm_eps
    )
    k_pe = _mla_rope(
        jnp.einsum("bd,de->be", x[:, 0], params["w_kpe"]).reshape(b, 1, -1), posv, cfg
    )
    return q_nope, q_pe, c_kv, k_pe


def mla_decode_paged(params, x, cfg: ModelConfig, cache, pos, tables,
                     window=None):
    """One-token MLA decode against the **latent page pools** — the paged
    twin of :func:`mla_decode`.  The token's latent/rope entries are
    scattered into the page holding position ``pos`` through the block
    table, then the absorbed queries attend the gathered pages with a
    ragged length mask (ops.mla_paged: the paged MLA tile kernel, or its
    oracle on XLA hosts)."""
    b = x.shape[0]
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    q_nope, q_pe, c_kv, k_pe = _mla_decode_qkv(params, x, cfg, posb[:, None])
    page_size = jax.tree.leaves(cache)[0].shape[1]  # every pool: (pages, page, ·)
    logical = posb // page_size
    offset = posb % page_size
    phys = jnp.take_along_axis(tables, logical[:, None], axis=1)[:, 0]
    q_lat = _mla_absorbed_q(params, q_nope, cfg)
    sm = mla_softmax_scale(cfg)
    backend = cfg.kernel_backend if cfg.kernel_backend != "auto" else None
    if cfg.kv_dtype is not None:
        sdt = cache["ckv_scale_pages"].dtype
        cq, cs = ref.quantize_rows(c_kv, cfg.kv_dtype)
        pq, ps = ref.quantize_rows(k_pe[:, 0], cfg.kv_dtype)
        ckv_pages = cache["ckv_pages"].at[phys, offset].set(cq)
        kpe_pages = cache["kpe_pages"].at[phys, offset].set(pq)
        ckv_scales = cache["ckv_scale_pages"].at[phys, offset].set(cs.astype(sdt))
        kpe_scales = cache["kpe_scale_pages"].at[phys, offset].set(ps.astype(sdt))
        with jax.named_scope("mla.attend"):
            out_lat = ops.mla_paged_quant(
                q_lat.astype(cfg.dtype), q_pe.astype(cfg.dtype), ckv_pages,
                kpe_pages, ckv_scales, kpe_scales, tables, posb + 1,
                fmt=cfg.kv_dtype, sm_scale=sm, window=window,
                logit_soft_cap=cfg.logit_soft_cap, backend=backend,
            )
        proj = _mla_out_proj(params, out_lat, x.dtype, cfg)[:, None]
        return proj, {"ckv_pages": ckv_pages, "kpe_pages": kpe_pages,
                      "ckv_scale_pages": ckv_scales,
                      "kpe_scale_pages": kpe_scales}
    pages = cache["latent_pages"]
    row = ref.mla_rows(c_kv.astype(pages.dtype), k_pe[:, 0], pages.shape[-1])
    pages = pages.at[phys, offset].set(row)
    with jax.named_scope("mla.attend"):
        out_lat = ops.mla_paged(
            q_lat.astype(cfg.dtype), q_pe.astype(cfg.dtype), pages,
            tables, posb + 1, sm_scale=sm, window=window,
            logit_soft_cap=cfg.logit_soft_cap, backend=backend,
        )
    proj = _mla_out_proj(params, out_lat, x.dtype, cfg)[:, None]
    return proj, {"latent_pages": pages}


def _mla_prefill_qkv(params, x, cfg: ModelConfig, posmat):
    """Shared chunk-wide MLA projections for the prefill paths."""
    m = cfg.mla
    b, c, _ = x.shape
    h = cfg.num_heads
    q = jnp.einsum("bsd,de->bse", x, params["w_q"]).reshape(
        b, c, h, m.qk_nope_head_dim + m.qk_rope_head_dim
    )
    q_nope, q_pe = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim :]
    q_pe = _mla_rope(q_pe, posmat, cfg)
    c_kv = rmsnorm(
        jnp.einsum("bsd,de->bse", x, params["w_dkv"]), params["kv_norm"], cfg.norm_eps
    )
    k_pe = _mla_rope(jnp.einsum("bsd,de->bse", x, params["w_kpe"]), posmat, cfg)
    q_lat = _mla_absorbed_q(params, q_nope, cfg)  # (b, c, h, r)
    sm = mla_softmax_scale(cfg)
    # (b, h, c, ·) for the kernels/oracles
    return (q_lat.transpose(0, 2, 1, 3), q_pe.transpose(0, 2, 1, 3),
            c_kv, k_pe, sm)


def mla_prefill_paged(params, x, cfg: ModelConfig, cache, pos, tables, lens,
                      window=None):
    """Chunk-wide MLA prefill against the latent page pools.  Same contract
    as :func:`attention_prefill_paged` — the chunk's latents land in the
    pages holding positions [pos, pos+lens) through the block table (inside
    the tile kernel on the Pallas path; a masked scatter on XLA), and every
    chunk query attends prior pages plus the chunk causally, all in latent
    space."""
    b, c, _ = x.shape
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    posmat = posb[:, None] + jnp.arange(c, dtype=jnp.int32)
    q_lat, q_pe, c_kv, k_pe, sm = _mla_prefill_qkv(params, x, cfg, posmat)
    backend = cfg.kernel_backend if cfg.kernel_backend != "auto" else None
    if cfg.kv_dtype is not None:
        with jax.named_scope("mla.attend"):
            out_lat, ckv_pages, kpe_pages, ckv_scales, kpe_scales = (
                ops.mla_prefill_quant(
                    q_lat.astype(cfg.dtype), q_pe.astype(cfg.dtype), c_kv, k_pe,
                    cache["ckv_pages"], cache["kpe_pages"],
                    cache["ckv_scale_pages"], cache["kpe_scale_pages"],
                    tables, posb, jnp.asarray(lens, jnp.int32), fmt=cfg.kv_dtype,
                    sm_scale=sm, window=window,
                    logit_soft_cap=cfg.logit_soft_cap, backend=backend,
                )
            )
        proj = _mla_out_proj(params, out_lat.transpose(0, 2, 1, 3), x.dtype, cfg)
        return proj, {"ckv_pages": ckv_pages, "kpe_pages": kpe_pages,
                      "ckv_scale_pages": ckv_scales,
                      "kpe_scale_pages": kpe_scales}
    with jax.named_scope("mla.attend"):
        out_lat, pages = ops.mla_prefill(
            q_lat.astype(cfg.dtype), q_pe.astype(cfg.dtype), c_kv, k_pe,
            cache["latent_pages"], tables, posb,
            jnp.asarray(lens, jnp.int32), sm_scale=sm, window=window,
            logit_soft_cap=cfg.logit_soft_cap, backend=backend,
        )
    proj = _mla_out_proj(params, out_lat.transpose(0, 2, 1, 3), x.dtype, cfg)
    return proj, {"latent_pages": pages}


def mla_prefill(params, x, cfg: ModelConfig, cache, pos, lens, window=None):
    """Chunk-wide MLA prefill against the contiguous latent strips — the
    latent twin of :func:`attention_prefill`.  The strip stays full-length
    (no ring variant): a sliding ``window`` only masks scores.  Prior
    context comes from the per-slot strip; the chunk is written back as a
    gather-select (no scatter)."""
    b, c, _ = x.shape
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    lens = jnp.asarray(lens, jnp.int32)
    posmat = posb[:, None] + jnp.arange(c, dtype=jnp.int32)
    q_lat, q_pe, c_kv, k_pe, sm = _mla_prefill_qkv(params, x, cfg, posmat)
    size = cache["c_kv"].shape[1]
    r = jnp.arange(size, dtype=jnp.int32)[None, :]  # (1, S)
    ctx_pos = jnp.where(r < posb[:, None], r, -1)
    with jax.named_scope("mla.attend"):
        out_lat = ref.mla_prefill(
            q_lat.astype(cfg.dtype), q_pe.astype(cfg.dtype), c_kv, k_pe,
            cache["c_kv"][:, :, 0], cache["k_pe"][:, :, 0], ctx_pos, posmat,
            lens, sm_scale=sm, window=window, logit_soft_cap=cfg.logit_soft_cap,
        )
    proj = _mla_out_proj(params, out_lat.transpose(0, 2, 1, 3), x.dtype, cfg)
    # write the chunk into the strip as a gather-select over cache entries
    rel = r - posb[:, None]  # (B, S)
    live = (rel >= 0) & (rel < lens[:, None])
    cg = jnp.clip(rel, 0, c - 1)[:, :, None]  # (B, S, 1)
    cdt = cache["c_kv"].dtype
    sel = live[:, :, None, None]
    ckv_new = jnp.where(
        sel,
        jnp.take_along_axis(c_kv.astype(cdt), cg, axis=1)[:, :, None, :],
        cache["c_kv"],
    )
    kpe_new = jnp.where(
        sel,
        jnp.take_along_axis(k_pe.astype(cdt), cg, axis=1)[:, :, None, :],
        cache["k_pe"],
    )
    return proj, {"c_kv": ckv_new, "k_pe": kpe_new}


# ---------------------------------------------------------------------------
# MLP (dense)
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, d_ff=None) -> Params:
    d_ff = d_ff or cfg.d_ff
    ks = _split(key, 3)
    if cfg.act in ("silu", "geglu"):
        return {
            "w_gate": _dense_init(ks[0], cfg.d_model, d_ff, cfg.dtype),
            "w_up": _dense_init(ks[1], cfg.d_model, d_ff, cfg.dtype),
            "w_down": _dense_init(ks[2], d_ff, cfg.d_model, cfg.dtype),
        }
    return {
        "w_up": _dense_init(ks[0], cfg.d_model, d_ff, cfg.dtype),
        "w_down": _dense_init(ks[1], d_ff, cfg.d_model, cfg.dtype),
    }


def mlp(params: Params, x, cfg: ModelConfig):
    if "w_gate" in params:
        g = jnp.einsum("...d,df->...f", x, params["w_gate"])
        u = jnp.einsum("...d,df->...f", x, params["w_up"])
        act = jax.nn.gelu(g) if cfg.act == "geglu" else jax.nn.silu(g)
        h = act * u
    else:
        h = jax.nn.gelu(jnp.einsum("...d,df->...f", x, params["w_up"]))
    return jnp.einsum("...f,fd->...d", h, params["w_down"])


# ---------------------------------------------------------------------------
# MoE: dropless top-k routing over the held experts
# ---------------------------------------------------------------------------


def init_moe(key, cfg: ModelConfig) -> Params:
    """The router scores every routed expert; expert weights exist only for
    the ``held`` experts (``MoEConfig``)."""
    mo = cfg.moe
    d, fe, e = cfg.d_model, mo.d_ff_expert, mo.held
    ks = _split(key, 5)
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": _dense_init(ks[0], d, mo.num_experts, "float32"),
        "w_gate": (jax.random.normal(ks[1], (e, d, fe), jnp.float32) * scale).astype(cfg.dtype),
        "w_up": (jax.random.normal(ks[2], (e, d, fe), jnp.float32) * scale).astype(cfg.dtype),
        "w_down": (jax.random.normal(ks[3], (e, fe, d), jnp.float32) / math.sqrt(fe)).astype(cfg.dtype),
    }
    if mo.num_shared_experts:
        p["shared"] = init_mlp(ks[4], cfg, d_ff=mo.num_shared_experts * fe)
    return p


def moe_layers(cfg: ModelConfig) -> int:
    """How many layers of ``cfg`` route through :func:`moe`."""
    mo = cfg.moe
    if mo is None or not mo.num_experts:
        return 0
    return cfg.num_layers - mo.first_dense_layers


def moe_rows(cfg: ModelConfig, tokens: int) -> int:
    """Expert FFN rows one :func:`moe` layer computes for ``tokens`` token
    rows: every held expert runs every row (dense compute)."""
    return tokens * cfg.moe.held


def moe(params: Params, x, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns ``(output, aux_loss, routed)``.

    Dropless top-k routing: the router's softmax over all ``num_experts``
    picks each token's ``experts_per_token`` (gates renormalized only with
    ``norm_topk_prob``), and the layer adds, for every token, the gated
    output of each held expert it picked, plus the shared experts.  Every
    held expert runs every token (einsums over the held experts, gate 0
    where unpicked), so no token is ever dropped and a token's output does
    not depend on its batch-mates.  What experts held elsewhere add is left
    out: this is one device's part of an expert-parallel layer.
    ``routed`` (B, S) int32 counts each token's picks among the held
    experts; ``aux_loss`` is the Switch-style load-balance loss over all
    experts."""
    mo = cfg.moe
    b, s, d = x.shape
    t = b * s
    e, k = mo.num_experts, mo.experts_per_token
    xt = x.reshape(t, d)
    with jax.named_scope("moe.router"):
        logits = jnp.einsum(
            "td,de->te", xt.astype(jnp.float32), params["router"].astype(jnp.float32)
        )
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, k)  # (t, k)
        if mo.norm_topk_prob:
            gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
        held = mo.first_held_expert + jnp.arange(mo.held, dtype=gate_idx.dtype)
        picked = gate_idx[:, :, None] == held  # (t, k, held)
        gates = jnp.sum(jnp.where(picked, gate_vals[:, :, None], 0.0), axis=1)
        routed = jnp.sum(picked, axis=(1, 2), dtype=jnp.int32)
    with jax.named_scope("moe.experts"):
        g_ = jnp.einsum("td,edf->etf", xt, params["w_gate"])
        u = jnp.einsum("td,edf->etf", xt, params["w_up"])
        h = jax.nn.silu(g_) * u * gates.T[:, :, None].astype(x.dtype)
        out = jnp.einsum("etf,efd->td", _hint("moe_expert", h), params["w_down"])
    if "shared" in params:
        out = out + mlp(params["shared"], xt, cfg)
    # load-balance auxiliary loss (Switch-style)
    density = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], e, dtype=jnp.float32), axis=0)
    density_prob = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_prob) * e * mo.router_aux_weight
    return out.reshape(b, s, d).astype(x.dtype), aux, routed.reshape(b, s)


# ---------------------------------------------------------------------------
# Mamba-2 (SSD) layer
# ---------------------------------------------------------------------------


def init_mamba2(key, cfg: ModelConfig) -> Params:
    # separate projections (not one fused in_proj) so each shards cleanly:
    # z/x column-parallel over d_inner, B/C/dt small (replicated or sharded)
    sm = cfg.ssm
    d = cfg.d_model
    di = sm.d_inner(d)
    nh = sm.num_heads(d)
    conv_dim = di + 2 * sm.state_dim
    ks = _split(key, 7)
    return {
        "w_z": _dense_init(ks[0], d, di, cfg.dtype),
        "w_x": _dense_init(ks[1], d, di, cfg.dtype),
        "w_B": _dense_init(ks[2], d, sm.state_dim, cfg.dtype),
        "w_C": _dense_init(ks[3], d, sm.state_dim, cfg.dtype),
        "w_dt": _dense_init(ks[4], d, nh, cfg.dtype),
        "conv_w": (jax.random.normal(ks[5], (sm.conv_width, conv_dim), jnp.float32) * 0.1).astype(cfg.dtype),
        "conv_b": jnp.zeros((conv_dim,), cfg.dtype),
        "a_log": jnp.zeros((nh,), jnp.float32),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "norm_w": jnp.ones((di,), cfg.dtype),
        "out_proj": _dense_init(ks[6], di, d, cfg.dtype),
    }


def _mamba_proj(params, x):
    z = jnp.einsum("...d,de->...e", x, params["w_z"])
    xin = jnp.einsum("...d,de->...e", x, params["w_x"])
    B = jnp.einsum("...d,de->...e", x, params["w_B"])
    C = jnp.einsum("...d,de->...e", x, params["w_C"])
    dt = jnp.einsum("...d,de->...e", x, params["w_dt"])
    return z, xin, B, C, dt


def _causal_conv(x, w, b):
    """x: (B, S, C); depthwise causal conv, width W."""
    width = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(
        pad[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(width)
    )
    return jax.nn.silu(out + b)


def mamba2_full(params: Params, x, cfg: ModelConfig):
    sm = cfg.ssm
    b, s, d = x.shape
    di = sm.d_inner(d)
    nh = sm.num_heads(d)
    z, xin, B, C, dt = _mamba_proj(params, x)
    conv_in = jnp.concatenate([xin, B, C], axis=-1)
    conv_out = _causal_conv(conv_in, params["conv_w"], params["conv_b"])
    xin, B, C = jnp.split(conv_out, [di, di + sm.state_dim], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (b,s,nh)
    # fold heads into batch for the SSD kernels
    xh = xin.reshape(b, s, nh, sm.head_dim).transpose(0, 2, 1, 3).reshape(b * nh, s, sm.head_dim)
    Bh = jnp.broadcast_to(B[:, None], (b, nh, s, sm.state_dim)).reshape(
        b * nh, s, sm.state_dim
    )
    Ch = jnp.broadcast_to(C[:, None], (b, nh, s, sm.state_dim)).reshape(
        b * nh, s, sm.state_dim
    )
    dth = dt.transpose(0, 2, 1).reshape(b * nh, s)
    a_log = jnp.broadcast_to(params["a_log"][None], (b, nh)).reshape(b * nh)
    chunk = min(sm.chunk, s)
    if s % chunk:
        chunk = math.gcd(s, chunk) or 1
    y = _ssd_batched(Ch, Bh, xh * dth[..., None].astype(xh.dtype), dth, a_log, chunk, cfg)
    y = y.reshape(b, nh, s, sm.head_dim)
    y = y + params["d_skip"][None, :, None, None] * xh.reshape(b, nh, s, sm.head_dim)
    y = y.transpose(0, 2, 1, 3).reshape(b, s, di)
    y = rmsnorm(y * jax.nn.silu(z), params["norm_w"], cfg.norm_eps)
    return jnp.einsum("bse,ed->bsd", y.astype(x.dtype), params["out_proj"])


def _ssd_batched(c, bm, x, dt, a_log, chunk, cfg: ModelConfig):
    """SSD with per-batch a_log (heads folded into batch)."""
    be = cfg.kernel_backend if cfg.kernel_backend != "auto" else None
    bsz, s, n = c.shape
    p = x.shape[-1]
    nc = s // chunk
    rs = lambda t: t.reshape(bsz, nc, chunk, *t.shape[2:])
    da = dt * (-jnp.exp(a_log))[:, None]
    da_cum = jnp.cumsum(da.reshape(bsz, nc, chunk), axis=-1)
    states = ops.chunk_state(rs(bm), rs(x), da_cum, backend=be)
    incoming = ref.state_recurrence(states, da_cum[..., -1])
    y = ops.chunk_scan(rs(c), rs(bm), rs(x), da_cum, incoming, backend=be)
    return y.reshape(bsz, s, p).astype(x.dtype)


def init_mamba2_cache(cfg: ModelConfig, batch: int):
    sm = cfg.ssm
    d = cfg.d_model
    nh = sm.num_heads(d)
    conv_dim = sm.d_inner(d) + 2 * sm.state_dim
    return {
        "ssm": jnp.zeros((batch, nh, sm.state_dim, sm.head_dim), jnp.float32),
        "conv": jnp.zeros((batch, sm.conv_width - 1, conv_dim), cfg.dtype),
    }


def mamba2_decode(params: Params, x, cfg: ModelConfig, cache):
    """Single-token SSM recurrence: h = exp(dt*A) h + dt * B^T x ; y = C h."""
    sm = cfg.ssm
    b, _, d = x.shape
    di = sm.d_inner(d)
    nh = sm.num_heads(d)
    z, xin, B, C, dt = (p[:, 0] for p in _mamba_proj(params, x))
    conv_in = jnp.concatenate([xin, B, C], axis=-1)  # (b, conv_dim)
    window = jnp.concatenate([cache["conv"], conv_in[:, None]], axis=1)
    w = params["conv_w"]
    conv_out = jax.nn.silu(
        jnp.sum(window * w[None], axis=1) + params["conv_b"]
    )
    xin, B, C = jnp.split(conv_out, [di, di + sm.state_dim], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (b, nh)
    xh = xin.reshape(b, nh, sm.head_dim).astype(jnp.float32)
    decay = jnp.exp(dt * (-jnp.exp(params["a_log"]))[None])  # (b, nh)
    upd = jnp.einsum("bn,bhp->bhnp", B.astype(jnp.float32), xh * dt[..., None])
    h = cache["ssm"] * decay[..., None, None] + upd
    y = jnp.einsum("bn,bhnp->bhp", C.astype(jnp.float32), h)
    y = y + params["d_skip"][None, :, None] * xh
    y = y.reshape(b, 1, di)
    y = rmsnorm(y * jax.nn.silu(z[:, None]).astype(jnp.float32), params["norm_w"], cfg.norm_eps)
    out = jnp.einsum("bse,ed->bsd", y.astype(x.dtype), params["out_proj"])
    return out, {"ssm": h, "conv": window[:, 1:]}
