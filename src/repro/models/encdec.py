"""Encoder-decoder transformer (whisper-tiny family, paper-assigned audio arch).

The audio conv frontend is a STUB per the assignment: the encoder consumes
precomputed frame embeddings (B, T_frames, d_model).  Learned absolute
positions, GELU MLPs, causal decoder with cross-attention; decode uses a
self-attention KV cache plus per-layer precomputed cross K/V.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref

from . import layers as L
from .config import ModelConfig

MAX_FRAMES = 1500  # whisper-tiny encoder positions (30 s of audio)


def _attn_proj(params, x, heads, kv_heads, head_dim):
    b, s, _ = x.shape
    q = jnp.einsum("bsd,de->bse", x, params["wq"]).reshape(b, s, heads, head_dim)
    k = jnp.einsum("bsd,de->bse", x, params["wk"]).reshape(b, s, kv_heads, head_dim)
    v = jnp.einsum("bsd,de->bse", x, params["wv"]).reshape(b, s, kv_heads, head_dim)
    return q, k, v


def init(cfg: ModelConfig, key) -> Dict:
    ks = L._split(key, 6 + cfg.encoder_layers + cfg.num_layers)
    d = cfg.d_model
    # whisper's own decoder caps at 448 positions; the assigned decode_32k /
    # long-context cells need 32k, so the table is sized to the largest cell.
    max_dec_pos = 32768 if cfg.vocab_size > 10000 else 2048
    params: Dict[str, Any] = {
        "embed": {"embedding": L._dense_init(ks[0], cfg.vocab_size, d, cfg.dtype, 1.0)},
        "enc_pos": L._dense_init(ks[1], MAX_FRAMES, d, cfg.dtype, 0.02),
        "dec_pos": L._dense_init(ks[2], max_dec_pos, d, cfg.dtype, 0.02),
        "enc_final_norm": jnp.ones((d,), cfg.dtype),
        "final_norm": jnp.ones((d,), cfg.dtype),
    }

    def enc_layer(k):
        k1, k2 = jax.random.split(k)
        return {
            "norm1": jnp.ones((d,), cfg.dtype),
            "attn": L.init_attention(k1, cfg),
            "norm2": jnp.ones((d,), cfg.dtype),
            "mlp": L.init_mlp(k2, cfg),
        }

    def dec_layer(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "norm1": jnp.ones((d,), cfg.dtype),
            "attn": L.init_attention(k1, cfg),
            "norm_x": jnp.ones((d,), cfg.dtype),
            "xattn": L.init_attention(k2, cfg),
            "norm2": jnp.ones((d,), cfg.dtype),
            "mlp": L.init_mlp(k3, cfg),
        }

    enc = [enc_layer(ks[3 + i]) for i in range(cfg.encoder_layers)]
    dec = [dec_layer(ks[3 + cfg.encoder_layers + i]) for i in range(cfg.num_layers)]
    params["enc_layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *enc)
    params["dec_layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *dec)
    return params


def _self_attn(p, x, cfg, causal, kv_len=None, cache=None, pos=None):
    b, s, _ = x.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q, k, v = _attn_proj(p, x, h, hkv, hd)
    if cache is not None:
        knew = jax.lax.dynamic_update_slice(
            cache["k"], k.transpose(0, 2, 1, 3), (0, 0, pos, 0)
        )
        vnew = jax.lax.dynamic_update_slice(
            cache["v"], v.transpose(0, 2, 1, 3), (0, 0, pos, 0)
        )
        out = ref.attention(
            q.transpose(0, 2, 1, 3), knew, vnew, causal=False,
            kv_len=jnp.full((b,), pos + 1, jnp.int32),
        )
        out = out.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
        return jnp.einsum("bse,ed->bsd", out.astype(x.dtype), p["wo"]), {
            "k": knew, "v": vnew,
        }
    out = ops.attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=causal,
        backend=L.kernel_backend(cfg),
    )
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
    return jnp.einsum("bse,ed->bsd", out.astype(x.dtype), p["wo"]), None


def _cross_attn(p, x, enc_kv, cfg):
    """enc_kv: precomputed (k, v) each (B, H, T, hd)."""
    b, s, _ = x.shape
    h, hd = cfg.num_heads, cfg.head_dim
    q = jnp.einsum("bsd,de->bse", x, p["wq"]).reshape(b, s, h, hd)
    out = ref.attention(q.transpose(0, 2, 1, 3), enc_kv[0], enc_kv[1], causal=False)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
    return jnp.einsum("bse,ed->bsd", out.astype(x.dtype), p["wo"])


def encode(params, cfg: ModelConfig, frames, unroll: int = 1):
    """frames: (B, T, d_model) precomputed embeddings (conv frontend stub)."""
    t = frames.shape[1]
    x = frames.astype(cfg.dtype) + params["enc_pos"][None, :t]

    def body(x, p):
        h = L.rmsnorm(x, p["norm1"], cfg.norm_eps)
        a, _ = _self_attn(p["attn"], h, cfg, causal=False)
        x = x + a
        h2 = L.rmsnorm(x, p["norm2"], cfg.norm_eps)
        return x + L.mlp(p["mlp"], h2, cfg), None

    x, _ = jax.lax.scan(body, x, params["enc_layers"], unroll=unroll)
    return L.rmsnorm(x, params["enc_final_norm"], cfg.norm_eps)


def cross_kv(params, cfg: ModelConfig, enc_out):
    """Precompute per-decoder-layer cross K/V from the encoder output."""
    b, t, _ = enc_out.shape
    h, hd = cfg.num_heads, cfg.head_dim

    def one(p):
        k = jnp.einsum("btd,de->bte", enc_out, p["xattn"]["wk"]).reshape(
            b, t, cfg.num_kv_heads, hd
        )
        v = jnp.einsum("btd,de->bte", enc_out, p["xattn"]["wv"]).reshape(
            b, t, cfg.num_kv_heads, hd
        )
        return k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)

    return jax.lax.map(one, params["dec_layers"])


def decode_hidden(params, cfg: ModelConfig, tokens, enc_out, unroll: int = 1,
                  remat: bool = False):
    """Teacher-forced decoder pass -> final hidden (B, S, d)."""
    b, s = tokens.shape
    x = L.embed(params["embed"], tokens).astype(cfg.dtype) + params["dec_pos"][None, :s]
    ckv = cross_kv(params, cfg, enc_out)

    def body(x, inp):
        p, (ck, cv) = inp
        h = L.rmsnorm(x, p["norm1"], cfg.norm_eps)
        a, _ = _self_attn(p["attn"], h, cfg, causal=True)
        x = x + a
        hx = L.rmsnorm(x, p["norm_x"], cfg.norm_eps)
        x = x + _cross_attn(p["xattn"], hx, (ck, cv), cfg)
        h2 = L.rmsnorm(x, p["norm2"], cfg.norm_eps)
        return x + L.mlp(p["mlp"], h2, cfg), None

    body_fn = jax.checkpoint(body) if remat else body
    x, _ = jax.lax.scan(body_fn, x, (params["dec_layers"], ckv), unroll=unroll)
    return L.rmsnorm(x, params["final_norm"], cfg.norm_eps)


def decode_full(params, cfg: ModelConfig, tokens, enc_out, unroll: int = 1):
    """Teacher-forced decoder pass -> logits (B, S, V)."""
    x = decode_hidden(params, cfg, tokens, enc_out, unroll)
    return L.unembed(params["embed"], x, cfg)


def loss_fn(params, cfg: ModelConfig, frames, tokens, labels, unroll: int = 1,
            remat: bool = False, logits_chunk: int = 0):
    enc = encode(params, cfg, frames, unroll)
    x = decode_hidden(params, cfg, tokens, enc, unroll, remat)
    s = x.shape[1]

    def ce_of(xc, lc):
        logits = L.unembed(params["embed"], xc, cfg)
        logp = jax.nn.log_softmax(logits, axis=-1)
        mask = lc >= 0
        safe = jnp.where(mask, lc, 0)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * mask), jnp.sum(mask).astype(jnp.float32)

    if logits_chunk and s % logits_chunk == 0 and s > logits_chunk:
        nchunks = s // logits_chunk
        xc = x.reshape(x.shape[0], nchunks, logits_chunk, -1).swapaxes(0, 1)
        lc = labels.reshape(labels.shape[0], nchunks, logits_chunk).swapaxes(0, 1)

        @jax.checkpoint
        def chunk(carry, inp):
            n, c = ce_of(*inp)
            return (carry[0] + n, carry[1] + c), None

        (nll, cnt), _ = jax.lax.scan(
            chunk, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (xc, lc),
        )
    else:
        nll, cnt = ce_of(x, labels)
    ce = nll / jnp.maximum(cnt, 1)
    return ce, {"ce": ce}


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Stacked self-attention caches for every decoder layer (cross K/V is
    precomputed separately by `cross_kv` and passed to decode_step)."""
    kv = {
        "k": jnp.zeros((cfg.num_layers, batch, cfg.num_kv_heads, max_len, cfg.head_dim), cfg.dtype),
        "v": jnp.zeros((cfg.num_layers, batch, cfg.num_kv_heads, max_len, cfg.head_dim), cfg.dtype),
    }
    return {"self": kv}


def decode_step(params, cfg: ModelConfig, cache, token, pos, cross, unroll: int = 1):
    """cache: {"self": stacked per-layer kv}; cross: precomputed cross_kv."""
    b = token.shape[0]
    x = L.embed(params["embed"], token[:, None]).astype(cfg.dtype)
    x = x + jax.lax.dynamic_slice_in_dim(params["dec_pos"], pos, 1, axis=0)[None]

    def body(x, inp):
        p, kv, (ck, cv) = inp
        h = L.rmsnorm(x, p["norm1"], cfg.norm_eps)
        a, kv_new = _self_attn(p["attn"], h, cfg, causal=False, cache=kv, pos=pos)
        x = x + a
        hx = L.rmsnorm(x, p["norm_x"], cfg.norm_eps)
        x = x + _cross_attn(p["xattn"], hx, (ck, cv), cfg)
        h2 = L.rmsnorm(x, p["norm2"], cfg.norm_eps)
        return x + L.mlp(p["mlp"], h2, cfg), kv_new

    kvs = {"k": cache["self"]["k"], "v": cache["self"]["v"]}
    x, kv_new = jax.lax.scan(
        body, x, (params["dec_layers"], kvs, cross), unroll=unroll
    )
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(params["embed"], x, cfg)[:, 0]
    return logits, {"self": kv_new}
