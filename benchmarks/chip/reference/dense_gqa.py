"""Plain reference of a dense decoder with grouped-query attention.

Covers the Llama / Qwen2 block as published: RMSNorm before attention and
before the SwiGLU MLP, rotary position embedding in the Hugging Face
``rotate_half`` form, grouped-query attention with optional Q/K/V bias,
causal softmax, a final RMSNorm and a tied or untied output head.  It
imports nothing of the program under test: it makes its own weights from
the seed (``make_weights``, also the source of the program's weights) and
computes everything in float32 at ``highest`` matmul precision.

``precision`` selects how the operands of every linear layer are rounded
before the float32 product: ``"float32"`` (the reference itself),
``"bfloat16"`` (a witness at the served precision), and the two steps
below bfloat16 that a faster serving path would take: ``"int8"``
(symmetric int8 weights per output channel and int8 activations per token)
and ``"fp8"`` (float8 e4m3 with the same scaling).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "bfloat16", "int8", "fp8")
QUERY_BLOCK = 512  # query rows per attention block: bounds the score tensor


class Dims(NamedTuple):
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    tied: bool
    rope_theta: float
    eps: float


def dims(cfg: dict) -> Dims:
    """The sizes of a configuration file (Hugging Face ``config.json`` keys)."""
    heads = cfg["num_attention_heads"]
    return Dims(
        layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        heads=heads,
        kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim", cfg["hidden_size"] // heads),
        d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"],
        qkv_bias=bool(cfg["attention_bias"]),
        tied=bool(cfg["tie_word_embeddings"]),
        rope_theta=float(cfg["rope_theta"]),
        eps=float(cfg["rms_norm_eps"]),
    )


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, also one past 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def weight_shapes(d: Dims) -> Dict[str, tuple]:
    """Canonical layout: matrices are (in, out), layers stacked in front;
    Q and K columns per head in ``rotate_half`` order."""
    L, dm, q, kv = d.layers, d.d_model, d.heads * d.head_dim, d.kv_heads * d.head_dim
    shapes = {
        "embed": (d.vocab, dm),
        "attn_norm": (L, dm),
        "wq": (L, dm, q),
        "wk": (L, dm, kv),
        "wv": (L, dm, kv),
        "wo": (L, q, dm),
        "mlp_norm": (L, dm),
        "w_gate": (L, dm, d.d_ff),
        "w_up": (L, dm, d.d_ff),
        "w_down": (L, d.d_ff, dm),
        "final_norm": (dm,),
    }
    if d.qkv_bias:
        shapes.update(bq=(L, q), bk=(L, kv), bv=(L, kv))
    if not d.tied:
        shapes["lm_head"] = (d.vocab, dm)
    return shapes


def _init_scale(name: str, shape: tuple) -> float:
    """Standard deviations that keep every layer's output near unit scale.
    The embedding and the head are scaled by 1 / sqrt(d_model): with a tied
    head, a larger embedding would make each token's own logit dominate,
    and the model would only repeat its input."""
    if name in ("embed", "lm_head"):
        return 1.0 / math.sqrt(shape[-1])
    if name.startswith("b"):
        return 0.1
    return 1.0 / math.sqrt(shape[-2])


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, d: Dims):
    out = {}
    for i, (name, shape) in enumerate(sorted(weight_shapes(d).items())):
        k = jax.random.fold_in(key, i)
        w = jax.random.normal(k, shape, jnp.bfloat16)
        if name.endswith("norm"):
            w = 1 + w * jnp.bfloat16(0.1)  # norm gains near 1, not all 1
        else:
            w = w * jnp.bfloat16(_init_scale(name, shape))
        out[name] = w
    return out


def make_weights(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """Every weight, bfloat16, on the default device, in one jitted call."""
    return _make(seed_key(seed), dims(cfg))


# ---- the forward pass ------------------------------------------------------


def _round(x, precision: str, axis: int):
    """The operand of a linear layer as the given precision stores it."""
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    top = {"int8": 127.0, "fp8": 448.0}[precision]
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    if precision == "int8":
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _linear(x, w, precision: str):
    """x (S, in) @ w (in, out): activations rounded per row, weights per
    output column."""
    x = _round(x, precision, axis=-1)
    w = _round(w.astype(jnp.float32), precision, axis=0)
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g.astype(jnp.float32)


def _rope(x, theta: float):
    """Hugging Face ``rotate_half`` rotary embedding; x (S, H, D)."""
    s, _, dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2 :]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def _attention(q, k, v):
    """Causal softmax attention; q (S, H, D), k/v (S, Hkv, D)."""
    s, h, dh = q.shape
    group = h // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scale = 1.0 / math.sqrt(dh)
    blocks = []
    for start in range(0, s, QUERY_BLOCK):
        qb = q[start : start + QUERY_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=jax.lax.Precision.HIGHEST) * scale
        rows = start + jnp.arange(qb.shape[0])[:, None]
        sc = jnp.where(jnp.arange(s)[None, :] <= rows, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        blocks.append(jnp.einsum("hqk,khd->qhd", p, v, precision=jax.lax.Precision.HIGHEST))
    return jnp.concatenate(blocks, axis=0)


@functools.partial(jax.jit, static_argnums=(2, 3))
def hidden(weights, tokens, d: Dims, precision: str):
    """Final normed hidden states (S, d_model), float32, of one sequence."""
    s = tokens.shape[0]
    x = weights["embed"][tokens].astype(jnp.float32)

    def block(x, w):
        h = _rms(x, w["attn_norm"], d.eps)
        q = _linear(h, w["wq"], precision)
        k = _linear(h, w["wk"], precision)
        v = _linear(h, w["wv"], precision)
        if d.qkv_bias:
            q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
        q = _rope(q.reshape(s, d.heads, d.head_dim), d.rope_theta)
        k = _rope(k.reshape(s, d.kv_heads, d.head_dim), d.rope_theta)
        v = v.reshape(s, d.kv_heads, d.head_dim)
        a = _attention(q, k, v).reshape(s, d.heads * d.head_dim)
        x = x + _linear(a, w["wo"], precision)
        h = _rms(x, w["mlp_norm"], d.eps)
        g = _linear(h, w["w_gate"], precision)
        u = _linear(h, w["w_up"], precision)
        x = x + _linear(jax.nn.silu(g) * u, w["w_down"], precision)
        return x, None

    stacked = {k: w for k, w in weights.items() if k not in ("embed", "lm_head", "final_norm")}
    x, _ = jax.lax.scan(block, x, stacked)
    return _rms(x, weights["final_norm"], d.eps)


LOGIT_BLOCK = 512  # rows of logits per block: bounds the (rows, vocab) tensor


@functools.partial(jax.jit, static_argnums=(4,))
def _gap_block(head, ref_h, tokens, other_h, precision):
    ref_logits = jnp.dot(ref_h, head.astype(jnp.float32).T, precision=jax.lax.Precision.HIGHEST)
    best = jnp.max(ref_logits, axis=-1)
    served = jnp.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    other = _linear(other_h, head.T, precision)
    pick = jnp.argmax(other, axis=-1)
    picked = jnp.take_along_axis(ref_logits, pick[:, None], axis=-1)[:, 0]
    return best - served, best - picked


def gaps(weights, d: Dims, ref_h, tokens, other_h=None, precision: str = "float32"):
    """Per row, by how much the reference's logit of ``tokens`` lies below
    the reference's best, and (with ``other_h``, hidden states computed at
    ``precision``) by how much the reference's logit of the token that
    ``other_h`` puts first lies below it.  Rows of ``ref_h``/``other_h``
    are the positions whose next token is scored."""
    head = weights["embed"] if d.tied else weights["lm_head"]
    if other_h is None:
        other_h = ref_h
    served, picked = [], []
    n = ref_h.shape[0]
    for start in range(0, n, LOGIT_BLOCK):
        stop = min(n, start + LOGIT_BLOCK)
        pad = LOGIT_BLOCK - (stop - start)
        sl = slice(start, stop)
        rh = jnp.pad(ref_h[sl], ((0, pad), (0, 0)))
        oh = jnp.pad(other_h[sl], ((0, pad), (0, 0)))
        tk = jnp.pad(tokens[sl], (0, pad))
        a, b = _gap_block(head, rh, tk, oh, precision)
        served.append(a[: stop - start])
        picked.append(b[: stop - start])
    return jnp.concatenate(served), jnp.concatenate(picked)
