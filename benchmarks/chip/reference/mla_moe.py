"""Plain reference of a DeepSeek-V2 decoder: multi-head latent attention
and a mixture of experts with shared experts.

Follows the published ``modeling_deepseek.py`` of DeepSeek-V2-Lite
(arXiv:2405.04434) without its serving shortcuts: RMSNorm before attention
and before the FFN; attention with a full-rank query projection split into
a no-rope and a rope part per head, the joint ``kv_a_proj_with_mqa`` to a
512-wide latent plus one shared 64-wide rope key, ``kv_a_layernorm`` on the
latent and ``kv_b_proj`` up to per-head keys and values (un-absorbed);
YaRN rotary embedding (``DeepseekV2YarnRotaryEmbedding``) applied in the
published de-interleaved form; softmax scale ``q_head_dim ** -0.5`` times
``yarn_get_mscale(factor, mscale_all_dim) ** 2``; the first
``first_k_dense_replace`` layers a SwiGLU FFN of ``intermediate_size``,
every later layer a softmax router over all routed experts, greedy top-k,
gates renormalized only with ``norm_topk_prob``, scaled by
``routed_scaling_factor``, plus the shared experts as one SwiGLU FFN of
``n_shared_experts * moe_intermediate_size``; a final RMSNorm and an untied
head.  Everything in float32 at ``highest`` matmul precision.  It imports
nothing of the program under test and makes its own weights from the seed
(``make_weights``, also the source of the program's weights).

Departures from the published code:

* Expert parallelism.  The configuration's ``n_routed_experts`` counts the
  experts this chip holds, from ``first_held_expert`` on; the router scores
  the published count (``reduced.n_routed_experts.published``).  Each
  token gets the gated output of the held experts among its top k, and
  nothing for the others, which live on other chips: the partial sum one
  chip of the deployment computes.  Every held expert runs every token (a
  gate of 0 where it was not picked), so nothing is dropped.
* No auxiliary loss, no ``group_limited_greedy`` (``topk_method`` greedy,
  ``n_group`` 1 as published), no ``q_lora`` (``q_lora_rank`` null as
  published), no KV cache, no dropout.
* Random weights (``make_weights``), not the published checkpoint.

``precision`` selects how the operands of every linear layer (projections,
router, experts, head) are rounded before the float32 product, as in
``dense_gqa.py``: ``"float32"``, ``"bfloat16"``, ``"int8"``, ``"fp8"``.
"""
from __future__ import annotations

import functools
import importlib.util
import math
import pathlib
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp


def _sibling(name: str):
    path = pathlib.Path(__file__).with_name(f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_reference_{name}_for_mla_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# the precisions, rounding, norm, seed and logit-gap code of the dense reference
_G = _sibling("dense_gqa")
PRECISIONS = _G.PRECISIONS
seed_key = _G.seed_key
_linear = _G._linear
_rms = _G._rms
QUERY_BLOCK = 512  # query rows per attention block: bounds the score tensor


class Dims(NamedTuple):
    layers: int
    dense_layers: int
    d_model: int
    heads: int
    kv_lora_rank: int
    qk_nope: int
    qk_rope: int
    v_head: int
    d_ff: int
    d_ff_expert: int
    experts: int  # scored by the router
    held: int  # held on this chip
    first_held: int
    top_k: int
    shared: int
    norm_topk: bool
    routed_scale: float
    vocab: int
    rope_theta: float
    yarn_factor: float
    yarn_original: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_mscale: float
    yarn_mscale_all_dim: float
    eps: float
    tied: bool


def dims(cfg: dict) -> Dims:
    """The sizes of a configuration file (Hugging Face ``config.json`` keys)."""
    y = cfg["rope_scaling"]
    if y.get("type") != "yarn" or cfg.get("q_lora_rank") is not None:
        raise ValueError("mla_moe covers YaRN rope and a full-rank query projection")
    if cfg["scoring_func"] != "softmax" or cfg["topk_method"] != "greedy":
        raise ValueError("mla_moe covers softmax scores and greedy top-k")
    cut = cfg.get("reduced", {}).get("n_routed_experts")
    return Dims(
        layers=cfg["num_hidden_layers"],
        dense_layers=cfg["first_k_dense_replace"],
        d_model=cfg["hidden_size"],
        heads=cfg["num_attention_heads"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope=cfg["qk_nope_head_dim"],
        qk_rope=cfg["qk_rope_head_dim"],
        v_head=cfg["v_head_dim"],
        d_ff=cfg["intermediate_size"],
        d_ff_expert=cfg["moe_intermediate_size"],
        experts=cut["published"] if cut else cfg["n_routed_experts"],
        held=cfg["n_routed_experts"],
        first_held=cfg.get("first_held_expert", 0),
        top_k=cfg["num_experts_per_tok"],
        shared=cfg["n_shared_experts"],
        norm_topk=bool(cfg["norm_topk_prob"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        vocab=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        yarn_factor=float(y["factor"]),
        yarn_original=int(y["original_max_position_embeddings"]),
        yarn_beta_fast=float(y["beta_fast"]),
        yarn_beta_slow=float(y["beta_slow"]),
        yarn_mscale=float(y["mscale"]),
        yarn_mscale_all_dim=float(y["mscale_all_dim"]),
        eps=float(cfg["rms_norm_eps"]),
        tied=bool(cfg["tie_word_embeddings"]),
    )


def weight_shapes(d: Dims) -> Dict[str, tuple]:
    """Canonical layout: matrices are (in, out), layers stacked in front.
    Attention weights stack all layers; ``dense_*`` the leading dense
    layers' FFN; ``router``, ``exp_*`` and ``shared_*`` the MoE layers'.
    Per head, ``wq``'s columns are [no-rope | rope] and ``wkv_b``'s
    [key no-rope | value]; ``wkv_a``'s columns are [latent | rope key]."""
    L, D, H = d.layers, d.d_model, d.heads
    M, E = d.layers - d.dense_layers, d.held
    qk = d.qk_nope + d.qk_rope
    fs = d.shared * d.d_ff_expert
    return {
        "embed": (d.vocab, D),
        "lm_head": (d.vocab, D),
        "final_norm": (D,),
        "attn_norm": (L, D),
        "wq": (L, D, H * qk),
        "wkv_a": (L, D, d.kv_lora_rank + d.qk_rope),
        "kv_norm": (L, d.kv_lora_rank),
        "wkv_b": (L, d.kv_lora_rank, H * (d.qk_nope + d.v_head)),
        "wo": (L, H * d.v_head, D),
        "mlp_norm": (L, D),
        "dense_gate": (d.dense_layers, D, d.d_ff),
        "dense_up": (d.dense_layers, D, d.d_ff),
        "dense_down": (d.dense_layers, d.d_ff, D),
        "router": (M, D, d.experts),
        "exp_gate": (M, E, D, d.d_ff_expert),
        "exp_up": (M, E, D, d.d_ff_expert),
        "exp_down": (M, E, d.d_ff_expert, D),
        "shared_gate": (M, D, fs),
        "shared_up": (M, D, fs),
        "shared_down": (M, fs, D),
    }


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, d: Dims):
    """Normal weights scaled to keep every layer's output near unit size:
    matrices by 1 / sqrt(fan-in), the embedding and the head by
    1 / sqrt(d_model), norm gains near 1."""
    out = {}
    for i, (name, shape) in enumerate(sorted(weight_shapes(d).items())):
        w = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.bfloat16)
        if name.endswith("norm"):
            w = 1 + w * jnp.bfloat16(0.1)
        elif name in ("embed", "lm_head"):
            w = w * jnp.bfloat16(1.0 / math.sqrt(shape[-1]))
        else:
            w = w * jnp.bfloat16(1.0 / math.sqrt(shape[-2]))
        out[name] = w
    return out


def make_weights(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """Every weight, bfloat16, on the default device, in one jitted call."""
    return _make(seed_key(seed), dims(cfg))


# ---- YaRN (as published: yarn_find_correction_range, yarn_get_mscale) ------


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(d: Dims) -> jnp.ndarray:
    """The published ``DeepseekV2YarnRotaryEmbedding.inv_freq``."""
    dim, base = d.qk_rope, d.rope_theta

    def correction_dim(rotations):
        return (dim * math.log(d.yarn_original / (rotations * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(correction_dim(d.yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(d.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    freq_extra = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    freq_inter = 1.0 / (d.yarn_factor * base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    inv_freq_mask = 1.0 - ramp
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


def softmax_scale(d: Dims) -> float:
    scale = (d.qk_nope + d.qk_rope) ** -0.5
    if d.yarn_mscale_all_dim:
        scale *= yarn_get_mscale(d.yarn_factor, d.yarn_mscale_all_dim) ** 2
    return scale


def _rope(x, d: Dims):
    """The published ``apply_rotary_pos_emb``: de-interleave each head's
    rope dims (view (D/2, 2), transpose), then ``rotate_half``; cos and sin
    carry ``mscale / mscale_all_dim``.  x (S, H, D)."""
    s, h, dim = x.shape
    mscale = yarn_get_mscale(d.yarn_factor, d.yarn_mscale) / yarn_get_mscale(
        d.yarn_factor, d.yarn_mscale_all_dim)
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] * yarn_inv_freq(d)[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    cos, sin = jnp.cos(emb) * mscale, jnp.sin(emb) * mscale
    x = x.reshape(s, h, dim // 2, 2).swapaxes(-1, -2).reshape(s, h, dim)
    rot = jnp.concatenate([-x[..., dim // 2 :], x[..., : dim // 2]], axis=-1)
    return x * cos + rot * sin


def _attention(q, k, v, scale: float):
    """Causal softmax attention; q, k (S, H, Dqk), v (S, H, Dv)."""
    s = q.shape[0]
    blocks = []
    for start in range(0, s, QUERY_BLOCK):
        qb = q[start : start + QUERY_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=jax.lax.Precision.HIGHEST) * scale
        rows = start + jnp.arange(qb.shape[0])[:, None]
        sc = jnp.where(jnp.arange(s)[None, :] <= rows, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        blocks.append(jnp.einsum("hqk,khd->qhd", p, v, precision=jax.lax.Precision.HIGHEST))
    return jnp.concatenate(blocks, axis=0)


def _mla(x, w, d: Dims, precision: str):
    s = x.shape[0]
    h = _rms(x, w["attn_norm"], d.eps)
    q = _linear(h, w["wq"], precision).reshape(s, d.heads, d.qk_nope + d.qk_rope)
    q_nope, q_pe = q[..., : d.qk_nope], q[..., d.qk_nope :]
    kv_a = _linear(h, w["wkv_a"], precision)
    latent, k_pe = kv_a[:, : d.kv_lora_rank], kv_a[:, d.kv_lora_rank :]
    kv = _linear(_rms(latent, w["kv_norm"], d.eps), w["wkv_b"], precision)
    kv = kv.reshape(s, d.heads, d.qk_nope + d.v_head)
    k_nope, v = kv[..., : d.qk_nope], kv[..., d.qk_nope :]
    q_pe = _rope(q_pe, d)
    k_pe = _rope(k_pe.reshape(s, 1, d.qk_rope), d)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (s, d.heads, d.qk_rope))], axis=-1)
    a = _attention(q, k, v, softmax_scale(d)).reshape(s, d.heads * d.v_head)
    return x + _linear(a, w["wo"], precision)


def _swiglu(h, w_gate, w_up, w_down, precision: str):
    g = _linear(h, w_gate, precision)
    u = _linear(h, w_up, precision)
    return _linear(jax.nn.silu(g) * u, w_down, precision)


def _moe(h, w, d: Dims, precision: str):
    """The held experts' part of the routed experts, plus the shared ones."""
    probs = jax.nn.softmax(_linear(h, w["router"], precision), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, d.top_k)
    if d.norm_topk:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    top_w = top_w * d.routed_scale
    held = d.first_held + jnp.arange(d.held)
    gates = jnp.sum(jnp.where(top_i[:, :, None] == held, top_w[:, :, None], 0.0), axis=1)
    expert = jax.vmap(_swiglu, in_axes=(None, 0, 0, 0, None))
    y = expert(h, w["exp_gate"], w["exp_up"], w["exp_down"], precision)  # (E, S, D)
    routed = jnp.einsum("esd,se->sd", y, gates, precision=jax.lax.Precision.HIGHEST)
    return routed + _swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"], precision)


@functools.partial(jax.jit, static_argnums=(2, 3))
def hidden(weights, tokens, d: Dims, precision: str):
    """Final normed hidden states (S, d_model), float32, of one sequence."""
    x = weights["embed"][tokens].astype(jnp.float32)
    attn = ("attn_norm", "wq", "wkv_a", "kv_norm", "wkv_b", "wo", "mlp_norm")
    for i in range(d.dense_layers):
        w = {k: weights[k][i] for k in attn}
        x = _mla(x, w, d, precision)
        h = _rms(x, w["mlp_norm"], d.eps)
        x = x + _swiglu(h, weights["dense_gate"][i], weights["dense_up"][i],
                        weights["dense_down"][i], precision)

    def block(x, w):
        x = _mla(x, w, d, precision)
        return x + _moe(_rms(x, w["mlp_norm"], d.eps), w, d, precision), None

    moe_keys = ("router", "exp_gate", "exp_up", "exp_down", "shared_gate", "shared_up",
                "shared_down")
    stacked = {k: weights[k][d.dense_layers :] for k in attn}
    stacked.update({k: weights[k] for k in moe_keys})
    x, _ = jax.lax.scan(block, x, stacked)
    return _rms(x, weights["final_norm"], d.eps)


def gaps(weights, d: Dims, ref_h, tokens, other_h=None, precision: str = "float32"):
    """As ``dense_gqa.gaps``: per scored row, by how much the reference's
    logit of ``tokens`` (and of the token ``other_h`` puts first) lies below
    the reference's best."""
    return _G.gaps(weights, d, ref_h, tokens, other_h, precision)
