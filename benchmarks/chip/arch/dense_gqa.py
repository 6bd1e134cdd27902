"""The program's side of a dense grouped-query-attention configuration:
its ``ModelConfig`` and its parameter tree, built from the benchmark's
canonical weights (``reference/dense_gqa.py``).

The program rotates (even, odd) pairs of each head's dimensions where the
published models rotate (i, i + head_dim / 2): the columns of the Q and K
projections, and their biases, are interleaved here, which leaves every
attention score and so every output unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.models.config import ModelConfig


def model_config(cfg: dict, d) -> ModelConfig:
    return ModelConfig(
        name=cfg["name"],
        family="dense",
        num_layers=d.layers,
        d_model=d.d_model,
        num_heads=d.heads,
        num_kv_heads=d.kv_heads,
        head_dim=d.head_dim,
        d_ff=d.d_ff,
        vocab_size=d.vocab,
        act=cfg["hidden_act"],
        qkv_bias=d.qkv_bias,
        rope_theta=d.rope_theta,
        norm_eps=d.eps,
        tie_embeddings=d.tied,
        dtype=cfg["torch_dtype"],
    )


def _interleave(w, head_dim: int):
    """Columns of each head from (i, i + D/2) pairs to (2i, 2i + 1) pairs."""
    half = head_dim // 2
    perm = np.stack([np.arange(half), np.arange(half) + half], axis=1).reshape(-1)
    shape = w.shape
    return w.reshape(shape[:-1] + (shape[-1] // head_dim, head_dim))[..., perm].reshape(shape)


@functools.partial(jax.jit, static_argnums=(1, 2, 3), donate_argnums=(0,))
def _to_program(w, hd: int, qkv_bias: bool, tied: bool):
    attn = {
        "wq": _interleave(w["wq"], hd),
        "wk": _interleave(w["wk"], hd),
        "wv": w["wv"],
        "wo": w["wo"],
    }
    if qkv_bias:
        attn.update(bq=_interleave(w["bq"], hd), bk=_interleave(w["bk"], hd), bv=w["bv"])
    embed = {"embedding": w["embed"]}
    if not tied:
        embed["unembed"] = w["lm_head"].T
    return {
        "embed": embed,
        "prefix_layers": [],
        "layers": {
            "norm1": w["attn_norm"],
            "attn": attn,
            "norm2": w["mlp_norm"],
            "mlp": {"w_gate": w["w_gate"], "w_up": w["w_up"], "w_down": w["w_down"]},
        },
        "final_norm": w["final_norm"],
    }


def program_params(weights: dict, mcfg: ModelConfig):
    """The program's parameter tree; consumes ``weights``.  Its structure,
    shapes and dtypes must be those ``lm.init`` gives."""
    want = jax.eval_shape(functools.partial(lm.init, mcfg), jax.random.PRNGKey(0))
    params = _to_program(weights, mcfg.head_dim, mcfg.qkv_bias, mcfg.tie_embeddings)
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    exp = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got != exp:
        raise ValueError(f"parameter tree differs from lm.init's: {got} != {exp}")
    return params
