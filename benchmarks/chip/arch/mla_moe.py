"""The program's side of a DeepSeek-V2 (MLA + MoE) configuration: its
``ModelConfig`` and its parameter tree, built from the benchmark's
canonical weights (``reference/mla_moe.py``).

The published model rotates each head's rope dims as interleaved pairs
(its ``apply_rotary_pos_emb`` de-interleaves before ``rotate_half``), which
is the program's own pair order, so no column is permuted.  The joint
``kv_a_proj_with_mqa`` splits into the program's latent and rope-key
projections, and ``kv_b_proj`` into its per-head key (``w_uk``) and value
(``w_uv``) up-projections; the router is kept in float32, as the program
scores in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import lm
from repro.models.config import MLAConfig, ModelConfig, MoEConfig, YarnScaling


def model_config(cfg: dict, d) -> ModelConfig:
    if d.routed_scale != 1.0:
        raise ValueError("the program does not scale routed gates (routed_scaling_factor 1)")
    return ModelConfig(
        name=cfg["name"],
        family="moe",
        num_layers=d.layers,
        d_model=d.d_model,
        num_heads=d.heads,
        num_kv_heads=d.heads,
        head_dim=d.v_head,
        d_ff=d.d_ff,
        vocab_size=d.vocab,
        attention="mla",
        act=cfg["hidden_act"],
        rope_theta=d.rope_theta,
        norm_eps=d.eps,
        tie_embeddings=d.tied,
        dtype=cfg["torch_dtype"],
        mla=MLAConfig(
            kv_lora_rank=d.kv_lora_rank, q_lora_rank=0, qk_nope_head_dim=d.qk_nope,
            qk_rope_head_dim=d.qk_rope, v_head_dim=d.v_head,
            rope_scaling=YarnScaling(
                factor=d.yarn_factor, original_max_position_embeddings=d.yarn_original,
                beta_fast=d.yarn_beta_fast, beta_slow=d.yarn_beta_slow,
                mscale=d.yarn_mscale, mscale_all_dim=d.yarn_mscale_all_dim,
            ),
        ),
        moe=MoEConfig(
            num_experts=d.experts, experts_per_token=d.top_k, d_ff_expert=d.d_ff_expert,
            num_shared_experts=d.shared, first_dense_layers=d.dense_layers,
            held_experts=d.held, first_held_expert=d.first_held, norm_topk_prob=d.norm_topk,
        ),
    )


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7), donate_argnums=(0,))
def _to_program(w, layers: int, dense: int, r: int, h: int, nope: int, dv: int, dtype: str):
    w = {k: v.astype(dtype) for k, v in w.items()}
    kv_b = w["wkv_b"].reshape(layers, r, h, nope + dv)

    def attn(sl):
        return {
            "w_dkv": w["wkv_a"][sl, :, :r],
            "w_kpe": w["wkv_a"][sl, :, r:],
            "w_uk": kv_b[sl, :, :, :nope].reshape(-1, r, h * nope),
            "w_uv": kv_b[sl, :, :, nope:].reshape(-1, r, h * dv),
            "w_o": w["wo"][sl],
            "w_q": w["wq"][sl],
            "kv_norm": w["kv_norm"][sl],
        }

    prefix = []
    for i in range(dense):
        a = jax.tree.map(lambda x: x[0], attn(slice(i, i + 1)))
        prefix.append({
            "norm1": w["attn_norm"][i],
            "attn": a,
            "norm2": w["mlp_norm"][i],
            "mlp": {"w_gate": w["dense_gate"][i], "w_up": w["dense_up"][i],
                    "w_down": w["dense_down"][i]},
        })
    rest = slice(dense, None)
    return {
        "embed": {"embedding": w["embed"], "unembed": w["lm_head"].T},
        "prefix_layers": prefix,
        "layers": {
            "norm1": w["attn_norm"][rest],
            "attn": attn(rest),
            "norm2": w["mlp_norm"][rest],
            "moe": {
                "router": w["router"].astype(jnp.float32),
                "w_gate": w["exp_gate"],
                "w_up": w["exp_up"],
                "w_down": w["exp_down"],
                "shared": {"w_gate": w["shared_gate"], "w_up": w["shared_up"],
                           "w_down": w["shared_down"]},
            },
        },
        "final_norm": w["final_norm"],
    }


def program_params(weights: dict, mcfg: ModelConfig):
    """The program's parameter tree; consumes ``weights``.  Its structure,
    shapes and dtypes must be those ``lm.init`` gives."""
    want = jax.eval_shape(functools.partial(lm.init, mcfg), jax.random.PRNGKey(0))
    m = mcfg.mla
    params = _to_program(weights, mcfg.num_layers, mcfg.moe.first_dense_layers,
                         m.kv_lora_rank, mcfg.num_heads, m.qk_nope_head_dim, m.v_head_dim,
                         mcfg.dtype)
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    exp = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got != exp:
        raise ValueError(f"parameter tree differs from lm.init's: {got} != {exp}")
    return params
