"""The assigned (architecture × input-shape) grid: 10 archs × 4 shapes.

``input_specs`` produces ShapeDtypeStruct stand-ins for every model input —
weak-type-correct, shardable, no device allocation — and ``make_step``
builds the function each cell lowers:

* ``train_4k``                -> train_step (loss + grads + AdamW/ZeRO-1)
* ``prefill_32k``             -> forward (inference prefill)
* ``decode_32k`` / ``long_500k`` -> serve_step (one token against a KV/state
                                   cache of the cell's seq_len)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.distributed import sharding as shd
from repro.models import encdec, lm
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig, adamw_update, init_opt_state


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    kind: str  # train | prefill | decode
    seq: int
    batch: int


SHAPES: Dict[str, Cell] = {
    "train_4k": Cell("train_4k", "train", 4096, 256),
    "prefill_32k": Cell("prefill_32k", "prefill", 32768, 32),
    "decode_32k": Cell("decode_32k", "decode", 32768, 128),
    "long_500k": Cell("long_500k", "decode", 524288, 1),
}


def supported(cfg: ModelConfig, cell: Cell) -> Tuple[bool, str]:
    """long_500k needs sub-quadratic attention (skip for full-attention
    archs per the assignment, recorded in DESIGN.md)."""
    if cell.name == "long_500k" and not cfg.sub_quadratic:
        return False, "full quadratic attention at 524288 tokens (per spec: skip)"
    return True, ""


# ---------------------------------------------------------------------------
# shape-only state construction (jax.eval_shape — no allocation)
# ---------------------------------------------------------------------------


def params_shapes(cfg: ModelConfig):
    mod = encdec if cfg.is_encoder_decoder else lm
    return jax.eval_shape(lambda: mod.init(cfg, jax.random.PRNGKey(0)))


def train_state_shapes(cfg: ModelConfig):
    p = params_shapes(cfg)
    opt = jax.eval_shape(init_opt_state, p)
    return {"params": p, "opt": opt}


def cache_shapes(cfg: ModelConfig, batch: int, max_len: int):
    if cfg.is_encoder_decoder:
        return jax.eval_shape(lambda: encdec.init_cache(cfg, batch, max_len))
    return jax.eval_shape(lambda: lm.init_cache(cfg, batch, max_len))


def input_specs(cfg: ModelConfig, cell: Cell) -> Dict[str, jax.ShapeDtypeStruct]:
    """Data inputs for the cell's step function."""
    b, s = cell.batch, cell.seq
    tok = jax.ShapeDtypeStruct((b, s), jnp.int32)
    out: Dict[str, Any] = {}
    if cell.kind in ("train", "prefill"):
        out["tokens"] = tok
        if cell.kind == "train":
            out["labels"] = tok
        if cfg.is_encoder_decoder:
            out["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.frontend_seq, cfg.d_model), jnp.dtype(cfg.dtype)
            )
        elif cfg.frontend != "none":
            out["prefix_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.frontend_seq, cfg.d_model), jnp.dtype(cfg.dtype)
            )
    else:  # decode: one new token against a seq-long cache
        out["token"] = jax.ShapeDtypeStruct((b,), jnp.int32)
        out["pos"] = jax.ShapeDtypeStruct((), jnp.int32)
    return out


# ---------------------------------------------------------------------------
# sharding rules for the data/cache side
# ---------------------------------------------------------------------------


def data_specs(cfg: ModelConfig, cell: Cell, mesh: Mesh):
    bspec = shd.batch_spec(mesh, cell.batch)
    out: Dict[str, P] = {}
    if cell.kind in ("train", "prefill"):
        out["tokens"] = P(*tuple(bspec), None)
        if cell.kind == "train":
            out["labels"] = P(*tuple(bspec), None)
        if cfg.is_encoder_decoder:
            out["frames"] = P(*tuple(bspec), None, None)
        elif cfg.frontend != "none":
            out["prefix_embeds"] = P(*tuple(bspec), None, None)
    else:
        out["token"] = bspec
        out["pos"] = P()
    return out


def cache_specs(cfg: ModelConfig, cache_shape_tree, mesh: Mesh, batch: int):
    """Per-leaf cache sharding: batch over dp; heads (or failing that, the
    sequence axis) over `model`; SSM heads over `model`; MLA latent rank
    over `model`."""
    tp = shd.mesh_axis_size(mesh, "model")
    bspec = shd.batch_spec(mesh, batch)
    b_ax = tuple(bspec)[0] if len(tuple(bspec)) else None

    def rule(path, leaf):
        keys = [getattr(p, "key", getattr(p, "idx", "")) for p in path]
        names = [str(k) for k in keys]
        shape = leaf.shape
        nd = len(shape)
        base = [None] * nd

        def set_from_right(offset_from_right, axis_name):
            base[nd - offset_from_right] = axis_name

        if "kv" in names or "self" in names:  # attention K/V (.., B, H, S, hd)
            set_from_right(4, b_ax)
            if shape[nd - 3] % tp == 0:
                set_from_right(3, "model")  # head-sharded
            elif shape[nd - 2] % tp == 0:
                set_from_right(2, "model")  # split-KV over sequence
        elif "mla" in names:  # (.., B, S, 1, R)
            set_from_right(4, b_ax)
            if shape[nd - 1] % tp == 0:
                set_from_right(1, "model")
        elif names[-1] == "ssm" or "ssm" in names and shape and nd >= 4:
            # (.., B, H, N, P)
            if nd >= 4:
                set_from_right(4, b_ax)
                if shape[nd - 3] % tp == 0:
                    set_from_right(3, "model")
        elif "conv" in names:  # (.., B, W, C)
            if nd >= 3:
                set_from_right(3, b_ax)
                if shape[nd - 1] % tp == 0:
                    set_from_right(1, "model")
        # guard divisibility on the batch axis
        if nd >= 1:
            for i, ax in enumerate(base):
                if ax is not None and ax != "model":
                    sizes = (
                        np.prod([shd.mesh_axis_size(mesh, a) for a in (ax if isinstance(ax, tuple) else (ax,))])
                    )
                    if shape[i] % int(sizes) != 0:
                        base[i] = None
        return P(*base)

    return jax.tree_util.tree_map_with_path(rule, cache_shape_tree)


# ---------------------------------------------------------------------------
# interior sharding hints (see models.layers.shard_hints)
# ---------------------------------------------------------------------------


def make_hints(cfg: ModelConfig, mesh: Mesh, cell: Cell, opt_level: int = 0):
    """Activation constraints GSPMD can't infer on its own:

    * attention: shard heads over `model` when divisible; otherwise shard
      the q sequence axis (bounds the S^2 score tensor — flash-style
      partitioning) and keep K/V replicated on `model`.
    * MoE expert activations: EP over `model` when E divides, tokens over
      the data axes (TP stays inside the expert FFN).

    ``opt_level >= 1`` adds the §Perf collective optimizations:
    * "block_out": SP-constrain attention/FFN outputs so the row-parallel
      psum lowers as reduce-scatter (1/TP the wire bytes of all-reduce);
    * "attn_in": materialize the gathered (full-sequence) attention input
      once, deduping the per-projection all-gathers.
    """
    from repro.models import layers as L

    tp = shd.mesh_axis_size(mesh, "model")
    bspec = shd.batch_spec(mesh, cell.batch)
    b_ax = tuple(bspec)[0] if len(tuple(bspec)) else None

    def div(n, ax):
        if ax is None:
            return True
        names = ax if isinstance(ax, tuple) else (ax,)
        size = int(np.prod([shd.mesh_axis_size(mesh, a) for a in names]))
        return n % size == 0

    def constrain_with(specf):
        def f(x):
            spec = specf(x.shape)
            if spec is None:
                return x
            return shd.constrain(x, mesh, spec)
        return f

    hooks = {}
    if cfg.attends:
        def attn_q(shape):  # (B, H, S, hd)
            b, h, s, _ = shape
            if div(h, "model") and h >= tp:
                return P(b_ax if div(b, b_ax) else None, "model", None, None)
            if div(s, "model"):
                return P(b_ax if div(b, b_ax) else None, None, "model", None)
            return None

        def attn_kv(shape):
            b, h, s, _ = shape
            if div(h, "model") and h >= tp:
                return P(b_ax if div(b, b_ax) else None, "model", None, None)
            # replicated K/V on model when q is sequence-sharded
            return P(b_ax if div(b, b_ax) else None, None, None, None)

        hooks["attn_q"] = constrain_with(attn_q)
        hooks["attn_kv"] = constrain_with(attn_kv)
    if cfg.moe and cfg.moe.num_experts:
        def moe_expert(shape):  # (E, T, F): EP over model, tokens over data
            e, tokens = shape[0], shape[1]
            dp = shd.dp_axes(mesh)
            dp_ax = dp if len(dp) > 1 else (dp[0] if dp else None)
            t_ax = dp_ax if (dp_ax is not None and div(tokens, dp_ax)) else None
            e_ax = "model" if (div(e, "model") and e >= tp) else None
            return P(e_ax, t_ax, None)

        hooks["moe_expert"] = constrain_with(moe_expert)

    if opt_level >= 1 and cell.kind in ("train", "prefill"):
        res = shd.residual_spec(mesh, cell.batch, cell.seq)

        def block_out(shape):  # (B, S, D) — match the residual (SP) spec
            if len(shape) != 3:
                return None
            b, s, _ = shape
            sp = tuple(res)
            if not div(b, sp[0]) or (sp[1] == "model" and s % tp):
                return None
            return res

        def attn_in(shape):  # (B, S, D) gathered once before q/k/v
            if len(shape) != 3:
                return None
            b = shape[0]
            return P(b_ax if div(b, b_ax) else None, None, None)

        hooks["block_out"] = constrain_with(block_out)
        hooks["attn_in"] = constrain_with(attn_in)
    return hooks


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------


def make_train_step(cfg: ModelConfig, mesh: Mesh, cell: Cell,
                    adamw: Optional[AdamWConfig] = None,
                    logits_chunk: int = 256, unroll: int = 1,
                    opt_level: int = 0) -> Callable:
    adamw = adamw or AdamWConfig()
    res_spec = shd.residual_spec(mesh, cell.batch, cell.seq)

    def constrain(x):
        return shd.constrain(x, mesh, res_spec)

    hints = make_hints(cfg, mesh, cell, opt_level)
    zero1_pspecs = None
    if opt_level >= 1:
        # ZeRO gather optimization: pin the fp32->bf16 convert BEFORE the
        # param all-gather by constraining the casted params to the ZeRO
        # (data+model) sharding — the gather then moves bf16, half the bytes.
        pshapes_ = params_shapes(cfg)
        pspecs_ = shd.param_specs(pshapes_, cfg, mesh)
        zero1_pspecs = shd.zero1_specs(
            {"master": pshapes_, "m": pshapes_, "v": pshapes_, "step": None},
            pspecs_, mesh,
        )["master"]

    def train_step(state, batch):
        from repro.models import layers as L

        if cfg.is_encoder_decoder:
            def loss(p):
                return encdec.loss_fn(p, cfg, batch["frames"], batch["tokens"],
                                      batch["labels"], unroll=unroll,
                                      remat=True, logits_chunk=logits_chunk)
        else:
            def loss(p):
                return lm.loss_fn(
                    p, cfg, batch["tokens"], batch["labels"],
                    prefix_embeds=batch.get("prefix_embeds"),
                    remat=True,
                    residual_constraint=constrain,
                    logits_chunk=logits_chunk,
                    unroll=unroll,
                )
        with L.shard_hints(**hints):
            (l, parts), grads = jax.value_and_grad(loss, has_aux=True)(
                state["params"]
            )
        new_params, new_opt, om = adamw_update(state["params"], grads, state["opt"], adamw)
        if zero1_pspecs is not None:
            new_params = jax.tree.map(
                lambda x, s: shd.constrain(x, mesh, s), new_params, zero1_pspecs
            )
        metrics = {"loss": l, **parts, **om}
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, mesh: Mesh, cell: Cell,
                      unroll: int = 1, opt_level: int = 0) -> Callable:
    res_spec = shd.residual_spec(mesh, cell.batch, cell.seq)

    def constrain(x):
        return shd.constrain(x, mesh, res_spec)

    hints = make_hints(cfg, mesh, cell, opt_level)

    def prefill_step(params, batch):
        from repro.models import layers as L

        with L.shard_hints(**hints):
            if cfg.is_encoder_decoder:
                enc = encdec.encode(params, cfg, batch["frames"], unroll)
                logits = encdec.decode_full(params, cfg, batch["tokens"], enc, unroll)
                return logits[:, -1].astype(jnp.float32)
            x, _ = lm.hidden_forward(
                params, cfg, batch["tokens"],
                prefix_embeds=batch.get("prefix_embeds"),
                residual_constraint=constrain,
                unroll=unroll,
            )
            # prefill emits only the last-position logits (next-token)
            return lm._logits_of(params, cfg, x[:, -1:])[:, 0]

    return prefill_step


def make_serve_step(cfg: ModelConfig, mesh: Mesh, cell: Cell,
                    unroll: int = 1) -> Callable:
    if cfg.is_encoder_decoder:
        def serve_step(params, cache, cross, token, pos):
            return encdec.decode_step(params, cfg, cache, token, pos, cross, unroll)
        return serve_step

    def serve_step(params, cache, token, pos):
        return lm.decode_step(params, cfg, cache, token, pos, unroll=unroll)

    return serve_step
