"""Decoder-only language model covering dense / MoE / MLA / SSM / hybrid
families (all assigned architectures except whisper, which lives in
encdec.py).

Layers are homogeneous and scanned (``jax.lax.scan`` over stacked params) —
the standard trick for O(1) HLO size at hundreds of layers; heterogeneous
prefixes (e.g. DeepSeek-V2's first dense FFN layer) are kept as unscanned
python-list layers in front.  Decode caches carry static metadata (ring
windows, stacking) in pytree aux data so jit boundaries stay stable.

All functions are pure; params/caches are pytrees of jnp arrays.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

from . import layers as L
from .config import ModelConfig

BIG_WINDOW = 1 << 30  # "no window" sentinel for traced mask arithmetic


# ---------------------------------------------------------------------------
# per-layer param init
# ---------------------------------------------------------------------------


def _init_block(key, cfg: ModelConfig, layer_idx: int, dense_ffn: bool) -> Dict:
    ks = L._split(key, 4)
    p: Dict[str, Any] = {"norm1": jnp.ones((cfg.d_model,), cfg.dtype)}
    if cfg.attention == "gqa":
        p["attn"] = L.init_attention(ks[0], cfg)
    elif cfg.attention == "mla":
        p["attn"] = L.init_mla(ks[0], cfg)
    if cfg.family in ("ssm", "hybrid"):
        p["mamba"] = L.init_mamba2(ks[1], cfg)
        if cfg.family == "hybrid":
            p["norm_m"] = jnp.ones((cfg.d_model,), cfg.dtype)
    if cfg.d_ff or (cfg.moe and cfg.moe.num_experts):
        p["norm2"] = jnp.ones((cfg.d_model,), cfg.dtype)
        if cfg.moe and cfg.moe.num_experts and not dense_ffn:
            p["moe"] = L.init_moe(ks[2], cfg)
        elif cfg.d_ff:
            p["mlp"] = L.init_mlp(ks[2], cfg)
    return p


def init(cfg: ModelConfig, key) -> Dict:
    ks = L._split(key, cfg.num_layers + 2)
    params: Dict[str, Any] = {"embed": L.init_embedding(ks[0], cfg)}
    n_prefix = cfg.moe.first_dense_layers if cfg.moe else 0
    prefix = [
        _init_block(ks[1 + i], cfg, i, dense_ffn=True) for i in range(n_prefix)
    ]
    rest = [
        _init_block(ks[1 + i], cfg, i, dense_ffn=False)
        for i in range(n_prefix, cfg.num_layers)
    ]
    params["prefix_layers"] = prefix
    params["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *rest)
    params["final_norm"] = jnp.ones((cfg.d_model,), cfg.dtype)
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# per-layer windows
# ---------------------------------------------------------------------------


def static_windows(cfg: ModelConfig) -> List[Optional[int]]:
    """Python-level per-layer window (None = global attention)."""
    out: List[Optional[int]] = []
    for i in range(cfg.num_layers):
        w = cfg.window_for_layer(i)
        # Hymba-style hybrids keep first / middle / last layers global
        if cfg.family == "hybrid" and i in (0, cfg.num_layers // 2, cfg.num_layers - 1):
            w = None
        out.append(w)
    return out


def layer_windows(cfg: ModelConfig) -> jnp.ndarray:
    """(L,) int32 traced windows for the full-sequence (scan) path."""
    return jnp.asarray(
        [w if w is not None else BIG_WINDOW for w in static_windows(cfg)], jnp.int32
    )


# ---------------------------------------------------------------------------
# forward (training / prefill)
# ---------------------------------------------------------------------------


def _block_full(p, x, cfg: ModelConfig, positions, window, rope_fraction):
    """One transformer block, full-sequence.  Returns (x, aux_loss).

    Sharding-hint hooks (models.layers.shard_hints):
      * "attn_in": re-shard the normed input ONCE before the q/k/v
        projections (otherwise each projection re-gathers the SP residual).
      * "block_out": constrain attention/FFN outputs to the residual (SP)
        spec so GSPMD lowers the row-parallel psum as reduce-scatter
        instead of a full all-reduce.
    """
    aux = jnp.zeros((), jnp.float32)
    h = L.rmsnorm(x, p["norm1"], cfg.norm_eps)
    delta = jnp.zeros_like(x)
    if cfg.attention == "gqa":
        w = None if cfg.sliding_window is None else window
        delta = L.attention_full(
            p["attn"], L._hint("attn_in", h), cfg, positions, window=w,
            rope_fraction=rope_fraction,
        )
        delta = L._hint("block_out", delta)
    elif cfg.attention == "mla":
        delta = L._hint("block_out", L.mla_full(p["attn"], L._hint("attn_in", h), cfg, positions))
    if cfg.family == "ssm":
        delta = L._hint("block_out", L.mamba2_full(p["mamba"], h, cfg))
    elif cfg.family == "hybrid":
        hm = L.rmsnorm(x, p["norm_m"], cfg.norm_eps)
        delta = 0.5 * (delta + L._hint("block_out", L.mamba2_full(p["mamba"], hm, cfg)))
    x = x + delta
    if "moe" in p:
        h2 = L.rmsnorm(x, p["norm2"], cfg.norm_eps)
        out, aux, _ = L.moe(p["moe"], h2, cfg)
        x = x + L._hint("block_out", out)
    elif "mlp" in p:
        h2 = L.rmsnorm(x, p["norm2"], cfg.norm_eps)
        x = x + L._hint("block_out", L.mlp(p["mlp"], h2, cfg))
    return x, aux


def rope_fraction(cfg: ModelConfig) -> float:
    # ChatGLM's "2d RoPE" rotates half the head dim
    return 0.5 if "chatglm" in cfg.name else 1.0


def hidden_forward(
    params,
    cfg: ModelConfig,
    tokens,  # (B, S) int32
    prefix_embeds=None,  # (B, P, d) modality-stub prefix
    remat: bool = False,
    residual_constraint=None,  # fn(x)->x: SP sharding hint between layers
    unroll: int = 1,  # scan unroll factor (dry-run uses full unroll so HLO
                      # cost analysis sees every layer, not one loop body)
) -> Tuple[jax.Array, jax.Array]:
    """Returns (final hidden states (B, S_total, d), aux_loss)."""
    x = L.embed(params["embed"], tokens).astype(cfg.dtype)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(cfg.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    windows = layer_windows(cfg)
    rf = rope_fraction(cfg)
    aux_total = jnp.zeros((), jnp.float32)

    n_prefix = len(params["prefix_layers"])
    for i, p in enumerate(params["prefix_layers"]):
        x, aux = _block_full(p, x, cfg, positions, windows[i], rf)
        aux_total += aux

    def body(carry, inp):
        x, aux_acc = carry
        p, w = inp
        if residual_constraint is not None:
            x = residual_constraint(x)
        x, aux = _block_full(p, x, cfg, positions, w, rf)
        if residual_constraint is not None:
            # constrain the *outgoing* carry too: this is the tensor the
            # per-layer checkpoint saves for the backward pass — without the
            # hint it inherits the block's natural output sharding and the
            # saved residuals blow up by the TP degree.
            x = residual_constraint(x)
        return (x, aux_acc + aux), None

    body_fn = jax.checkpoint(body) if remat else body
    (x, aux_total), _ = jax.lax.scan(
        body_fn, (x, aux_total), (params["layers"], windows[n_prefix:]),
        unroll=unroll,
    )
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, aux_total


def _logits_of(params, cfg: ModelConfig, x):
    logits = L.unembed(params["embed"], x, cfg)
    if cfg.logit_soft_cap:
        logits = cfg.logit_soft_cap * jnp.tanh(logits / cfg.logit_soft_cap)
    return logits


def forward(params, cfg: ModelConfig, tokens, prefix_embeds=None,
            remat: bool = False, residual_constraint=None, unroll: int = 1):
    """Returns (logits (B, S_total, V) f32, aux_loss)."""
    x, aux = hidden_forward(params, cfg, tokens, prefix_embeds, remat,
                            residual_constraint, unroll)
    return _logits_of(params, cfg, x), aux


def _ce(params, cfg, x, labels):
    logits = _logits_of(params, cfg, x)
    logp = jax.nn.log_softmax(logits, axis=-1)
    mask = labels >= 0
    safe = jnp.where(mask, labels, 0)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * mask), jnp.sum(mask).astype(jnp.float32)


def loss_fn(params, cfg: ModelConfig, tokens, labels, prefix_embeds=None,
            remat: bool = False, residual_constraint=None,
            logits_chunk: int = 0, unroll: int = 1):
    """Causal LM loss; labels < 0 are masked out.

    ``logits_chunk`` > 0 streams the unembedding + softmax over sequence
    chunks (rematerialized in the backward pass), bounding the live logits
    tensor to (B, chunk, V) instead of (B, S, V) — essential for the 256k
    vocab archs at 4k sequence."""
    x, aux = hidden_forward(params, cfg, tokens, prefix_embeds, remat,
                            residual_constraint, unroll)
    if prefix_embeds is not None:
        x = x[:, prefix_embeds.shape[1]:]
    s = x.shape[1]
    if logits_chunk and s % logits_chunk == 0 and s > logits_chunk:
        nchunks = s // logits_chunk
        xc = x.reshape(x.shape[0], nchunks, logits_chunk, -1)
        lc = labels.reshape(labels.shape[0], nchunks, logits_chunk)

        @jax.checkpoint
        def chunk_ce(carry, inp):
            xi, li = inp
            nll, cnt = _ce(params, cfg, xi, li)
            return (carry[0] + nll, carry[1] + cnt), None

        (nll, cnt), _ = jax.lax.scan(
            chunk_ce,
            (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (xc.swapaxes(0, 1), lc.swapaxes(0, 1)),
        )
    else:
        nll, cnt = _ce(params, cfg, x, labels)
    ce = nll / jnp.maximum(cnt, 1)
    return ce + aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# decode caches
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
class Cache:
    """Decode cache with static layout metadata (aux data, not leaves).

    Two layouts behind one interface (``decode_step`` accepts either):

    * ``"contiguous"`` — per-slot strips of ``max_len`` (ring buffers for
      sliding-window layers), the lockstep/simple-batching layout;
    * ``"paged"`` — per-layer page pools plus a ``tables`` leaf, the
      (B, max_pages) int32 block table mapping each slot's logical KV
      blocks to physical pages (serving/paged_cache.py owns the host-side
      allocation; the engine refreshes ``tables`` via :meth:`with_tables`).
      GQA pages its KV heads; MLA pages its shared latent+rope cache
      (DESIGN.md §5.4).
    """

    def __init__(self, prefix, rest, stacked: bool, max_len: int,
                 layout: str = "contiguous", page_size: int = 0, tables=None):
        self.prefix = prefix
        self.rest = rest
        self.stacked = stacked
        self.max_len = max_len
        self.layout = layout
        self.page_size = page_size
        self.tables = tables

    def tree_flatten(self):
        return (
            (self.prefix, self.rest, self.tables),
            (self.stacked, self.max_len, self.layout, self.page_size),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0], aux[1], aux[2], aux[3],
                   tables=children[2])

    def with_tables(self, tables) -> "Cache":
        """Same cache contents under refreshed block tables."""
        return Cache(self.prefix, self.rest, self.stacked, self.max_len,
                     self.layout, self.page_size, tables)

    def kv_bytes(self) -> int:
        """Bytes held by attention KV state (pages or strips)."""
        total = 0
        for leaf in jax.tree.leaves((self.prefix, self.rest)):
            total += leaf.size * leaf.dtype.itemsize
        return total


def init_cache(cfg: ModelConfig, batch: int, max_len: int, *,
               layout: str = "contiguous", page_size: int = 16,
               num_blocks: Optional[int] = None) -> Cache:
    wlist = static_windows(cfg)
    if layout not in ("contiguous", "paged"):
        raise ValueError(f"unknown cache layout {layout!r}")
    if layout == "paged" and not cfg.attends:
        # loud, not a silent downgrade: the caller asked for paging and
        # this arch has no attention KV state to page
        raise ValueError(
            f"layout='paged' needs an attention KV cache; {cfg.name} "
            f"(attention={cfg.attention!r}) keeps only recurrent state — "
            "use layout='contiguous'."
        )
    max_pages = -(-max_len // page_size) if layout == "paged" else 0
    if layout == "paged" and num_blocks is None:
        num_blocks = batch * max_pages

    def one(layer_idx: int) -> Dict:
        c: Dict[str, Any] = {}
        if cfg.attention == "gqa":
            if layout == "paged":
                c["kv"] = L.init_paged_kv_cache(cfg, num_blocks, page_size)
            else:
                c["kv"] = L.init_kv_cache(
                    cfg, batch, max_len, window=wlist[layer_idx]
                )
        elif cfg.attention == "mla":
            if layout == "paged":
                c["mla"] = L.init_mla_paged_cache(cfg, num_blocks, page_size)
            else:
                c["mla"] = L.init_mla_cache(cfg, batch, max_len)
        if cfg.family in ("ssm", "hybrid"):
            c["ssm"] = L.init_mamba2_cache(cfg, batch)
        return c

    n_prefix = cfg.moe.first_dense_layers if cfg.moe else 0
    prefix = [one(i) for i in range(n_prefix)]
    rest = [one(i) for i in range(n_prefix, cfg.num_layers)]
    tables = (
        jnp.zeros((batch, max_pages), jnp.int32) if layout == "paged" else None
    )
    homogeneous = len({w for w in wlist[n_prefix:]}) <= 1
    if homogeneous and len(rest) > 1:
        rest_t = jax.tree.map(lambda *xs: jnp.stack(xs), *rest)
        return Cache(prefix, rest_t, True, max_len, layout, page_size, tables)
    return Cache(prefix, rest, False, max_len, layout, page_size, tables)


def copy_pages(cache: Cache, src, dst) -> Cache:
    """Device-side copy-on-write: duplicate physical pages ``src[i]`` onto
    ``dst[i]`` in every page-pool leaf of a paged cache.

    The serving engine calls this when a slot must write into a page shared
    with another table (``SlotTables.ensure_writable`` handed out a fresh
    page): the shared contents are copied on device — never staged through
    the host — and the repointed table is uploaded afterwards.  Page pools
    are identified by their leaf names (``*_pages``: GQA's k/v pools, MLA's
    latent/rope pools); every pool keeps its page axis at ``ndim - 3``
    (pages × page_size × feature, with optional head/layer-stack axes in
    front), so one gather/scatter covers both families, stacked or not.
    Pairs may be padded with ``(0, 0)`` — copying the reserved garbage page
    onto itself is a no-op.
    """
    if cache.layout != "paged":
        raise ValueError("copy_pages needs a paged cache")
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)

    def visit(path, leaf):
        if not _is_pool_leaf(path):
            return leaf
        pool = jnp.moveaxis(leaf, leaf.ndim - 3, 0)
        pool = pool.at[dst].set(pool[src])
        return jnp.moveaxis(pool, 0, leaf.ndim - 3)

    prefix = jax.tree_util.tree_map_with_path(visit, cache.prefix)
    rest = jax.tree_util.tree_map_with_path(visit, cache.rest)
    return Cache(prefix, rest, cache.stacked, cache.max_len, cache.layout,
                 cache.page_size, cache.tables)


def _is_pool_leaf(path) -> bool:
    """A cache leaf is a physical page pool iff some dict key on its path
    ends in ``_pages`` (GQA's k/v pools, MLA's latent/rope pools, and the
    quantized variants' ``*_scale_pages``) — the same contract
    :func:`copy_pages` keys on, with the page axis at ``ndim - 3``."""
    return any(
        str(p.key).endswith("_pages")
        for p in path if isinstance(p, jax.tree_util.DictKey)
    )


def gather_pages(cache: Cache, pages) -> list:
    """Contents of physical ``pages`` from every pool leaf, page axis
    leading — ``(len(pages), *per_page_shape)`` numpy arrays in the
    cache's flatten order (prefix leaves then rest), matching
    :func:`scatter_pages` and :func:`page_leaf_shapes`.  This is the
    serializable payload of the serving engine's ``snapshot()``."""
    if cache.layout != "paged":
        raise ValueError("gather_pages needs a paged cache")
    idx = jnp.asarray(list(pages), jnp.int32)
    out: list = []

    def visit(path, leaf):
        if _is_pool_leaf(path):
            pool = jnp.moveaxis(leaf, leaf.ndim - 3, 0)
            out.append(np.asarray(pool[idx]))
        return leaf

    jax.tree_util.tree_map_with_path(visit, cache.prefix)
    jax.tree_util.tree_map_with_path(visit, cache.rest)
    return out


def scatter_pages(cache: Cache, pages, values) -> Cache:
    """Inverse of :func:`gather_pages`: write ``values`` (one array per
    pool leaf, page axis leading) into physical ``pages`` of every pool
    leaf.  The engine's snapshot restore path — page *ids* are remapped by
    the caller, contents land wherever the fresh pool allocated them."""
    if cache.layout != "paged":
        raise ValueError("scatter_pages needs a paged cache")
    idx = jnp.asarray(list(pages), jnp.int32)
    vals = iter(values)

    def visit(path, leaf):
        if not _is_pool_leaf(path):
            return leaf
        v = jnp.asarray(next(vals)).astype(leaf.dtype)
        pool = jnp.moveaxis(leaf, leaf.ndim - 3, 0)
        pool = pool.at[idx].set(v)
        return jnp.moveaxis(pool, 0, leaf.ndim - 3)

    prefix = jax.tree_util.tree_map_with_path(visit, cache.prefix)
    rest = jax.tree_util.tree_map_with_path(visit, cache.rest)
    return Cache(prefix, rest, cache.stacked, cache.max_len, cache.layout,
                 cache.page_size, cache.tables)


def page_leaf_shapes(cache: Cache) -> list:
    """``(per_page_shape, dtype_name)`` for every pool leaf in gather
    order — the layout fingerprint snapshot loading validates before
    scattering foreign page contents into this cache."""
    if cache.layout != "paged":
        raise ValueError("page_leaf_shapes needs a paged cache")
    out: list = []

    def visit(path, leaf):
        if _is_pool_leaf(path):
            dims = list(leaf.shape)
            dims.pop(leaf.ndim - 3)
            out.append((tuple(dims), str(leaf.dtype)))
        return leaf

    jax.tree_util.tree_map_with_path(visit, cache.prefix)
    jax.tree_util.tree_map_with_path(visit, cache.rest)
    return out


def pages_walked(cfg: ModelConfig, seq_lens, page_size: int,
                 max_pages: int) -> Tuple[int, int]:
    """``(walked, table)`` for a paged decode step whose slots attend
    ``seq_lens`` tokens (any shape): the KV pages one KV head's attention
    walks and those its block tables hold, over slots and layers, as
    ``ops.paged_walk`` answers for each layer's call."""
    lens, windows = np.asarray(seq_lens), static_windows(cfg)
    kw = L.paged_kernel_facts(cfg)
    walked = sum(n * int(ops.paged_walk(lens, page_size, max_pages, w, **kw).sum())
                 for w, n in collections.Counter(windows).items())
    return walked, lens.size * max_pages * len(windows)


def prior_pages_walked(cfg: ModelConfig, starts, lens, chunk: int,
                       page_size: int, max_pages: int) -> Tuple[int, int]:
    """``(walked, table)`` for a paged prefill step whose slots hold
    ``starts`` prior tokens and ``lens`` live tokens of ``chunk``: the
    prior KV pages one KV head's attention walks and those its grid cells'
    tables hold, over cells, slots and layers, as ``ops.prefill_walk``
    answers for each layer's call."""
    kw, walked, table = L.paged_kernel_facts(cfg), 0, 0
    for w, n in collections.Counter(static_windows(cfg)).items():
        wk, tb = ops.prefill_walk(starts, lens, chunk, page_size, max_pages, w, **kw)
        walked, table = walked + n * int(wk.sum()), table + n * int(tb.sum())
    return walked, table


def expert_rows(cfg: ModelConfig, token_rows: int) -> int:
    """Rows of the held experts' FFNs a step computes for ``token_rows``
    token rows over its MoE layers; 0 for a model with none."""
    n = L.moe_layers(cfg)
    return n * L.moe_rows(cfg, token_rows) if n else 0


def _per_slot(mask, tree_a, tree_b):
    """Select ``tree_a`` where the (B,) ``mask`` holds, else ``tree_b``
    (leaves are batch-major)."""
    return jax.tree.map(
        lambda a, b: jnp.where(
            mask.reshape(mask.shape + (1,) * (a.ndim - 1)), a, b
        ),
        tree_a, tree_b,
    )


def _block_decode(p, x, cfg: ModelConfig, cache, pos, window,
                  layout="contiguous", tables=None, live=None):
    """``window`` must be a static python value here (ring layout / mask).

    ``live`` (optional (B,) bool) marks the slots actually taking a step.
    Positional caches (KV strips/pages, MLA latents) never need it — a dead
    slot's write lands beyond its live length and is masked on read — but
    *recurrent* SSM/conv state has no position to hide behind: without the
    mask a parked slot's state would keep evolving every batched tick.
    With ``live``, dead slots hold their state and a slot stepping at
    ``pos == 0`` starts from zeroed state, so a request's outputs do not
    depend on what previously occupied its slot.
    """
    h = L.rmsnorm(x, p["norm1"], cfg.norm_eps)
    new_cache: Dict[str, Any] = {}
    delta = jnp.zeros_like(x)
    if cfg.attention == "gqa":
        if layout == "paged":
            delta, kv = L.attention_decode_paged(
                p["attn"], h, cfg, cache["kv"], pos, tables, window=window,
                rope_fraction=rope_fraction(cfg),
            )
        else:
            delta, kv = L.attention_decode(
                p["attn"], h, cfg, cache["kv"], pos, window=window,
                rope_fraction=rope_fraction(cfg),
            )
        new_cache["kv"] = kv
    elif cfg.attention == "mla":
        if layout == "paged":
            delta, mc = L.mla_decode_paged(
                p["attn"], h, cfg, cache["mla"], pos, tables, window=window
            )
        else:
            delta, mc = L.mla_decode(
                p["attn"], h, cfg, cache["mla"], pos, window=window
            )
        new_cache["mla"] = mc
    if cfg.family in ("ssm", "hybrid"):
        ssm_in = cache["ssm"]
        if live is not None:
            posb = jnp.broadcast_to(
                jnp.asarray(pos, jnp.int32), (x.shape[0],)
            )
            fresh = live & (posb == 0)
            ssm_in = _per_slot(
                fresh, jax.tree.map(jnp.zeros_like, ssm_in), ssm_in
            )
        if cfg.family == "ssm":
            md, sc = L.mamba2_decode(p["mamba"], h, cfg, ssm_in)
            delta = md
        else:
            hm = L.rmsnorm(x, p["norm_m"], cfg.norm_eps)
            md, sc = L.mamba2_decode(p["mamba"], hm, cfg, ssm_in)
            delta = 0.5 * (delta + md)
        if live is not None:
            sc = _per_slot(live, sc, cache["ssm"])
        new_cache["ssm"] = sc
    x = x + delta
    x, routed = _ffn(p, x, cfg)
    return x, new_cache, routed


def _ffn(p, x, cfg: ModelConfig):
    """A block's FFN half for decode and prefill: ``(x, routed)``, where
    ``routed`` counts each token's picks among the held experts (None for
    a block without experts)."""
    if "moe" in p:
        h2 = L.rmsnorm(x, p["norm2"], cfg.norm_eps)
        out, _, routed = L.moe(p["moe"], h2, cfg)
        return x + out, routed
    if "mlp" in p:
        h2 = L.rmsnorm(x, p["norm2"], cfg.norm_eps)
        x = x + L.mlp(p["mlp"], h2, cfg)
    return x, None


def _plus(acc, n):
    return acc if n is None else acc + n


def _routed_zeros(cfg: ModelConfig, rows):
    """The starting count of held-expert picks for ``rows`` (B, S) tokens:
    zeros where the model has MoE layers, None where it has none."""
    return jnp.zeros(rows, jnp.int32) if L.moe_layers(cfg) else None


def decode_step(params, cfg: ModelConfig, cache: Cache, token, pos,
                unroll: int = 1, live=None, *, count_routed: bool = False):
    """One decode step: token (B,) int32, pos scalar int32 -> (logits, cache).

    ``live`` (optional (B,) bool) marks slots genuinely stepping — see
    :func:`_block_decode`; serving passes it so parked slots cannot mutate
    recurrent state and recycled slots start from clean state.  With
    ``count_routed`` a third result counts each slot's picks among the
    held experts over all layers ((B,) int32; None without MoE layers).
    """
    x = L.embed(params["embed"], token[:, None]).astype(cfg.dtype)
    wlist = static_windows(cfg)
    n_prefix = len(params["prefix_layers"])
    layout, tables = cache.layout, cache.tables
    routed = _routed_zeros(cfg, x.shape[:2])
    new_prefix = []
    for i, p in enumerate(params["prefix_layers"]):
        x, c, r = _block_decode(p, x, cfg, cache.prefix[i], pos, wlist[i],
                                layout, tables, live)
        routed = _plus(routed, r)
        new_prefix.append(c)

    if cache.stacked:
        wcommon = wlist[n_prefix] if cfg.num_layers > n_prefix else None

        def body(carry, inp):
            x, acc = carry
            p, c = inp
            x, cnew, r = _block_decode(p, x, cfg, c, pos, wcommon, layout,
                                       tables, live)
            return (x, _plus(acc, r)), cnew

        (x, routed), new_rest = jax.lax.scan(
            body, (x, routed), (params["layers"], cache.rest), unroll=unroll
        )
    else:
        new_rest = []
        layer_list = _unstack(params["layers"], cfg.num_layers - n_prefix)
        for j, (p, c) in enumerate(zip(layer_list, cache.rest)):
            x, cnew, r = _block_decode(p, x, cfg, c, pos, wlist[n_prefix + j],
                                       layout, tables, live)
            routed = _plus(routed, r)
            new_rest.append(cnew)

    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = L.unembed(params["embed"], x, cfg)[:, 0]
    if cfg.logit_soft_cap:
        logits = cfg.logit_soft_cap * jnp.tanh(logits / cfg.logit_soft_cap)
    cache = Cache(new_prefix, new_rest, cache.stacked, cache.max_len, layout,
                  cache.page_size, tables)
    if count_routed:
        return logits, cache, None if routed is None else routed[:, 0]
    return logits, cache


def decode_loop(params, cfg: ModelConfig, cache: Cache, feed, pos, key,
                live, remaining, *, n_steps: int, sample_fn, eos_id: int,
                max_len: int, unroll: int = 1):
    """Run up to ``n_steps`` decode ticks in one ``jax.lax.scan`` — the
    device-resident decode loop.  Everything the per-tick engine round-trips
    through the host each tick (feed build, upload, sample, download) lives
    in the scan carry instead; the host dispatches once and drains once.

    ``feed`` (B,) is each slot's last known token, ``pos`` (B,) its next
    write position, ``live`` (B,) bool the slots generating, ``remaining``
    (B,) each slot's token allowance.  ``sample_fn(logits, key, gate) ->
    (tokens, key)`` folds sampling into the loop body (serving passes
    :func:`repro.serving.sampling.sample_step`); ``gate`` is the any-slot-
    live flag so fully-dead tail iterations leave the key untouched.

    Per iteration, mirroring the per-tick engine's ``_emit_token`` exactly:
    a live slot feeds its token, samples the next, advances ``pos`` and
    burns one ``remaining``; it stops when the sampled token equals
    ``eos_id``, its allowance hits zero, or ``pos`` reaches ``max_len``.
    Greedy outputs are therefore byte-identical to per-tick stepping
    unconditionally.  At ``temperature > 0`` the key stream matches the
    per-tick engine's whenever the window covers the same ticks it would
    have run; if a slot frees mid-window while work is queued, per-tick
    admission would interleave a prefill key split before the boundary, so
    the streams are equally-valid draws but not bit-equal — scheduling
    deferral is visible through the PRNG, and callers needing bit-equality
    under sampling must keep windows off or the queue empty.
    Dead slots keep re-feeding their frozen token at their frozen ``pos``:
    the write lands beyond their live length (masked on read, overwritten
    on slot reuse) and recurrent state is held by the ``live`` mask inside
    ``decode_step``, so a dead iteration is behaviorally a no-op.

    Returns ``(tokens (n_steps, B), emitted (n_steps, B) bool, key, cache,
    routed)`` — ``emitted[t, b]`` marks a token the host must deliver; rows
    after the last live iteration are all-False.  ``routed`` counts the
    live slots' picks among the held experts over every tick and layer
    (int32; None without MoE layers).
    """
    counting = L.moe_layers(cfg) > 0

    def body(carry, _):
        cache, feed, pos, key, live, remaining, routed = carry
        if counting:
            logits, cache, r = decode_step(params, cfg, cache, feed, pos, unroll=unroll,
                                           live=live, count_routed=True)
            routed = routed + jnp.sum(jnp.where(live, r, 0))
        else:
            logits, cache = decode_step(params, cfg, cache, feed, pos,
                                        unroll=unroll, live=live)
        tok, key = sample_fn(logits, key, live.any())
        tok = jnp.where(live, tok, feed)
        pos = jnp.where(live, pos + 1, pos)
        remaining = jnp.where(live, remaining - 1, remaining)
        stop = (tok == eos_id) | (remaining <= 0) | (pos >= max_len)
        return (cache, tok, pos, key, live & ~stop, remaining, routed), (tok, live)

    carry = (cache, jnp.asarray(feed, jnp.int32), jnp.asarray(pos, jnp.int32),
             key, live, jnp.asarray(remaining, jnp.int32), _routed_zeros(cfg, ()))
    (cache, _, _, key, _, _, routed), (toks, emitted) = jax.lax.scan(
        body, carry, None, length=n_steps
    )
    return toks, emitted, key, cache, routed


def _block_prefill(p, x, cfg: ModelConfig, cache, pos, lens, window,
                   layout="contiguous", tables=None):
    """One transformer block over a (B, C) prefill chunk.  Mirrors
    ``_block_decode`` (same cache contract) with chunk-wide attention;
    ``window`` must be a static python value."""
    h = L.rmsnorm(x, p["norm1"], cfg.norm_eps)
    new_cache: Dict[str, Any] = {}
    if cfg.attention == "mla":
        if layout == "paged":
            delta, mc = L.mla_prefill_paged(
                p["attn"], h, cfg, cache["mla"], pos, tables, lens,
                window=window
            )
        else:
            delta, mc = L.mla_prefill(
                p["attn"], h, cfg, cache["mla"], pos, lens, window=window
            )
        new_cache["mla"] = mc
    elif layout == "paged":
        delta, kv = L.attention_prefill_paged(
            p["attn"], h, cfg, cache["kv"], pos, tables, lens, window=window,
            rope_fraction=rope_fraction(cfg),
        )
        new_cache["kv"] = kv
    else:
        delta, kv = L.attention_prefill(
            p["attn"], h, cfg, cache["kv"], pos, lens, window=window,
            rope_fraction=rope_fraction(cfg),
        )
        new_cache["kv"] = kv
    x = x + delta
    x, routed = _ffn(p, x, cfg)
    return x, new_cache, routed


def supports_chunked_prefill(cfg: ModelConfig) -> bool:
    """Chunked prefill covers the attention families — GQA through the
    prefill_attention kernel and MLA through mla_prefill (latent chunk
    writes).  SSM/hybrid state still replays token by token (recurrent
    state has no chunk-parallel write)."""
    return cfg.attention in ("gqa", "mla") and cfg.family not in ("ssm", "hybrid")


def _prefill_trunk(params, cfg: ModelConfig, cache: Cache, tokens, pos, lens,
                   unroll: int = 1):
    """The shared chunk-wide forward pass behind :func:`prefill_step` and
    :func:`verify_step`: embed, every block's chunk attention + KV page
    writes, final norm.  Returns ``(x (B, C, d), Cache, routed)`` — the
    hidden states of every chunk position, before any logits projection,
    and each position's picks among the held experts over all layers
    ((B, C) int32; None without MoE layers)."""
    if not supports_chunked_prefill(cfg):
        raise NotImplementedError(
            f"chunked prefill supports attention archs (GQA/MLA); {cfg.name} "
            f"(attention={cfg.attention}, family={cfg.family}) replays "
            "prompts through decode_step instead."
        )
    x = L.embed(params["embed"], tokens).astype(cfg.dtype)
    wlist = static_windows(cfg)
    n_prefix = len(params["prefix_layers"])
    layout, tables = cache.layout, cache.tables
    lens = jnp.asarray(lens, jnp.int32)
    routed = _routed_zeros(cfg, x.shape[:2])
    new_prefix = []
    for i, p in enumerate(params["prefix_layers"]):
        x, c, r = _block_prefill(p, x, cfg, cache.prefix[i], pos, lens, wlist[i],
                                 layout, tables)
        routed = _plus(routed, r)
        new_prefix.append(c)

    if cache.stacked:
        wcommon = wlist[n_prefix] if cfg.num_layers > n_prefix else None

        def body(carry, inp):
            x, acc = carry
            p, c = inp
            x, cnew, r = _block_prefill(p, x, cfg, c, pos, lens, wcommon,
                                        layout, tables)
            return (x, _plus(acc, r)), cnew

        (x, routed), new_rest = jax.lax.scan(
            body, (x, routed), (params["layers"], cache.rest), unroll=unroll
        )
    else:
        new_rest = []
        layer_list = _unstack(params["layers"], cfg.num_layers - n_prefix)
        for j, (p, c) in enumerate(zip(layer_list, cache.rest)):
            x, cnew, r = _block_prefill(p, x, cfg, c, pos, lens,
                                        wlist[n_prefix + j], layout, tables)
            routed = _plus(routed, r)
            new_rest.append(cnew)

    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, Cache(new_prefix, new_rest, cache.stacked, cache.max_len,
                    layout, cache.page_size, tables), routed


def prefill_step(params, cfg: ModelConfig, cache: Cache, tokens, pos, lens,
                 unroll: int = 1, *, count_routed: bool = False):
    """One chunked-prefill step: a (B, C) block of prompt tokens advances
    every slot with ``lens[b] > 0`` by ``lens[b]`` positions in a single
    forward pass (vs C batched decode steps under token replay).

    ``tokens`` (B, C) int32 (dead tail arbitrary), ``pos`` (B,) chunk start
    positions, ``lens`` (B,) live tokens per slot (0 = slot idle this step).
    Returns ``(logits, cache)`` where ``logits`` (B, V) belong to each
    slot's *last live* chunk token — exactly what sampling needs when a
    chunk completes its prompt.  Works against both cache layouts through
    the same ``Cache`` interface as ``decode_step``.  With ``count_routed``
    a third result counts each chunk position's picks among the held
    experts over all layers ((B, C) int32; None without MoE layers).
    """
    lens = jnp.asarray(lens, jnp.int32)
    x, cache, routed = _prefill_trunk(params, cfg, cache, tokens, pos, lens,
                                      unroll=unroll)
    # each slot's last live chunk position feeds the logits (idle slots
    # gather row 0 — garbage the engine ignores)
    last = jnp.clip(lens - 1, 0, x.shape[1] - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    logits = L.unembed(params["embed"], x_last, cfg)
    if cfg.logit_soft_cap:
        logits = cfg.logit_soft_cap * jnp.tanh(logits / cfg.logit_soft_cap)
    if count_routed:
        return logits, cache, routed
    return logits, cache


def verify_step(params, cfg: ModelConfig, cache: Cache, tokens, pos, lens,
                unroll: int = 1, *, count_routed: bool = False):
    """Speculative-decode verify: score every chunk position in one pass.

    This *is* chunked prefill — the same ``_prefill_trunk`` (same kernels,
    same table-directed KV page writes) — differing only in the logits
    projection: where :func:`prefill_step` unembeds each slot's last live
    token, verify unembeds the whole chunk, because accept/rollback needs
    the model's next-token distribution after *every* draft prefix.
    Returns ``(logits (B, C, V), cache)``; rows of idle slots
    (``lens == 0``) are garbage the caller masks.  ``count_routed`` adds
    ``routed`` as :func:`prefill_step` does.
    """
    x, cache, routed = _prefill_trunk(params, cfg, cache, tokens, pos, lens,
                                      unroll=unroll)
    logits = L.unembed(params["embed"], x, cfg)
    if cfg.logit_soft_cap:
        logits = cfg.logit_soft_cap * jnp.tanh(logits / cfg.logit_soft_cap)
    if count_routed:
        return logits, cache, routed
    return logits, cache


def ngram_propose(history, pos, feed, draft_len: int):
    """Self-speculation draft proposer: n-gram lookahead over the slot's own
    token history (prompt + committed output) — no second model, no weights.

    ``history`` (B, H) int32 holds each slot's tokens by sequence index
    (``history[b, pos[b]] == feed[b]``, entries past ``pos`` undefined).
    For each slot, find the most recent earlier occurrence of the current
    ``(prev, last)`` bigram, falling back to a unigram match on ``last``,
    and propose the ``draft_len`` tokens that followed it.  No match (or a
    match too close to the end) degrades to repeating ``feed`` — proposals
    are always *valid* token ids, and verify rejects wrong ones, so
    proposer quality only ever affects speed, never output.
    """
    b, h = history.shape
    last = jnp.asarray(feed, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    js = jnp.arange(h, dtype=jnp.int32)[None, :]
    known = js < pos[:, None]  # strictly-past indices only
    uni = known & (history == last[:, None])
    prev = jnp.where(
        pos > 0,
        jnp.take_along_axis(history, jnp.maximum(pos - 1, 0)[:, None],
                            axis=1)[:, 0],
        -1,
    )
    shifted = jnp.concatenate(
        [jnp.full((b, 1), -1, history.dtype), history[:, :-1]], axis=1
    )
    bi = uni & (shifted == prev[:, None])
    j_bi = jnp.max(jnp.where(bi, js, -1), axis=1)
    j_uni = jnp.max(jnp.where(uni, js, -1), axis=1)
    j = jnp.where(j_bi >= 0, j_bi, j_uni)
    cols = j[:, None] + 1 + jnp.arange(draft_len, dtype=jnp.int32)[None, :]
    ok = (j[:, None] >= 0) & (cols <= pos[:, None])
    cand = jnp.take_along_axis(history, jnp.clip(cols, 0, h - 1), axis=1)
    return jnp.where(ok, cand, last[:, None])


# Draft-proposer registry (ServeConfig.spec_decode names an entry): the plug
# point where a tiny draft *model* slots in later — any (history, pos, feed,
# draft_len) -> (B, draft_len) proposals function qualifies, because the
# verify/accept machinery never trusts a proposal.
DRAFT_PROPOSERS = {"ngram": ngram_propose}


def spec_decode_loop(params, cfg: ModelConfig, cache: Cache, feed, pos, key,
                     live, remaining, history, *, n_rounds: int,
                     draft_len: int, propose_fn, sample_fn, accept_fn,
                     eos_id: int, max_len: int, poison=None, unroll: int = 1):
    """``n_rounds`` draft-verify rounds in one ``jax.lax.scan`` dispatch —
    the speculative twin of :func:`decode_loop`, composing with it
    multiplicatively: where a decode-loop iteration emits one token, a
    round here drafts ``draft_len`` tokens (``propose_fn``), scores all of
    them plus the feed token in one chunk forward (:func:`verify_step` —
    batched verify *is* chunked prefill), and emits the accepted prefix
    plus the model's own next token, so one host dispatch covers up to
    ``n_rounds * (draft_len + 1)`` tokens.

    Accept/rollback are carry masks, not copies: the verify chunk writes
    KV for all ``draft_len + 1`` positions through the block tables, and a
    rejected tail is *logically* truncated by not advancing ``pos`` past
    the accepted prefix — the stale pages sit beyond the slot's live
    length, invisible to the ragged masks, and the next round's chunk
    write overwrites them (the engine's ``SlotTables.trim`` returns the
    unused grow-ahead at the sync boundary).

    ``sample_fn(logits (B, C, V), key, gate) -> (targets (B, C), key)``
    must advance the key by a *fixed* number of splits per gated round
    (``sampling.spec_sample_step``), so the stream is deterministic
    regardless of acceptance lengths; ``accept_fn(drafts, targets) ->
    (B, C) bool`` is the leading-accept mask (``sampling.spec_accept``).
    Greedy targets make the emitted stream byte-identical to plain decode
    by construction: every emitted token is the argmax after a committed,
    fully-verified prefix.

    ``poison`` (B,) bool overwrites a slot's verify logits with NaN (fault
    injection); slots whose logits hold no finite value — injected or
    genuine — emit nothing and stop, reported through ``bad`` for the
    engine to FAIL exactly that request.

    Returns ``(targets (n, B, C), emitted (n, B, C) bool, bad (n, B) bool,
    key, cache, routed)`` with ``C = draft_len + 1``; ``emitted[t, b, i]``
    marks target ``i`` of round ``t`` as a token the host must deliver, in
    order; ``routed`` counts the live slots' verify rows' picks among the
    held experts (int32; None without MoE layers).
    """
    c = draft_len + 1
    feed = jnp.asarray(feed, jnp.int32)
    if poison is None:
        poison = jnp.zeros(feed.shape, bool)
    idx = jnp.arange(c, dtype=jnp.int32)
    h = history.shape[1]

    def body(carry, _):
        cache, feed, pos, key, live, remaining, history, routed = carry
        drafts = propose_fn(history, pos, feed, draft_len)
        chunk = jnp.concatenate([feed[:, None], drafts], axis=1)
        lens = jnp.where(live, c, 0).astype(jnp.int32)
        logits, cache, r = verify_step(params, cfg, cache, chunk, pos, lens,
                                       unroll=unroll, count_routed=True)
        if r is not None:
            routed = routed + jnp.sum(jnp.where(live[:, None], r, 0))
        logits = jnp.where(poison[:, None, None], jnp.nan, logits)
        bad = jnp.any(~jnp.any(jnp.isfinite(logits), axis=-1), axis=-1) & live
        tgt, key = sample_fn(logits, key, live.any())
        eos_hit = tgt == eos_id
        ieos = eos_hit.astype(jnp.int32)
        prev_eos = (jnp.cumsum(ieos, axis=1) - ieos) > 0
        # target i is emitted iff every draft before it verified, no earlier
        # target was EOS, and the slot still had allowance/room — exactly
        # decode_loop's per-tick stop conditions, applied per position
        emit = (
            accept_fn(drafts, tgt)
            & ~prev_eos
            & ((pos[:, None] + idx[None, :]) < max_len)
            & (idx[None, :] < remaining[:, None])
            & live[:, None]
            & ~bad[:, None]
        )
        nem = emit.sum(axis=1, dtype=jnp.int32)
        last_tok = jnp.take_along_axis(
            tgt, jnp.clip(nem - 1, 0, c - 1)[:, None], axis=1
        )[:, 0]
        feed = jnp.where(nem > 0, last_tok, feed)
        # append the emitted tokens to the history so the next round's
        # n-gram lookahead sees them (rejected targets never land)
        wcols = jnp.where(emit, pos[:, None] + 1 + idx[None, :], h)
        history = history.at[jnp.arange(history.shape[0])[:, None], wcols].set(
            tgt, mode="drop"
        )
        pos = pos + nem
        remaining = remaining - nem
        stop = (
            (emit & eos_hit).any(axis=1)
            | (remaining <= 0)
            | (pos >= max_len)
            | bad
        )
        return (cache, feed, pos, key, live & ~stop, remaining, history, routed), (
            tgt, emit, bad,
        )

    carry = (cache, feed, jnp.asarray(pos, jnp.int32), key, live,
             jnp.asarray(remaining, jnp.int32),
             jnp.asarray(history, jnp.int32), _routed_zeros(cfg, ()))
    (cache, _, _, key, _, _, _, routed), (toks, emitted, bad) = jax.lax.scan(
        body, carry, None, length=n_rounds
    )
    return toks, emitted, bad, key, cache, routed


def _unstack(tree, n):
    return [jax.tree.map(lambda a, i=i: a[i], tree) for i in range(n)]
