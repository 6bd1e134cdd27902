"""Paged-attention decode in the tile DSL (vLLM-style KV paging).

Single-token decode attention over a **paged KV cache**: keys/values live in
a pool of fixed-size pages (``(kv_heads, num_pages, page_size, head_dim)``)
and each decode slot owns a *block table* mapping its logical KV blocks to
physical pages.  The kernel grid runs over (kv_head, slot) with the KV-block
axis pipelined; each step's K/V windows are gathered **through the block
table** — a ``T.ScalarTensor`` scalar-prefetch param whose elements appear
in the copy-region starts, so the Pallas lowering turns the gather into a
``PrefetchScalarGridSpec`` index map and the DMA pipeline double-buffers
non-contiguous pages exactly like contiguous ones (TileLoom's "plan
dataflow over non-contiguous tiles" as a one-line index change).

Softmax is the shared online-rescaling template (attention_core.py) with a
page-gather KV source and GQA group-major Q packing: ``Q`` and ``Output``
are ``(slots, kv_heads, group, head_dim)``, so each grid cell's block spans
the array's last two dimensions whole (Mosaic's block rule holds for any
group size, e.g. qwen2's 6); ragged sequence
lengths (every slot at its own position) and sliding windows compose the
ragged mask against the ``Lens`` scalar tensor.

The page walk is bounded by the slot's live length: the loop runs over
``attention_core.live_pages(Lens[bz], page_size, window)`` only, with
``max_pages`` as its static bound, so table entries past a slot's live
pages are never read and a slot of length 0 walks nothing (its output is
zeros).  The Pallas lowering makes that walk an in-kernel loop over
hand-issued page DMAs (``core/backends/pallas_tpu.py``) instead of a grid
axis over every table page; the mask still trims the partial last page
and the window's first.
"""

import math
from typing import Optional

import numpy as np

from repro.core import TileProgram
from repro.core import lang as T

from . import attention_core as AC


def paged_attention_program(
    slots: int,
    heads: int,
    kv_heads: int,
    head_dim: int,
    page_size: int,
    max_pages: int,
    num_pages: int,
    window: Optional[int] = None,
    dtype: str = "float32",
    accum_dtype: str = "float32",
    num_stages: int = 2,
    sm_scale: Optional[float] = None,
) -> TileProgram:
    if heads % kv_heads:
        raise ValueError("GQA requires heads % kv_heads == 0")
    group = heads // kv_heads
    scale = (sm_scale if sm_scale is not None else 1.0 / math.sqrt(head_dim)) * 1.44269504  # log2(e)

    @T.prim_func
    def PagedAttn(
        Tables: T.ScalarTensor((slots, max_pages), "int32"),
        Lens: T.ScalarTensor((slots,), "int32"),
        Q: T.Tensor((slots, kv_heads, group, head_dim), dtype),
        KPages: T.Tensor((kv_heads, num_pages, page_size, head_dim), dtype),
        VPages: T.Tensor((kv_heads, num_pages, page_size, head_dim), dtype),
        Output: T.Tensor((slots, kv_heads, group, head_dim), dtype),
    ):
        with T.Kernel(kv_heads, slots) as (bh, bz):
            Q_shared = T.alloc_shared((group, head_dim), dtype)
            K_shared = T.alloc_shared((page_size, head_dim), dtype)
            V_shared = T.alloc_shared((page_size, head_dim), dtype)
            acc_s = T.alloc_fragment((group, page_size), accum_dtype)
            # safe_div: empty slots (len 0) divide by the floor -> zeros
            ons = AC.OnlineSoftmax(group, head_dim, scale, accum_dtype,
                                   safe_div=True)

            T.copy(Q[bz, bh, 0, 0], Q_shared)

            def load_kv(k):
                # the paged gather: page index loaded from the block table
                T.copy(KPages[bh, Tables[bz, k], 0, 0], K_shared)
                T.copy(VPages[bh, Tables[bz, k], 0, 0], V_shared)
                return K_shared, V_shared

            # ragged mask: this slot's live KV positions are
            # [max(0, len-window), len) — the walk visits only their pages,
            # and the mask trims the rest of the first and last of them.
            def mask(k):
                return AC.ragged(Lens[bz], lambda j: k * page_size + j, window)

            first, end = AC.live_pages(Lens[bz], page_size, window)
            AC.attend(
                ons, acc_s, page_size, end, load_kv,
                lambda s, ks, k: AC.scores(s, Q_shared, ks), mask,
                num_stages=num_stages, start=first, max_extent=max_pages,
            )
            ons.finalize(Output[bz, bh, 0, 0])

    return PagedAttn


def paged_attention_quant_program(
    slots: int,
    heads: int,
    kv_heads: int,
    head_dim: int,
    page_size: int,
    max_pages: int,
    num_pages: int,
    fmt: str = "int8",
    window: Optional[int] = None,
    dtype: str = "float32",
    accum_dtype: str = "float32",
    num_stages: int = 2,
    sm_scale: Optional[float] = None,
) -> TileProgram:
    """Quantized paged decode: the fp kernel with ``load_kv`` routed through
    the :class:`attention_core.DequantStage` composition point.  Pages hold
    packed int8 K/V (``head_dim // pack`` bytes per token) plus a per-token
    scale column; the unpack+scale runs on the VPU between the page DMA and
    the score GEMM.  Everything else — grid, masks, online softmax — is the
    fp kernel unchanged."""
    if heads % kv_heads:
        raise ValueError("GQA requires heads % kv_heads == 0")
    group = heads // kv_heads
    pack = AC.KV_PACK[fmt]
    scale = (sm_scale if sm_scale is not None else 1.0 / math.sqrt(head_dim)) * 1.44269504  # log2(e)

    @T.prim_func
    def PagedAttnQuant(
        Tables: T.ScalarTensor((slots, max_pages), "int32"),
        Lens: T.ScalarTensor((slots,), "int32"),
        Q: T.Tensor((slots, kv_heads, group, head_dim), dtype),
        KPages: T.Tensor((kv_heads, num_pages, page_size, head_dim // pack), "int8"),
        VPages: T.Tensor((kv_heads, num_pages, page_size, head_dim // pack), "int8"),
        KScales: T.Tensor((kv_heads, num_pages, page_size, 1), dtype),
        VScales: T.Tensor((kv_heads, num_pages, page_size, 1), dtype),
        Output: T.Tensor((slots, kv_heads, group, head_dim), dtype),
    ):
        with T.Kernel(kv_heads, slots) as (bh, bz):
            Q_shared = T.alloc_shared((group, head_dim), dtype)
            kq = AC.DequantStage(page_size, head_dim, fmt, dtype)
            vq = AC.DequantStage(page_size, head_dim, fmt, dtype)
            acc_s = T.alloc_fragment((group, page_size), accum_dtype)
            ons = AC.OnlineSoftmax(group, head_dim, scale, accum_dtype,
                                   safe_div=True)

            T.copy(Q[bz, bh, 0, 0], Q_shared)

            def load_kv(k):
                # paged gather + inline dequant (page index from the table)
                ks = kq.load(KPages[bh, Tables[bz, k], 0, 0],
                             KScales[bh, Tables[bz, k], 0, 0])
                vs = vq.load(VPages[bh, Tables[bz, k], 0, 0],
                             VScales[bh, Tables[bz, k], 0, 0])
                return ks, vs

            def mask(k):
                return AC.ragged(Lens[bz], lambda j: k * page_size + j, window)

            first, end = AC.live_pages(Lens[bz], page_size, window)
            AC.attend(
                ons, acc_s, page_size, end, load_kv,
                lambda s, ks, k: AC.scores(s, Q_shared, ks), mask,
                num_stages=num_stages, start=first, max_extent=max_pages,
            )
            ons.finalize(Output[bz, bh, 0, 0])

    return PagedAttnQuant


# Live lengths of the ``*_edge_lens`` parity cases, one per slot: the
# bounded walk's edges at page 16 and 3 table pages — an empty slot (no
# page walked), one token, exactly one page, one past a page, the full
# table; under a window of 20 the walk of the last three starts past page 0.
# The ``lane`` cases (head_dim 128) take the Pallas in-kernel walk the chip
# runs; at head_dim 16, and for the quantized kernel's (page, 1) scale
# columns, the bounded loop lowers to the static grid over ``max_pages``
# (``lowering.grid.walks_in_kernel``).
EDGE_LENS = (0, 1, 16, 17, 48)
_EDGE = dict(slots=len(EDGE_LENS), heads=4, kv_heads=2, head_dim=16,
             page_size=16, max_pages=3, num_pages=16)

# Tiny-shape configs for the pallas-vs-reference parity suite
# (tests/test_pipeline.py); covers GQA + MQA head groupings, a sliding
# window, and the ragged case (block tables of different live lengths per
# slot — exercised through the input override below).  The _quant cases run
# the same shapes through the DequantStage KV source (int8 and the packed
# int4 sub-byte unpack).
PARITY_CASES = [
    (
        "paged_attention_mqa",
        dict(slots=2, heads=2, kv_heads=1, head_dim=16, page_size=16,
             max_pages=2, num_pages=4),
    ),
    (
        "paged_attention_gqa_ragged",
        dict(slots=3, heads=4, kv_heads=2, head_dim=16, page_size=16,
             max_pages=2, num_pages=8),
    ),
    (
        "paged_attention_windowed",
        dict(slots=2, heads=2, kv_heads=2, head_dim=16, page_size=16,
             max_pages=2, num_pages=4, window=12),
    ),
    (
        "paged_attention_quant_int8",
        dict(slots=3, heads=4, kv_heads=2, head_dim=16, page_size=16,
             max_pages=2, num_pages=8, fmt="int8"),
    ),
    (
        "paged_attention_quant_int4",
        dict(slots=2, heads=2, kv_heads=1, head_dim=16, page_size=16,
             max_pages=2, num_pages=4, fmt="int4"),
    ),
    ("paged_attention_edge_lens", _EDGE),
    ("paged_attention_windowed_edge_lens", dict(_EDGE, window=20)),
    ("paged_attention_quant_int8_edge_lens", dict(_EDGE, fmt="int8")),
    ("paged_attention_quant_int4_windowed_edge_lens",
     dict(_EDGE, fmt="int4", window=20)),
    ("paged_attention_lane_edge_lens", dict(_EDGE, head_dim=128)),
    ("paged_attention_lane_windowed_edge_lens",
     dict(_EDGE, head_dim=128, window=20)),
]


def parity_programs():
    for name, cfg in PARITY_CASES:
        maker = paged_attention_quant_program if "quant" in name else paged_attention_program
        yield name, maker(**cfg)


def parity_inputs(name, program, rng):
    """Valid inputs for the parity suite: block tables must hold live page
    ids and lens must be in range — random bytes won't do.  Tables are drawn
    without replacement (each physical page owned by one slot) and lens are
    ragged: every slot at a different fill level, including a partial page
    (``EDGE_LENS`` for the ``*_edge_lens`` cases).  Quantized cases get
    full-range packed bytes and positive scales.
    """
    cfg = dict(PARITY_CASES)[name]
    slots, mp, np_ = cfg["slots"], cfg["max_pages"], cfg["num_pages"]
    pages = rng.permutation(np_)[: slots * mp].reshape(slots, mp).astype("int32")
    max_len = mp * cfg["page_size"]
    if name.endswith("edge_lens"):
        lens = np.asarray(EDGE_LENS, "int32")
    else:
        lens = (rng.integers(1, max_len + 1, size=slots)).astype("int32")
    args = [pages, lens]
    for p in program.input_params()[2:]:
        if str(p.dtype).startswith("int"):
            args.append(rng.integers(-128, 128, size=p.shape).astype(p.dtype))
        elif p.name.endswith("Scales"):
            args.append(rng.uniform(0.05, 0.2, size=p.shape).astype(p.dtype))
        else:
            args.append(rng.standard_normal(p.shape).astype(p.dtype))
    return args
