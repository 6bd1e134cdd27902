"""FlashMLA in the tile DSL — the paper's Fig. 18 composed from the shared
attention core, plus its serving variants.

Multi-head Latent Attention (DeepSeek-V2): all query heads of a group attend
to one shared latent KV (dim) plus a rotary part (pe_dim); V is the latent
itself.  The paper reports this kernel at 98% of hand-optimized FlashMLA in
~70 lines — the headline usability result we reproduce here.

Three programs share the template (attention_core.py), differing only in
composition points:

* :func:`mla_program` — contiguous KV window, block-max softmax (the
  paper's formulation), no mask: the Fig. 18 port.
* :func:`mla_paged_program` — the **paged MLA decode** kernel: latent
  pages gathered through a block table (the same scalar-prefetch path as
  paged_attention.py), grid over slots, ragged live-length mask.  This is
  what admits MLA models to the vLLM-style serving cache.
* :func:`mla_prefill_program` — **MLA chunked prefill**: a (slots, chunk)
  block of prompt latents attends prior latent pages plus itself causally
  and writes its own pages from inside the kernel (table-directed output
  BlockSpecs, as in prefill_attention.py).

The paged kernels keep one **joint page** per token row, as FlashMLA and
vLLM store it: ``[latent (dim) | rope (pe_dim) | zeros]``, :func:`page_width`
lanes in all (640 at DeepSeek-V2's 512 + 64).  Queries are built the same
way, so one score GEMM per page covers both parts, and the page is a
lane-aligned tile that Mosaic can DMA by hand: both kernels walk only the
live pages inside the kernel (``lowering.grid.walks_in_kernel``), where a
separate 64-lane rope page could only be walked on a grid axis over the
whole table.  Their output carries every lane; the latent output is its
first ``dim``.  The quantized variants keep two packed pools with
``(page, 1)`` scale columns, which no layout makes lane-aligned, and their
static grid over the table.
"""

import math
from typing import Optional

import numpy as np

from repro.core import TileProgram
from repro.core import lang as T
from repro.core.layout import LANE

from . import attention_core as AC
from .paged_attention import EDGE_LENS


def mla_program(
    batch: int,
    heads: int,
    kv_head_num: int,
    seqlen_kv: int,
    dim: int,
    pe_dim: int,
    block_N: int = 128,
    block_H: int = 64,
    dtype: str = "float32",
    accum_dtype: str = "float32",
    num_stages: int = 2,
    sm_scale: Optional[float] = None,
    swizzle: Optional[int] = None,
) -> TileProgram:
    if seqlen_kv % block_N:
        raise ValueError("seqlen_kv must divide block_N")
    kv_group_num = heads // kv_head_num
    VALID_BLOCK_H = min(block_H, kv_group_num)
    if heads % VALID_BLOCK_H:
        raise ValueError("the valid head block must divide heads")
    scale = (
        sm_scale if sm_scale is not None else 1.0 / math.sqrt(dim + pe_dim)
    ) * 1.44269504  # log2(e)

    @T.prim_func
    def FlashMLA(
        Q: T.Tensor((batch, heads, dim), dtype),
        Q_pe: T.Tensor((batch, heads, pe_dim), dtype),
        KV: T.Tensor((batch, seqlen_kv, kv_head_num, dim), dtype),
        K_pe: T.Tensor((batch, seqlen_kv, kv_head_num, pe_dim), dtype),
        Output: T.Tensor((batch, heads, dim), dtype),
    ):
        with T.Kernel(batch, heads // VALID_BLOCK_H, threads=256) as (bx, by):
            Q_shared = T.alloc_shared((VALID_BLOCK_H, dim), dtype)
            S_shared = T.alloc_shared((VALID_BLOCK_H, block_N), dtype)
            Q_pe_shared = T.alloc_shared((VALID_BLOCK_H, pe_dim), dtype)
            KV_shared = T.alloc_shared((block_N, dim), dtype)
            K_pe_shared = T.alloc_shared((block_N, pe_dim), dtype)
            acc_s = T.alloc_fragment((VALID_BLOCK_H, block_N), accum_dtype)
            # the paper's Fig. 18 formulation: per-block max (not running),
            # probabilities staged through shared memory for the P·V GEMM
            ons = AC.OnlineSoftmax(VALID_BLOCK_H, dim, scale, accum_dtype,
                                   running_max=False, clamp_current=False,
                                   shared_scores=S_shared)

            cur_kv_head = by // (kv_group_num // VALID_BLOCK_H)
            if swizzle:
                T.use_swizzle(swizzle)

            T.copy(Q[bx, by * VALID_BLOCK_H : (by + 1) * VALID_BLOCK_H, :], Q_shared)
            T.copy(
                Q_pe[bx, by * VALID_BLOCK_H : (by + 1) * VALID_BLOCK_H, :], Q_pe_shared
            )

            def load_kv(k):
                T.copy(
                    KV[bx, k * block_N : (k + 1) * block_N, cur_kv_head, :], KV_shared
                )
                T.copy(
                    K_pe[bx, k * block_N : (k + 1) * block_N, cur_kv_head, :],
                    K_pe_shared,
                )
                return KV_shared, KV_shared  # V is the latent itself

            AC.attend(
                ons, acc_s, block_N, T.ceildiv(seqlen_kv, block_N), load_kv,
                lambda s, ks, k: AC.scores(
                    s, Q_shared, ks, extra=[(Q_pe_shared, K_pe_shared)]
                ),
                num_stages=num_stages,
            )
            ons.finalize(Output[bx, by * VALID_BLOCK_H : (by + 1) * VALID_BLOCK_H, :])

    return FlashMLA


def page_width(dim: int, pe_dim: int) -> int:
    """Lanes of one row of the joint latent page: ``dim + pe_dim`` rounded
    up to the TPU lane width (640 at DeepSeek-V2's 512 + 64)."""
    return -(-(dim + pe_dim) // LANE) * LANE


def mla_paged_program(
    slots: int,
    heads: int,
    dim: int,
    pe_dim: int,
    page_size: int,
    max_pages: int,
    num_pages: int,
    block_H: int = 64,
    dtype: str = "float32",
    accum_dtype: str = "float32",
    num_stages: int = 2,
    sm_scale: Optional[float] = None,
    window: Optional[int] = None,
) -> TileProgram:
    """Paged MLA decode: one query row block per slot against the joint
    latent pages of its block table (scalar prefetch), masked to each
    slot's live length (optionally sliding-window limited).  The latent is
    shared by every query head, so there is no kv-head grid axis.

    Pages and queries are joint rows of :func:`page_width` lanes,
    ``[latent | rope | 0]``: one score GEMM per page covers the latent and
    rope products, and the zero lanes add nothing.  V is the page itself,
    so the output carries every lane and its ``[0, dim)`` lanes are the
    latent output (the caller drops the rest).  The walk visits only the
    slot's live pages (``attention_core.live_pages``), as ``PagedAttn``."""
    bh = min(block_H, heads)
    if heads % bh:
        raise ValueError("the head block must divide heads")
    width = page_width(dim, pe_dim)
    scale = (
        sm_scale if sm_scale is not None else 1.0 / math.sqrt(dim + pe_dim)
    ) * 1.44269504  # log2(e)

    @T.prim_func
    def PagedMLA(
        Tables: T.ScalarTensor((slots, max_pages), "int32"),
        Lens: T.ScalarTensor((slots,), "int32"),
        Q: T.Tensor((slots, heads, width), dtype),
        Pages: T.Tensor((num_pages, page_size, width), dtype),
        Output: T.Tensor((slots, heads, width), dtype),
    ):
        with T.Kernel(heads // bh, slots) as (by, bz):
            Q_shared = T.alloc_shared((bh, width), dtype)
            P_shared = T.alloc_shared((page_size, width), dtype)
            acc_s = T.alloc_fragment((bh, page_size), accum_dtype)
            # safe_div: empty slots (len 0) divide by the floor -> zeros
            ons = AC.OnlineSoftmax(bh, width, scale, accum_dtype, safe_div=True)

            T.copy(Q[bz, by * bh, 0], Q_shared)

            def load_kv(k):
                # the paged gather: page index loaded from the block table
                T.copy(Pages[Tables[bz, k], 0, 0], P_shared)
                return P_shared, P_shared  # V: the page, its latent lanes first

            def mask(k):
                return AC.ragged(Lens[bz], lambda j: k * page_size + j, window)

            first, end = AC.live_pages(Lens[bz], page_size, window)
            AC.attend(
                ons, acc_s, page_size, end, load_kv,
                lambda s, ks, k: AC.scores(s, Q_shared, ks), mask,
                num_stages=num_stages, start=first, max_extent=max_pages,
            )
            ons.finalize(Output[bz, by * bh, 0])

    return PagedMLA


def mla_prefill_program(
    slots: int,
    heads: int,
    dim: int,
    pe_dim: int,
    chunk: int,
    page_size: int,
    max_pages: int,
    num_pages: int,
    dtype: str = "float32",
    accum_dtype: str = "float32",
    num_stages: int = 2,
    sm_scale: Optional[float] = None,
    window: Optional[int] = None,
) -> TileProgram:
    """MLA chunked prefill: a (slots, chunk) block of prompt latents attends
    prior joint latent pages (gathered through the block table) plus itself
    causally, and writes its own pages from inside the kernel.

    Queries are packed chunk-major with their head — row ``i * heads + h``
    is chunk position ``i`` of head ``h`` — so each grid cell attends a
    ``(page_size * heads, width)`` query tile (the prefill_attention packing
    with the whole head count as the group).  Rows, pages and the output
    are joint rows ``[latent | rope | 0]`` as in :func:`mla_paged_program`.
    Same contract as prefill_attention.py: ``chunk % page_size == 0``, live
    ``Starts`` page-aligned, dead chunk pages land in the reserved garbage
    page 0.

    A grid cell whose chunk page holds a live row walks the prior pages
    ``[0, Starts // page_size)``, from the first page the window's band
    reaches from the cell's lowest query under a window; a cell with no
    live row walks none.
    """
    if chunk % page_size:
        raise ValueError("chunk must be a multiple of page_size")
    cpp = chunk // page_size  # chunk pages written per slot
    rows = page_size * heads  # query rows per grid cell (chunk-major packed)
    width = page_width(dim, pe_dim)
    scale = (
        sm_scale if sm_scale is not None else 1.0 / math.sqrt(dim + pe_dim)
    ) * 1.44269504  # log2(e)

    @T.prim_func
    def PrefillMLA(
        Tables: T.ScalarTensor((slots, max_pages), "int32"),
        Starts: T.ScalarTensor((slots,), "int32"),  # prior tokens (page-aligned)
        Lens: T.ScalarTensor((slots,), "int32"),  # live tokens in the chunk
        Q: T.Tensor((slots, chunk * heads, width), dtype),
        KV: T.Tensor((slots, chunk, width), dtype),  # the chunk's own rows
        Pages: T.Tensor((num_pages, page_size, width), dtype),
        Output: T.Tensor((slots, chunk * heads, width), dtype),
    ):
        with T.Kernel(cpp, slots) as (bq, bz):
            Q_shared = T.alloc_shared((rows, width), dtype)
            Kc_shared = T.alloc_shared((chunk, width), dtype)
            Kp_shared = T.alloc_shared((page_size, width), dtype)
            acc_s = T.alloc_fragment((rows, page_size), accum_dtype)
            acc_c = T.alloc_fragment((rows, chunk), accum_dtype)
            # safe_div: rows past Lens are fully masked -> zeros, not nan
            ons = AC.OnlineSoftmax(rows, width, scale, accum_dtype,
                                   safe_div=True)

            T.copy(Q[bz, bq * rows, 0], Q_shared)
            T.copy(KV[bz, 0, 0], Kc_shared)

            # ---- prior latents, gathered through the block table ---------
            def load_prior(kp):
                T.copy(Pages[Tables[bz, kp], 0, 0], Kp_shared)
                return Kp_shared, Kp_shared  # V: the page, its latent lanes first

            q_lo = Starts[bz] + bq * page_size  # the cell's lowest query
            q_pos = lambda r: q_lo + r // heads

            def prior_mask(kp):
                k_pos = lambda j: kp * page_size + j
                m = AC.ragged(Starts[bz], k_pos)
                if window is not None:
                    m = AC.both(m, AC.banded(q_pos, k_pos, window))
                return m

            # the lowest query sees the positions [q_lo + 1 - window, q_lo]
            first, _ = AC.live_pages(q_lo + 1, page_size, window)
            live_cell = (bq * page_size) < Lens[bz]
            end = T.if_then_else(live_cell, Starts[bz] // page_size, 0)
            AC.attend(
                ons, acc_s, page_size, end, load_prior,
                lambda s, ks, kp: AC.scores(s, Q_shared, ks),
                prior_mask, num_stages=num_stages, start=first,
                max_extent=max_pages,
            )

            # ---- the chunk itself (rows straight from the KV input —
            # never read back through the pages we are writing) ----------
            AC.scores(acc_c, Q_shared, Kc_shared)
            in_pos = lambda r: bq * page_size + r // heads
            cmask = AC.both(
                AC.causal(in_pos, lambda j: j),
                AC.ragged(Lens[bz], lambda j: j),
            )
            if window is not None:
                cmask = AC.both(cmask, AC.banded(in_pos, lambda j: j, window))
            ons.update(acc_c, chunk, Kc_shared, cmask)

            ons.finalize(Output[bz, bq * rows, 0])

            # ---- the paged write: this cell's chunk page, placed through
            # the block table (scalar-prefetch output BlockSpec), same
            # self-defense as prefill_attention.py: dead chunk pages land
            # in the reserved garbage page 0, table index clamped ----------
            tidx = T.minimum(Starts[bz] // page_size + bq, max_pages - 1)
            dst_page = T.if_then_else(live_cell, Tables[bz, tidx], 0)
            T.copy(
                Kc_shared[bq * page_size : bq * page_size + page_size, :],
                Pages[dst_page, 0, 0],
            )

    return PrefillMLA


def mla_paged_quant_program(
    slots: int,
    heads: int,
    dim: int,
    pe_dim: int,
    page_size: int,
    max_pages: int,
    num_pages: int,
    block_H: int = 64,
    fmt: str = "int8",
    dtype: str = "float32",
    accum_dtype: str = "float32",
    num_stages: int = 2,
    sm_scale: Optional[float] = None,
    window: Optional[int] = None,
) -> TileProgram:
    """Quantized paged MLA decode: latent *and* rope pools stored packed
    int8 with per-token scales, dequantized inline through the
    :class:`attention_core.DequantStage` composition point.  V is the
    dequantized latent — exactly the fp kernel with ``load_kv`` swapped."""
    bh = min(block_H, heads)
    if heads % bh:
        raise ValueError("the head block must divide heads")
    pack = AC.KV_PACK[fmt]
    scale = (
        sm_scale if sm_scale is not None else 1.0 / math.sqrt(dim + pe_dim)
    ) * 1.44269504  # log2(e)

    @T.prim_func
    def PagedMLAQuant(
        Tables: T.ScalarTensor((slots, max_pages), "int32"),
        Lens: T.ScalarTensor((slots,), "int32"),
        Q: T.Tensor((slots, heads, dim), dtype),
        Q_pe: T.Tensor((slots, heads, pe_dim), dtype),
        KVPages: T.Tensor((num_pages, page_size, dim // pack), "int8"),
        KPePages: T.Tensor((num_pages, page_size, pe_dim // pack), "int8"),
        KVScales: T.Tensor((num_pages, page_size, 1), dtype),
        KPeScales: T.Tensor((num_pages, page_size, 1), dtype),
        Output: T.Tensor((slots, heads, dim), dtype),
    ):
        with T.Kernel(heads // bh, slots) as (by, bz):
            Q_shared = T.alloc_shared((bh, dim), dtype)
            Q_pe_shared = T.alloc_shared((bh, pe_dim), dtype)
            kvq = AC.DequantStage(page_size, dim, fmt, dtype)
            peq = AC.DequantStage(page_size, pe_dim, fmt, dtype)
            acc_s = T.alloc_fragment((bh, page_size), accum_dtype)
            ons = AC.OnlineSoftmax(bh, dim, scale, accum_dtype, safe_div=True)

            T.copy(Q[bz, by * bh, 0], Q_shared)
            T.copy(Q_pe[bz, by * bh, 0], Q_pe_shared)

            def load_kv(k):
                kv = kvq.load(KVPages[Tables[bz, k], 0, 0],
                              KVScales[Tables[bz, k], 0, 0])
                peq.load(KPePages[Tables[bz, k], 0, 0],
                         KPeScales[Tables[bz, k], 0, 0])
                return kv, kv  # V is the dequantized latent itself

            def mask(k):
                return AC.ragged(Lens[bz], lambda j: k * page_size + j, window)

            AC.attend(
                ons, acc_s, page_size, max_pages, load_kv,
                lambda s, ks, k: AC.scores(
                    s, Q_shared, ks, extra=[(Q_pe_shared, peq.out)]
                ),
                mask, num_stages=num_stages,
            )
            ons.finalize(Output[bz, by * bh, 0])

    return PagedMLAQuant


def mla_prefill_quant_program(
    slots: int,
    heads: int,
    dim: int,
    pe_dim: int,
    chunk: int,
    page_size: int,
    max_pages: int,
    num_pages: int,
    fmt: str = "int8",
    dtype: str = "float32",
    accum_dtype: str = "float32",
    num_stages: int = 2,
    sm_scale: Optional[float] = None,
    window: Optional[int] = None,
) -> TileProgram:
    """Quantized MLA chunked prefill: the chunk's latents/rope arrive
    pre-quantized (ops.py packs them), attend as the dequantized roundtrip,
    and the packed bytes + scales are written into the pools exactly as
    staged — the prefill_attention_quant composition with MLA's score
    split and the latent as V."""
    if chunk % page_size:
        raise ValueError("chunk must be a multiple of page_size")
    cpp = chunk // page_size
    rows = page_size * heads
    pack = AC.KV_PACK[fmt]
    scale = (
        sm_scale if sm_scale is not None else 1.0 / math.sqrt(dim + pe_dim)
    ) * 1.44269504  # log2(e)

    @T.prim_func
    def PrefillMLAQuant(
        Tables: T.ScalarTensor((slots, max_pages), "int32"),
        Starts: T.ScalarTensor((slots,), "int32"),  # prior tokens (page-aligned)
        Lens: T.ScalarTensor((slots,), "int32"),  # live tokens in the chunk
        Q: T.Tensor((slots, chunk * heads, dim), dtype),
        Q_pe: T.Tensor((slots, chunk * heads, pe_dim), dtype),
        CKV: T.Tensor((slots, chunk, dim // pack), "int8"),
        KPE: T.Tensor((slots, chunk, pe_dim // pack), "int8"),
        CKVScale: T.Tensor((slots, chunk, 1), dtype),
        KPEScale: T.Tensor((slots, chunk, 1), dtype),
        KVPages: T.Tensor((num_pages, page_size, dim // pack), "int8"),
        KPePages: T.Tensor((num_pages, page_size, pe_dim // pack), "int8"),
        KVScales: T.Tensor((num_pages, page_size, 1), dtype),
        KPeScales: T.Tensor((num_pages, page_size, 1), dtype),
        Output: T.Tensor((slots, chunk * heads, dim), dtype),
    ):
        with T.Kernel(cpp, slots) as (bq, bz):
            Q_shared = T.alloc_shared((rows, dim), dtype)
            Q_pe_shared = T.alloc_shared((rows, pe_dim), dtype)
            kc = AC.DequantStage(chunk, dim, fmt, dtype)
            pc = AC.DequantStage(chunk, pe_dim, fmt, dtype)
            kpq = AC.DequantStage(page_size, dim, fmt, dtype)
            ppq = AC.DequantStage(page_size, pe_dim, fmt, dtype)
            acc_s = T.alloc_fragment((rows, page_size), accum_dtype)
            acc_c = T.alloc_fragment((rows, chunk), accum_dtype)
            ons = AC.OnlineSoftmax(rows, dim, scale, accum_dtype,
                                   safe_div=True)

            T.copy(Q[bz, bq * rows, 0], Q_shared)
            T.copy(Q_pe[bz, bq * rows, 0], Q_pe_shared)
            Kc = kc.load(CKV[bz, 0, 0], CKVScale[bz, 0, 0])
            Pc = pc.load(KPE[bz, 0, 0], KPEScale[bz, 0, 0])

            # ---- prior latents: paged gather + inline dequant ------------
            def load_prior(kp):
                ks = kpq.load(KVPages[Tables[bz, kp], 0, 0],
                              KVScales[Tables[bz, kp], 0, 0])
                ppq.load(KPePages[Tables[bz, kp], 0, 0],
                         KPeScales[Tables[bz, kp], 0, 0])
                return ks, ks  # V is the dequantized latent itself

            q_pos = lambda r: Starts[bz] + bq * page_size + r // heads

            def prior_mask(kp):
                k_pos = lambda j: kp * page_size + j
                m = AC.ragged(Starts[bz], k_pos)
                if window is not None:
                    m = AC.both(m, AC.banded(q_pos, k_pos, window))
                return m

            AC.attend(
                ons, acc_s, page_size, max_pages, load_prior,
                lambda s, ks, kp: AC.scores(
                    s, Q_shared, ks, extra=[(Q_pe_shared, ppq.out)]
                ),
                prior_mask, num_stages=num_stages,
            )

            # ---- the chunk itself (dequantized roundtrip) ----------------
            AC.scores(acc_c, Q_shared, Kc, extra=[(Q_pe_shared, Pc)])
            in_pos = lambda r: bq * page_size + r // heads
            cmask = AC.both(
                AC.causal(in_pos, lambda j: j),
                AC.ragged(Lens[bz], lambda j: j),
            )
            if window is not None:
                cmask = AC.both(cmask, AC.banded(in_pos, lambda j: j, window))
            ons.update(acc_c, chunk, Kc, cmask)

            ons.finalize(Output[bz, bq * rows, 0])

            # ---- the paged write: packed bytes + scales as staged --------
            live_page = (bq * page_size) < Lens[bz]
            tidx = T.minimum(Starts[bz] // page_size + bq, max_pages - 1)
            dst_page = T.if_then_else(live_page, Tables[bz, tidx], 0)
            T.copy(
                kc.packed_rows(bq * page_size, bq * page_size + page_size),
                KVPages[dst_page, 0, 0],
            )
            T.copy(
                pc.packed_rows(bq * page_size, bq * page_size + page_size),
                KPePages[dst_page, 0, 0],
            )
            T.copy(
                kc.scale_shared[bq * page_size : bq * page_size + page_size, :],
                KVScales[dst_page, 0, 0],
            )
            T.copy(
                pc.scale_shared[bq * page_size : bq * page_size + page_size, :],
                KPeScales[dst_page, 0, 0],
            )

    return PrefillMLAQuant


# Tiny-shape configs for the pallas-vs-reference parity suite
# (tests/test_pipeline.py): the contiguous Fig. 18 kernel, the paged decode
# kernel (ragged lens through a block table) and the chunked-prefill kernel
# (multi-page chunk, in-kernel page writes).  The paged cases take their
# inputs from the override below — tables must hold valid page ids.  The
# _quant cases store both latent and rope pools packed (int8 / int4).
#
# Every fp paged case has a joint page of 128 lanes, so the Pallas lowering
# walks its live pages inside the kernel, as on the chip.  The ``lane``
# cases put a zero tail in that page (96 + 16 of 128 lanes) and run the
# bounded walk's edges: decode at ``EDGE_LENS`` (an empty slot, one token,
# one page, one past a page, the whole table), prefill at ``PREFILL_EDGE``:
# an idle slot (Lens 0 past a prior page), a fresh prompt (Starts 0), a
# chunk past page 0 and one whose second chunk page is dead; under a window
# of 20 the last chunk pages' walks start past page 0.
PREFILL_EDGE = dict(starts=(32, 0, 32, 16, 32), lens=(0, 20, 20, 32, 9))
_LANE = dict(heads=4, dim=96, pe_dim=16, page_size=16, num_pages=24)
_PAGED_EDGE = dict(_LANE, slots=len(EDGE_LENS), max_pages=3, block_H=2)
_PREFILL_EDGE = dict(_LANE, slots=len(PREFILL_EDGE["lens"]), chunk=32, max_pages=4)

PARITY_CASES = [
    (
        "mla",
        dict(batch=1, heads=4, kv_head_num=1, seqlen_kv=32, dim=16, pe_dim=8,
             block_N=16, block_H=2),
    ),
    (
        "mla_paged",
        dict(slots=3, heads=4, dim=16, pe_dim=8, page_size=16, max_pages=2,
             num_pages=8, block_H=2),
    ),
    (
        "mla_paged_windowed",
        dict(slots=3, heads=4, dim=16, pe_dim=8, page_size=16, max_pages=2,
             num_pages=8, block_H=2, window=12),
    ),
    ("mla_paged_lane_edge_lens", _PAGED_EDGE),
    ("mla_paged_lane_windowed_edge_lens", dict(_PAGED_EDGE, window=20)),
    (
        "mla_prefill",
        dict(slots=2, heads=2, dim=16, pe_dim=8, chunk=32, page_size=16,
             max_pages=4, num_pages=10),
    ),
    (
        "mla_prefill_windowed",
        dict(slots=2, heads=2, dim=16, pe_dim=8, chunk=32, page_size=16,
             max_pages=4, num_pages=10, window=20),
    ),
    ("mla_prefill_lane_edge_lens", _PREFILL_EDGE),
    ("mla_prefill_lane_windowed_edge_lens", dict(_PREFILL_EDGE, window=20)),
    (
        "mla_paged_quant_int8",
        dict(slots=3, heads=4, dim=16, pe_dim=8, page_size=16, max_pages=2,
             num_pages=8, block_H=2, fmt="int8"),
    ),
    (
        "mla_paged_quant_int4",
        dict(slots=2, heads=4, dim=16, pe_dim=8, page_size=16, max_pages=2,
             num_pages=8, block_H=2, fmt="int4"),
    ),
    (
        "mla_prefill_quant_int8",
        dict(slots=2, heads=2, dim=16, pe_dim=8, chunk=32, page_size=16,
             max_pages=4, num_pages=10, fmt="int8"),
    ),
    (
        "mla_prefill_quant_int4",
        dict(slots=2, heads=2, dim=16, pe_dim=8, chunk=32, page_size=16,
             max_pages=4, num_pages=10, fmt="int4"),
    ),
]


def parity_programs():
    for name, cfg in PARITY_CASES:
        if name == "mla":
            yield name, mla_program(**cfg)
        elif name.startswith("mla_paged_quant"):
            yield name, mla_paged_quant_program(**cfg)
        elif name.startswith("mla_paged"):
            yield name, mla_paged_program(**cfg)
        elif name.startswith("mla_prefill_quant"):
            yield name, mla_prefill_quant_program(**cfg)
        else:
            yield name, mla_prefill_program(**cfg)


def parity_inputs(name, program, rng):
    """Valid inputs for the paged parity cases: block tables drawn without
    replacement (each physical page owned by one slot), ragged lens, and —
    for the prefill kernel — page-aligned starts leaving room for the
    chunk's own pages (the serving engine's chunk contract).  Joint rows
    get a zero tail past ``dim + pe_dim``, and the chunk's rows past
    ``Lens`` are zero, so every dead chunk page writes the same zeros into
    the shared garbage page 0 whatever order the backend walks the grid."""
    if name == "mla":
        return None
    cfg = dict(PARITY_CASES)[name]
    slots, mp, np_ = cfg["slots"], cfg["max_pages"], cfg["num_pages"]
    ps = cfg["page_size"]
    pages = rng.permutation(np_ - 1)[: slots * mp] + 1  # page 0 reserved
    pages = pages.reshape(slots, mp).astype("int32")
    edge = name.endswith("edge_lens")
    if name.startswith("mla_paged"):
        if edge:
            lens = np.asarray(EDGE_LENS, "int32")
        else:
            lens = rng.integers(1, mp * ps + 1, size=slots).astype("int32")
        scalars = [pages, lens]
    else:
        chunk = cfg["chunk"]
        cpp = chunk // ps
        if edge:
            starts = np.asarray(PREFILL_EDGE["starts"], "int32")
            lens = np.asarray(PREFILL_EDGE["lens"], "int32")
        else:
            starts = (rng.integers(0, mp - cpp + 1, size=slots) * ps).astype("int32")
            # ragged within the last chunk page only
            lens = rng.integers(chunk - ps + 1, chunk + 1, size=slots).astype("int32")
        scalars = [pages, starts, lens]
    live = cfg["dim"] + cfg["pe_dim"]

    def fill(p):
        if str(p.dtype).startswith("int"):
            return rng.integers(-128, 128, size=p.shape).astype(p.dtype)
        if p.name.endswith(("Scale", "Scales")):
            return rng.uniform(0.05, 0.2, size=p.shape).astype(p.dtype)
        x = rng.standard_normal(p.shape).astype(p.dtype)
        if p.name in ("Q", "KV", "Pages"):  # joint rows: [latent | rope | 0]
            x[..., live:] = 0
        if p.name == "KV":
            x[np.arange(p.shape[1])[None, :] >= lens[:, None]] = 0
        return x

    args = list(scalars)
    for p in program.input_params()[len(scalars):]:
        args.append(fill(p))
    # in-out page pools ride after the pure inputs (aliased operands)
    for p in program.output_params():
        if p.name.endswith(("Pages", "Scales")):
            args.append(fill(p))
    return args
