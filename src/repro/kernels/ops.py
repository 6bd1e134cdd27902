"""Public kernel API: jit'd wrappers dispatching tile-DSL Pallas kernels or
the pure-jnp reference (XLA) path.

Backend selection (``kernel_backend``):

* ``"pallas"`` — compile the tile-DSL program via repro.core.  On CPU hosts
  the kernel runs in Pallas interpreter mode (bit-faithful to the TPU
  lowering's dataflow); on TPU it is the Mosaic-compiled kernel.
* ``"xla"``    — the ref.py oracle, letting XLA fuse (used by the model layer
  for the multi-pod dry-run, where kernels must trace through SPMD
  partitioning).
* ``"auto"``   — pallas on TPU, xla elsewhere.

A call that asks for the tile kernel but cannot get it — a feature the kernel
lacks (soft-capped logits, a chunk off the page grid), or no TPU attached so
the kernel is interpreted — still runs, on the oracle or the interpreter,
and is counted in :data:`FALLBACKS`.  On the chip the serving path must
leave it empty (``chip_smoke.py`` asserts so).

Compiled tile kernels are cached per (kernel, static config) — the TPU
realization of the paper's "dynamic parameter simplification" for kernel
libraries: a library entry recompiles per shape bucket and reuses the cached
schedule.  The local dict below only skips *re-tracing* the program factory;
the compile itself is additionally memoized inside repro.core.compiler on
(program fingerprint, schedule, target), shared with autotune and serving.
"""
from __future__ import annotations

import collections
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Schedule, analyze, compile as tl_compile

from . import attention_core as AC
from . import ref
from .dequant_matmul import dequant_matmul_program
from .flash_attention import flash_attention_program
from .linear_attention import chunk_scan_program, chunk_state_program
from .matmul import matmul_program
from .mla import (
    mla_paged_program,
    mla_paged_quant_program,
    mla_prefill_program,
    mla_prefill_quant_program,
    mla_program,
)
from .paged_attention import paged_attention_program, paged_attention_quant_program
from .prefill_attention import (
    prefill_attention_program,
    prefill_attention_quant_program,
)

_CACHE: dict = {}
# (op, reason) -> times a tile-kernel request ran something else (counted
# at trace time, so once per compiled shape, not per call)
FALLBACKS: "collections.Counter[Tuple[str, str]]" = collections.Counter()

# Obligation kinds (core.lowering.verify.Obligation) that guard_dispatch
# discharges.  A future kernel emitting a new kind must either extend the
# guard or keep the obligation out of the serving dispatch path; the test
# suite asserts every paged kernel's obligations stay within this set.
GUARDED_KINDS = frozenset({"table_in_range", "table_writes_disjoint"})


def guard_dispatch(tables, num_pages, page_size, work):
    """Discharge the static verifier's runtime obligations for one paged
    dispatch, before any page is read or written.

    ``tables`` is the (rows, max_pages) block table, ``num_pages`` the pool
    extent on the page axis (page 0 reserved as the garbage sink), and
    ``work`` an iterable of ``(row, read_end, write_begin, write_end)``
    token positions: the row will read KV for positions ``[0, read_end)``
    and write positions ``[write_begin, write_end)``.

    Checks (cheap, host-side, O(tokens) ints):

    * capacity — ``read_end``/``write_end`` within ``max_pages*page_size``;
    * ``table_in_range`` — every entry backing a live position lies in
      ``[1, num_pages)`` (0 is the reserved sink: a live position mapped
      there would read garbage or lose its write);
    * ``table_writes_disjoint`` — no page is written by two rows, written
      twice within a row, or written by one row while live in another.

    All violations are collected and raised as one :class:`GuardError`
    (``.violations`` = list of ``(row, kind, message)``) so a batch
    dispatcher can fail exactly the offending rows and keep the rest.
    """
    import numpy as np

    from repro.core.errors import GuardError

    tb = np.asarray(tables)
    max_pages = tb.shape[1]
    capacity = max_pages * page_size
    violations = []
    live: dict = {}  # row -> np entries backing positions [0, read_end)
    writes: dict = {}  # row -> np entries written in [write_begin, write_end)
    for row, read_end, wbeg, wend in work:
        if read_end > capacity or wend > capacity:
            violations.append(
                (row, "table_in_range",
                 f"length {max(read_end, wend)} exceeds page capacity "
                 f"{capacity} ({max_pages} pages x {page_size})")
            )
            continue
        n_live = -(-int(read_end) // page_size)
        entries = tb[row, :n_live].astype(np.int64)
        bad = np.flatnonzero((entries < 1) | (entries >= num_pages))
        if bad.size:
            j = int(bad[0])
            violations.append(
                (row, "table_in_range",
                 f"entry {j} is page {int(entries[j])}, not in "
                 f"[1, {num_pages}) (page 0 is the reserved sink)")
            )
            continue
        live[row] = entries
        if wend > wbeg:
            pbeg, pend = int(wbeg) // page_size, -(-int(wend) // page_size)
            writes[row] = tb[row, pbeg:pend].astype(np.int64)

    writer_of: dict = {}  # page -> first writer row
    bad_rows = set()
    for row, pages in writes.items():
        for pg in pages.tolist():
            other = writer_of.get(pg)
            if other is not None and (other != row or
                                      pages.tolist().count(pg) > 1):
                for r in {row, other} - bad_rows:
                    violations.append(
                        (r, "table_writes_disjoint",
                         f"page {pg} written by rows {other} and {row}")
                    )
                bad_rows.update({row, other})
            else:
                writer_of[pg] = row
    for row, pages in writes.items():
        if row in bad_rows:
            continue
        pset = set(pages.tolist())
        for other, lv in live.items():
            if other == row:
                continue
            shared = pset.intersection(lv.tolist())
            if shared:
                violations.append(
                    (row, "table_writes_disjoint",
                     f"page {sorted(shared)[0]} written by row {row} while "
                     f"live in row {other}")
                )
                bad_rows.add(row)
                break
    if violations:
        raise GuardError(violations)


def default_backend() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _fallback(op: str, reason: str) -> None:
    FALLBACKS[(op, reason)] += 1


def _schedule(op: str) -> Schedule:
    """Mosaic-compiled for the attached chip; the interpreter off the TPU."""
    if jax.default_backend() == "tpu":
        return Schedule(device_kind=jax.devices()[0].device_kind)
    _fallback(op, "interpreted: no TPU attached")
    return Schedule(interpret=True)


def _cached(key, builder):
    if key not in _CACHE:
        _CACHE[key] = tl_compile(builder(), _schedule(key[0]))
    return _CACHE[key]


def _oracle_reason(logit_soft_cap=None, chunk=None, page_size=None,
                   max_pages=None, **xla_kw) -> Optional[str]:
    """Why a tile-kernel request must run the oracle instead (None: it
    need not).  ``xla_kw`` holds the attention options no kernel takes."""
    if logit_soft_cap is not None:
        return "logit soft-cap"
    for name in ("window", "kv_len"):
        if xla_kw.get(name) is not None:
            return name
    if chunk is not None and (chunk % page_size or chunk // page_size > max_pages):
        return "chunk off the page grid"
    return None


def _resolve(backend: Optional[str]) -> str:
    return backend or default_backend()


def paged_walk(seq_lens, page_size: int, max_pages: int,
               window: Optional[int] = None, *, head_dim: int,
               kv_dtype: Optional[str] = None, attention: str = "gqa",
               logit_soft_cap=None, backend: Optional[str] = None) -> np.ndarray:
    """KV pages one KV head's decode attention visits for each slot of
    lengths ``seq_lens``, as this module runs the call
    (``paged_attention(_quant)``, or ``mla_paged(_quant)`` for
    ``attention="mla"``, whose one KV head is ``head_dim`` = latent + rope
    wide): the slot's live pages (the extent the kernel gives its page
    loop, ``attention_core.live_pages``, clamped to the table; 0 for an
    empty slot) where it walks them in the kernel (:func:`_walks_live_pages`),
    else the whole table, ``max_pages``."""
    lens = np.asarray(seq_lens, np.int64)
    if not _walks_live_pages(_resolve(backend), logit_soft_cap, attention,
                             head_dim, kv_dtype, False):
        return np.full(lens.shape, max_pages, np.int64)
    first, end = AC.live_pages(lens, page_size, window)
    return np.clip(end, 0, max_pages) - np.clip(first, 0, max_pages)


def prefill_walk(start_lens, chunk_lens, chunk: int, page_size: int,
                 max_pages: int, window: Optional[int] = None, *,
                 head_dim: int, kv_dtype: Optional[str] = None,
                 attention: str = "gqa", logit_soft_cap=None,
                 backend: Optional[str] = None) -> Tuple[np.ndarray, np.ndarray]:
    """``(walked, table)`` per slot: the prior pages one KV head's
    chunked-prefill attention visits for a slot of ``start_lens`` prior
    tokens and ``chunk_lens`` live chunk tokens, summed over the kernel's
    ``chunk // page_size`` grid cells, and the pages those cells' tables
    hold (cells × ``max_pages``), as this module runs the call
    (``prefill_attention(_quant)``, or ``mla_prefill(_quant)`` for
    ``attention="mla"``).  Where the kernel walks in the kernel
    (:func:`_walks_live_pages`), a cell holding a live row visits the
    pages ``[0, start // page_size)`` — from the first page the window
    reaches from the cell's lowest query under a window — and a cell with
    none visits nothing; elsewhere every cell visits the whole table."""
    starts = np.asarray(start_lens, np.int64)[:, None]
    lens = np.asarray(chunk_lens, np.int64)[:, None]
    cells = np.arange(max(chunk // page_size, 1))[None, :]
    table = np.full(starts.shape[0], cells.size * max_pages, np.int64)
    if _oracle_reason(logit_soft_cap, chunk, page_size, max_pages) or not (
            _walks_live_pages(_resolve(backend), logit_soft_cap, attention,
                              head_dim, kv_dtype, True)):
        return table, table
    first, _ = AC.live_pages(starts + cells * page_size + 1, page_size, window)
    end = np.where(cells * page_size < lens, starts // page_size, 0)
    first, end = np.clip(first, 0, max_pages), np.clip(end, 0, max_pages)
    return np.maximum(end - first, 0).sum(axis=1), table


@functools.lru_cache(maxsize=None)
def _walks_live_pages(backend: str, logit_soft_cap, attention: str,
                      head_dim: int, kv_dtype: Optional[str],
                      prefill: bool) -> bool:
    """Whether a paged decode (or chunked-prefill) call visits only live
    pages: it runs the tile kernel (not the oracle, :func:`_oracle_reason`),
    the kernel bounds its page loop, and the lowering walks that loop
    inside the kernel (``GridPlan.walk``, asked of the kernel at a small
    shape with the same tiles; a loop it cannot walk there runs every
    table page).  The quantized MLA kernels keep two packed pools whose
    ``(page, 1)`` scale columns no lowering DMAs by hand, and a static
    page loop."""
    if backend == "xla" or _oracle_reason(logit_soft_cap):
        return False
    shape = dict(slots=1, page_size=16, max_pages=2, num_pages=2)
    if attention == "mla":
        if kv_dtype:
            return False
        mla = dict(shape, heads=1, dim=head_dim, pe_dim=0)
        prog = (mla_prefill_program(chunk=16, **mla) if prefill
                else mla_paged_program(**mla))
    else:
        gqa = dict(shape, heads=1, kv_heads=1, head_dim=head_dim)
        if prefill:
            prog = (prefill_attention_quant_program(chunk=16, fmt=kv_dtype, **gqa)
                    if kv_dtype else prefill_attention_program(chunk=16, **gqa))
        else:
            prog = (paged_attention_quant_program(fmt=kv_dtype, **gqa) if kv_dtype
                    else paged_attention_program(**gqa))
    return analyze(prog, Schedule()).grid_plan.walk


def _pick_block(n: int, candidates=(128, 64, 32, 16, 8)) -> int:
    for c in candidates:
        if n % c == 0:
            return c
    return n


# ---------------------------------------------------------------------------
# GEMM
# ---------------------------------------------------------------------------


def matmul(a, b, *, out_dtype=None, backend: Optional[str] = None,
           block_m: Optional[int] = None, block_n: Optional[int] = None,
           block_k: Optional[int] = None, num_stages: int = 2):
    out_dtype = out_dtype or a.dtype
    if _resolve(backend) == "xla":
        return ref.matmul(a, b, out_dtype)
    M, K = a.shape
    _, N = b.shape
    bm = block_m or _pick_block(M)
    bn = block_n or _pick_block(N)
    bk = block_k or _pick_block(K, (256, 128, 64, 32, 16, 8))
    key = ("matmul", M, N, K, str(a.dtype), str(out_dtype), bm, bn, bk, num_stages)
    kern = _cached(
        key,
        lambda: matmul_program(
            M, N, K, str(a.dtype), str(jnp.dtype(out_dtype)), "float32",
            bm, bn, bk, num_stages,
        ),
    )
    return kern(a, b)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def attention(q, k, v, *, causal: bool = False, sm_scale=None,
              backend: Optional[str] = None, block_m: Optional[int] = None,
              block_n: Optional[int] = None, num_stages: int = 2, **xla_kw):
    be = _resolve(backend)
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    bm = block_m or _pick_block(sq)
    bn = block_n or _pick_block(sk)
    why = _oracle_reason(**xla_kw)
    if be == "xla" or why:
        if be != "xla":
            _fallback("fa", why)
        return ref.attention(q, k, v, causal=causal, sm_scale=sm_scale, **xla_kw)
    key = ("fa", b, hq, hkv, sq, sk, d, causal, str(q.dtype), bm, bn,
           num_stages, sm_scale)
    kern = _cached(
        key,
        lambda: flash_attention_program(
            b, hq, hkv, sq, sk, d, causal, bm, bn, str(q.dtype), "float32",
            num_stages, sm_scale,
        ),
    )
    return kern(q, k, v)


def paged_attention(q, k_pages, v_pages, block_tables, seq_lens, *,
                    sm_scale=None, window: Optional[int] = None,
                    logit_soft_cap=None, backend: Optional[str] = None,
                    num_stages: int = 2):
    """Single-token decode attention over a paged KV pool (see
    kernels/paged_attention.py for shapes).  The Pallas path gathers pages
    through the block table via scalar prefetch; the XLA path is
    ref.paged_attention (used by the serving engine on CPU hosts)."""
    be = _resolve(backend)
    why = _oracle_reason(logit_soft_cap)
    if be == "xla" or why:
        if be != "xla":
            _fallback("paged", why)
        return ref.paged_attention(
            q, k_pages, v_pages, block_tables, seq_lens, sm_scale=sm_scale,
            window=window, logit_soft_cap=logit_soft_cap,
        )
    b, hq, d = q.shape
    hkv, num_pages, page_size, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    key = ("paged", b, hq, hkv, num_pages, page_size, max_pages, d, window,
           str(q.dtype), num_stages, sm_scale)
    kern = _cached(
        key,
        lambda: paged_attention_program(
            b, hq, hkv, d, page_size, max_pages, num_pages, window,
            str(q.dtype), "float32", num_stages, sm_scale,
        ),
    )
    # pack queries with their GQA group: head h*group + g -> [h, g]
    qp = q.reshape(b, hkv, hq // hkv, d)
    return kern(block_tables, seq_lens, qp, k_pages, v_pages).reshape(b, hq, d)


def prefill_attention(q, k_new, v_new, k_pages, v_pages, block_tables,
                      start_lens, chunk_lens, *, sm_scale=None,
                      window: Optional[int] = None, logit_soft_cap=None,
                      backend: Optional[str] = None, num_stages: int = 2):
    """Chunked-prefill attention over a paged KV pool.

    ``q``/``k_new``/``v_new`` are the chunk's (B, H*, C, D) projections;
    ``start_lens`` (B,) counts prior resident tokens (the chunk's write
    offset) and ``chunk_lens`` (B,) the live tokens within the chunk.
    Returns ``(out, k_pages', v_pages')`` — the chunk's K/V are written into
    the pool pages through the block table, positions past ``chunk_lens``
    landing in the reserved garbage page 0.

    The Pallas path runs the tile kernel, which performs the page writes
    from inside the kernel via table-directed output BlockSpecs; it
    additionally requires chunk-aligned ``start_lens`` and in-range table
    entries (the serving engine's chunk contract).  The XLA path is the
    ref.prefill_attention oracle plus an explicit masked scatter.
    """
    be = _resolve(backend)
    b, hq, chunk, d = q.shape
    hkv, num_pages, page_size, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    why = _oracle_reason(logit_soft_cap, chunk, page_size, max_pages)
    if be != "xla" and why:
        _fallback("prefill", why)
    if be != "xla" and not why:
        group = hq // hkv
        key = ("prefill", b, hq, hkv, num_pages, page_size, max_pages, chunk,
               d, window, str(q.dtype), num_stages, sm_scale)
        kern = _cached(
            key,
            lambda: prefill_attention_program(
                b, hq, hkv, d, chunk, page_size, max_pages, num_pages, window,
                str(q.dtype), "float32", num_stages, sm_scale,
            ),
        )
        # pack queries chunk-major with their GQA group: row = i*group + g
        qp = q.reshape(b, hkv, group, chunk, d).transpose(0, 1, 3, 2, 4)
        qp = qp.reshape(b, hkv, chunk * group, d)
        kp, vp, out = kern(
            block_tables, start_lens, chunk_lens, qp, k_new, v_new,
            k_pages, v_pages,
        )
        out = out.reshape(b, hkv, chunk, group, d).transpose(0, 1, 3, 2, 4)
        return out.reshape(b, hq, chunk, d), kp, vp

    # ---- XLA path: masked scatter + gather through the table -------------
    pos = start_lens[:, None].astype(jnp.int32) + jnp.arange(chunk, dtype=jnp.int32)
    logical = jnp.clip(pos // page_size, 0, max_pages - 1)
    phys = jnp.take_along_axis(block_tables, logical, axis=1)  # (B, C)
    valid = jnp.arange(chunk)[None, :] < chunk_lens[:, None]
    phys = jnp.where(valid, phys, 0)  # dead tail -> reserved garbage page
    off = pos % page_size
    k_pages, v_pages = jnp.asarray(k_pages), jnp.asarray(v_pages)
    pdt = k_pages.dtype
    kp = k_pages.at[:, phys, off].set(
        jnp.asarray(k_new).transpose(1, 0, 2, 3).astype(pdt)
    )
    vp = v_pages.at[:, phys, off].set(
        jnp.asarray(v_new).transpose(1, 0, 2, 3).astype(pdt)
    )

    def gathered(pages):
        g = pages[:, block_tables]  # (Hkv, B, max_pages, page_size, D)
        return jnp.moveaxis(g, 0, 1).reshape(b, hkv, -1, d)

    s_total = max_pages * page_size
    si = jnp.arange(s_total, dtype=jnp.int32)
    ctx_pos = jnp.where(si[None, :] < start_lens[:, None], si[None, :], -1)
    out = ref.prefill_attention(
        q, k_new, v_new, gathered(k_pages), gathered(v_pages), ctx_pos, pos,
        chunk_lens, sm_scale=sm_scale, window=window,
        logit_soft_cap=logit_soft_cap,
    )
    return out, kp, vp


def paged_attention_quant(q, k_pages, v_pages, k_scales, v_scales,
                          block_tables, seq_lens, *, fmt: str = "int8",
                          sm_scale=None, window: Optional[int] = None,
                          logit_soft_cap=None, backend: Optional[str] = None,
                          num_stages: int = 2):
    """Quantized paged decode: packed int8 K/V pools + per-token scale
    columns (see kernels/paged_attention.py).  The Pallas path dequantizes
    page-at-a-time inside the kernel (DequantStage); the XLA path is
    ref.paged_attention_quant (dequantize pools, then the fp oracle)."""
    be = _resolve(backend)
    why = _oracle_reason(logit_soft_cap)
    if be == "xla" or why:
        if be != "xla":
            _fallback("paged_q", why)
        return ref.paged_attention_quant(
            q, k_pages, v_pages, k_scales, v_scales, block_tables, seq_lens,
            fmt=fmt, sm_scale=sm_scale, window=window,
            logit_soft_cap=logit_soft_cap,
        )
    b, hq, d = q.shape
    hkv, num_pages, page_size, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    key = ("paged_q", fmt, b, hq, hkv, num_pages, page_size, max_pages, d,
           window, str(q.dtype), num_stages, sm_scale)
    kern = _cached(
        key,
        lambda: paged_attention_quant_program(
            b, hq, hkv, d, page_size, max_pages, num_pages, fmt, window,
            str(q.dtype), "float32", num_stages, sm_scale,
        ),
    )
    qp = q.reshape(b, hkv, hq // hkv, d)
    out = kern(block_tables, seq_lens, qp, k_pages, v_pages, k_scales, v_scales)
    return out.reshape(b, hq, d)


def prefill_attention_quant(q, k_new, v_new, k_pages, v_pages, k_scales,
                            v_scales, block_tables, start_lens, chunk_lens, *,
                            fmt: str = "int8", sm_scale=None,
                            window: Optional[int] = None, logit_soft_cap=None,
                            backend: Optional[str] = None, num_stages: int = 2):
    """Quantized chunked prefill: quantizes the chunk's fp K/V per token
    here (the write-time quantization point), then either the tile kernel
    (packed chunk in, packed page + scale writes from inside the kernel) or
    the XLA masked scatter + oracle.  Both paths attend the *dequantized
    roundtrip* of the chunk — what every later decode step will read back —
    so prefill and decode see one consistent cache.

    Returns ``(out, k_pages', v_pages', k_scales', v_scales')``.
    """
    be = _resolve(backend)
    b, hq, chunk, d = q.shape
    hkv, num_pages, page_size, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    kq, ks_new = ref.quantize_rows(k_new, fmt)
    vq, vs_new = ref.quantize_rows(v_new, fmt)
    why = _oracle_reason(logit_soft_cap, chunk, page_size, max_pages)
    if be != "xla" and why:
        _fallback("prefill_q", why)
    if be != "xla" and not why:
        group = hq // hkv
        key = ("prefill_q", fmt, b, hq, hkv, num_pages, page_size, max_pages,
               chunk, d, window, str(q.dtype), num_stages, sm_scale)
        kern = _cached(
            key,
            lambda: prefill_attention_quant_program(
                b, hq, hkv, d, chunk, page_size, max_pages, num_pages, fmt,
                window, str(q.dtype), "float32", num_stages, sm_scale,
            ),
        )
        # pack queries chunk-major with their GQA group: row = i*group + g
        qp = q.reshape(b, hkv, group, chunk, d).transpose(0, 1, 3, 2, 4)
        qp = qp.reshape(b, hkv, chunk * group, d)
        kp, vp, ksp, vsp, out = kern(
            block_tables, start_lens, chunk_lens, qp, kq, vq, ks_new, vs_new,
            k_pages, v_pages, k_scales, v_scales,
        )
        out = out.reshape(b, hkv, chunk, group, d).transpose(0, 1, 3, 2, 4)
        return out.reshape(b, hq, chunk, d), kp, vp, ksp, vsp

    # ---- XLA path: masked scatter of packed bytes + scales, then the
    # oracle over the dequantized gather -----------------------------------
    pos = start_lens[:, None].astype(jnp.int32) + jnp.arange(chunk, dtype=jnp.int32)
    logical = jnp.clip(pos // page_size, 0, max_pages - 1)
    phys = jnp.take_along_axis(block_tables, logical, axis=1)  # (B, C)
    valid = jnp.arange(chunk)[None, :] < chunk_lens[:, None]
    phys = jnp.where(valid, phys, 0)  # dead tail -> reserved garbage page
    off = pos % page_size
    k_pages, v_pages = jnp.asarray(k_pages), jnp.asarray(v_pages)
    k_scales, v_scales = jnp.asarray(k_scales), jnp.asarray(v_scales)
    kp = k_pages.at[:, phys, off].set(kq.transpose(1, 0, 2, 3))
    vp = v_pages.at[:, phys, off].set(vq.transpose(1, 0, 2, 3))
    sdt = k_scales.dtype
    ksp = k_scales.at[:, phys, off].set(ks_new.transpose(1, 0, 2, 3).astype(sdt))
    vsp = v_scales.at[:, phys, off].set(vs_new.transpose(1, 0, 2, 3).astype(sdt))

    def gathered(pages, scales):
        g = ref.dequantize_rows(pages, scales, fmt).astype(q.dtype)
        g = g[:, block_tables]  # (Hkv, B, max_pages, page_size, D)
        return jnp.moveaxis(g, 0, 1).reshape(b, hkv, -1, d)

    k_new_dq = ref.dequantize_rows(kq, ks_new, fmt).astype(q.dtype)
    v_new_dq = ref.dequantize_rows(vq, vs_new, fmt).astype(q.dtype)
    s_total = max_pages * page_size
    si = jnp.arange(s_total, dtype=jnp.int32)
    ctx_pos = jnp.where(si[None, :] < start_lens[:, None], si[None, :], -1)
    out = ref.prefill_attention(
        q, k_new_dq, v_new_dq, gathered(kp, ksp), gathered(vp, vsp), ctx_pos,
        pos, chunk_lens, sm_scale=sm_scale, window=window,
        logit_soft_cap=logit_soft_cap,
    )
    return out, kp, vp, ksp, vsp


def mla(q, q_pe, kv, k_pe, *, sm_scale=None, backend: Optional[str] = None,
        block_n: Optional[int] = None, block_h: int = 64, num_stages: int = 2):
    be = _resolve(backend)
    if be == "xla":
        return ref.mla(q, q_pe, kv, k_pe, sm_scale=sm_scale)
    b, h, d = q.shape
    pe = q_pe.shape[-1]
    s, hkv = kv.shape[1], kv.shape[2]
    bn = block_n or _pick_block(s)
    group = h // hkv
    bh = min(block_h, group)
    key = ("mla", b, h, hkv, s, d, pe, str(q.dtype), bn, bh, num_stages,
           sm_scale)
    kern = _cached(
        key,
        lambda: mla_program(
            b, h, hkv, s, d, pe, bn, bh, str(q.dtype), "float32", num_stages, sm_scale
        ),
    )
    return kern(q, q_pe, kv, k_pe)


def mla_paged(q_lat, q_pe, pages, block_tables, seq_lens, *,
              sm_scale=None, window: Optional[int] = None,
              logit_soft_cap: Optional[float] = None,
              backend: Optional[str] = None, block_h: int = 64,
              num_stages: int = 2):
    """Paged MLA decode: latent queries (B, H, R) and rope queries
    (B, H, Dpe) against the joint page pool ``(P, page, W)`` of rows
    ``[latent | rope | 0]`` gathered through a block table (see
    kernels/mla.py).  The Pallas path is the scalar-prefetch tile kernel,
    which walks each slot's live pages; the XLA path is ref.mla_paged (what
    the serving engine runs on CPU hosts).  Soft-capped models route to the
    oracle — same policy as paged_attention.  Returns (B, H, R)."""
    be = _resolve(backend)
    why = _oracle_reason(logit_soft_cap)
    if be == "xla" or why:
        if be != "xla":
            _fallback("mla_paged", why)
        return ref.mla_paged(q_lat, q_pe, pages, block_tables, seq_lens,
                             sm_scale=sm_scale, window=window,
                             logit_soft_cap=logit_soft_cap)
    b, h, r = q_lat.shape
    pe = q_pe.shape[-1]
    num_pages, page_size, width = pages.shape
    max_pages = block_tables.shape[1]
    bh = min(block_h, h)
    while h % bh:
        bh -= 1
    key = ("mla_paged", b, h, r, pe, num_pages, page_size, max_pages,
           str(q_lat.dtype), bh, num_stages, sm_scale, window)
    kern = _cached(
        key,
        lambda: mla_paged_program(
            b, h, r, pe, page_size, max_pages, num_pages, bh,
            str(q_lat.dtype), "float32", num_stages, sm_scale, window,
        ),
    )
    out = kern(block_tables, seq_lens, ref.mla_rows(q_lat, q_pe, width), pages)
    return out[..., :r]


def mla_prefill(q_lat, q_pe, ckv_new, kpe_new, pages, block_tables,
                start_lens, chunk_lens, *, sm_scale=None,
                window: Optional[int] = None,
                logit_soft_cap: Optional[float] = None,
                backend: Optional[str] = None, num_stages: int = 2):
    """MLA chunked prefill over the joint latent page pool.

    ``q_lat``/``q_pe`` are the chunk's absorbed queries (B, H, C, ·);
    ``ckv_new``/``kpe_new`` (B, C, ·) the chunk's own latents;
    ``start_lens`` (B,) prior resident tokens (the chunk's write offset)
    and ``chunk_lens`` (B,) the live tokens within the chunk.  Returns
    ``(out, pages')`` — the chunk's joint rows ``[latent | rope | 0]`` are
    written into the pool pages through the block table, dead positions
    landing in the reserved garbage page 0.  Same contract split as
    :func:`prefill_attention`: the Pallas tile kernel writes pages from
    inside the kernel, walks only the prior pages its live rows need and
    requires chunk-aligned starts; the XLA path is the ref.mla_prefill
    oracle plus an explicit masked scatter.
    """
    be = _resolve(backend)
    b, h, chunk, r = q_lat.shape
    pe = q_pe.shape[-1]
    num_pages, page_size, width = pages.shape
    max_pages = block_tables.shape[1]
    rows_new = ref.mla_rows(jnp.asarray(ckv_new), jnp.asarray(kpe_new), width)
    why = _oracle_reason(logit_soft_cap, chunk, page_size, max_pages)
    if be != "xla" and why:
        _fallback("mla_prefill", why)
    if be != "xla" and not why:
        key = ("mla_prefill", b, h, r, pe, num_pages, page_size, max_pages,
               chunk, str(q_lat.dtype), num_stages, sm_scale, window)
        kern = _cached(
            key,
            lambda: mla_prefill_program(
                b, h, r, pe, chunk, page_size, max_pages, num_pages,
                str(q_lat.dtype), "float32", num_stages, sm_scale, window,
            ),
        )
        # pack queries chunk-major with their head: row = i*heads + h
        qp = ref.mla_rows(q_lat, q_pe, width).transpose(0, 2, 1, 3)
        pages, out = kern(
            block_tables, start_lens, chunk_lens,
            qp.reshape(b, chunk * h, width), rows_new.astype(q_lat.dtype), pages,
        )
        out = out.reshape(b, chunk, h, width)[..., :r].transpose(0, 2, 1, 3)
        return out, pages

    # ---- XLA path: masked scatter + gather through the table -------------
    pos = start_lens[:, None].astype(jnp.int32) + jnp.arange(chunk, dtype=jnp.int32)
    logical = jnp.clip(pos // page_size, 0, max_pages - 1)
    phys = jnp.take_along_axis(block_tables, logical, axis=1)  # (B, C)
    valid = jnp.arange(chunk)[None, :] < chunk_lens[:, None]
    phys = jnp.where(valid, phys, 0)  # dead tail -> reserved garbage page
    off = pos % page_size
    pages = jnp.asarray(pages)
    pages = pages.at[phys, off].set(rows_new.astype(pages.dtype))

    ctx = pages[block_tables].reshape(b, -1, width)
    s_total = max_pages * page_size
    si = jnp.arange(s_total, dtype=jnp.int32)
    ctx_pos = jnp.where(si[None, :] < start_lens[:, None], si[None, :], -1)
    out = ref.mla_prefill(
        q_lat, q_pe, ckv_new, kpe_new, ctx[..., :r], ctx[..., r : r + pe],
        ctx_pos, pos, chunk_lens, sm_scale=sm_scale, window=window,
        logit_soft_cap=logit_soft_cap,
    )
    return out, pages


def mla_paged_quant(q_lat, q_pe, ckv_pages, kpe_pages, ckv_scales, kpe_scales,
                    block_tables, seq_lens, *, fmt: str = "int8",
                    sm_scale=None, window: Optional[int] = None,
                    logit_soft_cap: Optional[float] = None,
                    backend: Optional[str] = None, block_h: int = 64,
                    num_stages: int = 2):
    """Quantized paged MLA decode: packed latent + rope pools with
    per-token scale columns.  Pallas path dequantizes inline
    (DequantStage); XLA path is ref.mla_paged_quant."""
    be = _resolve(backend)
    why = _oracle_reason(logit_soft_cap)
    if be == "xla" or why:
        if be != "xla":
            _fallback("mla_paged_q", why)
        return ref.mla_paged_quant(
            q_lat, q_pe, ckv_pages, kpe_pages, ckv_scales, kpe_scales,
            block_tables, seq_lens, fmt=fmt, sm_scale=sm_scale, window=window,
            logit_soft_cap=logit_soft_cap,
        )
    b, h, r = q_lat.shape
    pe = q_pe.shape[-1]
    num_pages, page_size, _ = ckv_pages.shape
    max_pages = block_tables.shape[1]
    bh = min(block_h, h)
    while h % bh:
        bh -= 1
    key = ("mla_paged_q", fmt, b, h, r, pe, num_pages, page_size, max_pages,
           str(q_lat.dtype), bh, num_stages, sm_scale, window)
    kern = _cached(
        key,
        lambda: mla_paged_quant_program(
            b, h, r, pe, page_size, max_pages, num_pages, bh, fmt,
            str(q_lat.dtype), "float32", num_stages, sm_scale, window,
        ),
    )
    return kern(block_tables, seq_lens, q_lat, q_pe, ckv_pages, kpe_pages,
                ckv_scales, kpe_scales)


def mla_prefill_quant(q_lat, q_pe, ckv_new, kpe_new, ckv_pages, kpe_pages,
                      ckv_scales, kpe_scales, block_tables, start_lens,
                      chunk_lens, *, fmt: str = "int8", sm_scale=None,
                      window: Optional[int] = None,
                      logit_soft_cap: Optional[float] = None,
                      backend: Optional[str] = None, num_stages: int = 2):
    """Quantized MLA chunked prefill: quantizes the chunk's latents/rope per
    token here (write-time quantization), attends the dequantized roundtrip
    and writes packed pages + scales.  Returns
    ``(out, ckv_pages', kpe_pages', ckv_scales', kpe_scales')``."""
    be = _resolve(backend)
    b, h, chunk, r = q_lat.shape
    pe = q_pe.shape[-1]
    num_pages, page_size, _ = ckv_pages.shape
    max_pages = block_tables.shape[1]
    cq, cs_new = ref.quantize_rows(ckv_new, fmt)
    pq, ps_new = ref.quantize_rows(kpe_new, fmt)
    why = _oracle_reason(logit_soft_cap, chunk, page_size, max_pages)
    if be != "xla" and why:
        _fallback("mla_prefill_q", why)
    if be != "xla" and not why:
        key = ("mla_prefill_q", fmt, b, h, r, pe, num_pages, page_size,
               max_pages, chunk, str(q_lat.dtype), num_stages, sm_scale, window)
        kern = _cached(
            key,
            lambda: mla_prefill_quant_program(
                b, h, r, pe, chunk, page_size, max_pages, num_pages, fmt,
                str(q_lat.dtype), "float32", num_stages, sm_scale, window,
            ),
        )
        # pack queries chunk-major with their head: row = i*heads + h
        qp = q_lat.transpose(0, 2, 1, 3).reshape(b, chunk * h, r)
        qpep = q_pe.transpose(0, 2, 1, 3).reshape(b, chunk * h, pe)
        ckv_p, kpe_p, cs_p, ps_p, out = kern(
            block_tables, start_lens, chunk_lens, qp, qpep, cq, pq, cs_new,
            ps_new, ckv_pages, kpe_pages, ckv_scales, kpe_scales,
        )
        out = out.reshape(b, chunk, h, r).transpose(0, 2, 1, 3)
        return out, ckv_p, kpe_p, cs_p, ps_p

    # ---- XLA path: masked scatter of packed bytes + scales, then the
    # oracle over the dequantized gather -----------------------------------
    pos = start_lens[:, None].astype(jnp.int32) + jnp.arange(chunk, dtype=jnp.int32)
    logical = jnp.clip(pos // page_size, 0, max_pages - 1)
    phys = jnp.take_along_axis(block_tables, logical, axis=1)  # (B, C)
    valid = jnp.arange(chunk)[None, :] < chunk_lens[:, None]
    phys = jnp.where(valid, phys, 0)  # dead tail -> reserved garbage page
    off = pos % page_size
    ckv_pages, kpe_pages = jnp.asarray(ckv_pages), jnp.asarray(kpe_pages)
    ckv_scales, kpe_scales = jnp.asarray(ckv_scales), jnp.asarray(kpe_scales)
    ckv_p = ckv_pages.at[phys, off].set(cq)
    kpe_p = kpe_pages.at[phys, off].set(pq)
    sdt = ckv_scales.dtype
    cs_p = ckv_scales.at[phys, off].set(cs_new.astype(sdt))
    ps_p = kpe_scales.at[phys, off].set(ps_new.astype(sdt))

    ckv_new_dq = ref.dequantize_rows(cq, cs_new, fmt).astype(q_lat.dtype)
    kpe_new_dq = ref.dequantize_rows(pq, ps_new, fmt).astype(q_lat.dtype)
    s_total = max_pages * page_size
    si = jnp.arange(s_total, dtype=jnp.int32)
    ctx_pos = jnp.where(si[None, :] < start_lens[:, None], si[None, :], -1)
    out = ref.mla_prefill(
        q_lat, q_pe, ckv_new_dq, kpe_new_dq,
        ref.dequantize_rows(ckv_p, cs_p, fmt).astype(q_lat.dtype)[
            block_tables
        ].reshape(b, -1, r),
        ref.dequantize_rows(kpe_p, ps_p, fmt).astype(q_lat.dtype)[
            block_tables
        ].reshape(b, -1, pe),
        ctx_pos, pos, chunk_lens, sm_scale=sm_scale, window=window,
        logit_soft_cap=logit_soft_cap,
    )
    return out, ckv_p, kpe_p, cs_p, ps_p


# ---------------------------------------------------------------------------
# Dequantized GEMM
# ---------------------------------------------------------------------------


def dequant_matmul(a, b_packed, *, fmt: str = "int4", scales=None,
                   backend: Optional[str] = None, block_m: Optional[int] = None,
                   block_n: Optional[int] = None, block_k: Optional[int] = None,
                   num_stages: int = 2, out_dtype=None):
    """Returns A @ dequant(B)^T with B stored (N, K//pack) packed int8.

    Note: the Pallas kernel emits the transposed product Ct[N, M] (paper
    layout) — we transpose back here so both backends agree on [M, N].
    """
    out_dtype = out_dtype or a.dtype
    be = _resolve(backend)
    if be == "xla":
        group = a.shape[1] // scales.shape[1] if scales is not None else 128
        return ref.dequant_matmul(a, b_packed, fmt, scales, group, out_dtype)
    M, K = a.shape
    N = b_packed.shape[0]
    bm = block_m or _pick_block(M, (64, 32, 16, 8))
    bn = block_n or _pick_block(N, (64, 32, 16, 8))
    bk = block_k or _pick_block(K, (128, 64, 32, 16))
    with_scales = scales is not None
    if with_scales and scales.shape[1] != K // bk:
        # kernel constraint: one scale group per K block
        _fallback("dq", "scale group is not one K block")
        return ref.dequant_matmul(
            a, b_packed, fmt, scales, K // scales.shape[1], out_dtype
        )
    key = ("dq", fmt, M, N, K, str(a.dtype), bm, bn, bk, num_stages, with_scales)
    kern = _cached(
        key,
        lambda: dequant_matmul_program(
            M, N, K, fmt, str(a.dtype), str(jnp.dtype(out_dtype)), "float32",
            bm, bn, bk, num_stages, with_scales,
        ),
    )
    args = (a, b_packed) + ((scales,) if with_scales else ())
    return kern(*args).T


# ---------------------------------------------------------------------------
# Mamba-2 SSD
# ---------------------------------------------------------------------------


def chunk_state(b_mat, x, da_cum, *, backend: Optional[str] = None):
    be = _resolve(backend)
    if be == "xla":
        return ref.chunk_state(b_mat, x, da_cum)
    bsz, nc, l, n = b_mat.shape
    p = x.shape[-1]
    key = ("cstate", bsz, nc, l, n, p, str(b_mat.dtype))
    kern = _cached(
        key, lambda: chunk_state_program(bsz, nc, l, n, p, str(b_mat.dtype))
    )
    return kern(b_mat, x, da_cum.astype(jnp.float32))


def chunk_scan(c_mat, b_mat, x, da_cum, prev_states, *, backend: Optional[str] = None):
    be = _resolve(backend)
    if be == "xla":
        return ref.chunk_scan(c_mat, b_mat, x, da_cum, prev_states)
    bsz, nc, l, n = c_mat.shape
    p = x.shape[-1]
    key = ("cscan", bsz, nc, l, n, p, str(x.dtype))
    kern = _cached(
        key, lambda: chunk_scan_program(bsz, nc, l, n, p, str(x.dtype))
    )
    return kern(
        c_mat, b_mat, x, da_cum.astype(jnp.float32), prev_states.astype(jnp.float32)
    )


def ssd(c_mat, b_mat, x, dt, a_log, *, chunk: int = 64, backend: Optional[str] = None):
    """Full SSD layer pass composed from the two kernels + the inter-chunk
    recurrence (tiny lax.scan at the JAX level, as in Mamba-2)."""
    be = _resolve(backend)
    if be == "xla":
        return ref.ssd(c_mat, b_mat, x, dt, a_log, chunk)
    bsz, s, n = c_mat.shape
    p = x.shape[-1]
    nc = s // chunk
    rs = lambda t: t.reshape(bsz, nc, chunk, *t.shape[2:])
    da = dt * (-jnp.exp(a_log))
    da_cum = jnp.cumsum(da.reshape(bsz, nc, chunk), axis=-1)
    states = chunk_state(rs(b_mat), rs(x), da_cum, backend=be)
    incoming = ref.state_recurrence(states, da_cum[..., -1])
    y = chunk_scan(rs(c_mat), rs(b_mat), rs(x), da_cum, incoming, backend=be)
    return y.reshape(bsz, s, p).astype(x.dtype)


def rmsnorm(x, weight, eps: float = 1e-6, *, backend: Optional[str] = None):
    return ref.rmsnorm(x, weight, eps)
