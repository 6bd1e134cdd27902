"""Per-kernel validation: Pallas lowering (interpret mode) vs ref.py oracle,
swept over shapes and dtypes; plus reference-backend cross-checks."""
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.core import Schedule, compile as tl_compile
from repro.kernels import (
    chunk_scan_program,
    chunk_state_program,
    dequant_matmul_program,
    flash_attention_program,
    matmul_program,
    mla_program,
    ops,
    ref,
)

ATOL = {"float32": 2e-3, "bfloat16": 8e-2, "float16": 2e-2}


def _rand(rng, shape, dtype):
    x = rng.standard_normal(shape, dtype=np.float32)
    return np.asarray(x, dtype=np.dtype(dtype) if dtype != "bfloat16" else np.float32)


# ---------------------------------------------------------------------------
# GEMM
# ---------------------------------------------------------------------------


class TestMatmul:
    @pytest.mark.parametrize(
        "M,N,K,bm,bn,bk",
        [
            (128, 128, 128, 64, 64, 64),
            (256, 128, 64, 64, 32, 32),
            (64, 256, 128, 32, 128, 64),
            (128, 128, 512, 128, 128, 128),
        ],
    )
    def test_shapes_f32(self, rng, M, N, K, bm, bn, bk):
        prog = matmul_program(M, N, K, block_M=bm, block_N=bn, block_K=bk)
        kern = tl_compile(prog, Schedule(interpret=True))
        a = rng.standard_normal((M, K), dtype=np.float32)
        b = rng.standard_normal((K, N), dtype=np.float32)
        np.testing.assert_allclose(np.asarray(kern(a, b)), a @ b, atol=2e-3)

    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    def test_dtypes(self, rng, dtype):
        import jax.numpy as jnp

        M = N = K = 128
        prog = matmul_program(M, N, K, in_dtype=dtype, out_dtype="float32",
                              block_M=64, block_N=64, block_K=64)
        kern = tl_compile(prog, Schedule(interpret=True))
        a = jnp.asarray(rng.standard_normal((M, K), dtype=np.float32), jnp.dtype(dtype))
        b = jnp.asarray(rng.standard_normal((K, N), dtype=np.float32), jnp.dtype(dtype))
        expect = np.asarray(a, np.float32) @ np.asarray(b, np.float32)
        np.testing.assert_allclose(np.asarray(kern(a, b)), expect, atol=ATOL[dtype] * K / 64)

    def test_pallas_matches_reference_backend(self, rng):
        prog = matmul_program(128, 128, 128, block_M=64, block_N=64, block_K=64)
        pk = tl_compile(prog, Schedule(interpret=True))
        rk = tl_compile(prog, backend="reference")
        a = rng.standard_normal((128, 128), dtype=np.float32)
        b = rng.standard_normal((128, 128), dtype=np.float32)
        np.testing.assert_allclose(np.asarray(pk(a, b)), np.asarray(rk(a, b)), atol=1e-4)

    def test_ops_wrapper_xla_vs_pallas(self, rng):
        a = rng.standard_normal((128, 64), dtype=np.float32)
        b = rng.standard_normal((64, 128), dtype=np.float32)
        x = ops.matmul(a, b, backend="xla")
        p = ops.matmul(a, b, backend="pallas")
        np.testing.assert_allclose(np.asarray(x), np.asarray(p), atol=2e-3)


# ---------------------------------------------------------------------------
# FlashAttention
# ---------------------------------------------------------------------------


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize(
        "B,Hq,Hkv,Sq,Sk,D,bm,bn",
        [
            (1, 2, 2, 64, 64, 32, 32, 32),   # MHA
            (2, 4, 2, 64, 128, 32, 32, 64),  # GQA 2:1
            (1, 8, 1, 32, 96, 64, 32, 32),   # MQA
        ],
    )
    def test_against_oracle(self, rng, causal, B, Hq, Hkv, Sq, Sk, D, bm, bn):
        prog = flash_attention_program(B, Hq, Hkv, Sq, Sk, D, causal, bm, bn)
        kern = tl_compile(prog, Schedule(interpret=True))
        q = rng.standard_normal((B, Hq, Sq, D), dtype=np.float32)
        k = rng.standard_normal((B, Hkv, Sk, D), dtype=np.float32)
        v = rng.standard_normal((B, Hkv, Sk, D), dtype=np.float32)
        out = np.asarray(kern(q, k, v))
        expect = np.asarray(ref.attention(q, k, v, causal=causal))
        np.testing.assert_allclose(out, expect, atol=2e-3)
        assert not np.any(np.isnan(out))

    def test_single_kv_block(self, rng):
        prog = flash_attention_program(1, 1, 1, 32, 32, 32, False, 32, 32)
        kern = tl_compile(prog, Schedule(interpret=True))
        q = rng.standard_normal((1, 1, 32, 32), dtype=np.float32)
        k = rng.standard_normal((1, 1, 32, 32), dtype=np.float32)
        v = rng.standard_normal((1, 1, 32, 32), dtype=np.float32)
        np.testing.assert_allclose(
            np.asarray(kern(q, k, v)),
            np.asarray(ref.attention(q, k, v)),
            atol=2e-3,
        )


# ---------------------------------------------------------------------------
# MLA (paper Fig. 18)
# ---------------------------------------------------------------------------


class TestMLA:
    @pytest.mark.parametrize(
        "B,H,Hkv,S,D,Pe,bn,bh",
        [
            (1, 16, 1, 128, 64, 16, 32, 16),
            (2, 8, 1, 64, 32, 8, 32, 8),
            (1, 32, 2, 128, 64, 32, 64, 16),
        ],
    )
    def test_against_oracle(self, rng, B, H, Hkv, S, D, Pe, bn, bh):
        prog = mla_program(B, H, Hkv, S, D, Pe, bn, bh)
        kern = tl_compile(prog, Schedule(interpret=True))
        q = rng.standard_normal((B, H, D), dtype=np.float32)
        qpe = rng.standard_normal((B, H, Pe), dtype=np.float32)
        kv = rng.standard_normal((B, S, Hkv, D), dtype=np.float32)
        kpe = rng.standard_normal((B, S, Hkv, Pe), dtype=np.float32)
        out = np.asarray(kern(q, qpe, kv, kpe))
        expect = np.asarray(ref.mla(q, qpe, kv, kpe))
        np.testing.assert_allclose(out, expect, atol=2e-3)

    def test_joint_pool_oracle_matches_two_pools(self, rng):
        """The paged oracles over the joint pool ``[latent | rope | 0]``
        give what the two separate latent and rope pools gave: decode
        (``ref.mla_paged``) against the masked latent attention over the
        two gathered pools, and prefill (``ops.mla_prefill``'s XLA path)
        against two masked scatters and ``ref.mla_prefill`` over their
        gathers.  The zero lanes add nothing to a score."""
        import jax.numpy as jnp

        from repro.kernels.mla import page_width

        slots, heads, r, pe, ps, mp, np_, chunk = 3, 4, 96, 16, 16, 4, 16, 32
        width = page_width(r, pe)
        assert width == 128
        tables = jnp.asarray((rng.permutation(np_ - 1)[: slots * mp] + 1)
                             .reshape(slots, mp).astype(np.int32))
        ckv = jnp.asarray(rng.standard_normal((np_, ps, r), dtype=np.float32))
        kpe = jnp.asarray(rng.standard_normal((np_, ps, pe), dtype=np.float32))
        pages = ref.mla_rows(ckv, kpe, width)
        assert not np.asarray(pages[..., r + pe :]).any()

        lens = jnp.asarray([1, 37, 64], jnp.int32)
        q_lat = jnp.asarray(rng.standard_normal((slots, heads, r), dtype=np.float32))
        q_pe = jnp.asarray(rng.standard_normal((slots, heads, pe), dtype=np.float32))
        two = ref.mla_masked(q_lat, q_pe, ckv[tables].reshape(slots, -1, r),
                             kpe[tables].reshape(slots, -1, pe), lens,
                             1.0 / np.sqrt(r + pe))
        np.testing.assert_allclose(
            np.asarray(ref.mla_paged(q_lat, q_pe, pages, tables, lens)),
            np.asarray(two), rtol=1e-5, atol=1e-5)

        starts = jnp.asarray([0, 16, 32], jnp.int32)
        clens = jnp.asarray([20, 32, 7], jnp.int32)
        ql = jnp.asarray(rng.standard_normal((slots, heads, chunk, r), dtype=np.float32))
        qp = jnp.asarray(rng.standard_normal((slots, heads, chunk, pe), dtype=np.float32))
        cn = jnp.asarray(rng.standard_normal((slots, chunk, r), dtype=np.float32))
        pn = jnp.asarray(rng.standard_normal((slots, chunk, pe), dtype=np.float32))
        out, new_pages = ops.mla_prefill(ql, qp, cn, pn, pages, tables, starts,
                                         clens, backend="xla")
        pos = starts[:, None] + jnp.arange(chunk, dtype=jnp.int32)
        phys = jnp.take_along_axis(tables, jnp.clip(pos // ps, 0, mp - 1), axis=1)
        phys = jnp.where(jnp.arange(chunk)[None, :] < clens[:, None], phys, 0)
        ckv2 = ckv.at[phys, pos % ps].set(cn)
        kpe2 = kpe.at[phys, pos % ps].set(pn)
        si = jnp.arange(mp * ps, dtype=jnp.int32)
        ctx_pos = jnp.where(si[None, :] < starts[:, None], si[None, :], -1)
        want = ref.mla_prefill(ql, qp, cn, pn, ckv2[tables].reshape(slots, -1, r),
                               kpe2[tables].reshape(slots, -1, pe), ctx_pos, pos,
                               clens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(new_pages),
                                      np.asarray(ref.mla_rows(ckv2, kpe2, width)))

    def test_loc_budget(self):
        """Paper headline: MLA in ~70 lines of Python."""
        prog = mla_program(1, 16, 1, 128, 64, 16, 32, 16)
        assert prog.source_lines <= 80


# ---------------------------------------------------------------------------
# Dequant GEMM
# ---------------------------------------------------------------------------


class TestDequantMatmul:
    @pytest.mark.parametrize("fmt", ["int4", "int2", "nf4", "int8"])
    def test_formats(self, rng, fmt):
        M, N, K = 32, 64, 128
        pack = {"int4": 2, "int2": 4, "nf4": 2, "int8": 1}[fmt]
        prog = dequant_matmul_program(
            M, N, K, fmt, block_M=16, block_N=16, block_K=32
        )
        kern = tl_compile(prog, Schedule(interpret=True))
        a = rng.standard_normal((M, K), dtype=np.float32)
        bp = rng.integers(-128, 128, size=(N, K // pack)).astype(np.int8)
        out = np.asarray(kern(a, bp))  # (N, M) transposed layout
        expect = np.asarray(ref.dequant_matmul(a, bp, fmt)).T
        np.testing.assert_allclose(out, expect, atol=2e-2)

    def test_with_scales(self, rng):
        M, N, K, bk = 32, 32, 128, 32
        prog = dequant_matmul_program(
            M, N, K, "int4", block_M=16, block_N=16, block_K=bk, with_scales=True
        )
        kern = tl_compile(prog, Schedule(interpret=True))
        a = rng.standard_normal((M, K), dtype=np.float32)
        bp = rng.integers(-128, 128, size=(N, K // 2)).astype(np.int8)
        sc = (rng.standard_normal((N, K // bk), dtype=np.float32) * 0.1).astype(np.float32)
        out = np.asarray(kern(a, bp, sc))
        expect = np.asarray(ref.dequant_matmul(a, bp, "int4", sc, bk)).T
        np.testing.assert_allclose(out, expect, atol=2e-3)

    def test_odd_k_blocks_accepted(self, rng):
        # K=48, block_K=16, pack=2: three K-blocks.  The old guard rejected
        # K % (block_K * pack) != 0 even though block_K already divides K.
        M, N, K = 16, 16, 48
        prog = dequant_matmul_program(
            M, N, K, "int4", block_M=16, block_N=16, block_K=16
        )
        kern = tl_compile(prog, Schedule(interpret=True))
        a = rng.standard_normal((M, K), dtype=np.float32)
        bp = rng.integers(-128, 128, size=(N, K // 2)).astype(np.int8)
        out = np.asarray(kern(a, bp))
        expect = np.asarray(ref.dequant_matmul(a, bp, "int4")).T
        np.testing.assert_allclose(out, expect, atol=2e-2)

    def test_block_k_must_cover_pack(self):
        # The real packing constraint: a block must hold whole packed bytes.
        with pytest.raises(ValueError, match="pack factor"):
            dequant_matmul_program(16, 16, 32, "int2", block_M=16, block_N=16,
                                   block_K=2)

    def test_k_must_divide_blocks(self):
        with pytest.raises(ValueError, match="divide problem shape"):
            dequant_matmul_program(16, 16, 40, "int4", block_M=16, block_N=16,
                                   block_K=16)


# ---------------------------------------------------------------------------
# Quantized KV cache (dequant KV source): ops-level pallas vs xla, which
# pins both the DequantStage kernels against the ref oracles and the
# in-out page/scale ordering of the prefill writes.
# ---------------------------------------------------------------------------


class TestQuantKV:
    @pytest.mark.parametrize("fmt", ["int8", "int4"])
    def test_paged_decode(self, rng, fmt):
        from repro.kernels.ref import KV_PACK

        slots, heads, hkv, d, ps, mp, np_ = 3, 4, 2, 16, 16, 2, 8
        pack = KV_PACK[fmt]
        tables = rng.permutation(np_)[: slots * mp].reshape(slots, mp).astype(np.int32)
        lens = rng.integers(1, mp * ps + 1, size=slots).astype(np.int32)
        q = rng.standard_normal((slots, heads, d), dtype=np.float32)
        kf = rng.standard_normal((hkv, np_, ps, d), dtype=np.float32)
        vf = rng.standard_normal((hkv, np_, ps, d), dtype=np.float32)
        kp, ks = ref.quantize_rows(kf, fmt)
        vp, vs = ref.quantize_rows(vf, fmt)
        x = ops.paged_attention_quant(q, kp, vp, ks, vs, tables, lens,
                                      fmt=fmt, backend="xla")
        p = ops.paged_attention_quant(q, kp, vp, ks, vs, tables, lens,
                                      fmt=fmt, backend="pallas")
        np.testing.assert_allclose(np.asarray(p), np.asarray(x), atol=2e-3)
        # and the quantized cache stays close to the fp attention
        full = np.asarray(
            ref.paged_attention(q, kf, vf, tables, lens)
        )
        atol = 0.05 if fmt == "int8" else 0.35
        np.testing.assert_allclose(np.asarray(x), full, atol=atol)

    @staticmethod
    def _live_rows(pool, tables, starts, lens, page_size):
        """Pool rows at live token positions (page axis at ndim-3).

        Dead-tail rows of a partially-live page and the reserved garbage
        page 0 legitimately differ between the kernel path (writes whole
        pages) and the XLA masked scatter (redirects dead rows to page 0)
        — same split as the fp twins — so equivalence is asserted on what
        the serving engine can ever read back: live positions only.
        """
        pool = np.moveaxis(np.asarray(pool), pool.ndim - 3, 0)
        rows = []
        for z in range(tables.shape[0]):
            for pos in range(int(starts[z]), int(starts[z] + lens[z])):
                rows.append(pool[tables[z, pos // page_size], ..., pos % page_size, :])
        return np.stack(rows)

    @pytest.mark.parametrize("fmt", ["int8", "int4"])
    def test_prefill(self, rng, fmt):
        slots, heads, hkv, d, chunk, ps, mp, np_ = 2, 4, 2, 16, 32, 16, 4, 9
        cpp = chunk // ps
        # page 0 is the engine's reserved garbage page — never owned
        tables = (rng.permutation(np_ - 1)[: slots * mp] + 1).reshape(
            slots, mp
        ).astype(np.int32)
        starts = (rng.integers(0, mp - cpp + 1, size=slots) * ps).astype(np.int32)
        lens = rng.integers(chunk - ps + 1, chunk + 1, size=slots).astype(np.int32)
        q = rng.standard_normal((slots, heads, chunk, d), dtype=np.float32)
        k_new = rng.standard_normal((slots, hkv, chunk, d), dtype=np.float32)
        v_new = rng.standard_normal((slots, hkv, chunk, d), dtype=np.float32)
        kprior = rng.standard_normal((hkv, np_, ps, d), dtype=np.float32)
        vprior = rng.standard_normal((hkv, np_, ps, d), dtype=np.float32)
        kp, ks = ref.quantize_rows(kprior, fmt)
        vp, vs = ref.quantize_rows(vprior, fmt)
        outs = {}
        for be in ("xla", "pallas"):
            outs[be] = ops.prefill_attention_quant(
                q, k_new, v_new, kp, vp, ks, vs, tables, starts, lens,
                fmt=fmt, backend=be,
            )
        np.testing.assert_allclose(
            np.asarray(outs["pallas"][0]), np.asarray(outs["xla"][0]), atol=2e-3
        )
        ends = starts + lens
        for i in range(1, 5):
            a = self._live_rows(outs["xla"][i], tables, starts * 0, ends, ps)
            b = self._live_rows(outs["pallas"][i], tables, starts * 0, ends, ps)
            np.testing.assert_allclose(
                b.astype(np.float32), a.astype(np.float32), atol=1e-6
            )

    @pytest.mark.parametrize("fmt", ["int8", "int4"])
    def test_mla_paged_decode(self, rng, fmt):
        slots, heads, r, pe, ps, mp, np_ = 3, 4, 16, 8, 16, 2, 8
        tables = (rng.permutation(np_ - 1)[: slots * mp] + 1).reshape(
            slots, mp
        ).astype(np.int32)
        lens = rng.integers(1, mp * ps + 1, size=slots).astype(np.int32)
        q_lat = rng.standard_normal((slots, heads, r), dtype=np.float32)
        q_pe = rng.standard_normal((slots, heads, pe), dtype=np.float32)
        ckvf = rng.standard_normal((np_, ps, r), dtype=np.float32)
        kpef = rng.standard_normal((np_, ps, pe), dtype=np.float32)
        cp, cs = ref.quantize_rows(ckvf, fmt)
        pp, pss = ref.quantize_rows(kpef, fmt)
        x = ops.mla_paged_quant(q_lat, q_pe, cp, pp, cs, pss, tables, lens,
                                fmt=fmt, backend="xla", block_h=2)
        p = ops.mla_paged_quant(q_lat, q_pe, cp, pp, cs, pss, tables, lens,
                                fmt=fmt, backend="pallas", block_h=2)
        np.testing.assert_allclose(np.asarray(p), np.asarray(x), atol=2e-3)

    @pytest.mark.parametrize("fmt", ["int8", "int4"])
    def test_mla_prefill(self, rng, fmt):
        slots, heads, r, pe, chunk, ps, mp, np_ = 2, 2, 16, 8, 32, 16, 4, 10
        cpp = chunk // ps
        tables = (rng.permutation(np_ - 1)[: slots * mp] + 1).reshape(
            slots, mp
        ).astype(np.int32)
        starts = (rng.integers(0, mp - cpp + 1, size=slots) * ps).astype(np.int32)
        lens = rng.integers(chunk - ps + 1, chunk + 1, size=slots).astype(np.int32)
        q_lat = rng.standard_normal((slots, heads, chunk, r), dtype=np.float32)
        q_pe = rng.standard_normal((slots, heads, chunk, pe), dtype=np.float32)
        ckv_new = rng.standard_normal((slots, chunk, r), dtype=np.float32)
        kpe_new = rng.standard_normal((slots, chunk, pe), dtype=np.float32)
        ckvf = rng.standard_normal((np_, ps, r), dtype=np.float32)
        kpef = rng.standard_normal((np_, ps, pe), dtype=np.float32)
        cp, cs = ref.quantize_rows(ckvf, fmt)
        pp, pss = ref.quantize_rows(kpef, fmt)
        outs = {}
        for be in ("xla", "pallas"):
            outs[be] = ops.mla_prefill_quant(
                q_lat, q_pe, ckv_new, kpe_new, cp, pp, cs, pss, tables,
                starts, lens, fmt=fmt, backend=be,
            )
        np.testing.assert_allclose(
            np.asarray(outs["pallas"][0]), np.asarray(outs["xla"][0]), atol=2e-3
        )
        ends = starts + lens
        for i in range(1, 5):
            a = self._live_rows(outs["xla"][i], tables, starts * 0, ends, ps)
            b = self._live_rows(outs["pallas"][i], tables, starts * 0, ends, ps)
            np.testing.assert_allclose(
                b.astype(np.float32), a.astype(np.float32), atol=1e-6
            )

    @pytest.mark.parametrize("fmt", ["int8", "int4"])
    def test_quantize_roundtrip(self, rng, fmt):
        x = rng.standard_normal((5, 7, 16), dtype=np.float32)
        packed, scales = ref.quantize_rows(x, fmt)
        back = np.asarray(ref.dequantize_rows(packed, scales, fmt))
        qmax = ref.KV_QMAX[fmt]
        # symmetric per-row quantization: error bounded by scale/2 per entry
        bound = np.asarray(scales) / 2 + 1e-7
        assert np.all(np.abs(back - x) <= bound)
        # packed size really shrinks by the pack factor
        assert packed.shape[-1] == x.shape[-1] // ref.KV_PACK[fmt]
        # all-zero rows survive exactly
        z = np.zeros((2, 16), np.float32)
        zp, zs = ref.quantize_rows(z, fmt)
        np.testing.assert_array_equal(np.asarray(ref.dequantize_rows(zp, zs, fmt)), z)


# ---------------------------------------------------------------------------
# Mamba-2 SSD chunk kernels
# ---------------------------------------------------------------------------


class TestLinearAttention:
    @pytest.mark.parametrize("L,N,P", [(32, 16, 32), (64, 32, 64)])
    def test_chunk_state(self, rng, L, N, P):
        B, C = 2, 4
        prog = chunk_state_program(B, C, L, N, P)
        kern = tl_compile(prog, Schedule(interpret=True))
        bm = rng.standard_normal((B, C, L, N), dtype=np.float32)
        x = rng.standard_normal((B, C, L, P), dtype=np.float32)
        da = np.cumsum(
            np.abs(rng.standard_normal((B, C, L), dtype=np.float32)) * 0.1, axis=-1
        ).astype(np.float32)
        out = np.asarray(kern(bm, x, da))
        expect = np.asarray(ref.chunk_state(bm, x, da))
        np.testing.assert_allclose(out, expect, atol=2e-3)

    @pytest.mark.parametrize("L,N,P", [(32, 16, 32), (64, 32, 64)])
    def test_chunk_scan(self, rng, L, N, P):
        B, C = 2, 3
        prog = chunk_scan_program(B, C, L, N, P)
        kern = tl_compile(prog, Schedule(interpret=True))
        c = rng.standard_normal((B, C, L, N), dtype=np.float32)
        bm = rng.standard_normal((B, C, L, N), dtype=np.float32)
        x = rng.standard_normal((B, C, L, P), dtype=np.float32)
        da = np.cumsum(
            np.abs(rng.standard_normal((B, C, L), dtype=np.float32)) * 0.1, axis=-1
        ).astype(np.float32)
        prev = rng.standard_normal((B, C, N, P), dtype=np.float32)
        out = np.asarray(kern(c, bm, x, da, prev))
        expect = np.asarray(ref.chunk_scan(c, bm, x, da, prev))
        np.testing.assert_allclose(out, expect, atol=2e-3)

    def test_full_ssd_composition(self, rng):
        Bz, S, N, P, chunk = 2, 128, 16, 32, 32
        c = rng.standard_normal((Bz, S, N), dtype=np.float32)
        bm = rng.standard_normal((Bz, S, N), dtype=np.float32)
        x = rng.standard_normal((Bz, S, P), dtype=np.float32)
        dt = np.abs(rng.standard_normal((Bz, S), dtype=np.float32)) * 0.1
        yp = ops.ssd(c, bm, x, dt, np.float32(0.5), chunk=chunk, backend="pallas")
        yr = ref.ssd(c, bm, x, dt, np.float32(0.5), chunk=chunk)
        np.testing.assert_allclose(np.asarray(yp), np.asarray(yr), atol=2e-3)

    def test_ssd_matches_naive_recurrence(self, rng):
        """The chunked SSD must equal the naive per-step SSM recurrence."""
        Bz, S, N, P, chunk = 1, 64, 8, 16, 16
        c = rng.standard_normal((Bz, S, N), dtype=np.float32) * 0.5
        bm = rng.standard_normal((Bz, S, N), dtype=np.float32) * 0.5
        x = rng.standard_normal((Bz, S, P), dtype=np.float32)
        dt = np.abs(rng.standard_normal((Bz, S), dtype=np.float32)) * 0.1
        a_log = np.float32(0.3)
        y = np.asarray(ref.ssd(c, bm, x, dt, a_log, chunk=chunk))
        # naive: h_t = exp(dA_t) h_{t-1} + B_t^T x_t ; y_t = C_t h_t
        da = dt * (-np.exp(a_log))
        h = np.zeros((Bz, N, P), np.float32)
        for t in range(S):
            h = np.exp(da[:, t])[:, None, None] * h + np.einsum(
                "bn,bp->bnp", bm[:, t], x[:, t]
            )
            np.testing.assert_allclose(
                y[:, t], np.einsum("bn,bnp->bp", c[:, t], h), atol=2e-2
            )


# ---------------------------------------------------------------------------
# Lowering is deterministic across processes
# ---------------------------------------------------------------------------

_LOWER_PAGED = """
import jax, numpy as np
from repro.core import Schedule, compile as tl_compile
from repro.kernels.paged_attention import PARITY_CASES, paged_attention_program, parity_inputs
prog = paged_attention_program(**dict(PARITY_CASES)["paged_attention_mqa"])
args = parity_inputs("paged_attention_mqa", prog, np.random.default_rng(0))
print(jax.make_jaxpr(tl_compile(prog, Schedule()))(*args))  # the Mosaic kernel body
"""


def test_kernel_text_is_independent_of_hash_seed():
    """JAX's persistent compilation cache keys on a program's text, so a
    kernel's body must come out the same whatever Python's per-process
    string hash seed (set iteration order) is.  Traced, not lowered: the
    chip's kernel cannot be lowered off the TPU, and interpret mode's
    lowering hides the order of the final scratch stores."""
    root = pathlib.Path(__file__).resolve().parents[1]
    texts = set()
    for seed in ("1", "2", "3"):
        env = dict(os.environ, PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu",
                   PYTHONPATH=str(root / "src"))
        out = subprocess.run([sys.executable, "-c", _LOWER_PAGED], env=env, cwd=root,
                             capture_output=True, text=True, check=True)
        texts.add(out.stdout)
    assert len(texts) == 1
