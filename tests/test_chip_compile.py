"""Ahead-of-time compiles for a described TPU v5e: the serving kernels and
steps at qwen2_1_5b's published widths, in bfloat16, through Mosaic
(``interpret=False``).  Nothing runs; each test asserts that the chip's
compiler accepts the program and that a Mosaic kernel (``tpu_custom_call``)
is in it — what the CPU interpret-mode suite cannot show (tile alignment,
SMEM scalar loads, VMEM limits).

The topology is described inside a module fixture, never at import: only one
process may hold the TPU library at a time, and every test worker imports
this file.  Keep these tests in this one file so one worker holds it.
"""
import collections
import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import Schedule, analyze, compile as tl_compile, program_fingerprint
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_program
from repro.kernels.matmul import matmul_program
from repro.kernels.mla import mla_paged_program, mla_prefill_program, page_width
from repro.kernels.paged_attention import paged_attention_program
from repro.kernels.prefill_attention import prefill_attention_program

QWEN = get_config("qwen2_1_5b")
SLOTS, MAX_LEN, PAGE, CHUNK = 8, 1024, 16, 128
MAX_PAGES = MAX_LEN // PAGE
NUM_PAGES = SLOTS * MAX_PAGES + 1


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    # entries compiled for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """Compile tile kernels for the chip, not the interpreter: off the TPU
    ``ops`` would interpret them, and must not reuse interpreted entries."""
    monkeypatch.setattr(ops, "_schedule", lambda op: Schedule())
    monkeypatch.setattr(ops, "_CACHE", {})
    monkeypatch.setattr(ops, "FALLBACKS", collections.Counter())


def compiled_text(fn, *shapes, sharding):
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), shapes
    )
    return jax.jit(fn).lower(*args).compile().as_text()


def sds(*shape, dtype=QWEN.dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def pools(fmt=None):
    """(k, v) page pools, plus scale pools for a quantized format."""
    hkv, d = QWEN.num_kv_heads, QWEN.head_dim
    if fmt is None:
        return (sds(hkv, NUM_PAGES, PAGE, d),) * 2
    return (sds(hkv, NUM_PAGES, PAGE, d, dtype="int8"),) * 2 + (
        sds(hkv, NUM_PAGES, PAGE, 1),) * 2


def decode_shapes(fmt=None):
    table, lens = sds(SLOTS, MAX_PAGES, dtype="int32"), sds(SLOTS, dtype="int32")
    q = sds(SLOTS, QWEN.num_heads, QWEN.head_dim)
    return q, *pools(fmt), table, lens


def prefill_shapes(fmt=None):
    hq, hkv, d = QWEN.num_heads, QWEN.num_kv_heads, QWEN.head_dim
    table, vec = sds(SLOTS, MAX_PAGES, dtype="int32"), sds(SLOTS, dtype="int32")
    q, kv = sds(SLOTS, hq, CHUNK, d), sds(SLOTS, hkv, CHUNK, d)
    return q, kv, kv, *pools(fmt), table, vec, vec


KERNELS = {
    "paged_attention": (ops.paged_attention, decode_shapes()),
    "paged_attention_int8": (
        functools.partial(ops.paged_attention_quant, fmt="int8"), decode_shapes("int8")),
    "prefill_attention": (ops.prefill_attention, prefill_shapes()),
    "prefill_attention_int8": (
        functools.partial(ops.prefill_attention_quant, fmt="int8"),
        prefill_shapes("int8")),
    "gemm": (ops.matmul, (sds(SLOTS * CHUNK, QWEN.d_model), sds(QWEN.d_model, QWEN.d_ff))),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, mosaic):
    fn, shapes = KERNELS[name]
    text = compiled_text(lambda *a: fn(*a, backend="pallas"), *shapes, sharding=one_chip)
    assert "tpu_custom_call" in text
    assert not ops.FALLBACKS, dict(ops.FALLBACKS)


def mla_shapes(slots, max_pages, prefill=False):
    """``ops.mla_paged`` / ``ops.mla_prefill`` arguments at deepseek_v2_lite_16b's
    widths: 16 heads, latent 512 + rope 64 in joint pages of 640 lanes."""
    m = get_config("deepseek_v2_lite_16b").mla
    h, r, pe = 16, m.kv_lora_rank, m.qk_rope_head_dim
    pages = sds(slots * max_pages + 1, PAGE, page_width(r, pe))
    table, vec = sds(slots, max_pages, dtype="int32"), sds(slots, dtype="int32")
    if prefill:
        return (sds(slots, h, CHUNK, r), sds(slots, h, CHUNK, pe), sds(slots, CHUNK, r),
                sds(slots, CHUNK, pe), pages, table, vec, vec)
    return sds(slots, h, r), sds(slots, h, pe), pages, table, vec


def test_mla_paged_compiles_at_sixteen_heads(one_chip, mosaic):
    """deepseek_v2_lite_16b's latent decode: 16 heads in one ``block_h``
    window spans the head axis whole, so Mosaic's block rule holds."""
    text = compiled_text(lambda *a: ops.mla_paged(*a, backend="pallas"),
                         *mla_shapes(SLOTS, MAX_PAGES), sharding=one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", ["mla_paged", "mla_prefill"])
def test_bounded_mla_compiles_for_v5e(kernel, one_chip, mosaic):
    """``PagedMLA`` and ``PrefillMLA`` at the deepseek_v2_lite_16b cell's
    shape (16 slots x 288 table pages): the joint 640-lane page is a tile
    Mosaic DMAs by hand, so each (head block, slot) or (chunk page, slot)
    cell walks its live pages inside the kernel, and nothing falls back."""
    slots, max_pages = 16, 288
    text = compiled_text(lambda *a: getattr(ops, kernel)(*a, backend="pallas"),
                         *mla_shapes(slots, max_pages, kernel == "mla_prefill"),
                         sharding=one_chip)
    assert "tpu_custom_call" in text
    assert not ops.FALLBACKS, dict(ops.FALLBACKS)
    m = get_config("deepseek_v2_lite_16b").mla
    shape = dict(slots=slots, heads=16, dim=m.kv_lora_rank, pe_dim=m.qk_rope_head_dim,
                 page_size=PAGE, max_pages=max_pages, num_pages=slots * max_pages + 1,
                 dtype="bfloat16")
    prog = (mla_prefill_program(chunk=CHUNK, **shape) if kernel == "mla_prefill"
            else mla_paged_program(**shape))
    plan = analyze(prog, Schedule())
    assert plan.grid_plan.walk and plan.grid_plan.kdim is None
    assert plan.grid == (slots, CHUNK // PAGE if kernel == "mla_prefill" else 1)


@pytest.mark.parametrize("step", ["decode", "prefill", "window"])
def test_serving_step_compiles_for_v5e(step, one_chip, mosaic):
    """The engine's jitted steps at published widths, cut to 2 layers (the
    layers are scanned, so depth does not change the program's body), with
    the Pallas kernels inside."""
    from repro.models import lm
    from repro.serving import engine as E

    cfg = dataclasses.replace(QWEN, num_layers=2, kernel_backend="pallas")
    params = jax.eval_shape(lambda k: lm.init(cfg, k), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: lm.init_cache(
        cfg, SLOTS, MAX_LEN, layout="paged", page_size=PAGE, num_blocks=NUM_PAGES))
    vec, flag = sds(SLOTS, dtype="int32"), sds(SLOTS, dtype="bool")
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    fn, args = {
        "decode": (E._decode_step_fn(cfg, 0.0),
                   (params, cache, vec, vec, key, flag, flag)),
        "prefill": (E._prefill_step_fn(cfg, 0.0),
                    (params, cache, sds(SLOTS, CHUNK, dtype="int32"), vec, vec, key, flag)),
        "window": (E._decode_loop_fn(cfg, 0.0, 8, -1, MAX_LEN),
                   (params, cache, vec, vec, key, flag, vec)),
    }[step]
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), args
    )
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("step", ["decode", "prefill", "window"])
def test_mla_moe_serving_step_compiles_for_v5e(step, one_chip, mosaic):
    """deepseek_v2_lite_16b's jitted steps at published widths, at the
    benchmark cell's 16 slots x 4,608 positions, holding 8 of 64 experts,
    cut to its dense layer and one MoE layer: ``PagedMLA`` and
    ``PrefillMLA`` compile through Mosaic beside the expert einsums, and
    nothing falls back."""
    from repro.models import lm
    from repro.serving import engine as E

    base = get_config("deepseek_v2_lite_16b")
    cfg = dataclasses.replace(base, num_layers=2, kernel_backend="pallas",
                              moe=dataclasses.replace(base.moe, held_experts=8))
    slots, max_len = 16, 4608
    num_pages = slots * (max_len // PAGE)
    params = jax.eval_shape(lambda k: lm.init(cfg, k), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: lm.init_cache(
        cfg, slots, max_len, layout="paged", page_size=PAGE, num_blocks=num_pages))
    vec, flag = sds(slots, dtype="int32"), sds(slots, dtype="bool")
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    fn, args = {
        "decode": (E._decode_step_fn(cfg, 0.0), (params, cache, vec, vec, key, flag, flag)),
        "prefill": (E._prefill_step_fn(cfg, 0.0),
                    (params, cache, sds(slots, CHUNK, dtype="int32"), vec, vec, key, flag)),
        "window": (E._decode_loop_fn(cfg, 0.0, 8, -1, max_len),
                   (params, cache, vec, vec, key, flag, vec)),
    }[step]
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), args
    )
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert not ops.FALLBACKS, dict(ops.FALLBACKS)


# The decode shapes of the benchmark's two cells (benchmarks/chip): slots,
# table pages, query heads, KV heads.  qwen2_1_5b: group 6 over 2 KV heads;
# deepseek_7b: MHA, 32 KV heads at group 1.
CELLS = {"qwen2_1_5b": (8, 288, 12, 2), "deepseek_7b": (8, 96, 32, 32)}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_bounded_paged_attention_compiles_for_v5e(cell, one_chip, mosaic):
    """The bounded walk at the cells' shapes: a (slots, kv_heads) grid whose
    cells loop over their live pages with hand-issued DMAs."""
    slots, max_pages, hq, hkv = CELLS[cell]
    pages = slots * max_pages + 1
    shapes = (sds(slots, hq, 128), sds(hkv, pages, PAGE, 128), sds(hkv, pages, PAGE, 128),
              sds(slots, max_pages, dtype="int32"), sds(slots, dtype="int32"))
    text = compiled_text(lambda *a: ops.paged_attention(*a, backend="pallas"), *shapes,
                         sharding=one_chip)
    assert "tpu_custom_call" in text
    assert not ops.FALLBACKS, dict(ops.FALLBACKS)
    m = analyze(paged_attention_program(slots, hq, hkv, 128, PAGE, max_pages, pages,
                                        dtype=str(QWEN.dtype)), Schedule())
    assert m.grid == (slots, hkv) and m.grid_plan.walk


# Static-extent kernels as the parent of the bounded walk lowered them:
# program fingerprint (the compile caches' key), grid, dimension semantics,
# and a digest of the traced Mosaic kernel body.  (XLA's persistent cache
# also hashes the kernel's source line numbers, which any edit of the
# backend moves; the body itself must not change.)
STATIC = {
    "FlashAttn": (
        lambda: flash_attention_program(1, 12, 2, 256, 256, 128, True, 128, 128,
                                        "bfloat16", "float32", 2, None),
        "f04377ede345565ecba18b0f7ad036e0f3cd28c8be3420ceb404594113fc8300",
        (1, 12, 2, 2), "f0ba35ca95a0811a"),
    "PrefillAttn": (
        lambda: prefill_attention_program(8, 12, 2, 128, 128, 16, 288, 2305, None,
                                          "bfloat16", "float32", 2, None),
        "205e2ac90d65760ae295c6fea43657389df51041aa480e56071f9e06667f4b63",
        (8, 8, 2, 288), "b813355d74d2c40a"),
    "Matmul": (
        lambda: matmul_program(1024, 1536, 8960, "bfloat16", "bfloat16", "float32",
                               128, 128, 256, 2),
        "2e5692dbaf64e74815f4f67e82705b740ed23a66585f9eb85768a3c73e44967b",
        (8, 12, 35), "2b0ce32d8279fcff"),
}


@pytest.mark.parametrize("name", sorted(STATIC))
def test_static_extent_kernels_keep_their_lowering(name):
    make, fingerprint, grid, body = STATIC[name]
    prog = make()
    m = analyze(prog, Schedule())
    assert program_fingerprint(prog) == fingerprint
    assert m.grid == grid and not m.grid_plan.walk
    assert m.dimension_semantics == ("parallel",) * (len(grid) - 1) + ("arbitrary",)
    kern = tl_compile(prog, Schedule())
    args = [jax.ShapeDtypeStruct(p.shape, jnp.dtype(p.dtype)) for p in kern.arg_params]
    text = str(jax.make_jaxpr(kern)(*args))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == body
