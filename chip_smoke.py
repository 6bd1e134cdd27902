#!/usr/bin/env python3
"""Smoke run of the serving main path on one TPU chip.

Serves ``qwen2_1_5b`` at its published widths (28 layers, d_model 1536,
12 query / 2 KV heads of 128, d_ff 8960, vocabulary 151,936, bfloat16) with
random weights drawn from ``--seed``, through the normal entry point
(``repro.launch.serve.main`` -> ``ServingEngine``), so the paged-decode and
chunked-prefill Pallas kernels run compiled by Mosaic.  From the checkout
root, on a host with a TPU:

    python3 chip_smoke.py

Phases, in order; any failure raises and the process exits non-zero:

1. refuse to run unless JAX's first device is a TPU;
2. kernel parity: ``ops.paged_attention`` and ``ops.prefill_attention`` at
   qwen2 widths against the ``kernels/ref.py`` oracles on the same chip;
3. compile the serving decode step, prefill step and 8-tick window, timing
   tracing and compiling apart: a second run with the same compile cache
   reads the compiled programs back instead of compiling them;
4. serve 8 requests (prompts of 100-500 random tokens, 32 new tokens each,
   8 slots, chunked prefill of 128, windows of 8 ticks); every request must
   complete with exactly 32 tokens and no dispatch-guard failure;
5. the programs the engine dispatched hold Mosaic kernels
   (``tpu_custom_call``), and no tile-kernel request fell back
   (``ops.FALLBACKS`` is empty);
6. full-model logits: two prefill chunks and one decode step, with the
   Pallas kernels and with the XLA oracle path, must agree within twice the
   XLA path's own distance from a float32 reference.

The numbers printed along the way (compile seconds, errors, peak device
memory, tokens and wall seconds) describe this run; they are not benchmark
results.  The last line of standard output is one JSON object naming the
device.  The script runs in one process and starts none.
"""
import argparse
import dataclasses
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "qwen2_1_5b"
SLOTS, MAX_LEN, PAGE, CHUNK, WINDOW = 8, 1024, 16, 128, 8
REQUESTS, NEW_TOKENS, PROMPT_LENS = 8, 32, (100, 500)
# Kernel vs oracle on unit-normal bf16 inputs: the outputs are bf16 and lie
# within |x| < 4, where one bf16 ulp is 2**-6; the bound allows two.
KERNEL_ATOL = 2 * 2.0**-6
# Pallas vs XLA bf16 logits, max |difference| over the std of the XLA
# logits, may be at most this many times the XLA bf16 path's own distance
# from a float32 reference: were the Pallas path no less accurate than XLA,
# the triangle inequality would bound it by 2.
LOGITS_NOISE_FACTOR = 2.0


def log(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def max_err(a, b) -> float:
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32) - jnp.asarray(b, jnp.float32))))


def check(name: str, value: float, bound: float) -> None:
    log(f"{name} = {value!r} (bound {bound!r})")
    if not value <= bound:
        raise AssertionError(f"{name} = {value!r} exceeds {bound!r}")


# ---- 2. kernel parity ------------------------------------------------------


def kernel_parity(cfg, rng, slots=SLOTS, max_len=MAX_LEN, page=PAGE, chunk=CHUNK):
    from repro.kernels import ops, ref

    hq, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    mp = max_len // page
    num_pages = slots * mp + 1  # page 0: the reserved garbage page
    dt = jnp.dtype(cfg.dtype)
    normal = lambda *s: jnp.asarray(rng.standard_normal(s), dt)
    # every slot owns distinct live pages
    tables = jnp.asarray(1 + rng.permutation(slots * mp).reshape(slots, mp), jnp.int32)
    k_pages, v_pages = normal(hkv, num_pages, page, d), normal(hkv, num_pages, page, d)

    lens = jnp.asarray(rng.integers(1, max_len + 1, slots), jnp.int32)
    q = normal(slots, hq, d)
    out = ops.paged_attention(q, k_pages, v_pages, tables, lens, backend="pallas")
    with jax.default_matmul_precision("highest"):
        want = ref.paged_attention(q, k_pages, v_pages, tables, lens)
    check("paged_attention max |kernel - oracle|", max_err(out, want), KERNEL_ATOL)

    starts = jnp.asarray(chunk * rng.integers(0, max_len // chunk, slots), jnp.int32)
    chunk_lens = jnp.asarray(rng.integers(1, chunk + 1, slots), jnp.int32)
    q = normal(slots, hq, chunk, d)
    k_new, v_new = normal(slots, hkv, chunk, d), normal(slots, hkv, chunk, d)
    args = (q, k_new, v_new, k_pages, v_pages, tables, starts, chunk_lens)
    out, kp, vp = ops.prefill_attention(*args, backend="pallas")
    with jax.default_matmul_precision("highest"):
        want, kw, vw = ops.prefill_attention(*args, backend="xla")
    # rows past a slot's live length are garbage on both paths
    live = jnp.arange(chunk)[None, None, :, None] < chunk_lens[:, None, None, None]
    check("prefill_attention max |kernel - oracle|",
          max_err(jnp.where(live, out, 0), jnp.where(live, want, 0)), KERNEL_ATOL)
    # Compare every pool position that holds defined contents: not page 0,
    # which takes every dead write in an order neither path fixes, and not
    # the dead tail of a slot's last written page (the kernel writes whole
    # pages, the oracle only live positions; both are masked on read).
    defined = np.ones((num_pages, page), bool)
    defined[0] = False
    for s, (start, n) in enumerate(zip(np.asarray(starts), np.asarray(chunk_lens))):
        for pos in range(start + n, -(-(start + n) // page) * page):
            defined[int(tables[s, pos // page]), pos % page] = False
    m = jnp.asarray(defined)[None, :, :, None]
    check("prefill_attention max |pages - oracle pages|",
          max(max_err(jnp.where(m, kp, 0), jnp.where(m, kw, 0)),
              max_err(jnp.where(m, vp, 0), jnp.where(m, vw, 0))), 0.0)


# ---- 3. compile the serving steps ------------------------------------------


def step_programs(cfg, slots=SLOTS, max_len=MAX_LEN, page=PAGE, chunk=CHUNK, window=WINDOW):
    """The engine's jitted decode step, prefill step and window, with
    argument shapes as the engine passes them."""
    from repro.models import lm
    from repro.serving import engine as E

    nb = slots * (max_len // page)
    params = jax.eval_shape(functools.partial(lm.init, cfg), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: lm.init_cache(
        cfg, slots, max_len, layout="paged", page_size=page, num_blocks=nb + 1))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    flag = jax.ShapeDtypeStruct((slots,), jnp.bool_)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    return {
        "decode": (E._decode_step_fn(cfg, 0.0),
                   (params, cache, i32(slots), i32(slots), key, flag, flag)),
        "prefill": (E._prefill_step_fn(cfg, 0.0),
                    (params, cache, i32(slots, chunk), i32(slots), i32(slots), key, flag)),
        f"window{window}": (E._decode_loop_fn(cfg, 0.0, window, -1, max_len),
                            (params, cache, i32(slots), i32(slots), key, flag, i32(slots))),
    }


def compile_steps(programs) -> None:
    for name, (fn, args) in programs.items():
        t0 = time.perf_counter()
        lowered = fn.lower(*args)
        t1 = time.perf_counter()
        text = lowered.compile().as_text()
        t2 = time.perf_counter()
        log(f"compile {name}: trace+lower {t1 - t0!r} s, compile {t2 - t1!r} s, "
            f"{text.count('tpu_custom_call')} tpu_custom_call")


# ---- 4-5. serve, then inspect what was served ------------------------------


def serve(seed: int):
    from repro.launch import serve as serve_mod

    argv = ["--arch", ARCH, "--seed", str(seed), "--requests", str(REQUESTS),
            "--slots", str(SLOTS), "--max-new", str(NEW_TOKENS),
            "--max-len", str(MAX_LEN), "--page-size", str(PAGE),
            "--prompt-len", *map(str, PROMPT_LENS),
            "--prefill-chunk", str(CHUNK), "--sync-every", str(WINDOW)]
    t0 = time.perf_counter()
    engine = serve_mod.main(argv)
    wall = time.perf_counter() - t0
    done = engine.completed
    tokens = sum(len(r.output) for r in done)
    log(f"served {len(done)} requests, {tokens} tokens, {wall!r} s wall "
        f"(compiles included), {engine.decode_windows} windows, "
        f"{engine.guard_failures} guard failures")
    bad = [(r.uid, r.status, len(r.output)) for r in done
           if r.status != "completed" or len(r.output) != NEW_TOKENS]
    if len(done) != REQUESTS or bad or engine.guard_failures:
        raise AssertionError(f"serving: {len(done)} ended, bad {bad}, "
                             f"{engine.guard_failures} guard failures")
    if not engine.decode_windows:
        raise AssertionError("serving never ran the device-resident window")
    return engine


def served_programs(engine):
    """Compiled text of every step program the engine dispatched, for the
    argument shapes it dispatched them with (read back from the cache)."""
    b = engine.scfg.slots
    vec, flag = np.zeros(b, np.int32), np.zeros(b, bool)
    p, c, key = engine.params, engine.cache, engine._key
    lowered = {
        "decode": engine._step.lower(p, c, vec, vec, key, flag, flag),
        "prefill": engine._prefill.lower(
            p, c, np.zeros((b, engine.prefill_chunk), np.int32), vec, vec, key, flag),
    }
    for n, loop in sorted(engine._loop_fns.items()):
        lowered[f"window{n}"] = loop.lower(p, c, vec, vec, key, flag, vec)
    return {name: low.compile().as_text() for name, low in lowered.items()}


def check_kernels_ran(engine) -> None:
    from repro.kernels import ops

    for name, text in served_programs(engine).items():
        n = text.count("tpu_custom_call")
        log(f"served {name}: {n} tpu_custom_call")
        if not n:
            raise AssertionError(f"served {name} program holds no Mosaic kernel")
    if ops.FALLBACKS:
        raise AssertionError(f"tile-kernel fallbacks taken: {dict(ops.FALLBACKS)}")


# ---- 6. full-model logits: Pallas vs XLA ---------------------------------


def logits_check(cfg, params, rng, slots=SLOTS, max_len=MAX_LEN, page=PAGE, chunk=CHUNK):
    """Two prefill chunks and one decode step through three paths: bf16
    with the Pallas kernels, bf16 with the XLA oracle, and a float32 XLA
    reference (matmuls at full precision) that sets the bf16 noise floor."""
    from repro.models import lm

    mp = max_len // page
    tables = 1 + np.arange(slots * mp, dtype=np.int32).reshape(slots, mp)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (3, slots, chunk)), jnp.int32)
    full = jnp.full((slots,), chunk, jnp.int32)
    f32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    paths = {"pallas": (cfg, params, "default"), "xla": (cfg, params, "default"),
             "f32": (dataclasses.replace(cfg, dtype="float32"), f32, "highest")}
    logits = {}
    for backend, (c, p, precision) in paths.items():
        c = dataclasses.replace(c, kernel_backend="xla" if backend == "f32" else backend)
        prefill = jax.jit(lambda p, cache, t, pos, lens, c=c:
                          lm.prefill_step(p, c, cache, t, pos, lens), donate_argnums=1)
        decode = jax.jit(lambda p, cache, t, pos, c=c:
                         lm.decode_step(p, c, cache, t, pos), donate_argnums=1)
        cache = lm.init_cache(c, slots, max_len, layout="paged", page_size=page,
                              num_blocks=slots * mp + 1).with_tables(jnp.asarray(tables))
        with jax.default_matmul_precision(precision):
            first, cache = prefill(p, cache, toks[0], 0 * full, full)
            second, cache = prefill(p, cache, toks[1], full, full)
            step, cache = decode(p, cache, toks[2, :, 0], 2 * full)
        logits[backend] = (first, second, step)
    del f32
    for i, name in enumerate(("chunk 1", "chunk 2", "decode")):
        pal, xla, ref = (logits[b][i] for b in ("pallas", "xla", "f32"))
        floor = max_err(xla, ref) / float(jnp.std(ref))
        log(f"logits {name}: max |pallas - f32| / std(f32) = "
            f"{max_err(pal, ref) / float(jnp.std(ref))!r}, "
            f"max |xla - f32| / std(f32) = {floor!r}")
        check(f"logits {name}: max |pallas - xla| / std(xla)",
              max_err(pal, xla) / float(jnp.std(xla)), LOGITS_NOISE_FACTOR * floor)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX's first device is {dev.platform}")
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    from repro.configs import get_config
    from repro.launch import compile_cache

    log(f"device {dev.device_kind}, {len(jax.devices())} device(s), "
        f"compile cache {compile_cache.enable()}")
    cfg = get_config(ARCH)
    rng = np.random.default_rng(args.seed)

    kernel_parity(cfg, rng)
    compile_steps(step_programs(cfg))
    engine = serve(args.seed)
    check_kernels_ran(engine)
    logits_check(cfg, engine.params, rng)
    stats = dev.memory_stats() or {}
    log(f"peak device memory {stats.get('peak_bytes_in_use')!r} bytes")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
