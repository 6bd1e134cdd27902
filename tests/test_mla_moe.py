"""DeepSeek-V2's block against the plain reference of the chip benchmark
(``benchmarks/chip/reference/mla_moe.py``, float32, un-absorbed MLA, YaRN,
softmax top-6 over 64 experts of which 8 are held here, a dense first layer
and shared experts), at a small size on the CPU with seeded random weights.
The widths keep the published proportions: rope dims half the no-rope
dims, a 32-wide latent for 4 heads of 16, 64 routed experts, top 6, 2
shared, the first layer dense."""
import dataclasses
import importlib.util
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import layers as L
from repro.models import lm
from repro.models.config import YarnScaling
from repro.serving import ServeConfig, ServingEngine
from repro.serving import engine as engine_mod

BENCH = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"


def _load(rel: str, name: str):
    spec = importlib.util.spec_from_file_location(name, BENCH / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("reference/mla_moe.py", "test_reference_mla_moe")
ARCH = _load("arch/mla_moe.py", "test_arch_mla_moe")

CONFIG = {
    "name": "small_mla_moe", "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 344, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "kv_lora_rank": 32, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "moe_intermediate_size": 44, "n_routed_experts": 8, "n_shared_experts": 2,
    "num_experts_per_tok": 6, "norm_topk_prob": False, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "topk_method": "greedy", "vocab_size": 256,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
                     "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "tie_word_embeddings": False, "torch_dtype": "float32", "first_held_expert": 16,
    "reduced": {"n_routed_experts": {"published": 64, "here": 8}},
}
SEED = 2**31 + 3

# Served logits (largest about 4.3) against the reference's: float32 on
# both sides differs only in the order of sums (absorbed against expanded
# attention, fused against separate expert products), 3.6e-6 at most here;
# the same program in bfloat16 misses by 0.050.  The tolerance lies between,
# 280 times above the one and 50 times below the other.
LOGIT_TOL = 1e-3


def _program(cfg_json, dtype):
    d = REF.dims(cfg_json)
    mcfg = dataclasses.replace(ARCH.model_config(dict(cfg_json, torch_dtype=dtype), d),
                               kernel_backend="xla")
    params = ARCH.program_params(REF.make_weights(cfg_json, SEED), mcfg)
    return d, mcfg, params


@pytest.fixture
def fresh_programs():
    """The engine shares its jitted programs across engines: a spy must be
    traced into new ones, and must not leak into other tests."""
    engine_mod._STEP_FNS.clear()
    yield
    engine_mod._STEP_FNS.clear()


def _served_logits(dtype, monkeypatch, prompt_len=37, new=12):
    """Serve one prompt (three prefill chunks, then paged decode ticks)
    through ``ServingEngine`` and return the logits its programs sampled
    from, the last prefill chunk's and each decode tick's, with the served
    sequence."""
    d, mcfg, params = _program(CONFIG, dtype)
    seen = []
    real = engine_mod.sample_step

    def spy(logits, key, **kw):
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), logits, ordered=True)
        return real(logits, key, **kw)

    monkeypatch.setattr(engine_mod, "sample_step", spy)
    eng = ServingEngine(mcfg, params, ServeConfig(
        slots=1, max_len=64, max_new_tokens=new, page_size=16, prefill_chunk=16,
        sync_every=1, cache="paged", prefill="chunked"))
    prompt = np.random.default_rng(5).integers(0, CONFIG["vocab_size"], prompt_len).tolist()
    req = eng.submit(prompt)
    eng.run()
    jax.effects_barrier()
    assert req.status == "completed" and len(req.output) == new
    assert len(seen) == 3 + new - 1  # three prefill chunks, then a tick per token
    served = np.concatenate([x[0:1] for x in seen[-new:]]).astype(np.float64)
    return served, prompt + req.output, d


def _reference_logits(seq, d, n_prompt, new):
    w = REF.make_weights(CONFIG, SEED)
    h = REF.hidden(w, jnp.asarray(seq, jnp.int32), d, "float32")
    logits = jnp.dot(h, w["lm_head"].astype(jnp.float32).T, precision=jax.lax.Precision.HIGHEST)
    return np.asarray(logits[n_prompt - 1 : n_prompt - 1 + new], np.float64)


def test_served_logits_match_reference(monkeypatch, fresh_programs):
    served, seq, d = _served_logits("float32", monkeypatch)
    ref = _reference_logits(seq[:-1], d, 37, 12)
    assert np.max(np.abs(served - ref)) <= LOGIT_TOL
    # the served tokens are the program's greedy picks
    assert list(np.argmax(served, axis=-1)) == seq[37:]


def test_bfloat16_program_misses_the_float32_tolerance(monkeypatch, fresh_programs):
    """The comparison is tight enough to tell a lower precision than the
    configuration states."""
    served, seq, d = _served_logits("bfloat16", monkeypatch)
    ref = _reference_logits(seq[:-1], d, 37, 12)
    assert np.max(np.abs(served - ref)) > LOGIT_TOL


def test_expert_shares_sum_to_uncut_layer():
    """One MoE layer split over 8 chips of 8 experts each: the routed parts
    of the 8 shares, plus the shared experts counted once, give what the
    uncut reference layer gives, and every token's top 6 land in the shares
    exactly once."""
    uncut = dict(CONFIG, n_routed_experts=64, first_held_expert=0)
    del uncut["reduced"]
    d = REF.dims(uncut)
    w = REF.make_weights(uncut, SEED)
    layer = {k: w[k][0] for k in ("router", "exp_gate", "exp_up", "exp_down", "shared_gate",
                                  "shared_up", "shared_down")}
    layer = jax.tree.map(lambda a: a.astype(jnp.float32), layer)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, 9, 64)), jnp.float32)
    want = REF._moe(x.reshape(18, 64), layer, d, "float32").reshape(2, 9, 64)

    mcfg = ARCH.model_config(dict(uncut, torch_dtype="float32"), d)
    shared = {"w_gate": layer["shared_gate"], "w_up": layer["shared_up"],
              "w_down": layer["shared_down"]}
    shared_out = L.mlp(shared, x, mcfg)
    total, picks = shared_out, 0
    for i in range(8):
        e = slice(8 * i, 8 * i + 8)
        cfg = dataclasses.replace(mcfg, moe=dataclasses.replace(
            mcfg.moe, held_experts=8, first_held_expert=8 * i))
        p = {"router": layer["router"], "w_gate": layer["exp_gate"][e],
             "w_up": layer["exp_up"][e], "w_down": layer["exp_down"][e], "shared": shared}
        out, _, routed = L.moe(p, x, cfg)
        total = total + (out - shared_out)
        picks = picks + routed
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-5, rtol=0)
    assert np.all(np.asarray(picks) == 6)


def test_logits_do_not_depend_on_batch_mates():
    """Dropless routing: a sequence's prefill and decode logits are those it
    gets alone, whoever shares its batch (capacity routing dropped tokens
    by their batch-mates' picks)."""
    _, mcfg, params = _program(CONFIG, "float32")
    rng = np.random.default_rng(2)
    mine = rng.integers(0, 256, (1, 16))
    mates = rng.integers(0, 256, (2, 16))

    def run(tokens, row):
        b = tokens.shape[0]
        cache = lm.init_cache(mcfg, b, 32, layout="paged", page_size=16, num_blocks=2 * b + 1)
        cache = cache.with_tables(jnp.arange(b * 2, dtype=jnp.int32).reshape(b, 2) + 1)
        logits, cache = lm.prefill_step(params, mcfg, cache, jnp.asarray(tokens, jnp.int32),
                                        jnp.zeros(b, jnp.int32), jnp.full(b, 16, jnp.int32))
        out = [logits[row]]
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for t in range(4):
            logits, cache = lm.decode_step(params, mcfg, cache, tok, jnp.full(b, 16 + t, jnp.int32))
            out.append(logits[row])
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        return np.stack([np.asarray(x) for x in out])

    alone = run(mine, 0)
    batched = run(np.concatenate([mates[:1], mine, mates[1:]]), 1)
    np.testing.assert_allclose(batched, alone, atol=1e-5, rtol=0)


def test_yarn_frequencies_and_scale_in_closed_form():
    """DeepSeek-V2-Lite's YaRN (factor 40 over 4,096 positions, beta 32 and
    1) on its 64 rope dims: pairs below 10 keep theta ** (-2i / 64), pairs
    from 23 on are divided by 40, the ramp blends between; the softmax scale
    is 192 ** -0.5 * (0.1 * 0.707 * ln 40 + 1) ** 2."""
    y = YarnScaling(factor=40.0, original_max_position_embeddings=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)
    inv, rot = L.rope_freqs(64, 10000.0, yarn=y)
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # correction dims: 64 ln(4096 / (2 pi beta)) / (2 ln 10000) = 10.47 and 22.5
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(np.asarray(inv), base / 40 * ramp + base * (1 - ramp), rtol=1e-6)
    assert rot == 64 and inv[9] == pytest.approx(base[9]) and inv[23] == pytest.approx(base[23] / 40)
    cfg = get_config("deepseek_v2_lite_16b")
    assert L.mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2)
    # the reference's published-form YaRN agrees
    d = REF.dims(dict(CONFIG, qk_rope_head_dim=64))
    np.testing.assert_allclose(np.asarray(REF.yarn_inv_freq(d)), np.asarray(inv), rtol=1e-6)
    # at mscale == mscale_all_dim the rotation keeps its length
    x = jnp.asarray(np.random.default_rng(3).standard_normal((1, 5, 2, 64)), jnp.float32)
    r = L.apply_rope(x, jnp.arange(5)[None], 10000.0, yarn=y)
    np.testing.assert_allclose(np.linalg.norm(r, axis=-1), np.linalg.norm(x, axis=-1), rtol=1e-5)


def test_published_config():
    cfg = get_config("deepseek_v2_lite_16b")
    mo = cfg.moe
    assert (mo.num_experts, mo.held, mo.experts_per_token, mo.num_shared_experts) == (64, 64, 6, 2)
    assert not mo.norm_topk_prob and mo.d_ff_expert == 1408
    shapes = jax.eval_shape(lambda k: lm.init(cfg, k), jax.random.PRNGKey(0))
    assert shapes["prefix_layers"][0]["mlp"]["w_gate"].shape == (2048, 10944)
    assert shapes["layers"]["moe"]["router"].shape == (26, 2048, 64)
    assert 15.5e9 < cfg.param_count() < 16.0e9  # 15.7 B published


@pytest.mark.parametrize("spec", [None, "ngram"])
def test_engine_logs_expert_rows(spec):
    """Each program's record in the dispatch log counts the held experts'
    rows it computed (layers x held experts x token rows) and the picks of
    its live tokens among them: with all 64 experts held, every live token
    picks 6 in each of the 2 MoE layers, so prefill counts 12 a prompt
    token and decode 12 a generated token but the first (which prefill
    emits)."""
    from repro.serving import telemetry

    uncut = dict(CONFIG, n_routed_experts=64, first_held_expert=0)
    del uncut["reduced"]
    _, mcfg, params = _program(uncut, "float32")
    eng = ServingEngine(mcfg, params, ServeConfig(
        slots=2, max_len=64, max_new_tokens=9, page_size=16, prefill_chunk=16, sync_every=4,
        spec_decode=spec, draft_len=2))
    start = len(telemetry.LOG)
    rng = np.random.default_rng(4)
    reqs = [eng.submit(rng.integers(0, 256, n).tolist()) for n in (5, 21, 9)]
    eng.run()
    recs = [r for r in list(telemetry.LOG)[start:] if isinstance(r, telemetry.Dispatch)
            and r.program != "copy_pages"]
    assert all(r.expert_rows > 0 for r in recs)
    assert all(0 < r.expert_rows_routed <= r.expert_rows for r in recs)
    prefill = [r for r in recs if r.program == "prefill_step"]
    assert sum(r.expert_rows for r in prefill) == 2 * 64 * sum(r.rows for r in prefill)
    assert sum(r.expert_rows_routed for r in prefill) == 2 * 6 * sum(len(q.prompt) for q in reqs)
    if spec is None:
        decode = [r for r in recs if r.program != "prefill_step"]
        generated = sum(len(q.output) - 1 for q in reqs)
        assert sum(r.expert_rows_routed for r in decode) == 2 * 6 * generated
        assert any(r.program.startswith("decode_window_") for r in decode)
