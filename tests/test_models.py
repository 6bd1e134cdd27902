"""Per-architecture smoke tests: REDUCED config of the same family, one
forward + one train-grad step + one decode step on CPU; asserts output
shapes and absence of NaNs (the full configs are exercised only via the
dry-run)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models import encdec, lm

B, S = 2, 32


def _tokens(cfg, rng):
    return jnp.asarray(rng.integers(0, cfg.vocab_size, size=(B, S)), jnp.int32)


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch, key, rng):
    cfg = get_config(arch).reduced()
    if cfg.is_encoder_decoder:
        params = encdec.init(cfg, key)
        frames = jnp.asarray(
            rng.standard_normal((B, cfg.frontend_seq, cfg.d_model), dtype=np.float32)
        )
        tokens = _tokens(cfg, rng)
        labels = tokens

        def loss(p):
            return encdec.loss_fn(p, cfg, frames, tokens, labels)[0]

        l, grads = jax.value_and_grad(loss)(params)
        logits = encdec.decode_full(
            params, cfg, tokens, encdec.encode(params, cfg, frames)
        )
        assert logits.shape == (B, S, cfg.vocab_size)
    else:
        params = lm.init(cfg, key)
        tokens = _tokens(cfg, rng)
        prefix = None
        if cfg.frontend != "none":
            prefix = jnp.asarray(
                rng.standard_normal((B, cfg.frontend_seq, cfg.d_model), dtype=np.float32)
            )
        logits, _ = lm.forward(params, cfg, tokens, prefix_embeds=prefix)
        total = S + (cfg.frontend_seq if prefix is not None else 0)
        assert logits.shape == (B, total, cfg.vocab_size)
        assert not np.any(np.isnan(np.asarray(logits)))

        def loss(p):
            return lm.loss_fn(p, cfg, tokens, tokens, prefix_embeds=prefix)[0]

        l, grads = jax.value_and_grad(loss)(params)
    assert np.isfinite(float(l))
    gnorm = jnp.sqrt(
        sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in jax.tree.leaves(grads))
    )
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_decode_step(arch, key, rng):
    cfg = get_config(arch).reduced()
    max_len = 16
    if cfg.is_encoder_decoder:
        params = encdec.init(cfg, key)
        frames = jnp.asarray(
            rng.standard_normal((B, cfg.frontend_seq, cfg.d_model), dtype=np.float32)
        )
        enc = encdec.encode(params, cfg, frames)
        cross = encdec.cross_kv(params, cfg, enc)
        cache = encdec.init_cache(cfg, B, max_len)
        tok = jnp.zeros((B,), jnp.int32)
        logits, cache = encdec.decode_step(params, cfg, cache, tok, 0, cross)
        logits2, _ = encdec.decode_step(params, cfg, cache, tok + 1, 1, cross)
        assert logits.shape == (B, cfg.vocab_size)
        assert not np.any(np.isnan(np.asarray(logits2)))
        return
    params = lm.init(cfg, key)
    cache = lm.init_cache(cfg, B, max_len)
    tok = jnp.zeros((B,), jnp.int32)
    logits, cache = lm.decode_step(params, cfg, cache, tok, 0)
    logits2, cache = lm.decode_step(params, cfg, cache, tok + 1, 1)
    assert logits.shape == (B, cfg.vocab_size)
    assert not np.any(np.isnan(np.asarray(logits2)))


def test_decode_matches_forward_dense(key, rng):
    """Greedy decode logits must match teacher-forced forward logits."""
    cfg = get_config("qwen2_1_5b").reduced()
    params = lm.init(cfg, key)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(1, 8)), jnp.int32)
    full_logits, _ = lm.forward(params, cfg, toks)
    cache = lm.init_cache(cfg, 1, 8)
    for t in range(8):
        step_logits, cache = lm.decode_step(params, cfg, cache, toks[:, t], t)
        np.testing.assert_allclose(
            np.asarray(step_logits[0]),
            np.asarray(full_logits[0, t]),
            atol=2e-3,
            err_msg=f"position {t}",
        )


def test_decode_matches_forward_ssm(key, rng):
    """SSM decode recurrence must match the chunked SSD forward."""
    cfg = get_config("mamba2_2_7b").reduced()
    params = lm.init(cfg, key)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(1, 8)), jnp.int32)
    full_logits, _ = lm.forward(params, cfg, toks)
    cache = lm.init_cache(cfg, 1, 8)
    for t in range(8):
        step_logits, cache = lm.decode_step(params, cfg, cache, toks[:, t], t)
        np.testing.assert_allclose(
            np.asarray(step_logits[0]),
            np.asarray(full_logits[0, t]),
            atol=5e-3,
            err_msg=f"position {t}",
        )


def test_moe_balanced_dispatch(key, rng):
    """MoE keeps shapes static and routes every token to its top-k experts:
    dropless, so with every expert held each token counts k picks."""
    from repro.models import layers as L

    cfg = get_config("granite_moe_3b_a800m").reduced()
    params = lm.init(cfg, key)
    p_moe = jax.tree.map(lambda a: a[0], params["layers"])["moe"]
    x = jnp.asarray(rng.standard_normal((2, 16, cfg.d_model), dtype=np.float32))
    out, aux, routed = L.moe(p_moe, x, cfg)
    assert out.shape == x.shape
    assert float(aux) >= 0
    assert np.all(np.asarray(routed) == cfg.moe.experts_per_token)

def test_param_counts_in_range():
    """Full configs should land near their nameplate sizes."""
    expect = {
        "gemma_7b": (7.0e9, 10.5e9),     # 8.5B incl 786M embed (256k vocab)
        "deepseek_7b": (6.0e9, 8.0e9),
        "qwen2_1_5b": (1.2e9, 2.1e9),
        "mamba2_2_7b": (2.2e9, 3.2e9),
        "deepseek_v2_lite_16b": (13e9, 18e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B not in [{lo/1e9}, {hi/1e9}]"


def test_decode_matches_forward_whisper(key, rng):
    """Enc-dec decode path must match teacher-forced decode_full."""
    cfg = get_config("whisper_tiny").reduced()
    params = encdec.init(cfg, key)
    frames = jnp.asarray(
        rng.standard_normal((1, cfg.frontend_seq, cfg.d_model), dtype=np.float32)
    )
    enc = encdec.encode(params, cfg, frames)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(1, 8)), jnp.int32)
    full = encdec.decode_full(params, cfg, toks, enc)
    cross = encdec.cross_kv(params, cfg, enc)
    cache = encdec.init_cache(cfg, 1, 8)
    for t in range(8):
        step, cache = encdec.decode_step(params, cfg, cache, toks[:, t], t, cross)
        np.testing.assert_allclose(
            np.asarray(step[0]), np.asarray(full[0, t]), atol=2e-3,
            err_msg=f"position {t}",
        )


def test_decode_matches_forward_mla(key, rng):
    """MLA latent-cache decode must match the expanded training attention
    (the MoE layers route dropless, so the batched forward and one-token
    decode route every token alike)."""
    cfg = get_config("deepseek_v2_lite_16b").reduced()
    params = lm.init(cfg, key)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(1, 8)), jnp.int32)
    full_logits, _ = lm.forward(params, cfg, toks)
    cache = lm.init_cache(cfg, 1, 8)
    for t in range(8):
        step_logits, cache = lm.decode_step(params, cfg, cache, toks[:, t], t)
        np.testing.assert_allclose(
            np.asarray(step_logits[0]), np.asarray(full_logits[0, t]), atol=5e-3,
            err_msg=f"position {t}",
        )


def test_decode_matches_forward_hybrid(key, rng):
    """Hybrid (attn ring-buffer + SSM state) decode parity with forward."""
    cfg = get_config("hymba_1_5b").reduced()
    params = lm.init(cfg, key)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(1, 8)), jnp.int32)
    full_logits, _ = lm.forward(params, cfg, toks)
    cache = lm.init_cache(cfg, 1, 8)
    for t in range(8):
        step_logits, cache = lm.decode_step(params, cfg, cache, toks[:, t], t)
        np.testing.assert_allclose(
            np.asarray(step_logits[0]), np.asarray(full_logits[0, t]), atol=5e-3,
            err_msg=f"position {t}",
        )
