"""The correctness check at a size the CPU holds: sound runs pass, the
control (the reference at a precision below bfloat16 in the program's
place) fails, and a run with the timed path broken underneath fails.

The harness runs as on the chip, except that it does not look for one.
"""
import time

import pytest

import tiny  # sets up the import path
import harness

# Readings of this tiny cell on the CPU (seeds 1, 2, 3, 2**31 + 5):
# the program's widest gap 0.0035 to 0.0141, the bfloat16 witness 0 to
# 0.0132, int8 0.079 to 0.093, fp8 0.25 to 0.75.  The tiny limit sits
# between the program and fp8; int8 does not separate at these widths.
TINY_LIMIT = 0.1
SEEDS = (1, 2**31 + 5)


def _measure(seed, **kw):
    cell = tiny.cell(max_logit_gap=TINY_LIMIT)
    return harness.measure(cell, seed, 2.5, False, t_start=time.monotonic(), peak=tiny.PEAK, **kw)


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_run_passes_and_control_fails(seed):
    result, checks, info = _measure(seed, control=True)
    assert result["correct"], checks
    assert info["check"]["max_gap"] <= TINY_LIMIT
    assert info["check"]["fp8_gap"] > TINY_LIMIT


@pytest.fixture
def fresh_programs():
    """The engine shares its jitted steps across engines; a planted fault
    must be traced anew, and must not leak into other tests."""
    from repro.serving import engine

    engine._STEP_FNS.clear()
    yield
    engine._STEP_FNS.clear()


def test_altered_token_fails(monkeypatch, fresh_programs):
    from repro.serving import engine

    emit = engine.ServingEngine._emit_token

    def altered(self, s, req, tok):
        if len(req.output) == 5:  # every request's sixth token is wrong
            tok = (tok + 1) % tiny.CONFIG["vocab_size"]
        return emit(self, s, req, tok)

    monkeypatch.setattr(engine.ServingEngine, "_emit_token", altered)
    result, checks, info = _measure(7)
    assert not result["correct"]
    assert info["check"]["max_gap"] > TINY_LIMIT


def test_state_left_unchanged_fails(monkeypatch, fresh_programs):
    """A decode step that returns the KV pages it was given: later tokens
    attend over positions that were never written."""
    from repro.models import lm

    step = lm.decode_step

    def stale(params, cfg, cache, token, pos, unroll=1, live=None):
        logits, _ = step(params, cfg, cache, token, pos, unroll=unroll, live=live)
        return logits, cache

    monkeypatch.setattr(lm, "decode_step", stale)
    result, checks, info = _measure(7)
    assert not result["correct"]
    assert info["check"]["max_gap"] > TINY_LIMIT
