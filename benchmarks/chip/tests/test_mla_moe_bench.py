"""The MLA + MoE architecture in the benchmark: its cost functions against
calls worked by hand, its two readers on a hand-made dispatch log, its
configuration against the published one, and its correctness check on a
tiny cell (a sound run passes, the float8 control fails)."""
import json
import math
import time
import types

import pytest

import tiny  # sets up the import path
import tiny_mla_moe
import harness
import costs
import costs_mla_moe as C

from repro.serving import telemetry

REF = harness._load_module(harness.BENCH_DIR / "reference" / "mla_moe.py", "bench_reference_mla_moe")
LITE = REF.dims(json.loads((harness.BENCH_DIR / "configs" / "deepseek_v2_lite_16b.json").read_text()))
PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def test_config_keeps_published_widths():
    d = LITE
    assert (d.layers, d.dense_layers, d.d_model, d.heads) == (27, 1, 2048, 16)
    assert (d.kv_lora_rank, d.qk_nope, d.qk_rope, d.v_head) == (512, 128, 64, 128)
    assert (d.d_ff, d.d_ff_expert, d.experts, d.held, d.top_k, d.shared) == (10944, 1408, 64, 8, 6, 2)
    assert (d.vocab, d.norm_topk, d.yarn_factor, d.yarn_mscale_all_dim) == (102400, False, 40.0, 0.707)
    # 3.11 B parameters held here: 6.22 GB in bfloat16
    n = sum(math.prod(s) for s in REF.weight_shapes(d).values())
    assert 3.10e9 < n < 3.12e9


def test_paged_mla_one_slot_by_hand():
    # one slot with 100 tokens: 16 heads score a 576-wide key and read back
    # a 512-wide value, 2 FLOPs a multiply-add; the slot's 576-wide latents
    # are read once, its queries (576) and outputs (512) per head
    flops, nbytes = C.paged_mla([100], LITE)
    assert flops == 2 * 16 * (576 + 512) * 100 == 3481600
    assert nbytes == 2 * (576 * 100 + 16 * (576 + 512)) == 150016
    _, bound = costs.min_seconds(flops, nbytes, PEAK)
    assert bound == "bandwidth"


def test_prefill_mla_one_chunk_by_hand():
    # a chunk of 3 tokens after 5 prior ones: 6 + 7 + 8 = 21 keys
    flops, nbytes = C.prefill_mla([(5, 3)], LITE)
    assert flops == 2 * 16 * (576 + 512) * 21
    assert nbytes == 2 * (576 * 5 + 16 * (576 + 512) * 3 + 2 * 576 * 3)


def test_token_flops_by_parts():
    d = LITE
    proj = 2 * (2048 * 16 * 192 + 2048 * 576 + 16 * 128 * 512 + 16 * 512 * 128 + 16 * 128 * 2048)
    assert C.attention_projection_flops(d) == proj
    attend = 2 * 16 * 1088 * 10  # position 9 attends 10 keys
    dense = 2 * 3 * 2048 * 10944
    moe = 26 * (2 * 2048 * 64 + 2 * 3 * 2048 * 2816)
    head = 2 * 2048 * 102400
    assert C.token_flops(d, 9) == 27 * (proj + attend) + dense + moe + head
    assert C.expert_pair_flops(d) == 2 * 3 * 2048 * 1408


def _dispatch(program, t, ticks, rows, routed):
    rec = telemetry.Dispatch(program, ticks, (0, 1), expert_rows=rows, expert_rows_routed=routed)
    for i, phase in enumerate(("upload", "dispatch", "wait", "readback", "drain")):
        setattr(rec, phase, (t + i, t + i + 0.5))
    return rec


def _reader(name):
    return harness._load_module(harness.BENCH_DIR / "metrics" / f"{name}.py", "bench_metric_" + name)


@pytest.fixture
def log(monkeypatch):
    monkeypatch.setattr(telemetry, "LOG", type(telemetry.LOG)(maxlen=telemetry.LOG_MAX))
    telemetry.LOG.extend([
        _dispatch("decode_window_8", 100.0, 8, 8 * 16 * 208, 200),
        _dispatch("prefill_step", 110.0, 1, 2048 * 208, 30),
        _dispatch("decode_step", 120.0, 1, 16 * 208, 10),
        _dispatch("decode_window_8", 900.0, 8, 999, 999),  # after the window
    ])


def _view(t0, t1, decode=(), dims=LITE):
    step = types.SimpleNamespace(t0=t0, t1=t1, decode=list(decode))
    return types.SimpleNamespace(steps=[step], dims=dims, peak=PEAK)


def test_routed_row_share_of_a_synthetic_log(log):
    reader = _reader("expert_routed_row_share")
    view = _view(99.0, 130.0)
    rows = (8 * 16 + 2048 + 16) * 208
    assert reader.read(view) == 100.0 * 240 / rows
    assert reader.note(view) == {"expert_rows": rows, "expert_rows_routed": 240,
                                 "decode_expert_rows_routed": 210}


def test_decode_mfu_of_a_synthetic_log(log):
    reader = _reader("mfu.decode_mla_moe")
    # one slot decoding 9 ticks from position 40, another 1 tick from 7
    view = _view(99.0, 130.0, decode=[(0, 40, 9), (1, 7, 1)])
    flops = sum(C.token_flops(LITE, 40 + t) for t in range(9)) + C.token_flops(LITE, 7)
    flops += 210 * C.expert_pair_flops(LITE)
    decode_s = 2 * 1.5  # dispatch start to wait end of each decode program
    assert reader.read(view) == pytest.approx(100.0 * flops / (decode_s * PEAK["flops_per_s"]))


def test_readers_read_nothing_without_the_counts(monkeypatch):
    summary = {"window_s": 1.0, "decode_ms_per_tick": 1.0}  # a log that predates them
    for name in ("expert_routed_row_share", "mfu.decode_mla_moe"):
        reader = _reader(name)
        mod = reader if name != "mfu.decode_mla_moe" else C
        monkeypatch.setattr(mod.telemetry, "report", lambda t0, t1: summary)
        assert reader.read(_view(0.0, 1.0)) is None
    monkeypatch.setattr(C, "telemetry", None)  # a program without the log
    assert _reader("mfu.decode_mla_moe").read(_view(0.0, 1.0)) is None


# Readings of the tiny cell on the CPU (seeds 2**31 + 5, 2**31 + 6, 2.5 s
# windows): the program's widest gap 0.017 and 0.018, the bfloat16 witness
# the same, int8 0.094 and 0.112, fp8 1.04 and 0.75.  The tiny limit sits
# between the program and fp8.
TINY_LIMIT = 0.25


@pytest.mark.parametrize("seed", (2**31 + 6,))
def test_sound_run_passes_and_fp8_control_fails(seed):
    cell = tiny_mla_moe.cell(max_logit_gap=TINY_LIMIT)
    result, checks, info = harness.measure(cell, seed, 2.5, False, t_start=time.monotonic(),
                                           peak=tiny.PEAK, control=True)
    assert result["correct"], checks
    assert info["check"]["max_gap"] <= TINY_LIMIT
    assert info["check"]["fp8_gap"] > TINY_LIMIT


def test_decode_mfu_reads_nothing_for_another_architecture(log):
    dense = harness._load_module(harness.BENCH_DIR / "reference" / "dense_gqa.py", "bench_reference_dense_gqa")
    view = _view(99.0, 130.0, decode=[(0, 40, 9)], dims=dense.dims(tiny.CONFIG))
    assert _reader("mfu.decode_mla_moe").read(view) is None
