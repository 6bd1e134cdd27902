"""A cell small enough for the CPU: the dense GQA block at toy widths,
served through the same harness as the chip's cells."""
import pathlib
import sys

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
for p in (BENCH_DIR, BENCH_DIR.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import harness  # noqa: E402

CONFIG = {
    "name": "tiny_gqa", "architecture": "dense_gqa", "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 256,
    "rms_norm_eps": 1e-6, "rope_theta": 10000.0, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16", "attention_bias": True,
}
# prompts past one chunk, so that admissions prefill in several ticks
MIX = {"arrivals": "backlog", "prompt_len": {"lo": 8, "hi": 40},
       "output_len": {"lo": 16, "hi": 48}, "block": 8}
PARAMS = {
    "slots": 4, "max_len": 96, "page_size": 16, "prefill_chunk": 16, "sync_every": 8,
    "num_blocks": None, "requests": 256, "queue_depth": 4,
    "limits": {"max_logit_gap": 0.5, "min_served_tokens": 32},
}


def cell(**limits) -> "harness.Cell":
    import json

    bench = json.loads((BENCH_DIR.parents[1] / "BENCHMARK.json").read_text())
    params = dict(PARAMS, limits=dict(PARAMS["limits"], **limits))
    return harness.Cell(
        name="tiny.backlog", chips=1, config=dict(CONFIG), mix=MIX, params=params,
        end_to_end=list(bench["end_to_end"]), per_layer=list(bench["per_layer"]),
    )


PEAK = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
