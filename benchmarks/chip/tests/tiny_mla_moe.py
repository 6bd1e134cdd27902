"""A DeepSeek-V2-style cell small enough for the CPU: MLA with YaRN and a
dense first layer, then MoE layers routing over 64 experts of which this
chip holds 8, served through the same harness as the chip's cells."""
import json

import tiny  # sets up the import path
import harness

CONFIG = {
    "name": "tiny_mla_moe", "architecture": "mla_moe", "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4, "num_key_value_heads": 4,
    "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "moe_intermediate_size": 24, "n_routed_experts": 8,
    "n_shared_experts": 2, "num_experts_per_tok": 6, "norm_topk_prob": False,
    "routed_scaling_factor": 1, "scoring_func": "softmax", "topk_method": "greedy",
    "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
                     "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "tie_word_embeddings": False, "torch_dtype": "bfloat16", "first_held_expert": 8,
    "reduced": {"n_routed_experts": {"published": 64, "here": 8}},
}


def cell(**limits) -> "harness.Cell":
    bench = json.loads((tiny.BENCH_DIR.parents[1] / "BENCHMARK.json").read_text())
    params = dict(tiny.PARAMS, limits=dict(tiny.PARAMS["limits"], **limits))
    name = "deepseek_v2_lite_16b.gen_backlog"
    mine = [m for m in bench["per_layer"] if name in m.get("workloads", [name])]
    return harness.Cell(
        name="tiny_mla_moe.backlog", chips=1, config=dict(CONFIG), mix=tiny.MIX, params=params,
        end_to_end=[m for m in bench["end_to_end"] if name in m.get("workloads", [name])],
        per_layer=mine,
    )
