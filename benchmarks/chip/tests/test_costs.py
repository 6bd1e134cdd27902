"""The operation and byte counts of the kernels, against calls worked by
hand, and the harness's account of the work a run dispatched."""
import pathlib
import sys

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH_DIR))

import costs  # noqa: E402
from reference.dense_gqa import Dims  # noqa: E402

# qwen2-1.5b widths: 12 query heads, 2 KV heads of 128
QWEN = Dims(layers=28, d_model=1536, heads=12, kv_heads=2, head_dim=128, d_ff=8960,
            vocab=151936, qkv_bias=True, tied=True, rope_theta=1e6, eps=1e-6)
PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def test_paged_attn_one_slot_by_hand():
    # one slot with 100 tokens: Q.K^T and P.V are 2 * 12 * 128 * 100 FLOPs
    # each; K and V are 2 * 2 * 100 * 128 bf16 values, Q and the output
    # 12 * 128 each
    flops, nbytes = costs.paged_attn([100], QWEN)
    assert flops == 2 * (2 * 12 * 128 * 100) == 614400
    assert nbytes == 2 * (2 * 2 * 100 * 128 + 2 * 12 * 128) == 108544
    t, bound = costs.min_seconds(flops, nbytes, PEAK)
    assert bound == "bandwidth" and t == 108544 / 819e9


def test_prefill_attn_one_chunk_by_hand():
    # a chunk of 3 tokens after 5 prior ones: the queries see 6, 7 and 8
    # keys, 21 in all
    flops, nbytes = costs.prefill_attn([(5, 3)], QWEN)
    assert flops == 4 * 12 * 128 * 21 == 129024
    # prior K/V 2*2*5*128, chunk Q 12*3*128 in and out, chunk K/V read and
    # written 2 * (2*2*3*128), all bf16
    assert nbytes == 2 * (2 * 2 * 5 * 128 + 2 * 12 * 3 * 128 + 4 * 2 * 3 * 128) == 29696


def test_model_flops_per_token():
    per_layer = 1536 * (2 * 1536 + 2 * 256) + 3 * 1536 * 8960
    assert costs.matmul_flops_per_token(QWEN) == 2 * 28 * per_layer
    assert costs.token_flops(QWEN, 9) == 2 * 28 * per_layer + 4 * 28 * 12 * 128 * 10


def test_harness_accounts_every_position_once():
    """Over a tiny run, the chunks and decode ticks the harness records
    cover each finished request's prompt once and its output tokens but
    the last (the first comes from the prefill's logits)."""
    import collections

    import tiny
    import harness
    import traffic

    cell = tiny.cell()
    run = harness.Run(cell, 11)
    run.warm_programs(cell.params["prefill_chunk"])
    run.records.clear()
    run.start_traffic(traffic.generate(cell.mix, 11, tiny.CONFIG["vocab_size"], 12))
    while run.busy() or run._next is not None:
        run.top_up(cell.params["queue_depth"])
        run.step()
    pre, dec = collections.Counter(), collections.Counter()
    for rec in run.records:
        for uid, start, n in rec.prefill:
            pre[uid] += n
        for uid, _p0, k in rec.decode:
            dec[uid] += k
    assert len(run.tracked) == 12
    for t in run.tracked:
        r = t.req
        assert r.status == "completed" and r.preemptions == 0
        assert pre[r.uid] == len(r.prompt), r.uid
        assert dec[r.uid] == len(r.output) - 1, r.uid
    assert sum(rec.emitted for rec in run.records) == sum(len(t.req.output) for t in run.tracked)
    d = run.dims
    per_req = sum(sum(costs.token_flops(d, p) for p in range(len(t.req.prompt) + len(t.req.output) - 1))
                  + len(t.req.output) * costs.head_flops(d) for t in run.tracked)
    assert abs(costs.step_flops(run.records, d) - per_req) <= 1e-9 * per_req
