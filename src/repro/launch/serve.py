"""Serving driver: continuous-batching engine over a reduced (CPU) or full
(TPU) model, with random weights drawn from ``--seed``.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2_1_5b --reduced \
        --requests 16 --max-new 32

Exits non-zero unless every request completes.  Compiled programs are kept
in the persistent compilation cache (``repro.launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch import compile_cache
from repro.models import lm
from repro.serving import ServeConfig, ServingEngine, telemetry


def dispatch_line(report) -> str:
    """One line from the dispatch log (``telemetry.report``): host seconds
    per phase, decode milliseconds per tick, prefill live-row share."""
    if report is None:
        return "dispatch log: cut short (the run outlasted the log)"
    phases = ", ".join(f"{k} {v:.3f}" for k, v in report["phase_s"].items())
    line = f"dispatch log: {report['dispatches']} programs; host s: {phases}"
    if report["decode_ms_per_tick"] is not None:
        line += f"; decode {report['decode_ms_per_tick']:.2f} ms/tick"
    if report["prefill_rows"]:
        live, rows = report["prefill_live_rows"], report["prefill_rows"]
        line += f"; prefill live rows {live}/{rows} ({100.0 * live / rows:.1f}%)"
    return line


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, nargs="+", default=[8],
                    metavar=("LEN", "MAX"),
                    help="prompt length, or a range [LEN, MAX] drawn "
                         "uniformly per request")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cache", choices=["paged", "contiguous"], default="paged",
                    help="KV layout (paged = block pool + block tables)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="KV pool size in blocks; below slots*max_pages "
                         "oversubscribes memory and exercises preemption")
    ap.add_argument("--prefill", choices=["chunked", "replay"],
                    default="chunked",
                    help="prompt ingestion: chunked fast path (token-budget "
                         "scheduler) or legacy one-token-per-tick replay")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="prompt tokens per chunk-wide forward pass")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="per-tick token budget shared by the decode batch "
                         "and prefill chunks (default slots+prefill_chunk)")
    ap.add_argument("--sync-every", type=int, default=1,
                    help="decode ticks per host dispatch: >1 runs the "
                         "device-resident jax.lax.scan loop when every "
                         "active slot is generating (scheduler runs at "
                         "sync boundaries only)")
    ap.add_argument("--spec-decode", choices=["ngram"], default=None,
                    help="speculative decoding draft proposer: each round "
                         "drafts --draft-len tokens (ngram = self-"
                         "speculation over the slot's own history) and "
                         "verifies all of them in one chunk forward; "
                         "greedy output stays byte-identical to plain "
                         "decode, composes multiplicatively with "
                         "--sync-every")
    ap.add_argument("--draft-len", type=int, default=4,
                    help="draft tokens proposed per speculative round "
                         "(verify chunk is draft_len+1 wide)")
    ap.add_argument("--audit", action="store_true",
                    help="run the serving invariant auditor after every "
                         "tick (page conservation, refcounts, radix "
                         "reachability, slot hygiene); raises AuditError "
                         "at the tick the books diverge")
    ap.add_argument("--guards", choices=["on", "off"], default="on",
                    help="discharge the kernels' runtime obligations "
                         "(block-table range + disjoint-write checks) "
                         "before every paged dispatch; 'off' benchmarks "
                         "raw dispatch cost without the host-side checks")
    ap.add_argument("--deadline-ticks", type=int, default=None,
                    help="per-request deadline in engine ticks; expired "
                         "requests exit TIMED_OUT with partial output")
    args = ap.parse_args(argv)
    if len(args.prompt_len) > 2:
        ap.error("--prompt-len takes LEN or LEN MAX")
    lo, hi = args.prompt_len[0], args.prompt_len[-1]

    compile_cache.enable()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.is_encoder_decoder:
        raise SystemExit("enc-dec serving demo lives in examples/; use an LM arch")

    # one compiled program, not one dispatch per weight
    params = jax.jit(functools.partial(lm.init, cfg))(jax.random.PRNGKey(args.seed))
    engine = ServingEngine(
        cfg, params,
        ServeConfig(slots=args.slots, max_len=args.max_len,
                    max_new_tokens=args.max_new,
                    temperature=args.temperature, seed=args.seed,
                    cache=args.cache, page_size=args.page_size,
                    num_blocks=args.num_blocks, prefill=args.prefill,
                    prefill_chunk=args.prefill_chunk,
                    token_budget=args.token_budget,
                    sync_every=args.sync_every,
                    spec_decode=args.spec_decode, draft_len=args.draft_len,
                    audit=args.audit,
                    guards=args.guards == "on"),
    )
    rng = np.random.default_rng(args.seed)
    for _ in range(args.requests):
        n = lo if lo == hi else int(rng.integers(lo, hi + 1))
        prompt = rng.integers(0, cfg.vocab_size, size=n).tolist()
        engine.submit(prompt, deadline_ticks=args.deadline_ticks)

    t0 = time.time()
    m0 = telemetry.clock()
    done = engine.run()
    dt = time.time() - t0
    report = telemetry.report(m0, telemetry.clock())
    total_tokens = sum(len(r.output) for r in done)
    extra = ""
    if engine.pool is not None:
        extra = (
            f", {engine.cache_mode} cache: peak {engine.peak_kv_blocks()} "
            f"blocks, {engine.preemptions} preemptions"
        )
    if engine.sync_every > 1:
        extra += (
            f", {engine.decode_windows} multi-step windows "
            f"({engine.window_fallbacks} fallbacks)"
        )
    if engine.spec_proposer is not None:
        rate = engine.spec_accepted / max(engine.spec_proposed, 1)
        extra += (
            f", {engine.spec_windows} spec windows: "
            f"{engine.spec_accepted}/{engine.spec_proposed} drafts accepted "
            f"({rate:.2f})"
        )
    ttfts = [r.ttft_ticks for r in done if r.ttft_ticks is not None]
    if ttfts:
        extra += f", mean TTFT {sum(ttfts)/len(ttfts):.1f} ticks"
    if args.audit:
        extra += f", {engine.audits_run} audits clean"
    not_completed = [r for r in done if r.status != "completed"]
    if not_completed:
        extra += f", {len(not_completed)} not completed (" + ", ".join(
            f"{r.uid}:{r.status}" for r in not_completed[:4]) + ")"
    print(
        f"served {len(done)} requests, {total_tokens} tokens in {dt:.2f}s "
        f"({total_tokens/max(dt,1e-9):.1f} tok/s, {engine.steps_run} engine steps"
        f" [{engine.prefill_mode} prefill]{extra})"
    )
    print(dispatch_line(report))
    for r in done[:3]:
        print(f"  req {r.uid}: prompt {r.prompt[:4]}... -> {r.output[:8]}...")
    if not_completed or len(done) != args.requests:
        raise SystemExit(
            f"{args.requests - len(done) + len(not_completed)} of "
            f"{args.requests} requests did not complete"
        )
    return engine


if __name__ == "__main__":
    main()
