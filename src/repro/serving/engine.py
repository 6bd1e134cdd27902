"""Batched serving engine: continuous batching over a paged KV cache.

The engine owns a fixed number of decode *slots* (static shapes — the jit'd
step never retraces).  Requests are admitted into free slots, prefilled,
and generate until EOS / max_tokens, at which point the slot is recycled
for the next queued request.

Prefill comes in two modes (``ServeConfig.prefill``):

* ``"chunked"`` (default, Sarathi-style) — each engine tick spends a fixed
  **token budget**: every generating slot consumes one budget token for its
  decode step, and the leftover budget feeds prompt *chunks* (up to
  ``prefill_chunk`` tokens, oldest-admitted request first) through one
  chunk-wide forward pass (``lm.prefill_step`` — the prefill_attention
  kernel path).  A 1k-token prompt then costs ~``1k / prefill_chunk``
  ticks instead of 1k full decode steps, while decode latency stays
  bounded: no tick ever exceeds ``token_budget`` tokens.  Covers the
  attention families (GQA via prefill_attention, MLA via mla_prefill);
  falls back to replay only for architectures without chunk-parallel cache
  writes (SSM / hybrid recurrent state).
* ``"replay"`` — the legacy baseline: prompts stream one token per engine
  tick through the decode step.

KV memory comes in two layouts behind one ``decode_step`` interface
(``ServeConfig.cache``):

* ``"paged"`` (default) — vLLM-style block pool: KV lives in fixed-size
  pages; each slot owns a block table (serving/paged_cache.py).  The
  scheduler is real: **admission** requires enough free blocks for the
  request's resident tokens, **preemption** evicts the lowest-priority
  (then youngest) request back to the queue when the pool is exhausted
  (recompute-style resume: its prompt *and* generated tokens replay through
  prefill), and completion **recycles blocks immediately** at EOS.

Paged mode additionally runs a **prefix cache** (``ServeConfig.
prefix_cache``, on by default for the attention families): full pages of
prompt tokens are indexed in a radix tree over token ids
(paged_cache.PrefixCache) when a request finishes prefilling, and a new
request whose prompt prefixes a cached chain *attaches* those pages at
admission — positions advance past them with **no kernel dispatch at
all**, so a warm-prefix request's TTFT collapses to the divergent tail
(~one chunk under chunked prefill).  Pages are refcounted; a slot that
must write into a shared page goes through copy-on-write
(``ensure_writable`` + ``lm.copy_pages``) before the step runs, and every
repoint marks the device block table dirty.  Cached pages nobody
references are reclaimed LRU-first when admission, growth or grow-ahead
grants run short — a hot pool degrades to the uncached engine rather than
refusing admission.  SSM/hybrid families gate the cache off: skipped
positions would skip recurrent-state updates.
* ``"contiguous"`` — the legacy per-slot ``max_len`` strip (ring buffers
  for sliding-window layers); preallocates ``slots × max_len`` regardless
  of real prompt lengths.  Kept as the comparison baseline.

Both layouts cover every attention family: GQA/MQA page their KV heads,
MLA pages its shared latent+rope cache (DESIGN.md §5.4).  Pure-SSM archs
have no attention KV state to page — asking for ``cache="paged"`` there is
a loud ``ValueError``, never a silent layout downgrade.

Both layouts produce identical outputs for identical requests — asserted in
tests/test_serving.py.

The decode hot loop is **device-resident** (``ServeConfig.sync_every``):

* Sampling is folded into the jit'd step (``sampling.sample_step``) — the
  engine uploads token feeds and downloads sampled token *ids*; logits
  never cross the device boundary.  The PRNG key is a device carry with a
  greedy fast path that never splits it.
* The jit'd steps **donate** the cache (``donate_argnums``): XLA updates
  the KV pages/strips in place instead of copying the full cache every
  tick.  The device block-table tensor is cached on the engine and
  re-uploaded only when the scheduler actually mutates tables.
* With ``sync_every > 1``, up to that many decode ticks run in a single
  ``jax.lax.scan`` dispatch (``lm.decode_loop``): EOS and per-slot token
  limits become on-device stop masks, emitted tokens land in a device
  buffer drained once per dispatch, and the Python scheduler (admission,
  growth, preemption) runs only at sync boundaries.  Paged slots are
  pre-granted grow-ahead pages for the worst-case window, all-or-nothing;
  when the pool is too tight the engine falls back to per-tick stepping
  for that boundary, so scheduling fidelity is never traded for speed.

**Failure model** (DESIGN.md §5.7): every request ends in exactly one
terminal status (COMPLETED / TIMED_OUT / CANCELLED / FAILED / REJECTED)
through one exit path (``_terminate``) that releases its pages — requests
carry ``deadline_ticks``/``max_retries`` declaratively and expose
``cancel()``; ``drain()``/``shutdown()`` wind the engine down to an empty
pool.  A :class:`serving.faults.FaultInjector` can force pool exhaustion,
grant failure or logits poisoning at the real allocation/dispatch sites,
and ``ServeConfig.audit=True`` re-checks page conservation, refcount
consistency, radix reachability and slot hygiene after every tick.
``snapshot()``/``restore()`` persist the radix index plus its page
contents across engine restarts so warm-prefix TTFT survives a crash.
"""
from __future__ import annotations

import collections
import copy
import dataclasses
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.errors import GuardError
from repro.kernels.ops import guard_dispatch, paged_walk, prefill_walk
from repro.models import layers as L
from repro.models import lm
from repro.models.config import ModelConfig

from . import telemetry
from .faults import FaultInjector, audit_engine
from .paged_cache import (
    BlockPool,
    PoolExhausted,
    PrefixCache,
    SlotTables,
    blocks_for,
)
from .sampling import sample_step, spec_accept, spec_sample_step

# One jit'd decode step per (model configuration, sampling temperature),
# shared by every engine instance (and so by every request): constructing a
# fresh ``jax.jit`` wrapper per engine discards XLA's trace cache and
# recompiles the step for each new engine even when the config is
# identical.  Keyed on the config's dataclass repr (deterministic over
# field values); the closure captures a deep copy so later mutation of the
# caller's config object cannot change what a cached entry computes.
# LRU-bounded so config sweeps don't pin an XLA executable per visited
# config for process lifetime.  Both cache layouts share one entry: the
# layout lives in the cache pytree's treedef, so jax.jit keeps one trace
# per layout under the same wrapper.
#
# Every cached step **donates its cache argument** (``donate_argnums``):
# the caller's cache pytree is consumed — XLA writes the new KV in place
# instead of materializing a second full cache per tick — and the returned
# cache is the only live reference afterwards.  The engine upholds this by
# always replacing ``self.cache`` with the step's output.
_STEP_FNS: "collections.OrderedDict[tuple, object]" = collections.OrderedDict()
_STEP_FNS_MAX = 8


def _with_count(out, count):
    """``out`` with one more entry along its first axis, whose first element
    is ``count``: a count the host wants rides back in the transfer that
    reads ``out``, with no copy of its own (``ServingEngine._run_program``
    takes it off again)."""
    extra = jnp.zeros((out[:1].size,), out.dtype).at[0].set(count.astype(out.dtype))
    return jnp.concatenate([out, extra.reshape(out[:1].shape)])


def _cached_fn(key, build):
    fn = _STEP_FNS.get(key)
    if fn is None:
        fn = build()
        _STEP_FNS[key] = fn
        while len(_STEP_FNS) > _STEP_FNS_MAX:
            _STEP_FNS.popitem(last=False)
    else:
        _STEP_FNS.move_to_end(key)
    return fn


def _decode_step_fn(cfg: ModelConfig, temperature: float):
    """Fused decode tick: model step + sampling in one jit'd program.
    Returns ``(tokens, bad, cache, key)`` — logits stay on device.
    ``poison`` is the fault injector's NaN overwrite mask (all-False in
    normal operation) and ``bad`` flags rows whose logits held no finite
    value — injected or genuine — so the engine can fail exactly the
    affected request instead of emitting garbage.  A model with MoE layers
    appends its live slots' held-expert picks to ``tokens``
    (:func:`_with_count`)."""

    def build():
        snap = copy.deepcopy(cfg)
        routes = L.moe_layers(snap) > 0

        def decode_step(p, c, tok, pos, key, live, poison):
            if routes:
                logits, c, routed = lm.decode_step(p, snap, c, tok, pos, live=live,
                                                   count_routed=True)
            else:
                logits, c = lm.decode_step(p, snap, c, tok, pos, live=live)
            logits = jnp.where(poison[:, None], jnp.nan, logits)
            bad = ~jnp.any(jnp.isfinite(logits), axis=-1)
            tok, key = sample_step(logits, key, temperature=temperature)
            if routes:
                tok = _with_count(tok, jnp.sum(jnp.where(live, routed, 0)))
            return tok, bad, c, key

        return jax.jit(decode_step, donate_argnums=(1,))

    return _cached_fn(("decode", repr(cfg), temperature), build)


def _prefill_step_fn(cfg: ModelConfig, temperature: float):
    """One jit'd chunk-wide prefill step per model config (the chunk width
    is a trace-time shape, so differing ``prefill_chunk`` values simply
    trace separate entries under the same wrapper).  Sampling is fused like
    the decode step: the returned tokens are what a chunk that completes
    its prompt emits.  ``poison``/``bad`` and the held-expert picks (of the
    live chunk positions) mirror the decode step."""

    def build():
        snap = copy.deepcopy(cfg)
        routes = L.moe_layers(snap) > 0

        def prefill_step(p, c, toks, pos, lens, key, poison):
            if routes:
                logits, c, routed = lm.prefill_step(p, snap, c, toks, pos, lens,
                                                    count_routed=True)
            else:
                logits, c = lm.prefill_step(p, snap, c, toks, pos, lens)
            logits = jnp.where(poison[:, None], jnp.nan, logits)
            bad = ~jnp.any(jnp.isfinite(logits), axis=-1)
            tok, key = sample_step(logits, key, temperature=temperature)
            if routes:
                chunk_live = jnp.arange(toks.shape[1]) < lens[:, None]
                tok = _with_count(tok, jnp.sum(jnp.where(chunk_live, routed, 0)))
            return tok, bad, c, key

        return jax.jit(prefill_step, donate_argnums=(1,))

    return _cached_fn(("prefill", repr(cfg), temperature), build)


def _decode_loop_fn(cfg: ModelConfig, temperature: float, n_steps: int,
                    eos_id: int, max_len: int):
    """The multi-step window: ``n_steps`` fused decode ticks in one
    ``jax.lax.scan`` dispatch (``lm.decode_loop``), stop masks and emitted
    tokens on device."""

    def build():
        snap = copy.deepcopy(cfg)

        def sample_fn(logits, key, gate):
            return sample_step(logits, key, temperature=temperature,
                               gate=gate)

        def loop(p, c, feed, pos, key, live, remaining):
            toks, emitted, key, c, routed = lm.decode_loop(
                p, snap, c, feed, pos, key, live, remaining,
                n_steps=n_steps, sample_fn=sample_fn, eos_id=eos_id,
                max_len=max_len,
            )
            if routed is not None:
                toks = _with_count(toks, routed)
            return toks, emitted, key, c

        loop.__name__ = loop.__qualname__ = f"decode_window_{n_steps}"
        return jax.jit(loop, donate_argnums=(1,))

    return _cached_fn(
        ("decode_loop", repr(cfg), temperature, n_steps, eos_id, max_len),
        build,
    )


def _spec_loop_fn(cfg: ModelConfig, temperature: float, proposer: str,
                  n_rounds: int, draft_len: int, eos_id: int, max_len: int):
    """The speculative window: ``n_rounds`` draft-verify rounds in one
    ``jax.lax.scan`` dispatch (``lm.spec_decode_loop``) — each round
    proposes ``draft_len`` tokens from the slot's own history, scores them
    in one chunk forward through the prefill kernels, and commits the
    accepted prefix as on-device masks."""

    def build():
        snap = copy.deepcopy(cfg)
        propose = lm.DRAFT_PROPOSERS[proposer]

        def sample_fn(logits, key, gate):
            return spec_sample_step(logits, key, temperature=temperature,
                                    gate=gate)

        def loop(p, c, feed, pos, key, live, remaining, history, poison):
            toks, emitted, bad, key, c, routed = lm.spec_decode_loop(
                p, snap, c, feed, pos, key, live, remaining, history,
                n_rounds=n_rounds, draft_len=draft_len, propose_fn=propose,
                sample_fn=sample_fn, accept_fn=spec_accept, eos_id=eos_id,
                max_len=max_len, poison=poison,
            )
            if routed is not None:
                toks = _with_count(toks, routed)
            return toks, emitted, bad, key, c

        loop.__name__ = loop.__qualname__ = f"spec_window_{n_rounds}"
        return jax.jit(loop, donate_argnums=(1,))

    return _cached_fn(
        ("spec_loop", repr(cfg), temperature, proposer, n_rounds, draft_len,
         eos_id, max_len),
        build,
    )


def _copy_pages_fn(cfg: ModelConfig):
    """jit'd copy-on-write page duplication (``lm.copy_pages``), donating
    the cache like every other step so XLA copies pages in place.  One
    wrapper per model config; distinct pair-count shapes trace separate
    entries under it (the engine pads pair lists to powers of two to bound
    the variants)."""

    def build():
        return jax.jit(lm.copy_pages, donate_argnums=(0,))

    return _cached_fn(("copy_pages", repr(cfg)), build)


def plan_prefill_chunks(
    budget: int,
    n_gen: int,
    pending: Sequence[Tuple[int, int, int]],  # (slot, admit_seq, remaining)
    chunk: int,
) -> Dict[int, int]:
    """Sarathi-style budget split: decode tokens are spent first (one per
    generating slot), the leftover feeds prompt chunks oldest-admitted
    first.  Grants are all-or-nothing per request — always ``min(chunk,
    remaining)``, never a room-limited partial — so every chunk *starts* at
    a multiple of ``chunk``: the page-alignment contract of the prefill
    kernel's table-directed page writes (a room-limited partial would shift
    every later chunk of that prompt off page boundaries).  Invariants
    (property-tested): ``n_gen + sum(result.values()) <= max(budget,
    n_gen)``, every grant equals ``min(chunk, remaining)``, and grants form
    an age-ordered prefix of ``pending`` (no head-of-line skipping)."""
    room = budget - n_gen
    out: Dict[int, int] = {}
    for slot, _seq, remaining in sorted(pending, key=lambda t: t[1]):
        n = min(chunk, remaining)
        if n <= 0:
            continue
        if n > room:
            break
        out[slot] = n
        room -= n
    return out


@dataclasses.dataclass
class ServeConfig:
    slots: int = 8  # decode batch width
    max_len: int = 1024  # per-request logical cache length
    max_new_tokens: int = 128
    eos_id: int = -1  # -1: never stops early
    temperature: float = 0.0
    seed: int = 0
    cache: str = "paged"  # "paged" | "contiguous"
    page_size: int = 16  # tokens per KV block (paged mode)
    # pool size in blocks; None = slots * ceil(max_len / page_size), i.e.
    # parity with the contiguous footprint.  Size it below that to actually
    # oversubscribe memory (that's the point of paging).
    num_blocks: Optional[int] = None
    # KV storage format for the page pools: None = model dtype; "int8"/"int4"
    # = packed per-token quantization with per-row scales (paged mode only).
    # Overrides ModelConfig.kv_dtype for this engine; the attention kernels
    # dequantize inline at gather, so quality degrades gracefully while
    # per-page bytes shrink ~2-4x (see BlockPool.page_bytes).
    kv_dtype: Optional[str] = None
    # -- prefill fast path ------------------------------------------------
    prefill: str = "chunked"  # "chunked" | "replay"
    # prompt tokens per chunk-wide forward pass; clamped at engine init to
    # token_budget - slots + 1 so a chunk always fits the leftover budget
    # (grants are all-or-nothing to keep chunk starts page-aligned)
    prefill_chunk: int = 16
    # per-tick token budget shared by the decode batch and prefill chunks;
    # None = slots + prefill_chunk (one full chunk rides along with a full
    # decode batch).  Effective budget is floored at `slots` so a full
    # generation batch always fits.
    token_budget: Optional[int] = None
    # -- prefix caching ---------------------------------------------------
    # index full prompt pages in a radix tree and attach cache-hit pages at
    # admission (refcounted sharing + copy-on-write).  Paged mode only;
    # gated off automatically for SSM/hybrid families, whose recurrent
    # state cannot skip positions.
    prefix_cache: bool = True
    # -- device-resident decode loop --------------------------------------
    # decode ticks per host dispatch: 1 = legacy per-tick stepping; N > 1
    # runs up to N ticks in one jax.lax.scan when every active slot is
    # generating (EOS / token limits become on-device stop masks, scheduling
    # happens only at sync boundaries).  Paged slots must win an
    # all-or-nothing grow-ahead page grant for the worst-case window, else
    # that boundary falls back to a per-tick step.
    sync_every: int = 1
    # -- speculative decoding ---------------------------------------------
    # draft proposer name (lm.DRAFT_PROPOSERS) or None = off.  "ngram" is
    # self-speculation: an on-device lookahead over each slot's own emitted
    # tokens — no second model, no new weights; the registry is the plug
    # point for a tiny draft model later.  A speculative round drafts
    # draft_len tokens, scores all of them plus the feed token in ONE chunk
    # forward through the prefill kernels (batched verify *is* chunked
    # prefill), and commits the accepted prefix on device — so it composes
    # multiplicatively with sync_every: one host dispatch covers up to
    # sync_every * (draft_len + 1) tokens.  Requires an arch with
    # supports_chunked_prefill (checked at engine init, where the model
    # config is known).  Greedy output is byte-identical to plain decode by
    # construction; temperature streams advance the PRNG key a fixed
    # draft_len + 2 splits per round regardless of acceptance length.
    spec_decode: Optional[str] = None
    draft_len: int = 4
    # -- fault tolerance --------------------------------------------------
    # run the invariant auditor (serving.faults.audit_engine) after every
    # tick: page conservation, refcount consistency, radix reachability,
    # no orphaned slots.  O(pool) per tick — chaos/debug machinery.
    audit: bool = False
    # base ticks a preemption victim waits before re-admission, doubling
    # per preemption (capped at 32x).  0 = legacy immediate re-admission.
    # Under a preemption storm, backoff lets the slots drain instead of
    # thrashing the same victims through recompute-resume every tick.
    retry_backoff: int = 0
    # discharge the kernels' runtime obligations (core.lowering.verify)
    # before every paged dispatch: block-table entries in range, no
    # duplicate writable pages, lengths within capacity.  A violation FAILs
    # exactly the offending request (graceful degradation) instead of
    # letting a corrupt table scribble on another request's pages.  On by
    # default; opt out (e.g. to benchmark raw dispatch cost) with
    # ``guards=False`` / ``--guards off``.
    guards: bool = True

    def __post_init__(self):
        # loud at construction, not a shape error three layers down
        for name in ("slots", "max_len", "max_new_tokens", "page_size",
                     "prefill_chunk", "draft_len"):
            v = getattr(self, name)
            if v <= 0:
                raise ValueError(f"{name} must be positive, got {v}")
        if self.num_blocks is not None and self.num_blocks <= 0:
            raise ValueError(
                f"num_blocks must be positive, got {self.num_blocks}"
            )
        if self.token_budget is not None and self.token_budget < self.slots:
            raise ValueError(
                f"token_budget={self.token_budget} < slots={self.slots}: "
                "a full generation batch could never fit in one tick"
            )
        if self.kv_dtype not in (None, "int8", "int4"):
            raise ValueError(
                f"unknown kv_dtype {self.kv_dtype!r} "
                "(expected None, 'int8' or 'int4')"
            )
        if self.cache not in ("paged", "contiguous"):
            raise ValueError(f"unknown cache mode {self.cache!r}")
        if self.prefill not in ("chunked", "replay"):
            raise ValueError(f"unknown prefill mode {self.prefill!r}")
        if self.retry_backoff < 0:
            raise ValueError(
                f"retry_backoff must be >= 0, got {self.retry_backoff}"
            )
        if (self.spec_decode is not None
                and self.spec_decode not in lm.DRAFT_PROPOSERS):
            raise ValueError(
                f"unknown spec_decode proposer {self.spec_decode!r} "
                f"(registered: {sorted(lm.DRAFT_PROPOSERS)})"
            )


# Request lifecycle: QUEUED <-> RUNNING (preemption re-queues), ending in
# exactly one terminal status.  Reaching *any* terminal status releases
# every block the request held — the freed-page guarantee lives in the
# engine's single exit path (``_terminate``) and is checked live by the
# auditor (serving.faults).
QUEUED = "queued"
RUNNING = "running"
COMPLETED = "completed"  # EOS / token limit reached
TIMED_OUT = "timed_out"  # deadline_ticks expired before completion
CANCELLED = "cancelled"  # cancel() honored, or engine shutdown
FAILED = "failed"  # poisoned logits, retry budget, or outgrew the pool
REJECTED = "rejected"  # could never be served (admission fail-fast)
TERMINAL = (COMPLETED, TIMED_OUT, CANCELLED, FAILED, REJECTED)

# snapshot()/restore() wire format version (DESIGN.md §5.7)
SNAPSHOT_FORMAT = 1


@dataclasses.dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new_tokens: Optional[int] = None
    priority: int = 0  # higher survives preemption longer
    # ticks from submission before the request times out wherever it is
    # (queued or mid-generation); None = no deadline.  Partial output is
    # preserved on the request when the deadline fires.
    deadline_ticks: Optional[int] = None
    # preemption re-admissions before the request fails instead of
    # retrying; None = retry forever (the legacy behavior)
    max_retries: Optional[int] = None
    # filled by the engine:
    status: str = QUEUED  # QUEUED <-> RUNNING -> one of TERMINAL
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    preemptions: int = 0
    error: Optional[str] = None  # why a non-COMPLETED request ended
    submit_step: int = 0  # engine tick at submission
    first_token_step: Optional[int] = None  # tick that produced output[0]
    admit_step: Optional[int] = None  # tick of first admission into a slot
    cached_tokens: int = 0  # prompt tokens covered by prefix-cache hits
    _cancel: bool = dataclasses.field(default=False, repr=False)

    def cancel(self) -> None:
        """Request cancellation; honored at the next scheduler boundary
        (the engine frees the slot/queue entry and marks the request
        CANCELLED).  A no-op once the request is terminal."""
        if not self.done:
            self._cancel = True

    @property
    def ttft_ticks(self) -> Optional[int]:
        """Engine ticks from submission to the first generated token."""
        if self.first_token_step is None:
            return None
        return self.first_token_step - self.submit_step + 1

    @property
    def ttft_admit_ticks(self) -> Optional[int]:
        """Engine ticks from first admission to the first generated token —
        the queue-independent TTFT (what prefix caching shrinks: prefill
        work, not time spent waiting for a slot)."""
        if self.first_token_step is None or self.admit_step is None:
            return None
        return self.first_token_step - self.admit_step + 1


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, serve_cfg: ServeConfig,
                 injector: Optional[FaultInjector] = None):
        if serve_cfg.kv_dtype is not None and cfg.kv_dtype != serve_cfg.kv_dtype:
            # the storage format is a property of the cache pytree the step
            # functions trace over, so it lives on the model config (and so
            # inside the jit-cache keys) — the engine just forwards it
            cfg = dataclasses.replace(cfg, kv_dtype=serve_cfg.kv_dtype)
        self.cfg = cfg
        self._moe_layers = L.moe_layers(cfg)
        self.params = params
        self.scfg = serve_cfg
        b = serve_cfg.slots
        mode = serve_cfg.cache
        if mode not in ("paged", "contiguous"):
            raise ValueError(f"unknown cache mode {mode!r}")
        if cfg.kv_dtype is not None and mode != "paged":
            raise ValueError(
                f"kv_dtype={cfg.kv_dtype!r} requires cache='paged'"
            )
        # no silent downgrades: every attention family pages (GQA/MQA
        # through KV pages, MLA through latent pages); an arch with no
        # attention KV state fails loudly inside lm.init_cache instead of
        # being quietly handed a different memory layout than requested
        self.cache_mode = mode

        if mode == "paged":
            ps = serve_cfg.page_size
            self.max_pages = blocks_for(serve_cfg.max_len, ps)
            nb = serve_cfg.num_blocks or b * self.max_pages
            # physical page 0 is reserved (padding/garbage page), so the
            # device pool holds nb + 1 pages and the allocator hands out
            # ids 1..nb.
            self.cache = lm.init_cache(
                cfg, b, serve_cfg.max_len, layout="paged", page_size=ps,
                num_blocks=nb + 1,
            )
            # bytes one physical page costs across every layer's pool leaves
            # (packed data + scale columns for quantized caches) — the unit
            # byte-budget sizing works in (paged_cache.blocks_for_bytes)
            page_bytes = self.cache.kv_bytes() // (nb + 1)
            self.pool = BlockPool(nb, ps, base=1, page_bytes=page_bytes)
            self.tables = SlotTables(self.pool, b, self.max_pages)
        else:
            self.pool = None
            self.tables = None
            self.cache = lm.init_cache(cfg, b, serve_cfg.max_len)

        # prefix cache: paged attention families only — skipping cached
        # positions is only sound when all per-position state lives in the
        # (shareable) KV pages; recurrent SSM/hybrid state must replay
        self.prefix: Optional[PrefixCache] = None
        if (
            mode == "paged"
            and serve_cfg.prefix_cache
            and lm.supports_chunked_prefill(cfg)
        ):
            self.prefix = PrefixCache(
                self.pool, salt=(cfg.name, serve_cfg.page_size)
            )
        self.pages_shared = 0  # cache-hit pages attached at admission
        self.pages_copied = 0  # copy-on-write page duplications
        self.pages_deduped = 0  # duplicate prefill pages absorbed at insert

        self.pos = np.zeros((b,), np.int32)  # next write position per slot
        self.slot_req: List[Optional[Request]] = [None] * b
        # chunked mode: "prefill" until the replay cursor reaches the end of
        # prompt+output, then "gen" (replay mode leaves these unused)
        self.slot_state: List[Optional[str]] = [None] * b
        self.queue: collections.deque[Request] = collections.deque()
        self._uid = itertools.count()
        self._admit_seq = itertools.count()
        self._key = jax.random.PRNGKey(serve_cfg.seed)
        self._step = _decode_step_fn(cfg, serve_cfg.temperature)
        if serve_cfg.prefill not in ("chunked", "replay"):
            raise ValueError(f"unknown prefill mode {serve_cfg.prefill!r}")
        self.prefill_mode = (
            "chunked"
            if serve_cfg.prefill == "chunked" and lm.supports_chunked_prefill(cfg)
            else "replay"
        )
        self._prefill = (
            _prefill_step_fn(cfg, serve_cfg.temperature)
            if self.prefill_mode == "chunked" else None
        )
        self.sync_every = max(1, serve_cfg.sync_every)
        self._loop_fns: Dict[int, object] = {}  # window length -> jit'd loop
        # -- speculative decoding -----------------------------------------
        # gated on the model config (hence here, not __post_init__): the
        # verify pass routes through the chunked-prefill kernels, so an
        # arch that cannot chunk-prefill cannot verify drafts either
        if serve_cfg.spec_decode is not None and not lm.supports_chunked_prefill(cfg):
            raise ValueError(
                f"spec_decode={serve_cfg.spec_decode!r} requires a chunked-"
                f"prefill arch (GQA/MLA); {cfg.name} (attention="
                f"{cfg.attention}, family={cfg.family}) cannot run the "
                "verify pass"
            )
        self.spec_proposer = serve_cfg.spec_decode
        self._spec_loop_fns: Dict[int, object] = {}  # rounds -> jit'd loop
        self.spec_windows = 0  # speculative dispatches taken
        self.spec_rounds = 0  # draft-verify rounds drained (>=1 emit or bad)
        self.spec_proposed = 0  # draft tokens scored by verify
        self.spec_accepted = 0  # draft tokens accepted (excl. bonus token)
        self.spec_all_rejected = 0  # live slot-rounds accepting zero drafts
        self.spec_fallbacks = 0  # spec window declined -> plain window/tick
        # the device-side block-table tensor is cached across ticks and
        # re-uploaded only after the scheduler mutates tables (admission
        # growth, grow-ahead grants/trims, preemption, EOS recycling)
        self._tables_dirty = True
        self.table_uploads = 0  # perf counter: host->device table transfers
        self.decode_windows = 0  # multi-step dispatches taken
        self.window_fallbacks = 0  # grow-ahead denied -> per-tick boundary
        self.dispatches = 0  # step() calls that ran device work: a window
        # counts once however many ticks it covers — the deterministic
        # measure of host-round-trip amortization (the flaky-free companion
        # to wall-clock tok/s in the bench trajectory)
        # effective per-tick budget: a full generation batch always fits
        self.token_budget = max(
            serve_cfg.token_budget or (b + serve_cfg.prefill_chunk), b
        )
        # effective chunk: grants are all-or-nothing (chunk starts must stay
        # chunk-aligned — the kernel's page-write contract), so the chunk is
        # clamped to the worst-case leftover room (budget minus a full
        # generation batch less the prefilling slot itself).  Guarantees a
        # prefill slot always makes progress: room = budget - n_gen >=
        # budget - (slots-1) >= chunk.
        self.prefill_chunk = max(
            1, min(serve_cfg.prefill_chunk, self.token_budget - b + 1)
        )
        # per-tick spend, bounded like every other per-process accumulator
        # here (a heavy-traffic engine must not grow state per tick)
        self.tick_tokens: "collections.deque[int]" = collections.deque(
            maxlen=4096
        )
        self.completed: List[Request] = []
        self.steps_run = 0
        self.preemptions = 0
        # -- fault tolerance --------------------------------------------
        self.admission_open = True  # drain()/shutdown() close intake
        self.poisoned_rows = 0  # logits rows with no finite value seen
        self.audits_run = 0  # invariant audits executed (scfg.audit)
        self.guard_failures = 0  # requests FAILed by the dispatch guard
        self.table_corruptions = 0  # injected table_corrupt faults fired
        self._corrupt_mode = 0  # cycles injected-corruption flavors
        self.injector = injector
        if injector is not None:
            injector.bind_clock(lambda: self.steps_run)
            if self.pool is not None:
                self.pool.injector = injector

    # ------------------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens=None,
               priority: int = 0, deadline_ticks: Optional[int] = None,
               max_retries: Optional[int] = None) -> Request:
        req = Request(next(self._uid), list(prompt), max_new_tokens,
                      priority=priority, deadline_ticks=deadline_ticks,
                      max_retries=max_retries, submit_step=self.steps_run)
        self.queue.append(req)
        return req

    # -- scheduler ------------------------------------------------------
    def _resident_tokens(self, req: Request) -> int:
        """Tokens the request must hold to make forward progress: its full
        replay (prompt + already-generated) plus the next write."""
        return len(req.prompt) + len(req.output) + 1

    def _admit(self):
        """FIFO admission into free slots; paged mode additionally gates on
        free-block count, allocating the request's replay footprint up front
        (no head-of-line skipping — deterministic order).  The one sanctioned
        exception: preemption victims still in retry backoff step aside and
        let younger requests pass until their wait expires.  Closed entirely
        once ``drain()``/``shutdown()`` stops intake."""
        if not self.admission_open:
            return
        for s in range(self.scfg.slots):
            if self.slot_req[s] is not None or not self.queue:
                continue
            req = None
            for cand in self.queue:
                if getattr(cand, "_not_before", 0) > self.steps_run:
                    continue  # backing off after a preemption storm
                req = cand
                break
            if req is None:
                break  # everyone queued is backing off
            if self.pool is not None:
                need = blocks_for(self._resident_tokens(req), self.pool.page_size)
                if need > min(self.pool.num_blocks, self.max_pages):
                    # can never fit — pool too small, or prompt beyond the
                    # per-slot table (max_len): fail fast instead of wedging
                    # the queue head forever (or crashing ensure_capacity).
                    self.queue.remove(req)
                    self._terminate(req, REJECTED, error=(
                        f"needs {need} KV blocks; pool holds "
                        f"{self.pool.num_blocks}, table holds {self.max_pages}"
                    ))
                    continue
                matched: List[int] = []
                if self.prefix is not None:
                    # cap the match so at least one replay token remains (the
                    # decode loop needs a real last token to feed) and so only
                    # prompt pages are ever consumed from the cache — resumed
                    # preemptees replay prompt + output, but output pages are
                    # never published to the index.
                    ps = self.pool.page_size
                    replay_len = len(req.prompt) + len(req.output)
                    cap = min(len(req.prompt), replay_len - 1) // ps
                    matched = self.prefix.match(req.prompt, cap)
                shortfall = (need - len(matched)) - self.pool.free
                if shortfall > 0 and self.prefix is not None:
                    self.prefix.evict(shortfall, protect=frozenset(matched))
                if self.pool.free < need - len(matched):
                    break
            else:
                matched = []
            self.queue.remove(req)
            self.slot_req[s] = req
            self.slot_state[s] = "prefill"
            req.status = RUNNING
            start = len(matched) * self.pool.page_size if matched else 0
            self.pos[s] = start
            req._cursor = start  # type: ignore[attr-defined]
            req._admit_seq = next(self._admit_seq)  # type: ignore[attr-defined]
            req._prefix_done = False  # type: ignore[attr-defined]
            if req.admit_step is None:
                req.admit_step = self.steps_run
            req.cached_tokens = start
            if self.tables is not None:
                if matched:
                    self.tables.attach(s, matched)
                    self.pages_shared += len(matched)
                    self._tables_dirty = True
                try:
                    if self.tables.ensure_capacity(
                        s, self._resident_tokens(req), req.uid
                    ):
                        self._tables_dirty = True
                except PoolExhausted:
                    # an injected alloc fault fired past the free-count
                    # gate: roll the whole admission back (matched pages
                    # return their references) and retry next tick
                    self.tables.release_slot(s)
                    self._tables_dirty = True
                    self.slot_req[s] = None
                    self.slot_state[s] = None
                    self.pos[s] = 0
                    req._cursor = 0  # type: ignore[attr-defined]
                    req.cached_tokens = 0
                    req.status = QUEUED
                    self.queue.appendleft(req)
                    break

    def _pick_victim(self, exclude) -> Optional[int]:
        """Preemption victim: lowest priority, then youngest admission.
        ``exclude`` is a slot or a collection of slots never picked (e.g.
        every slot in the dispatch currently being assembled)."""
        excluded = {exclude} if isinstance(exclude, int) else set(exclude)
        best = None
        for s in range(self.scfg.slots):
            if s in excluded or self.slot_req[s] is None:
                continue
            r = self.slot_req[s]
            key = (r.priority, -r._admit_seq)  # type: ignore[attr-defined]
            if best is None or key < best[0]:
                best = (key, s)
        return None if best is None else best[1]

    def _preempt(self, s: int):
        """Evict slot ``s``: blocks back to the pool, request to the front of
        the queue (recompute resume — prompt + generated tokens replay).
        A victim past its ``max_retries`` budget fails instead of retrying;
        with ``retry_backoff`` set, storm victims wait out an exponential
        backoff before re-admission."""
        req = self.slot_req[s]
        req.preemptions += 1
        self.preemptions += 1
        if req.max_retries is not None and req.preemptions > req.max_retries:
            self._terminate(req, FAILED, slot=s, error=(
                f"preempted {req.preemptions} times "
                f"(max_retries={req.max_retries})"
            ))
            return
        self.tables.release_slot(s)
        self._tables_dirty = True
        self.slot_req[s] = None
        self.slot_state[s] = None
        self.pos[s] = 0
        req._cursor = 0  # type: ignore[attr-defined]
        req.status = QUEUED
        if self.scfg.retry_backoff > 0:
            wait = self.scfg.retry_backoff * (
                1 << min(req.preemptions - 1, 5)
            )
            req._not_before = self.steps_run + wait  # type: ignore[attr-defined]
        self.queue.appendleft(req)

    def _reclaim(self, want: int) -> int:
        """Evict up to ``want`` unreferenced prefix-cache pages back to the
        pool. Cached-but-unused pages are the cheapest blocks to reclaim, so
        they always go before any live slot is preempted."""
        if self.prefix is None or want <= 0:
            return 0
        return self.prefix.evict(want)

    def _ensure_with_evict(self, s: int, target_tokens: int, owner) -> bool:
        """ensure_capacity with prefix-cache eviction as the pressure valve.
        Returns False only when eviction cannot free enough blocks."""
        while True:
            try:
                if self.tables.ensure_capacity(s, target_tokens, owner):
                    self._tables_dirty = True
                return True
            except PoolExhausted:
                need = blocks_for(target_tokens, self.pool.page_size) - self.tables.num_blocks(s)
                if self.prefix is None or self.prefix.evict(need - self.pool.free) == 0:
                    return False

    def _grow(self, s: int) -> bool:
        """Ensure slot ``s`` can write at ``pos[s]``; preempt on exhaustion.
        Returns False when ``s`` itself was evicted to make room."""
        req = self.slot_req[s]
        if blocks_for(int(self.pos[s]) + 1, self.pool.page_size) > self.pool.num_blocks:
            # outgrew the entire pool mid-generation; no preemption can help
            self._terminate(req, FAILED, slot=s,
                            error="request outgrew the KV block pool")
            return False
        while True:
            if self._ensure_with_evict(s, int(self.pos[s]) + 1, req.uid):
                return True
            victim = self._pick_victim(exclude=s)
            if victim is None:
                self._preempt(s)
                return False
            # don't evict someone strictly more important than s
            v = self.slot_req[victim]
            if (v.priority, -v._admit_seq) > (req.priority, -req._admit_seq):  # type: ignore[attr-defined]
                self._preempt(s)
                return False
            self._preempt(victim)

    def _terminate(self, req: Request, status: str,
                   slot: Optional[int] = None,
                   error: Optional[str] = None):
        """The single request exit path: every request ends exactly once,
        through here, with its slot's pages released — whatever the reason
        (COMPLETED / TIMED_OUT / CANCELLED / FAILED / REJECTED).  The
        freed-page guarantee the auditor checks lives here, not scattered
        per exit site."""
        if slot is not None:
            self.slot_req[slot] = None
            self.slot_state[slot] = None
            self.pos[slot] = 0
            if self.tables is not None:
                self.tables.release_slot(slot)  # blocks recycle immediately
                self._tables_dirty = True
        if error is not None:
            req.error = error
        req.status = status
        req.done = True
        self.completed.append(req)

    def _sweep_lifecycle(self):
        """Honor ``cancel()`` and ``deadline_ticks`` before dispatching: an
        expired or cancelled request exits through ``_terminate`` wherever
        it currently lives (queue or slot), freeing its pages on the spot.
        Partial output stays on the request."""
        now = self.steps_run
        for req in list(self.queue):
            verdict = self._lifecycle_verdict(req, now)
            if verdict is not None:
                self.queue.remove(req)
                self._terminate(req, verdict[0], error=verdict[1])
        for s in range(self.scfg.slots):
            req = self.slot_req[s]
            if req is None:
                continue
            verdict = self._lifecycle_verdict(req, now)
            if verdict is not None:
                self._terminate(req, verdict[0], slot=s, error=verdict[1])

    @staticmethod
    def _lifecycle_verdict(req: Request, now: int):
        if req._cancel:
            return (CANCELLED, "cancelled by caller")
        if (req.deadline_ticks is not None
                and now - req.submit_step >= req.deadline_ticks):
            return (TIMED_OUT,
                    f"deadline of {req.deadline_ticks} ticks exceeded")
        return None

    def _emit_token(self, s: int, req: Request, tok: int):
        """Record a generated token and apply the stop conditions."""
        req.output.append(tok)
        if req.first_token_step is None:
            req.first_token_step = self.steps_run
        limit = req.max_new_tokens or self.scfg.max_new_tokens
        if (
            tok == self.scfg.eos_id
            or len(req.output) >= limit
            or self.pos[s] >= self.scfg.max_len
        ):
            self._terminate(req, COMPLETED, slot=s)

    # ------------------------------------------------------------------
    def _fresh_cache(self):
        """The cache to feed the next jit'd step.  The device block-table
        tensor is cached across ticks (it rides inside ``self.cache`` as the
        ``tables`` leaf, threaded through every step) and re-uploaded only
        after a scheduler mutation — the per-tick upload the profile blamed
        for most of the paged-vs-contiguous gap."""
        if self.tables is not None and self._tables_dirty:
            self.cache = self.cache.with_tables(
                jnp.asarray(self.tables.tables())
            )
            self._tables_dirty = False
            self.table_uploads += 1
        return self.cache

    def _run_program(self, fn, inputs: Sequence, n_out: int,
                     slots: Sequence[int], ticks: int = 1, rows: int = 0,
                     live_rows: int = 0):
        """Run one jitted step program through the dispatch log's phases:
        upload (the block table when dirty, and the host arrays among
        ``inputs``), dispatch ``fn(params, cache, *inputs)``, wait for its
        outputs, read back the first ``n_out``.  Returns ``(host outputs,
        the rest of the outputs, record)``: the rest are the new cache and
        PRNG key in ``fn``'s order, still on the device.  The caller drains
        under ``telemetry.span("drain", record)``.  For a model with MoE
        layers the record counts the expert rows the program computed
        (``rows`` token rows for prefill, one per returned token otherwise)
        and the picks that live tokens made of them, which the program
        appended to its first output (:func:`_with_count`)."""
        rec = telemetry.Dispatch(
            fn.__name__, ticks, tuple(self.slot_req[s].uid for s in slots),
            rows, live_rows,
        )
        with telemetry.span("upload", rec):
            cache = self._fresh_cache()
            args = [jnp.asarray(a) if isinstance(a, np.ndarray) else a
                    for a in inputs]
        with telemetry.span("dispatch", rec):
            out = fn(self.params, cache, *args)
        with telemetry.span("wait", rec):
            jax.block_until_ready(out)
        with telemetry.span("readback", rec):
            host = [np.asarray(x) for x in out[:n_out]]
        if self._moe_layers:
            rec.expert_rows_routed = int(host[0][-1].reshape(-1)[0])
            host[0] = host[0][:-1]
            rec.expert_rows = self._moe_layers * L.moe_rows(self.cfg, rows or host[0].size)
        telemetry.LOG.append(rec)
        return host, out[n_out:], rec

    def _page_walk(self, rec: telemetry.Dispatch, pos) -> None:
        """Count on ``rec`` the KV pages the decode program's attention
        walks, for positions ``pos`` (ticks x slots; a slot attends its
        ``pos + 1`` tokens), against the pages its block tables hold —
        summed over ticks, slots and layers (every layer of a paged model
        attends; ``telemetry.Dispatch``).  What is walked is the kernel
        layer's answer (``ops.paged_walk``), asked as the layer calls it."""
        if self.tables is None:
            return
        cfg, pos = self.cfg, np.asarray(pos)
        layers = collections.Counter(lm.static_windows(cfg))  # window -> layers
        rec.pages_table = pos.size * self.max_pages * sum(layers.values())
        rec.pages_walked = int(sum(
            n * paged_walk(
                pos + 1, self.pool.page_size, self.max_pages, w,
                **self._walk_kw(),
            ).sum()
            for w, n in layers.items()
        ))

    def _prefill_walk(self, rec: telemetry.Dispatch, starts, lens) -> None:
        """Count on ``rec`` the prior KV pages the prefill program's
        attention walks for slots of ``starts`` prior tokens and ``lens``
        live chunk tokens, against the pages its grid cells' tables hold —
        summed over cells, slots and layers, as ``ops.prefill_walk``
        answers for the call the layer makes."""
        if self.tables is None:
            return
        layers = collections.Counter(lm.static_windows(self.cfg))
        for w, n in layers.items():
            walked, table = prefill_walk(
                starts, lens, self.prefill_chunk, self.pool.page_size,
                self.max_pages, w, **self._walk_kw(),
            )
            rec.prior_pages_walked += n * int(walked.sum())
            rec.prior_pages_table += n * int(table.sum())

    def _walk_kw(self) -> dict:
        """The kernel-selecting facts of the attention calls, as the
        layers pass them to ``ops``."""
        cfg = self.cfg
        return dict(
            head_dim=cfg.kv_head_dim, kv_dtype=cfg.kv_dtype,
            attention=cfg.attention, logit_soft_cap=cfg.logit_soft_cap,
            backend=None if cfg.kernel_backend == "auto" else cfg.kernel_backend,
        )

    def _gen_ready(self, s: int) -> bool:
        """Slot ``s`` is in steady-state generation: its next feed is its
        last known token and every later feed is a model output — exactly
        the shape of work the device-resident loop can run without the
        host."""
        req = self.slot_req[s]
        if self.prefill_mode == "chunked" and self.slot_state[s] != "gen":
            return False
        return (
            req._cursor  # type: ignore[attr-defined]
            == len(req.prompt) + len(req.output) - 1
        )

    def step(self) -> int:
        """One engine tick (one host dispatch).  Replay mode: one batched
        decode step (slots still replaying their prompt feed the next
        replay token).  Chunked mode: one decode step for the generating
        slots plus prompt chunks for prefilling slots, together bounded by
        ``token_budget``.  With ``sync_every > 1`` and every active slot
        generating, one dispatch runs up to ``sync_every`` decode ticks on
        device.  Cancellations and deadlines are honored before the
        dispatch; with ``ServeConfig.audit`` the invariant auditor runs
        after it.  Returns #active slots.  Each call and each device
        program it runs go into the dispatch log (``serving.telemetry``)."""
        t0 = telemetry.clock()
        with telemetry.span("step"):
            n = self._step_inner()
            if self.scfg.audit:
                self.audits_run += 1
                audit_engine(self)
        telemetry.LOG.append(telemetry.Step(t0, telemetry.clock()))
        return n

    def _step_inner(self) -> int:
        with telemetry.span("schedule"):
            self._sweep_lifecycle()
            self._admit()
            if self.tables is not None:
                for s in range(self.scfg.slots):
                    if self.slot_req[s] is not None:
                        self._grow(s)
                self._admit()  # preemption may have freed blocks for the queue head
            active = [s for s in range(self.scfg.slots) if self.slot_req[s] is not None]
        if not active:
            if self.queue and self.admission_open:
                # every queued request is waiting out a retry backoff: the
                # clock must still advance or backoffs (and deadlines)
                # would never expire
                self.steps_run += 1
            return 0
        self.dispatches += 1
        all_gen = all(self._gen_ready(s) for s in active)
        spec_ok = self.spec_proposer is not None and all_gen
        window_ok = self.sync_every > 1 and all_gen
        if (self.injector is not None and self.injector.pending("poison")):
            # poison faults land per-tick, where per-row detection runs;
            # the plain window has no mid-scan logits check (the spec
            # window checks verify logits, but through its own site)
            spec_ok = window_ok = False
        if spec_ok:
            done = self._step_spec_window(active)
            if done is not None:
                return done
            self.spec_fallbacks += 1  # no headroom / grant denied
        if window_ok:
            done = self._step_window(active)
            if done is not None:
                return done
            self.window_fallbacks += 1  # pool too tight for grow-ahead
        if self.prefill_mode == "chunked":
            return self._step_chunked(active)
        return self._step_replay(active)

    # -- device-resident multi-step window ------------------------------
    def _grant_window(self, active: List[int], spans: Dict[int, int]) -> bool:
        """All-or-nothing grow-ahead: every active slot gets pages covering
        its worst-case window write span (``spans[s]`` tokens past its
        current position, never past ``max_len``) — so a slot near its
        token limit doesn't inflate the ask with pages it can never touch.
        On any shortfall the grant rolls back *exactly* — every slot
        trimmed to its pre-grant block count and the table-dirty flag
        restored, so a failed grant costs no table re-upload — and the
        boundary falls back to per-tick stepping.  The grant itself never
        preempts, so a tight pool degrades throughput, not scheduling."""
        if self.injector is not None and self.injector.fire("grant"):
            return False  # injected mid-window grant failure
        pre = {s: self.tables.num_blocks(s) for s in active}
        dirty_before = self._tables_dirty
        for s in active:
            req = self.slot_req[s]
            target = min(int(self.pos[s]) + spans[s], self.scfg.max_len)
            if not self._ensure_with_evict(s, target, req.uid):
                ps = self.pool.page_size
                for t in active:
                    self.tables.trim(t, pre[t] * ps)
                self._tables_dirty = dirty_before
                return False
        return True

    def _prepare_window(self, active: List[int],
                        spans: Dict[int, int]) -> bool:
        """Shared paged-window preamble for the plain and speculative
        multi-step paths: grow-ahead grant, copy-on-write over the whole
        write span, and the dispatch guard over the granted tables.  On any
        failure the grow-ahead is returned (survivors trimmed to
        ``pos + 1``) and the caller falls back — per-tick stepping for the
        plain window, plain window for the speculative one.  ``spans[s]``
        is the slot's worst-case token span; the caller has already clamped
        it to ``max_len`` headroom."""
        if self.tables is None:
            return True
        if not self._grant_window(active, spans):
            return False
        pairs: List[Tuple[int, int]] = []
        try:
            for s in active:
                target = min(int(self.pos[s]) + spans[s], self.scfg.max_len)
                last = max(int(self.pos[s]), target - 1)
                self._cow_range(s, last, protect=frozenset(active),
                                out=pairs)
        except PoolExhausted:
            # a COW copy could not be satisfied even after eviction: apply
            # the copies already repointed (their tables reference the
            # fresh pages), give back the grow-ahead, and fall back — the
            # per-tick path's COW failure preempts
            self._apply_cow(pairs)
            for s in active:
                if self.tables.trim(s, int(self.pos[s]) + 1):
                    self._tables_dirty = True
            return False
        self._apply_cow(pairs)
        work = [(s, spans[s]) for s in active]
        if len(self._guard_work(work)) != len(work):
            # a guard violation FAILed the blamed slot(s): give back the
            # survivors' grow-ahead and fall back, where the next path's
            # own guard re-checks the trimmed dispatch
            for s in active:
                if self.slot_req[s] is not None:
                    if self.tables.trim(s, int(self.pos[s]) + 1):
                        self._tables_dirty = True
            return False
        return True

    def _step_window(self, active: List[int]) -> Optional[int]:
        """Up to ``sync_every`` decode ticks in one ``lax.scan`` dispatch.
        Feed, positions, PRNG key, stop flags and emitted tokens live on
        device (``lm.decode_loop``); the host uploads one feed vector and
        drains one token buffer.  Returns #active slots, or ``None`` when
        the paged pool cannot cover the worst-case window (caller falls
        back to a per-tick step)."""
        with telemetry.span("schedule"):
            b = self.scfg.slots
            feed = np.zeros((b,), np.int32)
            live = np.zeros((b,), bool)
            rem = np.zeros((b,), np.int32)
            for s in active:
                req = self.slot_req[s]
                feed[s] = (req.prompt + req.output)[req._cursor]  # type: ignore[attr-defined]
                live[s] = True
                limit = req.max_new_tokens or self.scfg.max_new_tokens
                rem[s] = limit - len(req.output)
            # clamp the window to the slots' host-known tick spans — token
            # allowance AND max_len headroom — by halving (not to the exact
            # span: every distinct length is its own scan trace, so lengths
            # are bounded to ~log2(sync_every) variants).  Guaranteed-dead
            # tail iterations would burn full-batch decode steps and delay
            # boundary-time admission of queued work.
            n = self.sync_every
            max_span = max(
                min(int(rem[s]), self.scfg.max_len - int(self.pos[s]))
                for s in active
            )
            while n // 2 >= max_span:
                n //= 2
            spans = {s: min(n, int(rem[s]) + 1) for s in active}
            if not self._prepare_window(active, spans):
                return None
            loop = self._loop_fns.get(n)
            if loop is None:
                loop = self._loop_fns[n] = _decode_loop_fn(
                    self.cfg, self.scfg.temperature, n, self.scfg.eos_id,
                    self.scfg.max_len,
                )
        (toks, emitted), (self._key, self.cache), rec = self._run_program(
            loop, (feed, self.pos, self._key, live, rem), 2, active, ticks=n,
        )
        with telemetry.span("drain", rec):
            # a slot's position advances on each tick it was live in
            self._page_walk(rec, self.pos + np.cumsum(emitted, 0) - emitted)
            self.decode_windows += 1
            # replay each in-window tick through the same host-side
            # bookkeeping the per-tick path runs, so Request state, tick
            # accounting and EOS recycling stay byte-for-byte identical
            for t in range(n):
                row = emitted[t]
                if not row.any():
                    break  # every slot stopped; later rows are all-False too
                for s in active:
                    if not row[s]:
                        continue
                    req = self.slot_req[s]
                    self.pos[s] += 1
                    req._cursor += 1  # type: ignore[attr-defined]
                    self._emit_token(s, req, int(toks[t, s]))
                self.tick_tokens.append(int(row.sum()))
                self.steps_run += 1
            if self.tables is not None:
                # return unused grow-ahead pages so boundary-time admission /
                # preemption sees the same pool a per-tick engine would
                for s in active:
                    if self.slot_req[s] is not None:
                        if self.tables.trim(s, int(self.pos[s]) + 1):
                            self._tables_dirty = True
        return len(active)

    # -- speculative draft-verify window --------------------------------
    def _step_spec_window(self, active: List[int]) -> Optional[int]:
        """Up to ``sync_every`` draft-verify rounds in one dispatch
        (``lm.spec_decode_loop``).  Each round's verify chunk writes
        ``draft_len + 1`` KV positions through the block tables, so the
        grow-ahead must cover the worst case ``n * (draft_len + 1)`` tokens
        per slot (capped by the slot's token allowance plus the round's
        unaccepted draft tail); rejected tails stay *logically* truncated
        behind the position carry and the grant's unused pages return via
        ``trim`` at the sync boundary — rollback never allocates, so it can
        never leak.  Returns #active slots, or ``None`` when a slot lacks
        ``max_len`` headroom for even one round or the grant/COW/guard
        preamble declines (caller falls back to the plain window, which is
        byte-identical by construction)."""
        with telemetry.span("schedule"):
            scfg = self.scfg
            k = scfg.draft_len
            c = k + 1
            b = scfg.slots
            feed = np.zeros((b,), np.int32)
            live = np.zeros((b,), bool)
            rem = np.zeros((b,), np.int32)
            for s in active:
                req = self.slot_req[s]
                feed[s] = (req.prompt + req.output)[req._cursor]  # type: ignore[attr-defined]
                live[s] = True
                limit = req.max_new_tokens or scfg.max_new_tokens
                rem[s] = limit - len(req.output)

            # a slot's worst-case write span over n rounds: every verify chunk
            # lands c positions from the current pos, and a live round commits
            # at least one token, so the furthest write is bounded both by
            # n * c and by the token allowance plus one round's draft tail
            def span(s: int, n: int) -> int:
                return min(n * c, int(rem[s]) + k)

            # clamp rounds by halving (each distinct n is its own scan trace):
            # first to the emission spans, then until every slot's worst-case
            # chunk write fits under max_len — unlike the plain window, a
            # verify chunk writes ahead of what it commits, so headroom is a
            # hard precondition, not an optimization
            n = self.sync_every
            max_rounds = max(
                -(-min(int(rem[s]), scfg.max_len - int(self.pos[s])) // c)
                for s in active
            )
            while n // 2 >= max_rounds:
                n //= 2
            while n > 1 and any(
                int(self.pos[s]) + span(s, n) > scfg.max_len for s in active
            ):
                n //= 2
            if any(int(self.pos[s]) + span(s, n) > scfg.max_len for s in active):
                return None  # a slot within c of max_len: plain path finishes it
            spans = {s: span(s, n) for s in active}
            if not self._prepare_window(active, spans):
                return None

            hist = np.zeros((b, scfg.max_len), np.int32)
            for s in active:
                req = self.slot_req[s]
                toks = req.prompt + req.output
                hist[s, : len(toks)] = toks
            poison = self._poison_mask(active, site="spec_poison")

            loop = self._spec_loop_fns.get(n)
            if loop is None:
                loop = self._spec_loop_fns[n] = _spec_loop_fn(
                    self.cfg, scfg.temperature, self.spec_proposer, n, k,
                    scfg.eos_id, scfg.max_len,
                )
        (toks, emitted, bad), (self._key, self.cache), rec = self._run_program(
            loop, (feed, self.pos, self._key, live, rem, hist, poison), 3,
            active, ticks=n,
        )
        with telemetry.span("drain", rec):
            self.spec_windows += 1
            # replay each round through the same host-side bookkeeping
            # the per-tick path runs — the device emit masks already encode
            # acceptance, EOS, token limits and max_len, so _emit_token's stop
            # conditions fire on exactly the tokens the mask delivers
            for t in range(n):
                row = emitted[t]
                rbad = bad[t]
                if not row.any() and not rbad.any():
                    break  # every slot stopped; later rounds are dead too
                self.spec_rounds += 1
                for s in active:
                    req = self.slot_req[s]
                    if req is None:
                        continue
                    if rbad[s]:
                        self.poisoned_rows += 1
                        self._terminate(
                            req, FAILED, slot=s,
                            error="poisoned verify logits (no finite value)")
                        continue
                    if not row[s].any():
                        continue
                    acc = int(row[s].sum()) - 1  # drafts accepted this round
                    self.spec_proposed += k
                    self.spec_accepted += acc
                    if acc == 0:
                        self.spec_all_rejected += 1
                    for i in range(c):
                        if not row[s, i]:
                            continue
                        self.pos[s] += 1
                        req._cursor += 1  # type: ignore[attr-defined]
                        self._emit_token(s, req, int(toks[t, s, i]))
                        if req.done:
                            break
                self.tick_tokens.append(int(row.sum()))
                self.steps_run += 1
            if self.tables is not None:
                # rejected draft tails sit in pages past pos under the
                # grow-ahead grant; trim reclaims them with the unused grant
                for s in active:
                    if self.slot_req[s] is not None:
                        if self.tables.trim(s, int(self.pos[s]) + 1):
                            self._tables_dirty = True
        return len(active)

    # -- prefix-cache bookkeeping ---------------------------------------
    def _register_prefix(self, s: int, req: Request):
        """Publish the slot's full prompt pages into the prefix index once
        prefill completes.  ``insert`` retains each new page; pages already
        cached come back as (idx, cached_page) pairs and the slot's table is
        repointed at the canonical copy so the duplicate recycles — the
        device copy of the table is re-uploaded before the next dispatch."""
        if self.prefix is None or getattr(req, "_prefix_done", False):
            return
        req._prefix_done = True  # type: ignore[attr-defined]
        ps = self.pool.page_size
        n_pages = min(len(req.prompt) // ps, self.tables.num_blocks(s))
        if n_pages <= 0:
            return
        pages = self.tables.blocks(s)[:n_pages]
        for idx, cached in self.prefix.insert(req.prompt[: n_pages * ps], pages):
            self.tables.repoint(s, idx, cached)
            self.pages_deduped += 1
            self._tables_dirty = True

    def _cow_range(self, s: int, last_pos: int,
                   protect: frozenset = frozenset(),
                   out: Optional[List[Tuple[int, int]]] = None,
                   ) -> List[Tuple[int, int]]:
        """Copy-on-write guard for the pages slot ``s`` may write this
        dispatch (positions ``pos[s]..last_pos``).  Shared pages (refcount
        > 1) are swapped for fresh private copies and the table repointed;
        returns the (src, dst) page pairs still needing a device-side copy
        (appended to ``out`` when given, so a caller that must recover from
        ``PoolExhausted`` still sees the pairs already repointed).

        Exhaustion during a copy tries, in order: prefix-cache eviction,
        then preempting a victim outside ``protect | {s}``; when neither
        frees a block ``PoolExhausted`` propagates and the caller decides
        (per-tick paths preempt ``s`` itself, the window path rolls back
        its grant and falls back to per-tick).

        In the normal flow the copy never fires: only *full* prompt pages
        are published to the index and matches are capped so the divergent
        tail starts page-aligned — a shared page is never written.  The
        guard exists so sharing stays safe by construction (tests pin it
        via manually attached partial pages), not by scheduler luck."""
        pairs = out if out is not None else []
        ps = self.pool.page_size
        req = self.slot_req[s]
        first = int(self.pos[s]) // ps
        last = min(last_pos // ps, self.tables.num_blocks(s) - 1)
        for pidx in range(first, last + 1):
            while True:
                try:
                    pair = self.tables.ensure_writable(s, pidx, req.uid)
                    break
                except PoolExhausted:
                    if self._reclaim(1):
                        continue
                    victim = self._pick_victim(exclude=protect | {s})
                    if victim is None:
                        raise
                    self._preempt(victim)
            if pair:
                pairs.append(pair)
        return pairs

    def _cow_or_preempt(self, work: List[Tuple[int, int]],
                        ) -> Tuple[List[int], List[Tuple[int, int]]]:
        """Run the COW gate for each ``(slot, last_pos)`` about to be
        dispatched.  A slot whose copy cannot be satisfied even after
        eviction and victim preemption is preempted itself and dropped
        from the dispatch — its partially-repointed pages roll back with
        its table, so the surviving slots' pairs stay valid.  Returns
        (surviving slots, device copy pairs)."""
        dispatch = frozenset(s for s, _ in work)
        survivors: List[int] = []
        pairs: List[Tuple[int, int]] = []
        for s, last in work:
            if self.slot_req[s] is None:
                continue  # became a victim earlier in this loop
            try:
                local = self._cow_range(s, last, protect=dispatch)
            except PoolExhausted:
                self._preempt(s)  # recompute resume replays it cleanly
                continue
            survivors.append(s)
            pairs += local
        return survivors, pairs

    def _poison_mask(self, rows: List[int],
                     site: str = "poison") -> np.ndarray:
        """(slots,) bool — rows the injector poisons this dispatch
        (``site``: "poison" for per-tick logits, "spec_poison" for the
        speculative window's verify logits).  A due fault targets
        ``fault.slot`` mod the dispatched rows, so a schedule stays
        meaningful whatever the slot occupancy is by then."""
        mask = np.zeros((self.scfg.slots,), bool)
        if self.injector is None or not rows:
            return mask
        while True:
            f = self.injector.fire(site)
            if f is None:
                break
            mask[rows[f.slot % len(rows)]] = True
        return mask

    def _fire_table_corrupt(self, work: List[Tuple[int, int]]):
        """Due ``table_corrupt`` faults overwrite one device-table entry of
        a dispatched slot — the page backing its write position, so the bad
        entry sits inside both the guarded live prefix and the write range.
        Corruption is physical: it fires whether or not guards are enabled
        (with guards off, the invariant auditor is what notices the row
        diverging from the block ledger).  Flavors cycle deterministically:
        out-of-range id, reserved page 0 in the live prefix, duplicate of
        another dispatched row's page."""
        if self.injector is None or self.tables is None or not work:
            return
        ps = self.pool.page_size
        out_of_range = self.pool.base + self.pool.num_blocks + 5
        while True:
            f = self.injector.fire("table_corrupt")
            if f is None:
                break
            s, n = work[f.slot % len(work)]
            j = max(0, -(-(int(self.pos[s]) + n) // ps) - 1)
            mode = self._corrupt_mode % 3
            self._corrupt_mode += 1
            if mode == 0:
                bad = out_of_range
            elif mode == 1:
                bad = 0  # reserved sink page inside the live prefix
            else:
                other = next((t for t, _ in work if t != s
                              and self.tables.num_blocks(t) > 0), None)
                bad = (self.tables.blocks(other)[0]
                       if other is not None else out_of_range)
            self.tables.poke(s, j, bad)
            self._tables_dirty = True
            self.table_corruptions += 1

    def _guard_work(self, work: List[Tuple[int, int]],
                    ) -> List[Tuple[int, int]]:
        """Discharge the kernels' runtime obligations for the ``(slot,
        n_tokens)`` pairs about to dispatch (core.lowering.verify emits
        them; this is where the engine pays): every live block-table entry
        in range, no duplicate writable pages, lengths within capacity.  A
        violating slot FAILs through ``_terminate`` — graceful degradation,
        never a kernel scribbling on another request's pages — and is
        dropped from the dispatch; the survivors proceed untouched."""
        if self.tables is None or not work:
            return work
        self._fire_table_corrupt(work)
        if not self.scfg.guards:
            return work
        rows = []
        for s, n in work:
            p = int(self.pos[s])
            rows.append((s, p + n, p, p + n))
        try:
            guard_dispatch(
                self.tables.tables(),
                self.pool.base + self.pool.num_blocks,
                self.pool.page_size, rows,
            )
        except GuardError as e:
            blamed = sorted({row for row, _, _ in e.violations})
            detail = {row: f"{kind}: {msg}"
                      for row, kind, msg in reversed(e.violations)}
            for s in blamed:
                req = self.slot_req[s]
                if req is None:
                    continue
                self.guard_failures += 1
                self._terminate(req, FAILED, slot=s,
                                error=f"dispatch guard: {detail[s]}")
            dead = set(blamed)
            return [(s, n) for s, n in work if s not in dead]
        return work

    def _apply_cow(self, pairs: List[Tuple[int, int]]):
        """Run the device-side page copies for COW repoints.  Pairs are
        padded to a power-of-two count to bound jit trace variants; padding
        copies page 0 onto itself (page 0 is reserved, never shared)."""
        if not pairs:
            return
        self.pages_copied += len(pairs)
        self._tables_dirty = True
        n = 1
        while n < len(pairs):
            n *= 2
        src = np.zeros((n,), np.int32)
        dst = np.zeros((n,), np.int32)
        for i, (a, b) in enumerate(pairs):
            src[i] = a
            dst[i] = b
        # the copy's outputs stay on the device: it has no wait or readback
        rec = telemetry.Dispatch("copy_pages", 0, ())
        with telemetry.span("upload", rec):
            src, dst = jnp.asarray(src), jnp.asarray(dst)
        with telemetry.span("dispatch", rec):
            self.cache = _copy_pages_fn(self.cfg)(self.cache, src, dst)
        telemetry.LOG.append(rec)

    # -- per-tick paths -------------------------------------------------
    def _step_replay(self, active: List[int]) -> int:
        with telemetry.span("schedule"):
            if self.tables is not None:
                active, pairs = self._cow_or_preempt(
                    [(s, int(self.pos[s])) for s in active]
                )
                self._apply_cow(pairs)
                active = [s for s, _ in self._guard_work([(s, 1) for s in active])]
                if not active:
                    self.dispatches -= 1  # nothing actually dispatched
                    return 0
            feed = np.zeros((self.scfg.slots,), np.int32)
            live = np.zeros((self.scfg.slots,), bool)
            full_len: Dict[int, int] = {}
            for s in active:
                req = self.slot_req[s]
                cur = req._cursor  # type: ignore[attr-defined]
                np_ = len(req.prompt)
                full_len[s] = np_ + len(req.output)
                feed[s] = (
                    req.prompt[cur] if cur < np_ else req.output[cur - np_]
                )
                live[s] = True
            poison = self._poison_mask(active)
        (next_tok, bad), (self.cache, self._key), rec = self._run_program(
            self._step, (feed, self.pos, self._key, live, poison), 2, active,
        )
        with telemetry.span("drain", rec):
            self._page_walk(rec, self.pos[None])
            for s in active:
                req = self.slot_req[s]
                cur = req._cursor  # type: ignore[attr-defined]
                self.pos[s] += 1
                req._cursor = cur + 1  # type: ignore[attr-defined]
                if bad[s]:
                    self.poisoned_rows += 1
                    self._terminate(req, FAILED, slot=s,
                                    error="poisoned logits row (no finite value)")
                    continue
                if cur + 1 >= full_len[s]:  # this step produced a real token
                    self._register_prefix(s, req)
                    self._emit_token(s, req, int(next_tok[s]))
            self.tick_tokens.append(len(active))
            self.steps_run += 1
        return len(active)

    def _step_chunked(self, active: List[int]) -> int:
        """One token-budget tick: decode for generating slots + prompt
        chunks for prefilling slots (oldest admitted first) within the
        leftover budget: up to two device programs, ``decode_step`` then
        ``prefill_step``."""
        with telemetry.span("schedule"):
            gen = [s for s in active if self.slot_state[s] == "gen"]
            pending = []
            for s in active:
                if self.slot_state[s] != "prefill":
                    continue
                req = self.slot_req[s]
                remaining = len(req.prompt) + len(req.output) - req._cursor  # type: ignore[attr-defined]
                pending.append((s, req._admit_seq, remaining))  # type: ignore[attr-defined]
            chunk_lens = plan_prefill_chunks(
                self.token_budget, len(gen), pending, self.prefill_chunk
            )
            if gen and self.tables is not None:
                gen, pairs = self._cow_or_preempt(
                    [(s, int(self.pos[s])) for s in gen]
                )
                self._apply_cow(pairs)
                gen = [s for s, _ in self._guard_work([(s, 1) for s in gen])]
            if gen:
                feed = np.zeros((self.scfg.slots,), np.int32)
                live = np.zeros((self.scfg.slots,), bool)
                for s in gen:
                    req = self.slot_req[s]
                    feed[s] = req.output[-1]
                    live[s] = True
                poison = self._poison_mask(gen)
        if gen:
            (next_tok, bad), (self.cache, self._key), rec = self._run_program(
                self._step, (feed, self.pos, self._key, live, poison), 2, gen,
            )
            with telemetry.span("drain", rec):
                self._page_walk(rec, self.pos[None])
                for s in gen:
                    req = self.slot_req[s]
                    self.pos[s] += 1
                    req._cursor += 1  # type: ignore[attr-defined]
                    if bad[s]:
                        self.poisoned_rows += 1
                        self._terminate(
                            req, FAILED, slot=s,
                            error="poisoned logits row (no finite value)")
                        continue
                    self._emit_token(s, req, int(next_tok[s]))

        with telemetry.span("schedule"):
            # COW during the gen dispatch may have preempted a prefilling slot
            chunk_lens = {s: n for s, n in chunk_lens.items()
                          if self.slot_req[s] is not None}
            if chunk_lens and self.tables is not None:
                ok, pairs = self._cow_or_preempt(
                    [(s, int(self.pos[s]) + n - 1) for s, n in chunk_lens.items()]
                )
                chunk_lens = {s: chunk_lens[s] for s in ok}
                self._apply_cow(pairs)
                chunk_lens = dict(self._guard_work(list(chunk_lens.items())))
            if chunk_lens:
                width = self.prefill_chunk
                toks = np.zeros((self.scfg.slots, width), np.int32)
                lens = np.zeros((self.scfg.slots,), np.int32)
                for s, n in chunk_lens.items():
                    req = self.slot_req[s]
                    cur = req._cursor  # type: ignore[attr-defined]
                    replay = (req.prompt + req.output)[cur : cur + n]
                    toks[s, :n] = replay
                    lens[s] = n
                poison = self._poison_mask(sorted(chunk_lens))
        if chunk_lens:
            (ptok, pbad), (self.cache, self._key), rec = self._run_program(
                self._prefill, (toks, self.pos, lens, self._key, poison), 2,
                sorted(chunk_lens), rows=toks.size,
                live_rows=sum(chunk_lens.values()),
            )
            with telemetry.span("drain", rec):
                self._prefill_walk(rec, self.pos, lens)
                for s, n in chunk_lens.items():
                    req = self.slot_req[s]
                    self.pos[s] += n
                    req._cursor += n  # type: ignore[attr-defined]
                    if pbad[s]:
                        self.poisoned_rows += 1
                        self._terminate(
                            req, FAILED, slot=s,
                            error="poisoned logits row (no finite value)")
                        continue
                    if req._cursor >= len(req.prompt) + len(req.output):  # type: ignore[attr-defined]
                        # the chunk reached the end of the replay stream:
                        # its last live logits produce the next real token
                        self.slot_state[s] = "gen"
                        self._register_prefix(s, req)
                        self._emit_token(s, req, int(ptok[s]))

        self.tick_tokens.append(len(gen) + sum(chunk_lens.values()))
        self.steps_run += 1
        return len(active)

    def run(self, max_steps: int = 10_000) -> List[Request]:
        """Drive until queue + slots drain (or step budget)."""
        for _ in range(max_steps):
            if self.step() == 0 and not self.queue:
                break
        return self.completed

    # -- lifecycle: drain / shutdown ------------------------------------
    def drain(self, max_steps: int = 10_000) -> List[Request]:
        """Stop admission and finish every request already holding a slot.
        Queued requests stay queued — drain stops intake, it does not
        cancel.  Afterwards the pool holds only prefix-cache pages (and
        admission stays closed; reopen by setting ``admission_open``)."""
        self.admission_open = False
        for _ in range(max_steps):
            if self.step() == 0:
                break
        return self.completed

    def shutdown(self) -> List[Request]:
        """Drain in-flight work, cancel everything still queued, and flush
        the prefix index: afterwards the pool holds **zero** allocated
        blocks — the freed-page guarantee the chaos harness asserts."""
        self.drain()
        for s in range(self.scfg.slots):
            req = self.slot_req[s]
            if req is not None:  # drain ran out of its step budget
                self._terminate(req, CANCELLED, slot=s,
                                error="engine shutdown")
        while self.queue:
            self._terminate(self.queue.popleft(), CANCELLED,
                            error="engine shutdown")
        if self.prefix is not None:
            self.prefix.flush()
            self._tables_dirty = True
        if self.scfg.audit:
            self.audits_run += 1
            audit_engine(self)
        return self.completed

    # -- crash-safe persistence -----------------------------------------
    def snapshot(self, path: Optional[str] = None) -> dict:
        """Serialize the prefix-cache radix index *and* the KV contents of
        its pages — the warm state an engine restart would otherwise lose.
        In-flight slots are deliberately not captured: requests are
        re-submittable, the cached prefix KV is not.  Returns the snapshot
        dict; ``path`` additionally pickles it to disk."""
        if self.prefix is None:
            raise ValueError(
                "snapshot() needs the prefix cache enabled "
                "(paged cache + an attention family)"
            )
        entries = self.prefix.export()
        snap = {
            "format": SNAPSHOT_FORMAT,
            "model": self.cfg.name,
            "page_size": self.pool.page_size,
            "kv_dtype": self.cfg.kv_dtype,
            "nodes": [(parent, list(blk)) for parent, blk, _ in entries],
            "leaves": lm.gather_pages(
                self.cache, [page for _, _, page in entries]
            ),
        }
        if path is not None:
            import pickle

            with open(path, "wb") as f:
                pickle.dump(snap, f)
        return snap

    def load_snapshot(self, snap) -> int:
        """Graft a snapshot's cached page chains into this engine (normally
        a fresh one — see :meth:`restore`).  Config mismatches (model, page
        size, kv dtype, page-pool layout) are loud ``ValueError``s —
        silently serving stale KV would be wrong tokens, not an error
        message.  When the pool is smaller than the snapshot, the longest
        chain prefixes that fit are restored (children of a skipped node
        are skipped).  Returns pages restored."""
        if not isinstance(snap, dict):
            import pickle

            with open(snap, "rb") as f:
                snap = pickle.load(f)
        if self.prefix is None:
            raise ValueError("load_snapshot() needs the prefix cache enabled")
        if snap.get("format") != SNAPSHOT_FORMAT:
            raise ValueError(
                f"unknown snapshot format {snap.get('format')!r} "
                f"(this engine writes {SNAPSHOT_FORMAT})"
            )
        for field, mine in (
            ("model", self.cfg.name),
            ("page_size", self.pool.page_size),
            ("kv_dtype", self.cfg.kv_dtype),
        ):
            if snap[field] != mine:
                raise ValueError(
                    f"snapshot {field}={snap[field]!r} does not match "
                    f"engine {field}={mine!r}"
                )
        want = [(tuple(a.shape[1:]), str(a.dtype)) for a in snap["leaves"]]
        if want != lm.page_leaf_shapes(self.cache):
            raise ValueError(
                "snapshot page-pool layout does not match this engine's "
                "cache (different reduced config or leaf set)"
            )
        phys: Dict[int, int] = {}
        keep: List[int] = []
        for i, (parent, _blk) in enumerate(snap["nodes"]):
            if parent >= 0 and parent not in phys:
                continue  # ancestor skipped (pool ran short): skip the chain
            if not self.pool.free:
                continue  # partial restore: longest prefixes that fit
            phys[i] = self.pool.alloc(owner="prefix-snapshot")
            keep.append(i)
        if keep:
            dst = [phys[i] for i in keep]
            values = [np.asarray(a)[keep] for a in snap["leaves"]]
            self.cache = lm.scatter_pages(self.cache, dst, values)
            local = {i: j for j, i in enumerate(keep)}
            entries = []
            for i in keep:
                parent, blk = snap["nodes"][i]
                entries.append((
                    local[parent] if parent >= 0 else -1, tuple(blk), phys[i]
                ))
            self.prefix.import_nodes(entries)
        return len(keep)

    @classmethod
    def restore(cls, cfg: ModelConfig, params, serve_cfg: ServeConfig,
                snap, injector: Optional[FaultInjector] = None,
                ) -> "ServingEngine":
        """Crash-safe restart: a fresh engine pre-warmed with
        ``snapshot()``'s radix index and page contents, so a warm-prefix
        request hits the cache immediately — TTFT matches the pre-restart
        cached path instead of paying a cold prefill."""
        eng = cls(cfg, params, serve_cfg, injector=injector)
        eng.load_snapshot(snap)
        return eng

    # -- accounting -----------------------------------------------------
    def kv_cache_bytes(self) -> int:
        """Bytes held by attention KV state under the current layout."""
        return self.cache.kv_bytes()

    def peak_kv_blocks(self) -> Optional[int]:
        return None if self.pool is None else self.pool.peak_in_use
