"""Deterministic unit tests for the serving scheduler + paged KV cache.

Covers the ISSUE-2 acceptance surface:

* admission order (FIFO) and admission gating on free-block count;
* preemption-and-requeue when the pool is exhausted, including
  priority-aware victim selection and recompute-style resume;
* slot/block recycling at EOS (the pool drains back to empty);
* output equivalence between contiguous and paged cache modes across
  GQA / MQA / sliding-window / hybrid configs;
* the paged_attention kernel against its pure-JAX oracle.

Plus the ISSUE-4 device-resident decode loop:

* byte-identical outputs vs the per-tick engine across paged/contiguous,
  sync_every values, EOS mid-window, slots finishing mid-window, a pool
  too tight for the grow-ahead grant (per-tick fallback), preemption at a
  sync boundary, temperature sampling, and hybrid (recurrent-state) archs;
* the donation contract: the jit'd step consumes its cache argument;
* the cached device block-table tensor: re-uploaded only on mutation.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.serving import ServeConfig, ServingEngine
from repro.serving.paged_cache import (
    BlockPool,
    PoolExhausted,
    PrefixCache,
    SlotTables,
    blocks_for,
)


def _params(cfg, seed=0):
    return lm.init(cfg, jax.random.PRNGKey(seed))


def _qwen():
    return get_config("qwen2_1_5b").reduced()


# ---------------------------------------------------------------------------
# Block pool / tables (deterministic allocator unit tests; the hypothesis
# versions live in tests/test_property.py)
# ---------------------------------------------------------------------------


class TestBlockPool:
    def test_alloc_unique_and_exhaustion(self):
        pool = BlockPool(4, 8)
        got = [pool.alloc() for _ in range(4)]
        assert sorted(got) == [0, 1, 2, 3]
        assert pool.free == 0 and pool.in_use == 4
        with pytest.raises(PoolExhausted):
            pool.alloc()

    def test_release_roundtrip_and_double_free(self):
        pool = BlockPool(3, 4)
        a, b = pool.alloc("r1"), pool.alloc("r2")
        pool.release([a])
        assert pool.free == 2
        with pytest.raises(ValueError):
            pool.release([a])  # already free
        c = pool.alloc()
        assert c not in (b,)  # never double-assigned
        pool.release([b, c])
        assert pool.free == 3 and pool.in_use == 0

    def test_base_offset_reserves_page_zero(self):
        pool = BlockPool(4, 8, base=1)
        got = sorted(pool.alloc() for _ in range(4))
        assert got == [1, 2, 3, 4]  # page 0 never handed out

    def test_peak_accounting(self):
        pool = BlockPool(4, 8)
        xs = [pool.alloc() for _ in range(3)]
        pool.release(xs)
        pool.alloc()
        assert pool.peak_in_use == 3

    def test_blocks_for(self):
        assert blocks_for(1, 16) == 1
        assert blocks_for(16, 16) == 1
        assert blocks_for(17, 16) == 2


class TestSlotTables:
    def test_growth_lookup_and_table_tensor(self):
        pool = BlockPool(6, 4, base=1)
        st = SlotTables(pool, slots=2, max_pages=3)
        assert st.ensure_capacity(0, 5) == 2  # 5 tokens -> 2 pages
        assert st.ensure_capacity(0, 5) == 0  # idempotent
        assert st.ensure_capacity(1, 9) == 3
        t = st.tables()
        assert t.shape == (2, 3)
        assert t[0, 2] == 0  # padding entries point at the reserved page
        for pos in range(5):
            assert st.lookup(0, pos) == st.blocks(0)[pos // 4]
        owned = st.blocks(0) + st.blocks(1)
        assert len(set(owned)) == len(owned)  # no page shared across slots

    def test_exhaustion_allocates_nothing(self):
        pool = BlockPool(2, 4)
        st = SlotTables(pool, slots=2, max_pages=4)
        st.ensure_capacity(0, 8)
        with pytest.raises(PoolExhausted):
            st.ensure_capacity(1, 5)  # needs 2, pool has 0
        assert st.num_blocks(1) == 0 and pool.free == 0

    def test_release_slot_returns_blocks(self):
        pool = BlockPool(4, 4)
        st = SlotTables(pool, slots=1, max_pages=4)
        st.ensure_capacity(0, 16)
        assert pool.free == 0
        assert st.release_slot(0) == 4
        assert pool.free == 4
        assert not st.tables().any()

    def test_trim_releases_tail_only(self):
        pool = BlockPool(6, 4, base=1)
        st = SlotTables(pool, slots=1, max_pages=6)
        st.ensure_capacity(0, 20)  # 5 blocks (grow-ahead grant)
        kept = st.blocks(0)[:2]
        assert st.trim(0, 7) == 3  # 7 tokens -> 2 blocks
        assert st.blocks(0) == kept  # prefix untouched, order preserved
        assert pool.free == 4
        assert not st.tables()[0, 2:].any()
        assert st.trim(0, 7) == 0  # idempotent
        assert st.trim(0, 0) == 2  # trim-to-zero == full release


class TestRefcountedSharing:
    """Page sharing between tables: retain/attach/repoint and the
    copy-on-write gate (ISSUE-6)."""

    def test_retain_release_lifecycle(self):
        pool = BlockPool(2, 4)
        blk = pool.alloc("a")
        pool.retain(blk)
        assert pool.refcount(blk) == 2
        pool.release([blk])
        assert pool.refcount(blk) == 1 and pool.in_use == 1  # still live
        pool.release([blk])
        assert pool.refcount(blk) == 0 and pool.in_use == 0  # recycled
        with pytest.raises(ValueError):
            pool.retain(blk)  # can't retain a free block

    def test_attach_shares_pages_across_slots(self):
        pool = BlockPool(4, 4, base=1)
        st = SlotTables(pool, slots=2, max_pages=4)
        st.ensure_capacity(0, 8, owner="a")  # 2 pages
        shared = st.blocks(0)
        st.attach(1, shared)
        assert st.blocks(1) == shared
        assert all(pool.refcount(b) == 2 for b in shared)
        assert pool.in_use == 2  # physical pages, not references
        st.release_slot(0)
        assert all(pool.refcount(b) == 1 for b in shared)  # slot 1 holds on
        st.release_slot(1)
        assert pool.in_use == 0

    def test_attach_respects_max_pages(self):
        pool = BlockPool(8, 4, base=1)
        st = SlotTables(pool, slots=2, max_pages=2)
        st.ensure_capacity(0, 8, owner="a")
        with pytest.raises(ValueError):
            st.attach(1, st.blocks(0) + st.blocks(0))

    def test_repoint_swaps_reference(self):
        pool = BlockPool(4, 4, base=1)
        st = SlotTables(pool, slots=2, max_pages=2)
        st.ensure_capacity(0, 4, owner="a")
        st.ensure_capacity(1, 4, owner="b")
        canonical, dup = st.blocks(0)[0], st.blocks(1)[0]
        st.repoint(1, 0, canonical)
        assert st.blocks(1) == [canonical]
        assert pool.refcount(canonical) == 2
        assert pool.refcount(dup) == 0  # duplicate recycled
        assert st.tables()[1, 0] == canonical  # device tensor follows
        st.repoint(1, 0, canonical)  # same-page repoint is a no-op
        assert pool.refcount(canonical) == 2

    def test_ensure_writable_copies_only_shared_pages(self):
        pool = BlockPool(4, 4, base=1)
        st = SlotTables(pool, slots=2, max_pages=2)
        st.ensure_capacity(0, 8, owner="a")
        st.attach(1, st.blocks(0)[:1])  # share page 0 only
        st.ensure_capacity(1, 8, owner="b")  # private page 1
        assert st.ensure_writable(1, 1, "b") is None  # private: no copy
        src, dst = st.ensure_writable(1, 0, "b")  # shared: COW
        assert src == st.blocks(0)[0] and dst == st.blocks(1)[0]
        assert src != dst
        assert pool.refcount(src) == 1 and pool.refcount(dst) == 1
        assert st.tables()[1, 0] == dst
        assert st.ensure_writable(1, 0, "b") is None  # now exclusive

    def test_ensure_writable_exhaustion_frees_nothing(self):
        pool = BlockPool(2, 4, base=1)
        st = SlotTables(pool, slots=2, max_pages=2)
        st.ensure_capacity(0, 8, owner="a")  # pool drained
        st.attach(1, st.blocks(0)[:1])
        with pytest.raises(PoolExhausted):
            st.ensure_writable(1, 0, "b")
        # the failed gate changed nothing: still shared, still consistent
        assert st.blocks(1)[0] == st.blocks(0)[0]
        assert pool.refcount(st.blocks(0)[0]) == 2

    def test_trim_and_release_respect_sharing(self):
        pool = BlockPool(4, 4, base=1)
        st = SlotTables(pool, slots=2, max_pages=4)
        st.ensure_capacity(0, 16, owner="a")
        st.attach(1, st.blocks(0))
        st.trim(0, 4)  # slot 0 keeps 1 page; the other 3 survive via slot 1
        assert pool.in_use == 4
        assert st.num_blocks(1) == 4
        st.release_slot(1)
        assert pool.in_use == 1  # only slot 0's kept page remains


class TestPrefixIndex:
    """The radix index over token ids (unit level — engine integration is
    TestPrefixCaching below)."""

    def _cache(self, ps=4, nb=16):
        pool = BlockPool(nb, ps, base=1)
        return pool, PrefixCache(pool, salt=("test", ps))

    def test_insert_then_match_longest_chain(self):
        pool, pc = self._cache()
        toks = list(range(12))  # 3 full pages
        pages = [pool.alloc() for _ in range(3)]
        assert pc.insert(toks, pages) == []
        assert pc.pages == 3
        assert all(pool.refcount(p) == 2 for p in pages)  # index holds one
        assert pc.match(toks, max_pages=8) == pages
        assert pc.match(toks[:8] + [99, 99, 99, 99], 8) == pages[:2]
        assert pc.match([99] * 12, 8) == []
        # partial trailing page never matches (page granularity)
        assert pc.match(toks[:6], 8) == pages[:1]
        assert pc.hits == 3 and pc.lookups == 4

    def test_match_respects_cap(self):
        pool, pc = self._cache()
        toks = list(range(12))
        pc.insert(toks, [pool.alloc() for _ in range(3)])
        assert len(pc.match(toks, max_pages=1)) == 1
        assert pc.match(toks, max_pages=0) == []

    def test_insert_dedups_concurrent_prefills(self):
        pool, pc = self._cache()
        toks = list(range(8))
        first = [pool.alloc(), pool.alloc()]
        dup = [pool.alloc(), pool.alloc()]
        pc.insert(toks, first)
        # a second request prefilled the same prompt into its own pages:
        # the index reports the canonical pages so the caller repoints
        assert pc.insert(toks, dup) == [(0, first[0]), (1, first[1])]
        assert pc.pages == 2  # no duplicate nodes

    def test_hash_collision_cannot_alias(self):
        """Chain identity is content-checked: two different token blocks
        never resolve to the same cached page even if their hashes collide
        (lookup is by exact token tuple, the hash is only the chain key)."""
        pool, pc = self._cache()
        a, b = [0, 1, 2, 3], [4, 5, 6, 7]
        pa, pb = pool.alloc(), pool.alloc()
        pc.insert(a, [pa])
        pc.insert(b, [pb])
        assert pc.match(a, 1) == [pa]
        assert pc.match(b, 1) == [pb]

    def test_salt_keys_chains_per_model_config(self):
        pool = BlockPool(8, 4, base=1)
        pc1 = PrefixCache(pool, salt=("model-a", 4))
        pc2 = PrefixCache(pool, salt=("model-b", 4))
        assert pc1._root.key != pc2._root.key

    def test_evict_lru_leaves_first(self):
        pool, pc = self._cache()
        cold = list(range(8))
        hot = list(range(100, 108))
        cold_pages = [pool.alloc() for _ in range(2)]
        hot_pages = [pool.alloc() for _ in range(2)]
        pc.insert(cold, cold_pages)
        pc.insert(hot, hot_pages)
        pool.release(cold_pages + hot_pages)  # only the index holds them
        pc.match(cold, 2)
        pc.match(hot, 2)  # hot is most-recent
        assert pc.evict(1) == 1
        # the cold chain's leaf went first
        assert pc.match(cold, 2) == cold_pages[:1]
        assert pc.match(hot, 2) == hot_pages

    def test_evict_walks_chains_tail_first(self):
        pool, pc = self._cache()
        toks = list(range(12))
        pages = [pool.alloc() for _ in range(3)]
        pc.insert(toks, pages)
        pool.release(pages)
        assert pc.evict(3) == 3  # leaf, then exposed parent, then root child
        assert pc.pages == 0
        assert pool.in_use == 0

    def test_evict_skips_referenced_and_protected(self):
        pool, pc = self._cache()
        toks = list(range(8))
        pages = [pool.alloc() for _ in range(2)]
        pc.insert(toks, pages)  # rc 2 everywhere: caller + index
        assert pc.evict(8) == 0  # a table still references both
        pool.release([pages[1]])  # tail page goes cold (rc 1)
        assert pc.evict(8, protect=frozenset([pages[1]])) == 0  # protected
        assert pc.evict(8) == 1  # now reclaimable
        assert pool.refcount(pages[0]) == 2  # head survives untouched


# ---------------------------------------------------------------------------
# Scheduler behavior
# ---------------------------------------------------------------------------


class TestScheduler:
    def test_admission_order_fifo(self, rng):
        cfg = _qwen()
        eng = ServingEngine(cfg, _params(cfg), ServeConfig(
            slots=2, max_len=32, max_new_tokens=2))
        reqs = [eng.submit(rng.integers(0, cfg.vocab_size, size=3).tolist())
                for _ in range(4)]
        eng.step()
        assert [eng.slot_req[0].uid, eng.slot_req[1].uid] == [reqs[0].uid, reqs[1].uid]
        assert [r.uid for r in eng.queue] == [reqs[2].uid, reqs[3].uid]
        done = eng.run()
        assert [r.uid for r in done] == [r.uid for r in reqs]  # FIFO completion

    def test_admission_gated_by_free_blocks(self, rng):
        # prefix_cache off: this test pins the free-block admission gate,
        # which sharing the identical prompt would legitimately bypass
        cfg = _qwen()
        eng = ServingEngine(cfg, _params(cfg), ServeConfig(
            slots=2, max_len=16, max_new_tokens=2,
            page_size=4, num_blocks=4, prefix_cache=False))
        long_prompt = rng.integers(0, cfg.vocab_size, size=10).tolist()
        r1 = eng.submit(long_prompt)
        r2 = eng.submit(long_prompt)
        eng.step()
        # r1 holds 3 of 4 blocks; r2 (needs 3) must wait despite a free slot
        assert eng.slot_req[0] is r1 and eng.slot_req[1] is None
        assert list(eng.queue) == [r2]
        done = eng.run()
        assert [r.uid for r in done] == [r1.uid, r2.uid]
        assert eng.pool.in_use == 0  # everything recycled

    def test_preemption_requeue_and_recompute(self, rng):
        cfg = _qwen()
        params = _params(cfg)
        prompt1 = rng.integers(0, cfg.vocab_size, size=6).tolist()
        prompt2 = rng.integers(0, cfg.vocab_size, size=6).tolist()

        def alone(prompt):
            e = ServingEngine(cfg, params, ServeConfig(
                slots=1, max_len=16, max_new_tokens=6, page_size=4))
            r = e.submit(prompt)
            e.run()
            return r.output

        ref1, ref2 = alone(prompt1), alone(prompt2)

        # pool of 4 blocks: both requests admit at 2 blocks each, but each
        # needs a 3rd block mid-generation -> forced preemption
        # (prefix_cache off: published prompt pages would relieve exactly
        # the pool pressure this test constructs)
        eng = ServingEngine(cfg, params, ServeConfig(
            slots=2, max_len=16, max_new_tokens=6,
            page_size=4, num_blocks=4, prefix_cache=False))
        r1 = eng.submit(prompt1)
        r2 = eng.submit(prompt2)
        done = eng.run()
        assert eng.preemptions >= 1
        assert r2.preemptions >= 1  # younger same-priority request evicted
        assert r1.preemptions == 0
        assert [r.uid for r in done] == [r1.uid, r2.uid]
        # recompute resume is lossless: outputs match isolated runs exactly
        assert r1.output == ref1
        assert r2.output == ref2
        assert eng.pool.in_use == 0

    def test_preemption_respects_priority(self, rng):
        cfg = _qwen()
        eng = ServingEngine(cfg, _params(cfg), ServeConfig(
            slots=2, max_len=16, max_new_tokens=6,
            page_size=4, num_blocks=4))
        prompt = rng.integers(0, cfg.vocab_size, size=6).tolist()
        low = eng.submit(prompt, priority=0)
        high = eng.submit(prompt, priority=1)
        done = eng.run()
        # the older-but-lower-priority request is the victim
        assert low.preemptions >= 1 and high.preemptions == 0
        assert [r.uid for r in done] == [high.uid, low.uid]

    def test_blocks_recycled_at_eos(self, rng):
        cfg = _qwen()
        eng = ServingEngine(cfg, _params(cfg), ServeConfig(
            slots=2, max_len=32, max_new_tokens=3, page_size=4))
        for _ in range(5):
            eng.submit(rng.integers(0, cfg.vocab_size, size=5).tolist())
        done = eng.run()
        assert len(done) == 5
        # everything recycled at EOS except the pages the prefix index
        # deliberately keeps (one full prompt page per unique 5-token prompt)
        assert eng.pool.in_use == eng.prefix.pages
        # 5 requests through a 2-slot engine only ever hold 2 slots of blocks
        # (+ the retained cache pages of completed requests)
        assert eng.peak_kv_blocks() <= 2 * blocks_for(5 + 3, 4) + eng.prefix.pages

    def test_unservable_request_fails_fast(self, rng):
        cfg = _qwen()
        eng = ServingEngine(cfg, _params(cfg), ServeConfig(
            slots=1, max_len=64, max_new_tokens=2,
            page_size=4, num_blocks=2))  # pool holds 8 tokens
        big = eng.submit(rng.integers(0, cfg.vocab_size, size=20).tolist())
        ok = eng.submit(rng.integers(0, cfg.vocab_size, size=4).tolist())
        done = eng.run()
        assert big.error is not None and big.output == []
        assert ok.error is None and len(ok.output) == 2
        assert {r.uid for r in done} == {big.uid, ok.uid}

    def test_prompt_beyond_max_len_fails_fast(self, rng):
        """A prompt that outsizes the per-slot table (max_len) must fail the
        one request, not crash the engine — the pool may be big enough while
        the table is not."""
        cfg = _qwen()
        eng = ServingEngine(cfg, _params(cfg), ServeConfig(
            slots=2, max_len=32, max_new_tokens=2, page_size=16))  # 4-block pool
        big = eng.submit(rng.integers(0, cfg.vocab_size, size=40).tolist())
        ok = eng.submit(rng.integers(0, cfg.vocab_size, size=4).tolist())
        done = eng.run()
        assert big.error is not None and big.output == []
        assert ok.error is None and len(ok.output) == 2
        assert {r.uid for r in done} == {big.uid, ok.uid}

    def test_mla_serves_paged(self):
        """MLA archs page their latent cache — the PR-2 era contiguous
        downgrade is gone."""
        cfg = get_config("deepseek_v2_lite_16b").reduced()
        eng = ServingEngine(cfg, _params(cfg), ServeConfig(
            slots=1, max_len=16, max_new_tokens=2))
        assert eng.cache_mode == "paged"
        assert eng.cache.layout == "paged"

    def test_paged_without_attention_is_loud(self):
        """An arch with no attention KV state cannot page: asking for the
        paged layout raises instead of silently handing back a different
        memory layout than requested."""
        cfg = get_config("mamba2_2_7b").reduced()
        with pytest.raises(ValueError, match="paged"):
            ServingEngine(cfg, _params(cfg), ServeConfig(
                slots=1, max_len=16, max_new_tokens=2, cache="paged"))
        # contiguous still serves the recurrent-state arch
        eng = ServingEngine(cfg, _params(cfg), ServeConfig(
            slots=1, max_len=16, max_new_tokens=2, cache="contiguous"))
        assert eng.cache_mode == "contiguous"


# ---------------------------------------------------------------------------
# Device-resident multi-step decode loop (ISSUE-4)
# ---------------------------------------------------------------------------


def _run_engine(cfg, params, prompts, **scfg_kw):
    eng = ServingEngine(cfg, params, ServeConfig(**scfg_kw))
    reqs = [eng.submit(p) for p in prompts]
    eng.run()
    assert all(r.done for r in reqs)
    return [r.output for r in reqs], reqs, eng


class TestMultiStepDecode:
    """The multi-step window is an *optimization*, never a behavior change:
    every test drives the same requests through the per-tick engine and the
    device-resident loop and asserts byte-identical outputs."""

    def _prompts(self, cfg, rng, sizes=(6, 3, 9, 2)):
        return [rng.integers(0, cfg.vocab_size, size=n).tolist() for n in sizes]

    @pytest.mark.parametrize("cache", ["paged", "contiguous"])
    @pytest.mark.parametrize("sync", [4, 16])
    def test_matches_per_tick(self, cache, sync, rng):
        # max_new=5 is deliberately not a multiple of sync: slots finish
        # mid-window and the drained tail must line up with per-tick
        cfg = _qwen()
        params = _params(cfg)
        prompts = self._prompts(cfg, rng)
        base = dict(slots=2, max_len=48, max_new_tokens=5, cache=cache,
                    page_size=16)
        ref, ref_reqs, _ = _run_engine(cfg, params, prompts, **base)
        out, reqs, eng = _run_engine(cfg, params, prompts,
                                     sync_every=sync, **base)
        assert out == ref
        assert eng.decode_windows > 0  # the loop actually engaged
        assert ([r.ttft_ticks for r in reqs]
                == [r.ttft_ticks for r in ref_reqs])
        if cache == "paged":
            assert eng.pool.in_use == 0  # grow-ahead pages all recycled

    def test_eos_mid_window(self, rng):
        cfg = _qwen()
        params = _params(cfg)
        prompts = self._prompts(cfg, rng)
        # temperature makes the greedy-degenerate streams diverse so the
        # chosen EOS token fires mid-generation, not on the first token
        base = dict(slots=2, max_len=48, max_new_tokens=8, page_size=16,
                    temperature=0.9, seed=11)
        free, _, _ = _run_engine(cfg, params, prompts, **base)
        eos = free[0][3]  # a token the model actually emits mid-stream
        ref, _, _ = _run_engine(cfg, params, prompts, eos_id=eos, **base)
        out, _, eng = _run_engine(cfg, params, prompts, eos_id=eos,
                                  sync_every=8, **base)
        assert out == ref
        assert eng.decode_windows > 0
        # EOS genuinely cut at least one stream short of its token limit
        assert any(len(o) < 8 for o in out)

    def test_temperature_matches_per_tick(self, rng):
        """The PRNG-key carry advances exactly like the per-tick engine's
        when the window covers the same ticks per-tick would run (queue
        empty, so no admission can be deferred past a mid-window finish —
        the one case where the key streams legitimately diverge, see
        lm.decode_loop).  temperature=8.0 so streams are genuinely diverse:
        random-init logits are peaked enough that lower temperatures emit
        constant streams, which would mask a shifted subkey."""
        cfg = _qwen()
        params = _params(cfg)
        prompts = self._prompts(cfg, rng, sizes=(6, 3))  # <= slots: no queue
        base = dict(slots=2, max_len=48, max_new_tokens=6, page_size=16,
                    temperature=8.0, seed=3)
        ref, _, ref_eng = _run_engine(cfg, params, prompts, **base)
        out, _, eng = _run_engine(cfg, params, prompts, sync_every=4, **base)
        assert out == ref
        assert eng.decode_windows > 0
        # the sampled streams must be diverse enough to catch a shifted
        # subkey, and the final keys must agree bit for bit
        assert any(len(set(o)) > 1 for o in out)
        assert np.array_equal(np.asarray(eng._key), np.asarray(ref_eng._key))

    def test_hybrid_recurrent_state_matches_per_tick(self, rng):
        """Hybrid (attention + SSM) archs replay prompts and carry
        recurrent state: dead window iterations must not evolve a stopped
        slot's SSM state (the live mask inside decode_step)."""
        cfg = get_config("hymba_1_5b").reduced()
        params = _params(cfg)
        prompts = self._prompts(cfg, rng, sizes=(5, 3, 7, 2))
        base = dict(slots=2, max_len=48, max_new_tokens=5, page_size=16)
        ref, _, ref_eng = _run_engine(cfg, params, prompts, **base)
        out, _, eng = _run_engine(cfg, params, prompts, sync_every=4, **base)
        assert ref_eng.prefill_mode == "replay"  # SSM gates off chunking
        assert out == ref
        assert eng.decode_windows > 0

    def test_pool_too_tight_for_grow_ahead_falls_back(self, rng):
        """The pool exactly fits the per-tick footprint (page_size=1,
        2 slots x 8-token peak = 16 blocks), so a window whose
        allowance-clamped ask still includes the dead-iteration write
        (rem + 1) over-asks by one block per slot: the all-or-nothing
        grant must fail, fall back to per-tick stepping (never preempt),
        and still finish with per-tick-identical outputs.  Once the
        remaining allowance clamps the window to exactly fit, a window may
        legitimately run — fallback and windows coexist."""
        cfg = _qwen()
        params = _params(cfg)
        prompts = [rng.integers(0, cfg.vocab_size, size=3).tolist()
                   for _ in range(2)]
        base = dict(slots=2, max_len=16, max_new_tokens=6, page_size=1,
                    num_blocks=16, prefix_cache=False)
        ref, _, _ = _run_engine(cfg, params, prompts, **base)
        out, _, eng = _run_engine(cfg, params, prompts, sync_every=8, **base)
        assert out == ref
        assert eng.window_fallbacks > 0  # the 8-wide ask never fit
        assert eng.preemptions == 0  # the grant degrades, it doesn't evict
        assert eng.pool.in_use == 0

    def test_preemption_at_sync_boundary(self, rng):
        """Pool pressure mid-generation with the multi-step engine: growth
        (and so preemption + recompute resume) happens at sync boundaries
        and stays lossless."""
        cfg = _qwen()
        params = _params(cfg)
        prompt1 = rng.integers(0, cfg.vocab_size, size=6).tolist()
        prompt2 = rng.integers(0, cfg.vocab_size, size=6).tolist()
        ref1, _, _ = _run_engine(cfg, params, [prompt1], slots=1, max_len=16,
                                 max_new_tokens=6, page_size=4)
        ref2, _, _ = _run_engine(cfg, params, [prompt2], slots=1, max_len=16,
                                 max_new_tokens=6, page_size=4)
        out, reqs, eng = _run_engine(
            cfg, params, [prompt1, prompt2], slots=2, max_len=16,
            max_new_tokens=6, page_size=4, num_blocks=4, sync_every=4,
            prefix_cache=False)
        assert eng.preemptions >= 1
        assert reqs[1].preemptions >= 1 and reqs[0].preemptions == 0
        assert out == [ref1[0], ref2[0]]  # recompute resume is lossless
        assert eng.pool.in_use == 0

    def test_step_donates_cache(self, rng):
        """The jit'd steps consume their cache argument (donate_argnums):
        after a tick every pre-step buffer is invalidated — XLA reused it
        in place instead of copying the KV cache."""
        cfg = _qwen()
        eng = ServingEngine(cfg, _params(cfg), ServeConfig(
            slots=1, max_len=32, max_new_tokens=4))
        eng.submit(rng.integers(0, cfg.vocab_size, size=3).tolist())
        before = jax.tree.leaves((eng.cache.prefix, eng.cache.rest))
        eng.step()
        assert all(leaf.is_deleted() for leaf in before)
        after = jax.tree.leaves((eng.cache.prefix, eng.cache.rest))
        assert not any(leaf.is_deleted() for leaf in after)

    def test_device_table_uploaded_only_on_mutation(self, rng):
        """One block covers the whole request, so after admission no tick
        mutates the tables: the engine must reuse the cached device tensor
        for the entire run instead of re-uploading it per tick."""
        cfg = _qwen()
        eng = ServingEngine(cfg, _params(cfg), ServeConfig(
            slots=1, max_len=32, max_new_tokens=6, page_size=32))
        eng.submit(rng.integers(0, cfg.vocab_size, size=3).tolist())
        eng.run()
        assert eng.steps_run > 3  # several ticks actually ran
        assert eng.table_uploads == 1  # exactly the admission upload

    def test_greedy_never_splits_key(self, rng):
        """temperature <= 0 skips jax.random.split entirely: the PRNG key
        comes back from every fused step bit-identical."""
        cfg = _qwen()
        eng = ServingEngine(cfg, _params(cfg), ServeConfig(
            slots=2, max_len=32, max_new_tokens=4, seed=7))
        for n in (5, 3):
            eng.submit(rng.integers(0, cfg.vocab_size, size=n).tolist())
        eng.run()
        assert np.array_equal(
            np.asarray(eng._key), np.asarray(jax.random.PRNGKey(7))
        )


# ---------------------------------------------------------------------------
# Contiguous vs paged equivalence across attention variants
# ---------------------------------------------------------------------------


def _variants():
    q = _qwen()
    return [
        ("gqa", q),
        ("mqa", dataclasses.replace(q, num_kv_heads=1)),
        ("sliding_window", dataclasses.replace(
            q, sliding_window=12, global_attn_every=2)),
        ("soft_cap", dataclasses.replace(q, logit_soft_cap=5.0)),
        ("hybrid_windowed", get_config("hymba_1_5b").reduced()),
        ("mla", get_config("deepseek_v2_lite_16b").reduced()),
    ]


@pytest.mark.parametrize("name,cfg", _variants(), ids=[n for n, _ in _variants()])
def test_paged_matches_contiguous(name, cfg, rng):
    params = _params(cfg)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=n).tolist() for n in (6, 3, 9, 2)
    ]

    def drive(mode):
        eng = ServingEngine(cfg, params, ServeConfig(
            slots=2, max_len=48, max_new_tokens=5, cache=mode, page_size=16))
        reqs = [eng.submit(p) for p in prompts]
        eng.run()
        assert all(r.done for r in reqs)
        return [r.output for r in reqs]

    contig = drive("contiguous")
    paged = drive("paged")
    assert paged == contig  # identical decode outputs, token for token


# ---------------------------------------------------------------------------
# MLA end-to-end: the paged latent cache + chunked prefill (ISSUE-5)
# ---------------------------------------------------------------------------


def test_mla_paged_chunked_matches_contiguous_replay(rng):
    """The acceptance matrix: an MLA config serves through the paged latent
    cache and chunked prefill with outputs byte-identical to the legacy
    contiguous/replay path — all four layout x prefill combinations agree,
    and the paged runs recycle every block."""
    cfg = get_config("deepseek_v2_lite_16b").reduced()
    params = _params(cfg)
    prompts = [
        rng.integers(0, cfg.vocab_size, size=n).tolist() for n in (22, 3, 17, 9)
    ]

    def drive(cache, prefill):
        eng = ServingEngine(cfg, params, ServeConfig(
            slots=2, max_len=48, max_new_tokens=5, cache=cache,
            prefill=prefill, prefill_chunk=16, page_size=16))
        reqs = [eng.submit(p) for p in prompts]
        eng.run()
        assert all(r.done for r in reqs)
        return [r.output for r in reqs], eng

    ref_out, _ = drive("contiguous", "replay")
    for cache, prefill in [("contiguous", "chunked"), ("paged", "replay"),
                           ("paged", "chunked")]:
        out, eng = drive(cache, prefill)
        assert out == ref_out, f"{cache}/{prefill} diverged"
        assert eng.prefill_mode == prefill
        if cache == "paged":
            # every latent page recycled except the full prompt pages the
            # prefix index retains (22- and 17-token prompts @ ps=16 -> one
            # each); byte-identity above covers caching-on vs contiguous
            assert eng.pool.in_use == eng.prefix.pages
            assert eng.prefix.pages == 2


def test_mla_paged_multistep_matches_per_tick(rng):
    """The device-resident decode window over the **latent** page layout:
    grow-ahead grants/trims must account the head-axis-free ckv/kpe pools
    exactly like GQA KV pages — byte-identical to per-tick stepping, every
    latent page recycled, and the window genuinely engaged."""
    cfg = get_config("deepseek_v2_lite_16b").reduced()
    params = _params(cfg)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in (6, 3, 9, 2)]
    base = dict(slots=2, max_len=48, max_new_tokens=5, cache="paged",
                page_size=16)
    ref, _, _ = _run_engine(cfg, params, prompts, **base)
    for sync in (4, 16):
        out, _, eng = _run_engine(cfg, params, prompts, sync_every=sync,
                                  **base)
        assert out == ref
        assert eng.decode_windows > 0
        assert eng.pool.in_use == 0


def test_mla_paged_preemption_lossless(rng):
    """Pool pressure on the latent pages: preemption + recompute resume
    must stay lossless for MLA exactly as for GQA."""
    cfg = get_config("deepseek_v2_lite_16b").reduced()
    params = _params(cfg)
    prompt1 = rng.integers(0, cfg.vocab_size, size=6).tolist()
    prompt2 = rng.integers(0, cfg.vocab_size, size=6).tolist()

    def alone(prompt):
        e = ServingEngine(cfg, params, ServeConfig(
            slots=1, max_len=16, max_new_tokens=6, page_size=4))
        r = e.submit(prompt)
        e.run()
        return r.output

    ref1, ref2 = alone(prompt1), alone(prompt2)
    eng = ServingEngine(cfg, params, ServeConfig(
        slots=2, max_len=16, max_new_tokens=6, page_size=4, num_blocks=4))
    r1, r2 = eng.submit(prompt1), eng.submit(prompt2)
    eng.run()
    assert eng.preemptions >= 1
    assert r1.output == ref1 and r2.output == ref2
    # only the prefix-cached prompt pages outlive the requests (6-token
    # prompts @ ps=4 -> one full page each, shared with nobody)
    assert eng.pool.in_use == eng.prefix.pages


# ---------------------------------------------------------------------------
# Prefix caching: refcounted sharing + COW through the engine (ISSUE-6)
# ---------------------------------------------------------------------------


def test_copy_pages_copies_every_page_leaf(rng):
    """lm.copy_pages duplicates physical pages across every ``*_pages``
    leaf (GQA k/v pages, MLA latent + rope pages) and leaves all other
    pages untouched — the device half of copy-on-write."""
    import jax.numpy as jnp

    for name in ("qwen2_1_5b", "deepseek_v2_lite_16b"):
        cfg = get_config(name).reduced()
        cache = lm.init_cache(cfg, 1, 16, layout="paged", page_size=4,
                              num_blocks=6)

        def fill(leaf):
            vals = np.arange(leaf.size, dtype=np.float32) % 251
            return jnp.asarray(vals.reshape(leaf.shape), leaf.dtype)

        cache = lm.Cache(
            jax.tree_util.tree_map(fill, cache.prefix),
            jax.tree_util.tree_map(fill, cache.rest),
            cache.stacked, cache.max_len, cache.layout, cache.page_size,
            cache.tables,
        )
        out = lm.copy_pages(cache, [1, 2], [4, 5])

        def check(path, before, after):
            names = [
                str(p.key) for p in path
                if isinstance(p, jax.tree_util.DictKey)
            ]
            b = np.asarray(jnp.moveaxis(before, before.ndim - 3, 0))
            a = np.asarray(jnp.moveaxis(after, after.ndim - 3, 0))
            if any(n.endswith("_pages") for n in names):
                np.testing.assert_array_equal(a[4], b[1])
                np.testing.assert_array_equal(a[5], b[2])
                np.testing.assert_array_equal(a[3], b[3])  # bystander
            else:
                np.testing.assert_array_equal(a, b)  # non-page leaves

        jax.tree_util.tree_map_with_path(check, cache.prefix, out.prefix)
        jax.tree_util.tree_map_with_path(check, cache.rest, out.rest)


class TestPrefixCaching:
    """Engine-level prefix caching: cache-hit chunks never dispatch, shared
    pages are refcounted, divergence goes through copy-on-write, and every
    mode stays byte-identical to a caching-disabled run."""

    def _shared_prompts(self, cfg, rng, prefix_len=12, tails=(7, 3, 10, 1)):
        shared = rng.integers(0, cfg.vocab_size, size=prefix_len).tolist()
        return [
            shared + rng.integers(0, cfg.vocab_size, size=t).tolist()
            for t in tails
        ]

    def test_warm_prefix_ttft_collapses_to_one_chunk(self, rng):
        """The tentpole number: a warm shared prefix skips its cached pages
        entirely at admission, so TTFT falls from ceil(prompt/chunk) ticks
        to ~one chunk's worth for the divergent tail."""
        cfg = _qwen()
        params = _params(cfg)
        prompt = rng.integers(0, cfg.vocab_size, size=20).tolist()
        eng = ServingEngine(cfg, params, ServeConfig(
            slots=1, max_len=48, max_new_tokens=3, page_size=4,
            prefill_chunk=4, token_budget=5))
        r_cold = eng.submit(prompt)
        r_warm = eng.submit(prompt)  # slots=1: strictly after r_cold
        eng.run()
        assert r_cold.output == r_warm.output
        assert r_cold.cached_tokens == 0
        # 20-token prompt, 4-token chunks: cold prefill takes 5 ticks
        assert r_cold.ttft_admit_ticks == 5
        # warm: 4 of 5 pages cached (the last is held back so one replay
        # token remains); the 4-token tail is exactly one chunk
        assert r_warm.cached_tokens == 16
        assert r_warm.ttft_admit_ticks == 1
        assert eng.pages_shared == 4
        assert eng.prefix.hits >= 1

    def test_byte_identity_against_caching_disabled(self, rng):
        """Acceptance matrix: shared-prefix traffic produces byte-identical
        tokens with the prefix cache on vs off, across chunked and replay
        prefill."""
        cfg = _qwen()
        params = _params(cfg)
        prompts = self._shared_prompts(cfg, rng)
        for prefill in ("chunked", "replay"):
            base = dict(slots=2, max_len=48, max_new_tokens=4, page_size=4,
                        prefill=prefill)
            ref, _, off = _run_engine(cfg, params, prompts,
                                      prefix_cache=False, **base)
            out, reqs, on = _run_engine(cfg, params, prompts, **base)
            assert out == ref, f"{prefill}: caching changed tokens"
            assert on.pages_shared > 0  # sharing actually engaged
            assert off.pages_shared == 0
            # warm requests hold fewer fresh pages than the no-share path
            assert on.pool.peak_in_use < off.pool.peak_in_use + \
                on.prefix.pages

    def test_multistep_window_with_prefix_cache(self, rng):
        """sync_every > 1 over shared-prefix traffic: the device-resident
        window composes with attached cache pages, byte-identically."""
        cfg = _qwen()
        params = _params(cfg)
        prompts = self._shared_prompts(cfg, rng)
        base = dict(slots=2, max_len=48, max_new_tokens=6, page_size=4)
        ref, _, _ = _run_engine(cfg, params, prompts, prefix_cache=False,
                                **base)
        out, _, eng = _run_engine(cfg, params, prompts, sync_every=4, **base)
        assert out == ref
        assert eng.decode_windows > 0 and eng.pages_shared > 0

    def test_preemption_with_shared_pages_lossless(self, rng):
        """Mid-generation preemption while prefix pages are shared: the
        victim's references drop without disturbing the survivor or the
        index, and recompute resume (which re-matches the cache) stays
        byte-identical to isolated runs."""
        cfg = _qwen()
        params = _params(cfg)
        # shared first page, divergent second page: the shared page stays
        # pinned (refcount > 1) so eviction cannot relieve the pressure and
        # the scheduler must preempt the younger request mid-generation
        head = rng.integers(0, cfg.vocab_size, size=4).tolist()
        prompts = [head + rng.integers(0, cfg.vocab_size, size=4).tolist()
                   for _ in range(2)]
        refs = [_run_engine(cfg, params, [p], slots=1, max_len=16,
                            max_new_tokens=6, page_size=4)[0][0]
                for p in prompts]
        out, reqs, eng = _run_engine(
            cfg, params, prompts, slots=2, max_len=16,
            max_new_tokens=6, page_size=4, num_blocks=5)
        assert eng.preemptions >= 1
        assert reqs[1].preemptions >= 1
        assert out == refs
        assert eng.pages_shared > 0

    def test_cow_on_divergent_write_chunked(self, rng):
        """A write landing in a genuinely shared page triggers exactly one
        copy-on-write — fresh page, device copy, repoint — with outputs
        byte-identical to an unshared run.  (The scheduler's page-aligned
        sharing never produces this naturally, so the test constructs the
        alias directly.)"""
        cfg = _qwen()
        params = _params(cfg)
        prompt = rng.integers(0, cfg.vocab_size, size=6).tolist()
        ref, _, _ = _run_engine(cfg, params, [prompt], slots=1, max_len=32,
                                max_new_tokens=4, page_size=4)
        eng = ServingEngine(cfg, params, ServeConfig(
            slots=2, max_len=32, max_new_tokens=4, page_size=4,
            prefix_cache=False))
        r1, r2 = eng.submit(prompt), eng.submit(prompt)
        eng._admit()  # both resident, nothing dispatched yet
        # alias slot 1's first page onto slot 0's: the first prefill write
        # into it must now copy
        eng.tables.repoint(1, 0, eng.tables.blocks(0)[0])
        eng._tables_dirty = True
        eng.run()
        assert eng.pages_copied == 1
        assert r1.output == ref[0] and r2.output == ref[0]
        assert eng.pool.in_use == 0  # the COW copy was released too

    def test_cow_on_divergent_write_multistep(self, rng):
        """COW under the sync_every>1 decode window: a page shared
        mid-generation is copied before the on-device loop dispatches."""
        cfg = _qwen()
        params = _params(cfg)
        prompt = rng.integers(0, cfg.vocab_size, size=6).tolist()
        ref, _, _ = _run_engine(cfg, params, [prompt], slots=1, max_len=32,
                                max_new_tokens=6, page_size=4)
        eng = ServingEngine(cfg, params, ServeConfig(
            slots=2, max_len=32, max_new_tokens=6, page_size=4,
            sync_every=4, prefix_cache=False))
        r1, r2 = eng.submit(prompt), eng.submit(prompt)
        eng.step()  # prefill tick: both slots transition to gen
        assert all(st == "gen" for st in eng.slot_state)
        # identical prompts -> identical KV: alias slot 1's live tail page
        # onto slot 0's (content-preserving), forcing COW at the next write
        eng.tables.repoint(1, 1, eng.tables.blocks(0)[1])
        eng._tables_dirty = True
        eng.run()
        assert eng.pages_copied >= 1
        assert eng.decode_windows > 0
        assert r1.output == ref[0] and r2.output == ref[0]

    def test_pool_pressure_evicts_cold_cache_pages(self, rng):
        """Graceful degradation: when fresh requests need blocks the cold
        cached pages hold, eviction reclaims them (LRU) instead of refusing
        admission — the hot pool serves like an uncached engine."""
        cfg = _qwen()
        params = _params(cfg)
        prompts = [rng.integers(0, cfg.vocab_size, size=8).tolist()
                   for _ in range(3)]
        out, reqs, eng = _run_engine(
            cfg, params, prompts, slots=1, max_len=16, max_new_tokens=2,
            page_size=4, num_blocks=4)
        assert all(r.error is None for r in reqs)
        assert [len(o) for o in out] == [2, 2, 2]
        assert eng.prefix.evictions >= 1  # cold pages made room
        assert eng.pool.in_use == eng.prefix.pages

    def test_contiguous_and_recurrent_archs_skip_the_index(self):
        cfg = _qwen()
        eng = ServingEngine(cfg, _params(cfg), ServeConfig(
            slots=1, max_len=16, cache="contiguous"))
        assert eng.prefix is None
        cfg2 = get_config("mamba2_2_7b").reduced()
        eng2 = ServingEngine(cfg2, _params(cfg2), ServeConfig(
            slots=1, max_len=16, cache="contiguous"))
        assert eng2.prefix is None


# ---------------------------------------------------------------------------
# paged kernels vs their pure-JAX oracles
# ---------------------------------------------------------------------------


def test_mla_paged_kernel_matches_oracle(rng):
    from repro.core import Schedule, compile as tl_compile
    from repro.kernels import ref
    from repro.kernels.mla import (
        PARITY_CASES,
        mla_paged_program,
        parity_inputs,
    )

    for name, cfg in PARITY_CASES:
        if not name.startswith("mla_paged") or "quant" in name:
            continue
        r, pe = cfg["dim"], cfg["pe_dim"]
        prog = mla_paged_program(**cfg)
        kern = tl_compile(prog, Schedule(interpret=True), target="pallas")
        tbl, lens, q, pages = parity_inputs(name, prog, rng)
        out = np.asarray(kern(tbl, lens, q, pages))[..., :r]
        live = lens > 0  # the oracle's softmax over no key is nan
        oracle = np.asarray(
            ref.mla_paged(q[..., :r], q[..., r : r + pe], pages, tbl, lens,
                          window=cfg.get("window"))
        )
        np.testing.assert_allclose(out[live], oracle[live], rtol=1e-4, atol=2e-3)
        assert not out[~live].any()


def test_mla_soft_cap_routes_to_oracle(rng):
    """Soft-capped MLA decode takes the oracle path (same policy as GQA
    paged_attention) and the cap visibly changes the scores."""
    from repro.kernels import ops, ref
    from repro.kernels.mla import PARITY_CASES, parity_inputs, mla_paged_program

    cfg = dict(PARITY_CASES)["mla_paged"]
    r, pe = cfg["dim"], cfg["pe_dim"]
    prog = mla_paged_program(**cfg)
    tbl, lens, q, pages = parity_inputs("mla_paged", prog, rng)
    qlat, qpe = q[..., :r], q[..., r : r + pe]
    capped = ops.mla_paged(qlat, qpe, pages, tbl, lens,
                           logit_soft_cap=1.0, backend="pallas")
    oracle = ref.mla_paged(qlat, qpe, pages, tbl, lens, logit_soft_cap=1.0)
    np.testing.assert_allclose(np.asarray(capped), np.asarray(oracle),
                               rtol=1e-5, atol=1e-6)
    uncapped = ref.mla_paged(qlat, qpe, pages, tbl, lens)
    assert not np.allclose(np.asarray(capped), np.asarray(uncapped), atol=1e-4)


def test_paged_attention_kernel_matches_oracle(rng):
    from repro.core import Schedule, compile as tl_compile
    from repro.kernels import ref
    from repro.kernels.paged_attention import (
        PARITY_CASES,
        paged_attention_program,
        parity_inputs,
    )

    for name, cfg in PARITY_CASES:
        if "quant" in name:
            continue
        prog = paged_attention_program(**cfg)
        kern = tl_compile(prog, Schedule(interpret=True), target="pallas")
        tbl, lens, q, kp, vp = parity_inputs(name, prog, rng)
        # the kernel packs Q/Output as (slots, kv_heads, group, d); the
        # oracle takes (slots, heads, d)
        out = np.asarray(kern(tbl, lens, q, kp, vp)).reshape(q.shape[0], -1, q.shape[-1])
        oracle = np.asarray(
            ref.paged_attention(q.reshape(q.shape[0], -1, q.shape[-1]), kp, vp,
                                tbl, lens, window=cfg.get("window"))
        )
        np.testing.assert_allclose(out, oracle, rtol=1e-4, atol=2e-3)


# ---------------------------------------------------------------------------
# Quantized KV cache (ISSUE-7): int8/int4 page pools behind the same engine
# ---------------------------------------------------------------------------


class TestQuantizedKV:
    """The quantized page pools are a storage-format swap, not a scheduler
    change: admission, sharing, COW and the multi-step loop all run
    unchanged over packed ``*_pages`` + fp ``*_scale_pages`` leaves, while
    ``kv_bytes`` shrinks by the pack factor (plus the scale column)."""

    def _run(self, cfg, params, prompts, **kw):
        kw.setdefault("slots", 2)
        kw.setdefault("max_len", 64)
        kw.setdefault("max_new_tokens", 6)
        kw.setdefault("page_size", 8)
        return _run_engine(cfg, params, prompts, **kw)

    def test_int8_outputs_and_bytes(self, rng):
        """At the reduced config int8 holds greedy decode token-for-token
        while the cache drops below 0.55x of the fp footprint (ISSUE-7
        acceptance: <= 0.55x for int8)."""
        cfg = _qwen()
        params = _params(cfg)
        prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
                   for n in (13, 7, 19)]
        out_fp, _, eng_fp = self._run(cfg, params, prompts)
        out_q, _, eng_q = self._run(cfg, params, prompts, kv_dtype="int8")
        assert out_q == out_fp
        ratio = eng_q.cache.kv_bytes() / eng_fp.cache.kv_bytes()
        assert ratio <= 0.55
        # the scale pools ride along as *_pages leaves (COW-visible)
        kv = (eng_q.cache.rest["kv"] if eng_q.cache.stacked
              else eng_q.cache.rest[0]["kv"])
        assert sorted(kv.keys()) == [
            "k_pages", "k_scale_pages", "v_pages", "v_scale_pages"]
        assert str(kv["k_pages"].dtype) == "int8"

    def test_int4_bytes_ratio(self, rng):
        """int4 packs two values per byte: cache <= 0.30x fp (ISSUE-7
        acceptance) and the engine still serves to completion."""
        cfg = _qwen()
        params = _params(cfg)
        prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
                   for n in (9, 14)]
        out_fp, _, eng_fp = self._run(cfg, params, prompts)
        out_q, reqs, eng_q = self._run(cfg, params, prompts, kv_dtype="int4")
        assert all(len(o) == 6 for o in out_q)
        assert eng_q.cache.kv_bytes() / eng_fp.cache.kv_bytes() <= 0.30

    def test_fp_cache_shape_unchanged(self):
        """kv_dtype=None is byte-identical to before: no scale leaves, pool
        dtype = cfg.dtype (quantization is strictly opt-in)."""
        cfg = _qwen()
        eng = ServingEngine(cfg, _params(cfg), ServeConfig(
            slots=1, max_len=16, max_new_tokens=1))
        kv = (eng.cache.rest["kv"] if eng.cache.stacked
              else eng.cache.rest[0]["kv"])
        assert sorted(kv.keys()) == ["k_pages", "v_pages"]
        assert str(kv["k_pages"].dtype) == cfg.dtype

    def test_prefix_sharing_and_cow_on_quant_pages(self, rng):
        """Refcounted sharing + copy-on-write work on quantized pools: the
        scale pools are ``*_pages`` leaves, so ``lm.copy_pages`` duplicates
        packed bytes and scales together and a COW'd slot keeps decoding
        the same tokens as the fp engine."""
        cfg = _qwen()
        params = _params(cfg)
        shared = rng.integers(0, cfg.vocab_size, size=32).tolist()  # 4 pages
        prompts = [shared + [100 + i] for i in range(3)]
        out_fp, _, eng_fp = self._run(cfg, params, prompts, sync_every=4)
        out_q, reqs, eng_q = self._run(cfg, params, prompts, kv_dtype="int8",
                                       sync_every=4)
        assert out_q == out_fp
        assert eng_q.pages_shared > 0
        # force a COW mid-generation (same idiom as the fp COW tests):
        # identical prompts -> identical quantized KV, alias a live page
        prompt = rng.integers(0, cfg.vocab_size, size=6).tolist()
        ref_out, _, _ = self._run(cfg, params, [prompt], slots=1,
                                  kv_dtype="int8")
        eng = ServingEngine(cfg, params, ServeConfig(
            slots=2, max_len=32, max_new_tokens=6, page_size=4,
            prefix_cache=False, kv_dtype="int8"))
        r1, r2 = eng.submit(prompt), eng.submit(prompt)
        eng.step()  # prefill tick: both slots to gen
        eng.tables.repoint(1, 1, eng.tables.blocks(0)[1])
        eng._tables_dirty = True
        eng.run()
        assert eng.pages_copied >= 1
        assert r1.output == ref_out[0] and r2.output == ref_out[0]

    def test_mla_int8_matches_fp(self, rng):
        """The MLA latent pools quantize through the same composition point
        (latent + rope pages each carry their own scales)."""
        cfg = get_config("deepseek_v2_lite_16b").reduced()
        params = _params(cfg)
        prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
                   for n in (11, 6)]
        out_fp, _, eng_fp = self._run(cfg, params, prompts)
        out_q, _, eng_q = self._run(cfg, params, prompts, kv_dtype="int8")
        assert out_q == out_fp
        assert eng_q.cache.kv_bytes() < eng_fp.cache.kv_bytes()

    def test_contiguous_cache_rejects_kv_dtype(self):
        """No silent downgrade: the contiguous strips store fp only."""
        cfg = _qwen()
        with pytest.raises(ValueError, match="paged"):
            ServingEngine(cfg, _params(cfg), ServeConfig(
                slots=1, max_len=16, max_new_tokens=1, cache="contiguous",
                kv_dtype="int8"))

    def test_page_bytes_and_budget_sizing(self):
        """``BlockPool.page_bytes`` reflects the storage format; at a fixed
        byte budget the quantized pool affords strictly more pages
        (``blocks_for_bytes``) — the capacity win the pressure bench
        measures as fewer preemptions."""
        from repro.serving.paged_cache import blocks_for_bytes
        cfg = _qwen()
        params = _params(cfg)
        mk = lambda kv: ServingEngine(cfg, params, ServeConfig(
            slots=1, max_len=32, max_new_tokens=1, page_size=8, kv_dtype=kv))
        fp, q8 = mk(None), mk("int8")
        assert q8.pool.page_bytes < fp.pool.page_bytes
        budget = 64 * fp.pool.page_bytes
        assert blocks_for_bytes(budget, q8.pool.page_bytes) > \
            blocks_for_bytes(budget, fp.pool.page_bytes) == 64
        with pytest.raises(ValueError):
            blocks_for_bytes(budget, 0)


# ---------------------------------------------------------------------------
# ServeConfig construction validation (fail loud, not mid-serve)
# ---------------------------------------------------------------------------


class TestServeConfigValidation:
    OK = dict(slots=2, max_len=32, max_new_tokens=4)

    def test_defaults_construct(self):
        ServeConfig(**self.OK)  # the happy path stays happy

    @pytest.mark.parametrize("field", [
        "slots", "max_len", "max_new_tokens", "page_size", "prefill_chunk",
        "num_blocks", "draft_len",
    ])
    @pytest.mark.parametrize("bad", [0, -3])
    def test_nonpositive_sizes_rejected(self, field, bad):
        with pytest.raises(ValueError, match=field):
            ServeConfig(**{**self.OK, field: bad})

    def test_budget_below_slots_rejected(self):
        with pytest.raises(ValueError, match="token_budget"):
            ServeConfig(slots=4, max_len=32, max_new_tokens=2,
                        token_budget=3)

    def test_unknown_kv_dtype_rejected(self):
        with pytest.raises(ValueError, match="kv_dtype"):
            ServeConfig(**self.OK, kv_dtype="fp8")

    def test_unknown_cache_and_prefill_rejected(self):
        with pytest.raises(ValueError, match="cache"):
            ServeConfig(**self.OK, cache="unified")
        with pytest.raises(ValueError, match="prefill"):
            ServeConfig(**self.OK, prefill="speculative")

    def test_negative_backoff_rejected(self):
        with pytest.raises(ValueError, match="retry_backoff"):
            ServeConfig(**self.OK, retry_backoff=-1)


def test_int8_prefix_shared_preemption_resumes_exactly(rng):
    """A request holding prefix-shared *quantized* pages is preempted under
    pool pressure and resumes by recompute: the shared int8 page stays
    pinned in the index (refcount intact), the resumed replay re-attaches
    it, and the tokens match isolated single-slot int8 runs bit-for-bit —
    sharing + COW bookkeeping is format-agnostic."""
    cfg = _qwen()
    params = _params(cfg)
    head = rng.integers(0, cfg.vocab_size, size=4).tolist()
    prompts = [head + rng.integers(0, cfg.vocab_size, size=4).tolist()
               for _ in range(2)]
    base = dict(max_len=16, max_new_tokens=6, page_size=4, kv_dtype="int8")
    refs = [_run_engine(cfg, params, [p], slots=1, **base)[0][0]
            for p in prompts]
    out, reqs, eng = _run_engine(cfg, params, prompts, slots=2,
                                 num_blocks=5, audit=True, **base)
    assert eng.preemptions >= 1 and reqs[1].preemptions >= 1
    assert out == refs  # recompute resume over quantized pages is lossless
    assert eng.pages_shared > 0
    assert eng.pool.in_use == eng.prefix.pages  # only the index holds pages


# ---------------------------------------------------------------------------
# Speculative decoding (ISSUE-10): draft-verify inside the multi-step window
# ---------------------------------------------------------------------------


def _spec_mode_base(mode):
    """(cfg_name, extra ServeConfig kwargs) for the byte-identity matrix."""
    return {
        "gqa_paged": ("qwen2_1_5b", {}),
        "mla": ("deepseek_v2_lite_16b", {}),
        "int8_kv": ("qwen2_1_5b", {"kv_dtype": "int8"}),
    }[mode]


class TestSpeculativeDecode:
    """Speculative decoding is an *optimization*, never a behavior change:
    greedy verify emits only tokens that are the model's own argmax after a
    committed prefix, so every test drives the same requests through the
    plain per-tick engine and the draft-verify window and asserts
    byte-identical outputs."""

    BASE = dict(slots=2, max_len=64, max_new_tokens=6, page_size=4,
                temperature=0.0)

    _REF_CACHE: dict = {}

    def _ref(self, mode, cfg, params, prompts):
        key = mode
        if key not in self._REF_CACHE:
            name, extra = _spec_mode_base(mode)
            self._REF_CACHE[key] = _run_engine(
                cfg, params, prompts, **self.BASE, **extra)
        return self._REF_CACHE[key]

    def _setup(self, mode, rng):
        name, extra = _spec_mode_base(mode)
        cfg = get_config(name).reduced()
        params = _params(cfg)
        prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
                   for n in (5, 7, 3, 6)]
        return cfg, params, prompts, extra

    @pytest.mark.parametrize("mode", ["gqa_paged", "mla", "int8_kv"])
    @pytest.mark.parametrize("draft", [1, 2, 4])
    @pytest.mark.parametrize("sync", [1, 4])
    def test_greedy_byte_identity(self, mode, draft, sync, rng):
        cfg, params, prompts, extra = self._setup(mode, rng)
        ref, _, _ = self._ref(mode, cfg, params, prompts)
        out, _, eng = _run_engine(
            cfg, params, prompts, sync_every=sync, spec_decode="ngram",
            draft_len=draft, audit=True, **self.BASE, **extra)
        assert out == ref
        assert eng.spec_windows > 0  # the draft-verify loop actually engaged
        assert eng.pool.in_use == eng.prefix.pages  # rollback leaked nothing

    def test_composes_with_sync_every_fewer_dispatches(self, rng):
        """The acceptance-criterion shape at unit scale: on a self-similar
        prompt the n-gram proposer lands drafts, so the spec engine spends
        strictly fewer host dispatches than the sync-matched plain engine
        for the same (byte-identical) output."""
        cfg = _qwen()
        params = _params(cfg)
        motif = rng.integers(0, cfg.vocab_size, size=4).tolist()
        prompts = [motif * 3 for _ in range(2)]
        base = dict(slots=2, max_len=96, max_new_tokens=16, page_size=4,
                    temperature=0.0, sync_every=4, prefix_cache=False)
        ref, _, ref_eng = _run_engine(cfg, params, prompts, **base)
        out, _, eng = _run_engine(cfg, params, prompts, spec_decode="ngram",
                                  draft_len=4, **base)
        assert out == ref
        assert eng.spec_accepted > 0
        assert eng.dispatches < ref_eng.dispatches
        assert eng.pool.in_use == 0

    def test_eos_mid_window(self, rng):
        """A verified EOS must stop the stream inside the round: later
        targets of the same round (and all later rounds) are discarded by
        the on-device emit mask, exactly like plain decode stopping at
        EOS."""
        cfg = _qwen()
        params = _params(cfg)
        prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
                   for n in (5, 7, 3, 6)]
        base = dict(slots=2, max_len=64, max_new_tokens=8, page_size=4,
                    temperature=0.0)
        free, _, _ = _run_engine(cfg, params, prompts, **base)
        eos = free[0][2]  # a token the greedy model actually emits mid-stream
        ref, _, _ = _run_engine(cfg, params, prompts, eos_id=eos, **base)
        out, _, eng = _run_engine(cfg, params, prompts, eos_id=eos,
                                  sync_every=4, spec_decode="ngram",
                                  draft_len=4, **base)
        assert out == ref
        assert eng.spec_windows > 0
        assert any(len(o) < 8 for o in out)  # EOS genuinely cut a stream

    def test_all_rejected_rounds(self, rng):
        """A proposer that drafts garbage must cost speed only: every round
        still emits the model's own next token (the bonus position), so the
        output is byte-identical even when acceptance is zero."""
        import jax.numpy as jnp
        bad_name = "_test_pessimal"

        def pessimal(history, pos, feed, draft_len):
            # shift every draft off the feed token: near-certain mismatch
            k = jnp.arange(draft_len, dtype=jnp.int32)[None, :]
            return (jnp.asarray(feed, jnp.int32)[:, None] + 17 + k) % 101

        lm.DRAFT_PROPOSERS[bad_name] = pessimal
        try:
            cfg = _qwen()
            params = _params(cfg)
            prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
                       for n in (5, 3)]
            base = dict(slots=2, max_len=64, max_new_tokens=6, page_size=4,
                        temperature=0.0)
            ref, _, _ = _run_engine(cfg, params, prompts, **base)
            out, _, eng = _run_engine(cfg, params, prompts, sync_every=4,
                                      spec_decode=bad_name, draft_len=4,
                                      audit=True, **base)
        finally:
            del lm.DRAFT_PROPOSERS[bad_name]
        assert out == ref
        assert eng.spec_all_rejected > 0  # whole rounds accepted zero drafts
        # progress is still >= 1 token per live round: the loop never stalls
        assert all(len(o) == 6 for o in out)

    def test_preemption_resume_with_uncommitted_drafts(self, rng):
        """Pool pressure mid-draft-window: the victim's uncommitted draft
        tail lives only in pages behind the position carry, so recompute
        resume (which replays prompt + *committed* output) is lossless."""
        cfg = _qwen()
        params = _params(cfg)
        prompt1 = rng.integers(0, cfg.vocab_size, size=6).tolist()
        prompt2 = rng.integers(0, cfg.vocab_size, size=6).tolist()
        solo = dict(slots=1, max_len=16, max_new_tokens=6, page_size=4,
                    temperature=0.0)
        ref1, _, _ = _run_engine(cfg, params, [prompt1], **solo)
        ref2, _, _ = _run_engine(cfg, params, [prompt2], **solo)
        # pool of 4 blocks: both admit at 2 blocks, both need a 3rd
        # mid-generation -> forced preemption while drafts are in flight
        out, reqs, eng = _run_engine(
            cfg, params, [prompt1, prompt2], slots=2, max_len=16,
            max_new_tokens=6, page_size=4, num_blocks=4, sync_every=4,
            spec_decode="ngram", draft_len=4, prefix_cache=False,
            temperature=0.0)
        assert eng.preemptions >= 1
        assert out == [ref1[0], ref2[0]]  # recompute resume is lossless
        assert eng.pool.in_use == 0

    def test_temperature_stream_independent_of_acceptance(self, rng):
        """The key-stream determinism rule: a gated round always splits the
        key draft_len + 2 ways regardless of acceptance length, so one
        slot's token stream cannot depend on another slot's drafts.  Same
        seed, slot B's prompt fixed, slot A's prompt varied (same length,
        so prefill ticks match): B's output must not move."""
        cfg = _qwen()
        params = _params(cfg)
        pa1 = rng.integers(0, cfg.vocab_size, size=6).tolist()
        pa2 = rng.integers(0, cfg.vocab_size, size=6).tolist()
        pb = rng.integers(0, cfg.vocab_size, size=6).tolist()
        base = dict(slots=2, max_len=64, max_new_tokens=12, page_size=4,
                    temperature=0.8, seed=7, sync_every=4,
                    spec_decode="ngram", draft_len=3)
        out1, _, _ = _run_engine(cfg, params, [pa1, pb], **base)
        out2, _, _ = _run_engine(cfg, params, [pa2, pb], **base)
        assert out1[0] != out2[0]  # slot A genuinely diverged
        assert out1[1] == out2[1]  # slot B's stream never moved

    def test_temperature_runs_are_reproducible(self, rng):
        cfg = _qwen()
        params = _params(cfg)
        prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
                   for n in (5, 3)]
        base = dict(slots=2, max_len=64, max_new_tokens=8, page_size=4,
                    temperature=0.8, seed=3, sync_every=4,
                    spec_decode="ngram", draft_len=4)
        out1, _, eng1 = _run_engine(cfg, params, prompts, **base)
        out2, _, eng2 = _run_engine(cfg, params, prompts, **base)
        assert out1 == out2
        assert np.array_equal(np.asarray(eng1._key), np.asarray(eng2._key))

    def test_greedy_never_splits_key(self, rng):
        cfg = _qwen()
        eng = ServingEngine(cfg, _params(cfg), ServeConfig(
            slots=2, max_len=32, max_new_tokens=4, seed=7, page_size=4,
            spec_decode="ngram", draft_len=2))
        before = np.asarray(eng._key).copy()
        for n in (5, 3):
            eng.submit(rng.integers(0, cfg.vocab_size, size=n).tolist())
        eng.run()
        assert eng.spec_windows > 0
        assert np.array_equal(np.asarray(eng._key), before)

    def test_spec_requires_chunked_prefill_arch(self, rng):
        """The verify pass *is* chunked prefill, so an arch that cannot
        chunk-prefill (recurrent state) fails loudly at engine init."""
        cfg = get_config("mamba2_2_7b").reduced()
        with pytest.raises(ValueError, match="spec_decode"):
            ServingEngine(cfg, _params(cfg), ServeConfig(
                slots=1, max_len=16, max_new_tokens=2, cache="contiguous",
                spec_decode="ngram"))

    def test_unknown_proposer_rejected(self):
        with pytest.raises(ValueError, match="spec_decode"):
            ServeConfig(slots=2, max_len=32, max_new_tokens=4,
                        spec_decode="crystal_ball")


class TestNgramProposer:
    """The draft proposer in isolation: pure function of the history."""

    def test_bigram_match_preferred_and_most_recent(self):
        import jax.numpy as jnp
        hist = np.zeros((1, 16), np.int32)
        # ... 5 6 7 ... 5 6 9 ... cursor after a fresh (5, 6) bigram
        hist[0, :9] = [1, 5, 6, 7, 2, 5, 6, 9, 5]
        drafts = np.asarray(lm.ngram_propose(
            jnp.asarray(hist), jnp.asarray([9]), jnp.asarray([6]), 2))
        # most recent earlier (5,6) is at j=6 -> propose history[7:9] = 9, 5
        assert drafts.tolist() == [[9, 5]]

    def test_unigram_fallback(self):
        import jax.numpy as jnp
        hist = np.zeros((1, 16), np.int32)
        hist[0, :5] = [3, 8, 4, 2, 8]  # feed 8, prev 2: bigram (2,8) unseen
        drafts = np.asarray(lm.ngram_propose(
            jnp.asarray(hist), jnp.asarray([4]), jnp.asarray([8]), 2))
        # unigram 8 at j=1 -> propose history[2:4] = 4, 2
        assert drafts.tolist() == [[4, 2]]

    def test_no_match_repeats_feed(self):
        import jax.numpy as jnp
        hist = np.zeros((2, 8), np.int32)
        hist[0, :3] = [1, 2, 3]
        hist[1, :1] = [9]
        drafts = np.asarray(lm.ngram_propose(
            jnp.asarray(hist), jnp.asarray([2, 0]), jnp.asarray([3, 9]), 3))
        assert drafts.tolist() == [[3, 3, 3], [9, 9, 9]]

    def test_match_near_cursor_truncates_to_feed(self):
        import jax.numpy as jnp
        hist = np.zeros((1, 8), np.int32)
        hist[0, :4] = [5, 6, 5, 6]  # bigram (5,6) at j=1; only j=2..3 known
        drafts = np.asarray(lm.ngram_propose(
            jnp.asarray(hist), jnp.asarray([3]), jnp.asarray([6]), 4))
        # history[2:4] = 5, 6 then past the cursor -> repeat feed
        assert drafts.tolist() == [[5, 6, 6, 6]]
