"""The serving engine's dispatch log and profiler spans
(``repro.serving.telemetry``): what each step ran, for whom, and when each
host phase began and ended."""
import collections
import dataclasses
import functools
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.serving import ServeConfig, ServingEngine, telemetry
from repro.serving import engine as E

PHASES = ("upload", "dispatch", "wait", "readback", "drain")


def _qwen():
    return get_config("qwen2_1_5b").reduced()


def _engine(**kw):
    cfg = _qwen()
    scfg = dict(slots=4, max_len=96, page_size=8, prefill_chunk=16, max_new_tokens=12)
    scfg.update(kw)
    return ServingEngine(cfg, lm.init(cfg, jax.random.PRNGKey(0)), ServeConfig(**scfg))


def _submit(eng, rng, n, lo=5, hi=40, max_new=None):
    for _ in range(n):
        prompt = rng.integers(0, eng.cfg.vocab_size, size=int(rng.integers(lo, hi))).tolist()
        eng.submit(prompt, max_new_tokens=max_new)


def _records(t0):
    recs = telemetry.window(t0, telemetry.clock())
    assert recs is not None
    steps = [r for r in recs if isinstance(r, telemetry.Step)]
    runs = [r for r in recs if isinstance(r, telemetry.Dispatch)]
    return steps, runs


@pytest.mark.parametrize("sync_every", [1, 8])
def test_phases_nest_inside_their_step(sync_every, rng):
    eng = _engine(sync_every=sync_every)
    _submit(eng, rng, 6)
    t0 = telemetry.clock()
    eng.run()
    steps, runs = _records(t0)
    assert len(steps) >= eng.dispatches and runs
    for st in steps:
        inside = [r for r in runs if st.t0 <= r.dispatch[0] <= st.t1]
        total = 0.0
        for r in inside:
            spans = [getattr(r, p) for p in PHASES]
            for a, b in spans:
                assert st.t0 <= a <= b <= st.t1
            # each phase begins where the one before it ended, or later
            for (_, b), (a, _) in zip(spans, spans[1:]):
                assert b <= a
            total += sum(b - a for a, b in spans)
        assert total <= st.t1 - st.t0
    # every dispatch lies inside some step
    assert all(any(st.t0 <= r.dispatch[0] <= st.t1 for st in steps) for r in runs)


def test_chunked_step_logs_one_record_per_program(rng):
    eng = _engine(slots=2)
    eng.submit(rng.integers(0, eng.cfg.vocab_size, size=6).tolist(), max_new_tokens=8)
    eng.step()  # prefill of the first request: it emits its first token
    assert eng.slot_state[0] == "gen"
    uid = eng.submit(rng.integers(0, eng.cfg.vocab_size, size=40).tolist()).uid
    t0 = telemetry.clock()
    eng.step()  # the first decodes while the second prefills its first chunk
    steps, runs = _records(t0)
    assert len(steps) == 1
    assert [r.program for r in runs] == ["decode_step", "prefill_step"]
    decode, prefill = runs
    assert decode.uids == (eng.slot_req[0].uid,) and decode.ticks == 1
    assert prefill.uids == (uid,) and prefill.ticks == 1
    assert prefill.rows == 2 * eng.prefill_chunk
    assert prefill.live_rows == eng.prefill_chunk


def test_live_rows_are_the_granted_chunks(monkeypatch, rng):
    plan = E.plan_prefill_chunks
    grants = []

    def spy(*args):
        out = plan(*args)
        if out:
            grants.append(sum(out.values()))
        return out

    monkeypatch.setattr(E, "plan_prefill_chunks", spy)
    eng = _engine(slots=3)
    _submit(eng, rng, 7, lo=3, hi=50)
    t0 = telemetry.clock()
    eng.run()
    t1 = telemetry.clock()
    recs = telemetry.window(t0, t1)
    runs = [r for r in recs if isinstance(r, telemetry.Dispatch)]
    prefill = [r for r in runs if r.program == "prefill_step"]
    assert [r.live_rows for r in prefill] == grants
    assert all(r.rows == 3 * eng.prefill_chunk for r in prefill)
    assert not any(r.rows or r.live_rows for r in runs if r.program != "prefill_step")
    s = telemetry.summary(recs, t0, t1)
    assert (s["prefill_live_rows"], s["prefill_rows"]) == (sum(grants), 3 * eng.prefill_chunk * len(grants))


def test_window_records_carry_their_length(rng):
    eng = _engine(sync_every=8, max_new_tokens=30)
    _submit(eng, rng, 4, lo=5, hi=12)
    t0 = telemetry.clock()
    eng.run()
    _, runs = _records(t0)
    windows = [r for r in runs if r.program.startswith("decode_window_")]
    assert len(windows) == eng.decode_windows
    assert {r.ticks for r in windows} >= {8}
    for r in windows:
        assert r.program == f"decode_window_{r.ticks}"
        assert r.ticks in (1, 2, 4, 8)
        assert r.uids and len(set(r.uids)) == len(r.uids)


def test_spec_window_records(rng):
    eng = _engine(sync_every=4, spec_decode="ngram", max_new_tokens=24)
    _submit(eng, rng, 3, lo=5, hi=12)
    t0 = telemetry.clock()
    eng.run()
    _, runs = _records(t0)
    spec = [r for r in runs if r.program.startswith("spec_window_")]
    assert len(spec) == eng.spec_windows > 0
    assert all(r.program == f"spec_window_{r.ticks}" for r in spec)


def _shapes(cfg, slots, max_len, page):
    sds = lambda *shape, dtype="int32": jax.ShapeDtypeStruct(shape, dtype)
    params = jax.eval_shape(lambda k: lm.init(cfg, k), jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: lm.init_cache(
        cfg, slots, max_len, layout="paged", page_size=page, num_blocks=slots * max_len // page + 1))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    return params, cache, key, sds(slots), sds(slots, dtype="bool"), sds


@pytest.mark.parametrize("program", ["decode_step", "prefill_step", "decode_window_8",
                                     "spec_window_2", "copy_pages"])
def test_lowered_program_is_named(program):
    cfg = _qwen()
    slots, max_len, page = 4, 64, 8
    params, cache, key, vec, flag, sds = _shapes(cfg, slots, max_len, page)
    fn, args = {
        "decode_step": (E._decode_step_fn(cfg, 0.0), (params, cache, vec, vec, key, flag, flag)),
        "prefill_step": (E._prefill_step_fn(cfg, 0.0),
                         (params, cache, sds(slots, 16), vec, vec, key, flag)),
        "decode_window_8": (E._decode_loop_fn(cfg, 0.0, 8, -1, max_len),
                            (params, cache, vec, vec, key, flag, vec)),
        "spec_window_2": (E._spec_loop_fn(cfg, 0.0, "ngram", 2, 4, -1, max_len),
                          (params, cache, vec, vec, key, flag, vec, sds(slots, max_len), flag)),
        "copy_pages": (E._copy_pages_fn(cfg), (cache, sds(2), sds(2))),
    }[program]
    assert fn.__name__ == program
    assert f"module @jit_{program} " in fn.lower(*args).as_text()


def test_log_stays_at_its_maxlen(monkeypatch):
    log = collections.deque(maxlen=telemetry.LOG_MAX)
    monkeypatch.setattr(telemetry, "LOG", log)
    assert telemetry.LOG_MAX == 8192
    for i in range(telemetry.LOG_MAX + 10):
        telemetry.LOG.append(telemetry.Step(float(i), i + 0.5))
    assert len(log) == telemetry.LOG_MAX
    assert log[0].t0 == 10.0
    # a window that reaches back past the oldest record would be cut short
    assert telemetry.window(5.0, 100.0) is None
    assert len(telemetry.window(20.0, 29.9)) == 10
    # an engine keeps appending to the same bounded log
    eng = _engine(slots=2)
    eng.submit([1, 2, 3], max_new_tokens=3)
    eng.run()
    assert len(log) == telemetry.LOG_MAX
    assert isinstance(log[-1], telemetry.Step)


def test_summary_of_a_hand_made_window():
    """Two steps of one dispatch each, and the caller's time between them."""
    def run(program, t, ticks=1, **kw):
        # upload 1, dispatch 1, wait 10, readback 1, drain 2 (seconds)
        r = telemetry.Dispatch(program, ticks, (0,), **kw)
        r.upload, r.dispatch, r.wait, r.readback, r.drain = (
            (t, t + 1), (t + 1, t + 2), (t + 2, t + 12), (t + 12, t + 13), (t + 13, t + 15))
        return r

    recs = [telemetry.Step(0.0, 16.0), run("decode_window_8", 0.5, ticks=8),
            telemetry.Step(20.0, 36.0), run("prefill_step", 20.5, rows=64, live_rows=16)]
    s = telemetry.summary(recs, 0.0, 40.0)
    assert s["dispatches"] == 2
    assert s["phase_s"] == pytest.approx({
        "upload": 2, "dispatch": 2, "wait": 20, "readback": 2, "drain": 4,
        "schedule": 2, "caller": 8})
    # [0, 2.5] to the first enqueue, [12.5, 22.5] between, [32.5, 40] after
    assert s["host_gap_s"] == pytest.approx(2.5 + 10 + 7.5)
    assert s["host_gap_split"] == pytest.approx({
        "readback": 2, "drain": 4, "upload": 2, "dispatch": 2, "caller": 8, "schedule": 2})
    assert s["longest_readback"] == [pytest.approx(1.0), "prefill_step"]
    assert s["decode_ms_per_tick"] == pytest.approx(1e3 * 11 / 8)
    assert (s["prefill_rows"], s["prefill_live_rows"]) == (64, 16)


def test_spans_reach_the_profiler(tmp_path, rng):
    """With a profiler running, every phase is a host event of the trace."""
    eng = _engine(slots=2, sync_every=4)
    _submit(eng, rng, 2, max_new=6)
    eng.step()  # compile outside the trace
    _submit(eng, rng, 1, max_new=6)
    with jax.profiler.trace(str(tmp_path)):
        eng.run()
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True))[-1]
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines for ev in line.events}
    assert {telemetry.PREFIX + p for p in ("step", "schedule") + PHASES} <= names



def test_serve_prints_one_line_from_the_log():
    from repro.launch.serve import dispatch_line

    r = telemetry.Dispatch("prefill_step", 1, (0,), rows=64, live_rows=16)
    r.upload, r.dispatch, r.wait, r.readback, r.drain = (0, 1), (1, 2), (2, 3), (3, 4), (4, 5)
    line = dispatch_line(telemetry.summary([telemetry.Step(0.0, 6.0), r], 0.0, 6.0))
    assert line.startswith("dispatch log: 1 programs; host s: upload 1.000, dispatch 1.000")
    assert "schedule 1.000, caller 0.000" in line and "ms/tick" not in line
    assert line.endswith("prefill live rows 16/64 (25.0%)")
    assert "cut short" in dispatch_line(None)


def test_paged_walk_counts_the_live_pages():
    """``ops.paged_walk``: the pages each slot's bounded decode walk visits,
    from the page of the window's first live position to the last live
    page, clamped to the table — where the call runs the tile kernel and
    its lowering walks in the kernel; the whole table wherever else."""
    from repro.kernels import ops

    lens = [0, 1, 16, 17, 40, 48]
    walk = functools.partial(ops.paged_walk, lens, 16, 3, head_dim=128, backend="pallas")
    assert walk().tolist() == [0, 1, 1, 2, 3, 3]
    # window 20: the live range starts at max(0, len - 20)
    assert walk(window=20).tolist() == [0, 1, 1, 2, 2, 2]
    full = [3] * len(lens)
    assert walk(backend="xla").tolist() == full  # the oracle masks the table
    assert walk(logit_soft_cap=30.0).tolist() == full  # routed to the oracle
    # MLA's one KV head is a joint latent + rope row, 128 lanes here
    assert walk(attention="mla").tolist() == [0, 1, 1, 2, 3, 3]
    # tiles the lowering cannot DMA by hand: the static grid over the table
    assert walk(kv_dtype="int8").tolist() == full
    assert walk(head_dim=64).tolist() == full


def test_mla_paged_walk_counts_live_pages_for_fp_only():
    """``ops.paged_walk`` for MLA at DeepSeek-V2-Lite's one KV head (latent
    512 + rope 64, a joint page of 640 lanes): the fp kernel walks each
    slot's live pages in the kernel; the quantized kernel keeps its packed
    pools, its scale columns and its static grid over the whole table."""
    from repro.kernels import ops

    lens = [0, 1, 16, 17, 40, 48]
    walk = functools.partial(ops.paged_walk, lens, 16, 3, head_dim=576,
                             attention="mla", backend="pallas")
    assert walk().tolist() == [0, 1, 1, 2, 3, 3]
    assert walk(window=20).tolist() == [0, 1, 1, 2, 2, 2]
    assert walk(kv_dtype="int8").tolist() == [3] * len(lens)


def test_prefill_walk_counts_the_prior_pages():
    """``ops.prefill_walk``: per slot, the prior pages the chunk's grid
    cells walk and the pages their tables hold.  The fp MLA kernel walks
    ``[0, start // page)`` in each cell holding a live row, from the first
    page the window reaches from the cell's lowest query; a cell with no
    live row walks nothing.  ``PrefillAttn``, the quantized MLA kernel and
    the oracle walk the whole table in every cell."""
    from repro.kernels import ops

    starts, lens = [32, 0, 32, 16, 32], [0, 20, 20, 32, 9]  # chunk 32: 2 cells
    walk = functools.partial(ops.prefill_walk, starts, lens, 32, 16, 4,
                             head_dim=576, attention="mla", backend="pallas")
    walked, table = walk()
    assert walked.tolist() == [0, 0, 4, 2, 2] and table.tolist() == [8] * 5
    # window 20: the second cell of the 32-token starts reaches back from
    # position 48 to 29, so from page 1
    assert walk(window=20)[0].tolist() == [0, 0, 3, 2, 2]
    for whole in (walk(kv_dtype="int8"), walk(backend="xla"),
                  walk(attention="gqa", head_dim=128), walk(logit_soft_cap=30.0)):
        assert whole[0].tolist() == whole[1].tolist() == [8] * 5


@pytest.mark.parametrize("bounded", [True, False])
def test_prefill_records_count_their_prior_walk(bounded, monkeypatch, rng):
    """Each prefill program's record counts the prior pages its attention
    walks against the pages its grid cells' tables hold, over cells, slots
    and layers; decode programs count none.  Bounded, a slot's cells walk
    its prior pages while they hold live rows; unbounded (the XLA oracle
    the CPU engine runs, and ``PrefillAttn``), the whole table."""
    from repro.kernels import ops

    eng = _engine(slots=2)
    if bounded:  # as ops answers for the fp MLA kernel's in-kernel walk
        monkeypatch.setattr(ops, "_walks_live_pages", lambda *a: True)
    calls = []
    run = eng._run_program

    def spy(fn, inputs, *a, **kw):
        starts, lens = np.array(inputs[1]), np.array(inputs[2])
        out = run(fn, inputs, *a, **kw)
        calls.append((out[2], starts, lens))
        return out

    monkeypatch.setattr(eng, "_run_program", spy)
    _submit(eng, rng, 3, lo=20, hi=60)
    eng.run()
    layers, ps, width = eng.cfg.num_layers, eng.scfg.page_size, eng.max_pages
    cells = eng.prefill_chunk // ps
    prefill = [c for c in calls if c[0].program == "prefill_step"]
    # some chunk resumes a prompt past its first page
    assert any(n and st for _, starts, lens in prefill for st, n in zip(starts, lens))
    for rec, starts, lens in prefill:
        assert rec.prior_pages_table == layers * eng.scfg.slots * cells * width
        if not bounded:
            assert rec.prior_pages_walked == rec.prior_pages_table
            continue
        want = sum(starts[s] // ps for s in range(len(lens))
                   for c in range(cells) if c * ps < lens[s])
        assert rec.prior_pages_walked == layers * want
    assert all(c[0].prior_pages_table == c[0].prior_pages_walked == 0
               for c in calls if c[0].program != "prefill_step")


@pytest.mark.parametrize("bounded", [True, False])
@pytest.mark.parametrize("sync_every", [1, 8])
def test_decode_records_count_their_page_walk(sync_every, bounded, monkeypatch, rng):
    """Each decode program's record counts the KV pages its attention
    walks against the pages its tables hold, over ticks, slots and layers:
    with the walk bounded, ceil((pos + 1) / page) per slot and tick, a
    slot's position advancing on the ticks it was live; unbounded (the XLA
    oracle the CPU engine runs), the whole table."""
    from repro.kernels import ops

    eng = _engine(sync_every=sync_every, max_new_tokens=20)
    if bounded:  # as ops answers for the tile kernel's in-kernel walk
        monkeypatch.setattr(ops, "_walks_live_pages", lambda *a: True)
    calls = []
    run = eng._run_program

    def spy(fn, inputs, *a, **kw):
        pos = np.array(inputs[1])  # the engine advances self.pos in place
        out = run(fn, inputs, *a, **kw)
        calls.append((out[2], pos, out[0][1]))
        return out

    monkeypatch.setattr(eng, "_run_program", spy)
    _submit(eng, rng, 5, lo=5, hi=30)
    eng.run()
    layers, ps, width = eng.cfg.num_layers, eng.scfg.page_size, eng.max_pages
    decode = [(rec, pos, emitted) for rec, pos, emitted in calls
              if rec.program == "decode_step" or rec.program.startswith("decode_window_")]
    assert decode
    for rec, pos, emitted in decode:
        assert rec.pages_table == rec.ticks * eng.scfg.slots * width * layers
        if not bounded:
            assert rec.pages_walked == rec.pages_table
            continue
        # a window's second output is its emitted mask (ticks x slots); a
        # slot advances on the ticks it was live in
        walked, p = 0, pos.astype(int)
        for t in range(rec.ticks):
            walked += layers * sum(-(-(x + 1) // ps) for x in p.tolist())
            if emitted.ndim == 2:
                p = p + emitted[t]
        assert rec.pages_walked == walked
    others = [rec for rec, _, _ in calls if rec not in [d[0] for d in decode]]
    assert all(rec.pages_walked == rec.pages_table == 0 for rec in others)


def _walk_configs():
    qwen = _qwen()
    return {
        "gqa": qwen,
        "windowed": dataclasses.replace(qwen, sliding_window=24, global_attn_every=2),
        "mla_moe": get_config("deepseek_v2_lite_16b").reduced(),
    }


@pytest.mark.parametrize("bounded", [True, False])
@pytest.mark.parametrize("name", ["gqa", "windowed", "mla_moe"])
def test_lm_counts_what_its_layers_walk(name, bounded, monkeypatch):
    """``lm.pages_walked`` / ``lm.prior_pages_walked``: the per-layer sum of
    what ``ops.paged_walk`` / ``ops.prefill_walk`` answer for each layer's
    window and the kernel-selecting facts of its paged attention call."""
    from repro.kernels import ops

    cfg = _walk_configs()[name]
    if bounded:  # as ops answers for a kernel that walks in the kernel
        monkeypatch.setattr(ops, "_walks_live_pages", lambda *a: True)
    facts = dict(head_dim=cfg.kv_head_dim, kv_dtype=cfg.kv_dtype,
                 attention=cfg.attention, logit_soft_cap=cfg.logit_soft_cap,
                 backend=None if cfg.kernel_backend == "auto" else cfg.kernel_backend)
    windows = lm.static_windows(cfg)
    if name == "windowed":
        assert len(set(windows)) == 2
    ps, width, chunk = 8, 12, 32
    lens = np.array([[0, 1, 8, 9, 40, 96], [3, 17, 33, 50, 64, 90]])
    want = sum(int(ops.paged_walk(lens, ps, width, w, **facts).sum()) for w in windows)
    assert lm.pages_walked(cfg, lens, ps, width) == (want, lens.size * width * len(windows))
    if bounded:
        assert want < lens.size * width * len(windows)
    starts, live = np.array([0, 32, 64, 40, 8]), np.array([20, 32, 0, 9, 32])
    per_layer = [ops.prefill_walk(starts, live, chunk, ps, width, w, **facts) for w in windows]
    assert lm.prior_pages_walked(cfg, starts, live, chunk, ps, width) == (
        sum(int(wk.sum()) for wk, _ in per_layer), sum(int(tb.sum()) for _, tb in per_layer))


@pytest.mark.parametrize("name", ["gqa", "windowed", "mla_moe"])
def test_lm_counts_the_expert_rows(name):
    """``lm.expert_rows``: the held experts' rows of every MoE layer, each
    ``layers.moe_rows`` of the token rows; none for a dense model."""
    from repro.models import layers as L

    cfg = _walk_configs()[name]
    for rows in (1, 7, 64):
        want = L.moe_layers(cfg) * L.moe_rows(cfg, rows) if cfg.moe else 0
        assert lm.expert_rows(cfg, rows) == want
    assert (lm.expert_rows(cfg, 1) > 0) == (name == "mla_moe")


def test_engine_imports_the_model_not_its_layers_or_kernels():
    """The serving engine's one seam to the model is ``repro.models.lm``:
    it imports nothing of ``repro.models.layers`` and, of ``repro.kernels``,
    only the pool's runtime guard."""
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(E))
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                assert not alias.name.startswith(("repro.models.layers", "repro.kernels"))
        elif isinstance(node, ast.ImportFrom):
            mod, names = node.module or "", {a.name for a in node.names}
            if node.level:  # relative to the engine's package, repro.serving
                base = ["repro", "serving"][: 3 - node.level]
                mod = ".".join(base + ([mod] if mod else []))
            assert not mod.startswith("repro.models.layers")
            assert not (mod == "repro.models" and "layers" in names)
            if mod.startswith("repro.kernels"):
                assert mod == "repro.kernels.ops" and names == {"guard_dispatch"}
            if mod == "repro":
                assert "kernels" not in names
