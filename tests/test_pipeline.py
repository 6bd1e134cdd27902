"""Tests for the pass-based lowering pipeline and the backend registry.

Two halves:

* unit tests for each pass over a hand-built ``TileProgram`` — every pass
  is a plain function over the :class:`LoweredModule` artifact, so they can
  be run (and asserted on) individually;
* the backend-parity suite: every kernel in ``repro.kernels`` compiled with
  both ``target="pallas"`` (interpret mode) and ``target="reference"`` on
  tiny shapes must agree numerically.
"""
import collections

import numpy as np
import pytest

from repro.core import (
    LoweringError,
    Schedule,
    TileProgram,
    analyze,
    available_backends,
    compile as tl_compile,
    get_backend,
    program_fingerprint,
    register_backend,
)
from repro.core import lang as T
from repro.core.lowering import (
    LOOP,
    PRE,
    POST,
    LoweredModule,
    PIPELINE,
    run_pipeline,
    schedule_key,
)
from repro.core.lowering.pipeline import (
    pass_collect_windows,
    pass_estimate_cost,
    pass_plan_grid,
    pass_plan_params,
    pass_plan_stages,
    pass_plan_vmem,
    pass_split_phases,
)
from repro.kernels import parity_inputs, parity_programs


def small_gemm_program(bm=16, bn=16, bk=16, kext=2):
    """Hand-built pipelined GEMM used by the per-pass unit tests."""
    M, N, K = 2 * bm, 2 * bn, kext * bk

    @T.prim_func
    def SmallGemm(
        A: T.Tensor((M, K), "float32"),
        B: T.Tensor((K, N), "float32"),
        C: T.Tensor((M, N), "float32"),
    ):
        with T.Kernel(N // bn, M // bm) as (bx, by):
            A_s = T.alloc_shared((bm, bk))
            B_s = T.alloc_shared((bk, bn))
            C_l = T.alloc_fragment((bm, bn))
            T.clear(C_l)
            for k in T.Pipelined(kext, num_stages=2):
                T.copy(A[by * bm, k * bk], A_s)
                T.copy(B[k * bk, bx * bn], B_s)
                T.gemm(A_s, B_s, C_l)
            T.copy(C_l, C[by * bm, bx * bn])

    return SmallGemm


# ---------------------------------------------------------------------------
# Per-pass unit tests
# ---------------------------------------------------------------------------


class TestPasses:
    def _module(self, *passes, schedule=None):
        m = LoweredModule(small_gemm_program(), schedule or Schedule())
        for p in passes:
            p(m)
        return m

    def test_split_phases(self):
        m = self._module(pass_split_phases)
        assert len(m.phases.pre) == 1  # the clear
        assert m.phases.pipeline is not None and m.phases.pipeline.extent == 2
        assert len(m.phases.post) == 1  # the store copy

    def test_collect_windows(self):
        m = self._module(pass_split_phases, pass_collect_windows)
        assert len(m.in_windows) == 2 and len(m.out_windows) == 1
        assert all(w.phase == LOOP for w in m.in_windows)
        assert m.out_windows[0].phase == POST
        assert set(m.fed_by) == {w.onchip.name for w in m.in_windows}

    def test_plan_grid_orders_axes(self):
        m = self._module(pass_split_phases, pass_collect_windows, pass_plan_grid)
        # (by, bx) reversed + the pipelined axis innermost
        assert m.grid == (2, 2, 2)
        assert m.grid_plan.dimension_semantics == ("parallel", "parallel", "arbitrary")
        assert m.grid_plan.kdim == 2
        env = m.grid_plan.env_builder(1, 0, 1)
        assert env["bx"] == 0 and env["by"] == 1

    def test_plan_stages_schedule_override(self):
        m = self._module(pass_split_phases, pass_plan_stages)
        assert m.num_stages == 2  # from T.Pipelined
        m2 = self._module(
            pass_split_phases, pass_plan_stages, schedule=Schedule(num_stages=3)
        )
        assert m2.num_stages == 3

    def test_plan_vmem_multibuffers_loop_windows(self):
        m = self._module(
            pass_split_phases,
            pass_collect_windows,
            pass_plan_stages,
            pass_plan_vmem,
        )
        copies = {b.name: b.copies for b in m.vmem.buffers}
        for w in m.in_windows:
            assert copies[w.onchip.name] == 2  # double-buffered
        # the accumulator is single-copy scratch
        frag = [b for b in m.vmem.buffers if b.scope == "fragment"]
        assert frag and all(b.copies == 1 for b in frag)

    def test_plan_params(self):
        m = self._module(
            pass_split_phases, pass_collect_windows, pass_plan_params
        )
        assert [p.name for p in m.arg_params] == ["A", "B"]
        assert [p.name for p in m.out_params] == ["C"]
        assert m.window_param_idx == [0, 1]
        # the fragment accumulator is scratch (not window-backed)
        assert [b.name for b in m.scratch_bufs] == [m.phases.pre[0].buffer.name]

    def test_estimate_cost(self):
        m = self._module(
            pass_split_phases,
            pass_collect_windows,
            pass_plan_grid,
            pass_plan_stages,
            pass_plan_vmem,
            pass_plan_params,
            pass_estimate_cost,
        )
        # 2*M*N*K flops for the full problem
        assert m.cost.flops == 2 * 32 * 32 * 32
        assert m.cost.hbm_bytes > 0
        assert m.cost.grid == (2, 2, 2)

    def test_run_pipeline_fills_everything(self):
        m = run_pipeline(small_gemm_program(), Schedule())
        for field in ("phases", "inference", "grid_plan", "vmem", "cost"):
            assert getattr(m, field) is not None, field
        assert PIPELINE[0][0] == "split_phases" and PIPELINE[-1][0] == "estimate_cost"


class TestFingerprintAndCache:
    def test_fingerprint_stable_across_retrace(self):
        assert program_fingerprint(small_gemm_program()) == program_fingerprint(
            small_gemm_program()
        )

    def test_fingerprint_distinguishes_structure(self):
        assert program_fingerprint(small_gemm_program(bk=16)) != program_fingerprint(
            small_gemm_program(bk=8, kext=4)
        )

    def test_schedule_key_excludes_notes(self):
        a, b = Schedule(), Schedule()
        b.notes["advisory"] = 1
        assert schedule_key(a) == schedule_key(b)
        assert schedule_key(Schedule(num_stages=3)) != schedule_key(a)

    def test_analysis_cache_shared_across_retrace(self):
        sched = Schedule(interpret=True)
        assert analyze(small_gemm_program(), sched) is analyze(
            small_gemm_program(), sched
        )

    def test_compile_cache_returns_same_kernel(self):
        sched = Schedule(interpret=True)
        k1 = tl_compile(small_gemm_program(), sched)
        k2 = tl_compile(small_gemm_program(), sched)
        assert k1 is k2
        # a different target is a different cache entry
        k3 = tl_compile(small_gemm_program(), sched, target="reference")
        assert k3 is not k1 and k3.backend == "reference"


class TestRegistry:
    def test_builtins_registered(self):
        assert {"pallas", "reference"} <= set(available_backends())

    def test_aliases(self):
        assert get_backend("ref") is get_backend("reference")
        assert get_backend("pallas_tpu") is get_backend("pallas")

    def test_unknown_backend_raises(self):
        with pytest.raises(LoweringError, match="Unknown backend"):
            tl_compile(small_gemm_program(), target="cuda")

    def test_register_third_party_backend(self):
        calls = {}

        @register_backend("_test_counting")
        def emit(module):
            calls["module"] = module
            return get_backend("reference")(module)

        try:
            kern = tl_compile(small_gemm_program(), target="_test_counting")
            assert calls["module"].program is kern.program
            a = np.ones((32, 32), np.float32)
            np.testing.assert_allclose(np.asarray(kern(a, a)), a @ a, rtol=1e-5)
        finally:
            from repro.core.backends import _REGISTRY

            _REGISTRY.pop("_test_counting", None)


# ---------------------------------------------------------------------------
# Backend parity: every kernel, pallas(interpret) vs reference
# ---------------------------------------------------------------------------

_CASES = dict(parity_programs())


def _make_input(param, rng):
    if param.dtype.startswith(("int", "uint")):
        return rng.integers(-4, 4, size=param.shape).astype(param.dtype)
    return rng.standard_normal(param.shape).astype(param.dtype)


@pytest.mark.parametrize("name", sorted(_CASES))
def test_backend_parity(name, rng):
    prog = _CASES[name]
    pk = tl_compile(prog, Schedule(interpret=True), target="pallas")
    rk = tl_compile(prog, target="reference")
    assert pk.backend == "pallas" and rk.backend == "reference"
    assert [p.name for p in pk.arg_params] == [p.name for p in rk.arg_params]
    args = parity_inputs(name, prog, rng)
    if args is None:
        args = [_make_input(p, rng) for p in pk.arg_params]
    pout, rout = pk(*args), rk(*args)
    if not isinstance(pout, tuple):
        pout, rout = (pout,), (rout,)
    for p, r in zip(pout, rout):
        np.testing.assert_allclose(
            np.asarray(p), np.asarray(r), rtol=1e-4, atol=2e-3
        )


# ---------------------------------------------------------------------------
# The bounded page walk: PagedAttn / PagedAttnQuant visit only live pages
# ---------------------------------------------------------------------------

_PAGED = sorted(n for n in _CASES if n.startswith("paged_attention"))


def _paged_oracle(name, args):
    """``ref.paged_attention(_quant)`` on a paged parity case's inputs, in
    the kernels' (slots, kv_heads, group, head_dim) output layout."""
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.paged_attention import PARITY_CASES

    cfg = dict(PARITY_CASES)[name]
    tables, lens, q, *pools = (jnp.asarray(a) for a in args)
    slots, hkv, group, d = q.shape
    qh = q.reshape(slots, hkv * group, d)
    if "quant" in name:
        out = ref.paged_attention_quant(qh, *pools, tables, lens, fmt=cfg["fmt"],
                                        window=cfg.get("window"))
    else:
        out = ref.paged_attention(qh, *pools, tables, lens, window=cfg.get("window"))
    return np.asarray(out).reshape(q.shape)


@pytest.mark.parametrize("target", ["pallas", "reference"])
@pytest.mark.parametrize("name", _PAGED)
def test_paged_walk_matches_oracle(name, target, rng):
    """Both backends' bounded walk against the XLA oracle, which masks
    the whole table: skipping dead pages changes nothing, empty slots
    (``EDGE_LENS``' 0) read zeros.  The ``lane`` cases (head_dim 128) run
    the Pallas in-kernel walk; the others its static-grid lowering."""
    prog = _CASES[name]
    assert analyze(prog, Schedule()).grid_plan.walk == ("_lane_" in name)
    sched = Schedule(interpret=True) if target == "pallas" else None
    kern = tl_compile(prog, sched, target=target)
    args = parity_inputs(name, prog, rng)
    np.testing.assert_allclose(np.asarray(kern(*args)), _paged_oracle(name, args),
                               rtol=1e-4, atol=2e-3)


_MLA_FP = sorted(n for n in _CASES if n.startswith(("mla_paged", "mla_prefill"))
                 and "quant" not in n)


def _mla_check(name, args, got):
    """A paged MLA case's kernel outputs against the XLA oracle over the
    joint pool: the latent lanes of every live query row (the oracle's
    rows with no key are nan, the kernel's zeros), and for prefill each
    slot's live rows in its pages and the pages no table holds."""
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    from repro.kernels.mla import PARITY_CASES

    cfg = dict(PARITY_CASES)[name]
    r, pe, w = cfg["dim"], cfg["pe_dim"], cfg.get("window")
    if name.startswith("mla_paged"):
        tables, lens, q, pages = (jnp.asarray(a) for a in args)
        want = np.asarray(ref.mla_paged(q[..., :r], q[..., r : r + pe], pages,
                                        tables, lens, window=w))
        live = np.asarray(lens) > 0
        got = np.asarray(got)
        np.testing.assert_allclose(got[live, :, :r], want[live], rtol=1e-4, atol=2e-3)
        assert not got[~live].any()  # an empty slot reads zeros
        return
    tables, starts, lens, q, kv, pages = (jnp.asarray(a) for a in args)
    slots, chunk = kv.shape[:2]
    q = q.reshape(slots, chunk, cfg["heads"], -1).transpose(0, 2, 1, 3)
    want, want_pages = ops.mla_prefill(
        q[..., :r], q[..., r : r + pe], kv[..., :r], kv[..., r : r + pe], pages,
        tables, starts, lens, window=w, backend="xla")
    got_pages, out = (np.asarray(x) for x in got)
    out = out.reshape(slots, chunk, cfg["heads"], -1)[..., :r]
    want = np.asarray(want).transpose(0, 2, 1, 3)
    live = np.arange(chunk)[None, :] < np.asarray(lens)[:, None]
    np.testing.assert_allclose(out[live], want[live], rtol=1e-4, atol=2e-3)
    want_pages = np.asarray(want_pages)
    for b, n in enumerate(np.asarray(starts + lens)):  # every slot's live rows
        rows = lambda p: p[np.asarray(tables[b])].reshape(-1, p.shape[-1])[:n]
        np.testing.assert_allclose(rows(got_pages), rows(want_pages), atol=1e-6)
    spare = np.setdiff1d(np.arange(1, pages.shape[0]), np.asarray(tables))
    np.testing.assert_array_equal(got_pages[spare], np.asarray(pages)[spare])


@pytest.mark.parametrize("target", ["pallas", "reference"])
@pytest.mark.parametrize("name", _MLA_FP)
def test_mla_walk_matches_oracle(name, target, rng):
    """``PagedMLA`` and ``PrefillMLA`` on the joint page (128 lanes here):
    the Pallas lowering walks their bounded loops inside the kernel, and
    both backends match the oracle, which masks the whole table — an empty
    slot and a dead chunk page walk nothing, a window's walk starts past
    page 0."""
    prog = _CASES[name]
    assert analyze(prog, Schedule()).grid_plan.walk
    sched = Schedule(interpret=True) if target == "pallas" else None
    kern = tl_compile(prog, sched, target=target)
    args = parity_inputs(name, prog, rng)
    _mla_check(name, args, kern(*args))


@pytest.mark.parametrize("window", [None, 20])
def test_reference_walks_only_live_pages(window, monkeypatch):
    """The reference interpreter loads exactly the pages holding each
    slot's live positions, per (KV head, slot): ``ceil(len / page)`` from
    the window's first live page, none for an empty slot."""
    from repro.core.backends import reference
    from repro.core.tile_ops import CopyOp
    from repro.kernels.paged_attention import (
        EDGE_LENS,
        PARITY_CASES,
        paged_attention_program,
    )

    cfg = dict(dict(PARITY_CASES)["paged_attention_edge_lens"], window=window)
    prog = paged_attention_program(**cfg)
    seen = []
    ref_op = reference._ref_op

    def spy(op, globals_, tiles, env, jnp, san=None):
        if isinstance(op, CopyOp) and op.src.buffer.name == "KPages":
            seen.append((env["bx"], env["by"], int(env[prog.pipelined_ops()[0].var.name])))
        return ref_op(op, globals_, tiles, env, jnp, san)

    monkeypatch.setattr(reference, "_ref_op", spy)
    args = parity_inputs("paged_attention_edge_lens", prog, np.random.default_rng(0))
    tl_compile(prog, target="reference", use_cache=False)(*args)
    ps = cfg["page_size"]
    want = []
    for bz, n in enumerate(EDGE_LENS):
        first = 0 if window is None else max(0, n - window) // ps
        for bh in range(cfg["kv_heads"]):
            want += [(bh, bz, k) for k in range(first, -(-n // ps))]
    assert sorted(seen) == sorted(want)
    per_cell = collections.Counter((bh, bz) for bh, bz, _ in seen)
    assert per_cell[(0, 4)] == (3 if window is None else 2)  # 48 tokens
    assert (0, 0) not in per_cell  # the empty slot walks nothing


def test_reference_bounded_walk_under_jit(rng):
    """Under ``jax.jit`` the loop's bounds are traced: the reference walks
    to the static bound and keeps only the live steps' writes, giving what
    it gives eagerly."""
    import jax

    name = "paged_attention_windowed_edge_lens"
    prog = _CASES[name]
    kern = tl_compile(prog, target="reference")
    args = parity_inputs(name, prog, rng)
    np.testing.assert_allclose(np.asarray(jax.jit(kern)(*args)),
                               np.asarray(kern(*args)), rtol=1e-5, atol=1e-6)


def test_bounded_loop_lowering():
    """The fp kernel walks inside the kernel: its grid is (slots, kv_heads)
    alone.  The quantized kernel's (page, 1) scale columns cannot be DMA'd
    by hand, so its bounded loop keeps the grid axis over max_pages."""
    from repro.kernels.paged_attention import (
        paged_attention_program,
        paged_attention_quant_program,
    )

    shape = dict(slots=8, heads=12, kv_heads=2, head_dim=128, page_size=16,
                 max_pages=288, num_pages=2305, dtype="bfloat16")
    m = analyze(paged_attention_program(**shape), Schedule())
    assert m.grid == (8, 2) and m.grid_plan.walk and m.grid_plan.kdim is None
    assert m.dimension_semantics == ("parallel", "parallel")
    q = analyze(paged_attention_quant_program(fmt="int8", **shape), Schedule())
    assert q.grid == (8, 2, 288) and not q.grid_plan.walk and q.grid_plan.kdim == 2


def test_bounded_loop_wider_than_its_table_is_refused():
    """A bound past the block table's width would read table entries that
    do not exist: the verifier refuses it at lowering time."""
    from repro.core.errors import VerifyError

    @T.prim_func
    def TooWide(
        Tables: T.ScalarTensor((2, 3), "int32"),
        Lens: T.ScalarTensor((2,), "int32"),
        Pages: T.Tensor((8, 16, 128), "float32"),
        Out: T.Tensor((2, 16, 128), "float32"),
    ):
        with T.Kernel(2) as bz:
            P_s = T.alloc_shared((16, 128))
            acc = T.alloc_fragment((16, 128))
            T.clear(acc)
            for k in T.Pipelined(T.ceildiv(Lens[bz], 16), max_extent=4):
                T.copy(Pages[Tables[bz, k], 0, 0], P_s)
                for i, j in T.Parallel(16, 128):
                    acc[i, j] = acc[i, j] + P_s[i, j]
            T.copy(acc, Out[bz, 0, 0])

    with pytest.raises(VerifyError, match="Tables axis 1"):
        analyze(TooWide, Schedule(), use_cache=False)


def test_expression_extent_needs_a_bound():
    from repro.core.errors import TraceError

    with pytest.raises(TraceError, match="max_extent"):
        @T.prim_func
        def Unbounded(
            Lens: T.ScalarTensor((2,), "int32"),
            Out: T.Tensor((2, 8, 128), "float32"),
        ):
            with T.Kernel(2) as bz:
                acc = T.alloc_fragment((8, 128))
                T.clear(acc)
                for k in T.Pipelined(T.ceildiv(Lens[bz], 16)):
                    T.fill(acc, 1.0)
                T.copy(acc, Out[bz, 0, 0])


# ---------------------------------------------------------------------------
# DequantStage lane padding (ROADMAP §3 residue): packed int8 scratch must
# land on the TPU lane width; window-backed packed buffers must not be
# padded (their block shape mirrors the global page layout).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", ["int8", "int4"])
def test_dequant_stage_scratch_is_lane_aligned(fmt):
    from repro.core.layout import LANE
    from repro.core.lowering import run_pipeline
    from repro.kernels.prefill_attention import (
        prefill_attention_quant_program,
    )

    # head_dim // pack = 64 (int8) / 32 (int4): both narrower than LANE,
    # exactly the misaligned minor dims Mosaic pays relayout copies for
    m = run_pipeline(
        prefill_attention_quant_program(
            slots=1, heads=2, kv_heads=1, head_dim=64, chunk=8,
            page_size=8, max_pages=4, num_pages=8, fmt=fmt),
        Schedule(),
    )
    packed_scratch = [b for b in m.scratch_bufs if b.dtype == "int8"]
    assert packed_scratch  # the dequant stages' local fragments
    for b in packed_scratch:
        assert b.shape[-1] % LANE == 0, (b.name, b.shape)
    # the shared staging buffers are BlockSpec windows over the packed
    # pools: their block shape must stay exactly the global page layout
    cols = 64 // {"int8": 1, "int4": 2}[fmt]
    packed_windows = [w.onchip for w in m.in_windows
                      if w.onchip.dtype == "int8"]
    assert packed_windows
    for b in packed_windows:
        assert b.shape[-1] == cols, (b.name, b.shape)
