"""Pallas-TPU backend: ``LoweredModule -> pl.pallas_call`` (DESIGN.md §2, §4).

The central translation: a ``T.Pipelined`` loop over K with global->shared
``T.copy`` ops becomes the **Pallas grid pipeline** — the copies turn into
BlockSpec-managed windows whose index maps depend on the reduction grid
axis, so the hardware DMA double-buffers them and overlaps with compute
exactly like cp.async/TMA rings on GPUs.  Fragment buffers become VMEM
scratch accumulators persisting across the ``arbitrary`` axis.

A *bounded* ``T.Pipelined`` loop (its extent a slot's live length, read
from a scalar-prefetch param) gets no grid axis.  Each grid cell runs PRE
once, then an in-kernel ``lax.fori_loop`` over its live range only, with
the loop's tiles DMA'd by hand from operands left in HBM into
``num_stages``-deep VMEM rings, the next tile in flight while this one is
computed, and fragments carried through the loop; then POST once.  Where
Mosaic cannot copy a loop tile by hand (``lowering.grid.walks_in_kernel``)
the bounded loop lowers as a static loop over its bound: every step runs,
and the kernel's mask discards the steps outside the live range.  Static
loops lower as before, to the same kernel text.

With ``schedule.interpret=True`` the same kernel body executes on CPU for
validation; on a TPU host it is the Mosaic-compiled kernel.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from ..buffer import GLOBAL, SCALAR, TileBuffer
from ..errors import LoweringError, ScheduleError, VerifyError
from ..expr import BinExpr, ConstExpr, Expr, VarExpr, evaluate, static_eval
from ..lowering.indexing import make_index_map, no_loads
from ..lowering.verify import alias_wiring
from ..lowering.module import CompiledKernel, LoweredModule
from ..lowering.phases import LOOP, POST, PRE
from ..lowering.windows import _is_onchip
from ..tile_ops import (
    AtomicOp,
    CopyOp,
    CumsumOp,
    CustomOp,
    FillOp,
    GemmOp,
    ParallelOp,
    PipelinedOp,
    ReduceOp,
    ResolvedRegion,
    SerialOp,
    TileOp,
)
from . import register_backend


@register_backend("pallas")
def emit_pallas(module: LoweredModule) -> CompiledKernel:
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    program = module.program
    schedule = module.schedule
    if module.vmem is not None and not module.vmem.ok:
        raise ScheduleError(
            f"{program.name}: VMEM budget exceeded —\n{module.vmem.summary()}\n"
            "Reduce block shapes or num_stages."
        )
    phases = module.phases
    in_windows, out_windows = module.in_windows, module.out_windows
    plan = module.grid_plan
    grid, env_builder, kdim = plan.grid, plan.env_builder, plan.kdim
    dim_sem = plan.dimension_semantics
    pipe = phases.pipeline
    scratch_bufs, scratch_pos = module.scratch_bufs, module.scratch_pos
    arg_params, out_params = module.arg_params, module.out_params
    window_of, out_window_of = module.window_of, module.out_window_of

    # ---- operand list: one per input window (+ aliased outputs last) -----
    window_param_idx: List[int] = []
    for w, idx in zip(in_windows, module.window_param_idx):
        if idx is None:
            # a written global read back through a window — unsupported
            raise LoweringError(
                f"{program.name}: {w.param.name} is both written and read "
                "through separate windows; use T.atomic or split kernels."
            )
        window_param_idx.append(idx)
    aliased_js = [j for j, w in enumerate(out_windows) if w.aliased]
    n_in_ops = len(in_windows)

    # ---- a bounded loop walked in the kernel: its tiles are DMA'd by hand --
    walks = plan.walk
    manual = [i for i, w in enumerate(in_windows) if walks and w.phase == LOOP]
    if walks and any(w.phase == LOOP for w in out_windows):
        raise LoweringError(
            f"{program.name}: a store inside the bounded loop "
            f"{pipe.var.name}; store after the loop instead"
        )
    depth = max(2, module.num_stages)  # the VMEM plan's buffer count

    # ---- scalar-prefetch operands ----------------------------------------
    # T.ScalarTensor params ride ahead of the grid walk in SMEM
    # (PrefetchScalarGridSpec); every index map then receives their refs as
    # trailing args so window starts may load them (block-table gathers).
    # Output windows go through the same index-map derivation, so stores may
    # be table-directed too (the chunked-prefill kernel writing K/V pages);
    # combined with an in-out alias the unwritten pages keep their contents.
    scalar_params = module.scalar_params
    n_scalars = len(scalar_params)
    scalar_pos = {p.name: i for i, p in enumerate(scalar_params)}
    arg_pos = {id(p): i for i, p in enumerate(arg_params)}
    scalar_arg_idx = [arg_pos[id(p)] for p in scalar_params]

    def _index_map(region):
        return make_index_map(region, env_builder, scalar_params or None)

    # ---- specs -----------------------------------------------------------
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM) if i in manual
        else pl.BlockSpec(w.block_shape, _index_map(w.region))
        for i, w in enumerate(in_windows)
    ]
    alias_in_specs = [
        pl.BlockSpec(
            out_windows[j].block_shape,
            _index_map(out_windows[j].region),
        )
        for j in aliased_js
    ]
    out_specs = [
        pl.BlockSpec(w.block_shape, _index_map(w.region)) for w in out_windows
    ]
    out_shape = [
        jax.ShapeDtypeStruct(w.param.shape, jnp.dtype(w.param.dtype))
        for w in out_windows
    ]
    scratch_shapes = [
        pltpu.VMEM(b.shape, jnp.dtype(b.dtype)) for b in scratch_bufs
    ]
    # a ring of ``depth`` tiles per DMA'd window (its block less the
    # collapsed dims), and a DMA semaphore per ring slot
    scratch_shapes += [
        pltpu.VMEM((depth,) + in_windows[i].onchip.shape,
                   jnp.dtype(in_windows[i].param.dtype))
        for i in manual
    ] + [pltpu.SemaphoreType.DMA((depth,)) for _ in manual]
    # alias operand indices are positional over *all* pallas_call inputs —
    # scalar-prefetch operands included.  Cross-check against the verifier's
    # canonical wiring: a drift between the operand list assembled here and
    # the windows' aliased marks would silently alias the wrong buffers.
    input_output_aliases = {
        n_scalars + n_in_ops + i: j for i, j in enumerate(aliased_js)
    }
    expected_aliases = alias_wiring(module)
    if input_output_aliases != expected_aliases:
        raise VerifyError(
            f"{program.name}: input_output_aliases {input_output_aliases} "
            f"disagrees with the verifier wiring {expected_aliases}"
        )

    kext = pipe.extent if pipe is not None else None

    # ---- kernel body ------------------------------------------------------
    def body(*refs):
        scalar_refs = refs[:n_scalars]
        refs = refs[n_scalars:]
        n_in_total = n_in_ops + len(alias_in_specs)
        in_refs = refs[:n_in_total]
        out_refs = refs[n_in_total : n_in_total + len(out_windows)]
        scr_refs = refs[n_in_total + len(out_windows) :]
        n_scr = len(scratch_bufs)
        rings = dict(zip(manual, scr_refs[n_scr : n_scr + len(manual)]))
        sems = dict(zip(manual, scr_refs[n_scr + len(manual) :]))
        cursor: Dict[str, Any] = {}  # "slot": the ring slot of the current k

        grid_ids = tuple(pl.program_id(d) for d in range(len(grid)))
        env_scalars = env_builder(*grid_ids)
        kval = grid_ids[kdim] if kdim is not None else None

        values: Dict[str, Any] = {}
        dirty: set = set()

        def squeeze(arr, region: ResolvedRegion):
            keep = tuple(
                i for i, c in enumerate(region.collapsed) if not c
            )
            if len(keep) == arr.ndim:
                return arr
            return arr.reshape(tuple(arr.shape[i] for i in keep))

        def get(buf: TileBuffer):
            if buf.name in values:
                return values[buf.name]
            if buf.scope == SCALAR:
                # Mosaic loads only scalars from SMEM: see load_scalar
                raise LoweringError(
                    f"{program.name}: scalar-prefetch operand {buf.name} "
                    "must be read element by element"
                )
            if buf.name in window_of:
                i = window_of[buf.name]
                if i in rings:
                    val = rings[i][cursor["slot"]]
                else:
                    val = squeeze(in_refs[i][...], in_windows[i].region)
                val = val.astype(jnp.dtype(buf.dtype))
                values[buf.name] = val
                return val
            pos = scratch_pos[buf.name]
            val = scr_refs[pos][...]
            values[buf.name] = val
            return val

        def put(buf: TileBuffer, val):
            if buf.name in window_of:
                raise LoweringError(
                    f"{program.name}: write to window-backed tile {buf.name}"
                )
            val = val.astype(jnp.dtype(buf.dtype))
            val = jnp.broadcast_to(val, buf.shape)
            values[buf.name] = val
            if buf.name in scratch_pos:
                dirty.add(buf.name)

        def gput(buf: TileBuffer, new, phase: str):
            """Phase-guarded value update.

            PRE ops must only take effect at k==0 and POST ops at k==last —
            the body re-executes every grid step, and unguarded PRE/POST
            writes would corrupt accumulators carried across the reduction
            axis.  Guards are functional selects (Mosaic-friendly), not
            control flow."""
            g = guard(phase)
            if g is None:
                put(buf, new)
                return
            new = jnp.broadcast_to(
                jnp.asarray(new).astype(jnp.dtype(buf.dtype)), buf.shape
            )
            put(buf, jnp.where(g, new, get(buf).astype(new.dtype)))

        def load_scalar(buf: TileBuffer, idx_values):
            """One SMEM scalar load (``Lens[bz]``): every index must be a
            scalar (grid ids, constants), never a vector of lanes."""
            idx = tuple(jnp.asarray(v, jnp.int32) for v in idx_values)
            if any(i.ndim for i in idx):
                raise LoweringError(
                    f"{program.name}: {buf.name} is indexed per lane; "
                    "scalar-prefetch operands take scalar indices only"
                )
            return scalar_refs[scalar_pos[buf.name]][idx]

        def scalar_loads(buf, idx_values, idx_exprs):
            return load_scalar(buf, idx_values)

        def scalar_env():
            return dict(env_scalars)

        def eval_expr(e: Expr, extra: Dict[str, Any], load_fn):
            env = scalar_env()
            env.update(extra)
            return evaluate(e, env, load_fn)

        def guard(phase: str):
            """Functional guard for value ops outside the loop phase."""
            if kval is None:
                return None
            if phase == PRE:
                return kval == 0
            if phase == POST:
                return kval == kext - 1
            return None

        def run_fill(op: FillOp, phase: str, extra):
            fillval = eval_expr(op.value, extra, no_loads)
            tile = jnp.full(op.buffer.shape, fillval, dtype=jnp.dtype(op.buffer.dtype))
            gput(op.buffer, tile, phase)

        def region_value(region: ResolvedRegion, extra):
            """Read a region of an on-chip buffer as a tile value.  Static
            starts slice the value; a start that depends on a grid id reads
            the buffer's window or scratch ref through ``pl.ds`` (Mosaic
            lowers no dynamic_slice of a value)."""
            buf = region.buffer
            starts = [eval_expr(s, extra, no_loads) for s in region.starts]
            sizes = tuple(region.sizes)
            if all(isinstance(s, (int, np.integer)) for s in starts):
                val = get(buf)
                if sizes != tuple(buf.shape):
                    val = jax.lax.slice(
                        val, starts, [s + n for s, n in zip(starts, sizes)]
                    )
            else:
                val = ref_slice(buf, [pl.ds(s, n) for s, n in zip(starts, sizes)])
            return squeeze(val, region)

        def ref_slice(buf: TileBuffer, slices):
            if window_of.get(buf.name) in rings:
                ring = rings[window_of[buf.name]]
                return ring[(cursor["slot"], *slices)].astype(jnp.dtype(buf.dtype))
            if buf.name in window_of:
                w = in_windows[window_of[buf.name]]
                it = iter(slices)
                idx = tuple(0 if c else next(it) for c in w.region.collapsed)
                return in_refs[window_of[buf.name]][idx].astype(
                    jnp.dtype(buf.dtype)
                )
            if buf.name not in scratch_pos:
                raise LoweringError(
                    f"{program.name}: dynamic slice of {buf.name}, which "
                    "has no window or scratch ref"
                )
            ref = scr_refs[scratch_pos[buf.name]]
            if buf.name in dirty:  # the ref must hold the current value
                ref[...] = values[buf.name].astype(ref.dtype)
                dirty.discard(buf.name)
            return ref[tuple(slices)]

        def run_copy(op: CopyOp, phase: str, extra):
            s, d = op.src.buffer, op.dst.buffer
            if s.scope == GLOBAL and _is_onchip(d):
                val = get(d)  # window read; already cast
                values[d.name] = val
                return
            if _is_onchip(s) and d.scope == GLOBAL:
                j = out_window_of[id(d)]
                w = out_windows[j]
                val = region_value(op.src, extra).astype(jnp.dtype(d.dtype))
                block = val.reshape(w.block_shape)
                g = guard(phase)
                if g is None:
                    out_refs[j][...] = block
                else:
                    @pl.when(g)
                    def _():
                        out_refs[j][...] = block
                return
            # on-chip -> on-chip
            val = region_value(op.src, extra)
            if tuple(op.dst.tile_shape) == tuple(d.shape) and not any(op.dst.collapsed):
                gput(d, val, phase)
                return
            # a sub-region: store into the scratch ref (Mosaic lowers no
            # dynamic_update_slice of a value), then re-read it on next use
            sizes = tuple(op.dst.sizes)
            starts = [eval_expr(x, extra, no_loads) for x in op.dst.starts]
            ref = scr_refs[scratch_pos[d.name]]
            if d.name in dirty:
                ref[...] = values[d.name].astype(ref.dtype)
                dirty.discard(d.name)
            values.pop(d.name, None)
            idx = tuple(pl.ds(x, n) for x, n in zip(starts, sizes))
            upd = val.reshape(sizes).astype(ref.dtype)
            g = guard(phase)
            if g is None:
                ref[idx] = upd
            else:
                @pl.when(g)
                def _():
                    ref[idx] = upd

        def run_gemm(op: GemmOp, phase: str, extra):
            a, b = get(op.a), get(op.b)
            if op.transpose_a:
                a = a.T if a.ndim == 2 else jnp.swapaxes(a, -1, -2)
            if op.transpose_b:
                b = b.T if b.ndim == 2 else jnp.swapaxes(b, -1, -2)
            acc = get(op.c)
            prod = jax.lax.dot_general(
                a,
                b,
                dimension_numbers=(((a.ndim - 1,), (b.ndim - 2,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            gput(op.c, acc + prod.astype(acc.dtype), phase)

        def run_reduce(op: ReduceOp, phase: str, extra):
            src = get(op.src)
            if op.kind == "absmax":
                val = jnp.max(jnp.abs(src), axis=op.axis)
            elif op.kind == "sum":
                val = jnp.sum(src, axis=op.axis)
            elif op.kind == "max":
                val = jnp.max(src, axis=op.axis)
            elif op.kind == "min":
                val = jnp.min(src, axis=op.axis)
            elif op.kind == "prod":
                val = jnp.prod(src, axis=op.axis)
            else:
                raise LoweringError(f"Unknown reduce kind {op.kind}")
            if not op.clear:
                cur = get(op.dst)
                comb = {
                    "sum": jnp.add,
                    "max": jnp.maximum,
                    "min": jnp.minimum,
                    "prod": jnp.multiply,
                    "absmax": jnp.maximum,
                }[op.kind]
                val = comb(cur, val.astype(cur.dtype))
            gput(op.dst, val, phase)

        def run_cumsum(op: CumsumOp, phase: str, extra):
            src = get(op.src)
            if op.reverse:
                src = jnp.flip(src, axis=op.axis)
            val = jnp.cumsum(src, axis=op.axis)
            if op.reverse:
                val = jnp.flip(val, axis=op.axis)
            gput(op.dst, val, phase)

        def run_parallel(op: ParallelOp, phase: str, extra):
            nax = len(op.axes)
            axis_names = [a.name for a in op.axes]
            iotas = {}
            for i, (v, e) in enumerate(zip(op.axes, op.extents)):
                shape = [1] * nax
                shape[i] = e
                iotas[v.name] = jax.lax.broadcasted_iota(jnp.int32, tuple(shape), i)

            def structured_load(buffer, idx_exprs):
                """TPU-friendly load patterns over the parallel box — whole
                tiles and broadcasts, never a gather (Mosaic has none).

                Each buffer dim is indexed by
                * a box axis -> the dim as is (its first ``extent`` entries
                  when the buffer is lane-padded past the box);
                * ``ax // c`` -> jnp.repeat along the dim (the vectorized
                  sub-byte unpack idiom; the TPU analogue of PTX lop3
                  byte-extraction in the paper's dequant kernels);
                * the constant 0 on a unit dim -> a dim that broadcasts.
                The box axes must appear in increasing order; box axes the
                buffer lacks become unit dims (a row statistic ``m[i]`` in
                an ``(i, j)`` box is ``m[:, None]``).  Returns None when
                the pattern doesn't apply.
                """
                if len(idx_exprs) != buffer.ndim:
                    return None
                val = get(buffer)
                placed = []  # box axis of each buffer dim (None: unit dim)
                for d, e in enumerate(idx_exprs):
                    if isinstance(e, VarExpr) and e.name in axis_names:
                        a = axis_names.index(e.name)
                        if buffer.shape[d] < op.extents[a]:
                            return None
                        if buffer.shape[d] > op.extents[a]:  # lane padding
                            val = jax.lax.slice_in_dim(val, 0, op.extents[a], axis=d)
                    elif (
                        isinstance(e, BinExpr)
                        and e.op == "floordiv"
                        and isinstance(e.lhs, VarExpr)
                        and e.lhs.name in axis_names
                        and isinstance(e.rhs, ConstExpr)
                    ):
                        a = axis_names.index(e.lhs.name)
                        c = int(e.rhs.value)
                        n = op.extents[a] // c  # live columns (rest: lane pad)
                        if n * c != op.extents[a] or buffer.shape[d] < n:
                            return None
                        if buffer.shape[d] > n:
                            val = jax.lax.slice_in_dim(val, 0, n, axis=d)
                        val = jnp.repeat(val, c, axis=d)
                    elif static_eval(e) == 0 and buffer.shape[d] == 1:
                        a = None
                    else:
                        return None
                    placed.append(a)
                axes = [a for a in placed if a is not None]
                if axes != sorted(set(axes)):
                    return None
                if len(placed) == nax and all(
                    a is None or a == d for d, a in enumerate(placed)
                ):
                    return val
                return val.reshape(tuple(
                    op.extents[a] if a in axes else 1 for a in range(nax)
                ))

            def load_fn(buffer, idx_values, idx_exprs):
                if buffer.scope == SCALAR:
                    return load_scalar(buffer, idx_values)
                fast = structured_load(buffer, idx_exprs)
                if fast is not None:
                    return fast
                base = get(buffer)
                idx = tuple(jnp.asarray(v) for v in idx_values)
                return base[idx]

            for buf, idx_exprs, val_expr in op.stores:
                senv = scalar_env()
                senv.update(extra)
                senv.update(iotas)
                val = evaluate(val_expr, senv, load_fn)
                direct = (
                    len(idx_exprs) == nax
                    and all(
                        isinstance(e, VarExpr) and e.name == axis_names[i]
                        for i, e in enumerate(idx_exprs)
                    )
                    and tuple(buf.shape) == op.extents
                )
                if direct:
                    new = jnp.broadcast_to(val, op.extents)
                else:
                    cur0 = get(buf)
                    idx_vals = tuple(
                        jnp.asarray(evaluate(e, senv, load_fn)) for e in idx_exprs
                    )
                    new = cur0.at[idx_vals].set(jnp.asarray(val).astype(cur0.dtype))
                gput(buf, new, phase)

        def run_custom(op: CustomOp, phase: str, extra):
            vals = [get(b) for b in op.inputs]
            out = op.fn(*vals)
            if tuple(out.shape) != tuple(op.output.shape):
                raise LoweringError(
                    f"custom op {op.name}: produced {out.shape}, expected "
                    f"{op.output.shape}"
                )
            gput(op.output, out, phase)

        def run_atomic(op: AtomicOp, phase: str, extra):
            j = out_window_of[id(op.dst.buffer)]
            val = get(op.src).astype(jnp.dtype(op.dst.buffer.dtype))
            block = val.reshape(out_windows[j].block_shape)
            comb = {"add": jnp.add, "max": jnp.maximum, "min": jnp.minimum}[op.kind]
            g = guard(phase)
            if g is None:
                out_refs[j][...] = comb(out_refs[j][...], block)
            else:
                @pl.when(g)
                def _():
                    out_refs[j][...] = comb(out_refs[j][...], block)

        def run_ops(ops: List[TileOp], phase: str, extra):
            for op in ops:
                if isinstance(op, CopyOp):
                    run_copy(op, phase, extra)
                elif isinstance(op, GemmOp):
                    run_gemm(op, phase, extra)
                elif isinstance(op, FillOp):
                    run_fill(op, phase, extra)
                elif isinstance(op, ReduceOp):
                    run_reduce(op, phase, extra)
                elif isinstance(op, CumsumOp):
                    run_cumsum(op, phase, extra)
                elif isinstance(op, ParallelOp):
                    run_parallel(op, phase, extra)
                elif isinstance(op, CustomOp):
                    run_custom(op, phase, extra)
                elif isinstance(op, AtomicOp):
                    run_atomic(op, phase, extra)
                elif isinstance(op, SerialOp):
                    for i in range(op.extent):
                        e2 = dict(extra)
                        e2[op.var.name] = i
                        run_ops(op.body, phase, e2)
                elif isinstance(op, PipelinedOp):
                    raise LoweringError("nested T.Pipelined is unsupported")
                else:
                    raise LoweringError(f"Unhandled op {op!r}")

        def tile_copy(i, k, slot):
            """The DMA of loop step ``k``'s tile of window ``i`` into ring
            slot ``slot`` (the start and the wait build the same copy)."""
            w = in_windows[i]
            starts = [eval_expr(e, {pipe.var.name: k}, scalar_loads)
                      for e in w.region.starts]
            idx = [
                st if c else None if n == full else pl.ds(st, n)
                for st, n, c, full in zip(starts, w.region.sizes,
                                          w.region.collapsed, w.param.shape)
            ]
            while idx and idx[-1] is None:  # whole minor dims: no slice
                idx.pop()
            idx = tuple(slice(None) if x is None else x for x in idx)
            return pltpu.make_async_copy(
                in_refs[i].at[idx], rings[i].at[slot], sems[i].at[slot]
            )

        def run_walk():
            """The bounded loop: ``k`` over this cell's live range, the
            next ``depth - 1`` tiles in flight, fragments carried."""
            lo, hi = (eval_expr(e, {}, scalar_loads) for e in pipe.bounds)
            lo = jnp.maximum(jnp.asarray(lo, jnp.int32), 0)
            hi = jnp.minimum(jnp.asarray(hi, jnp.int32), kext)
            carried = sorted(
                {b.name: b for op in pipe.body for b in op.buffers_written()
                 if b.name in scratch_pos}.items()
            )
            names = [n for n, _ in carried]

            def prefetch(k):
                @pl.when(k < hi)
                def _():
                    for i in manual:
                        tile_copy(i, k, k % depth).start()

            for d in range(depth - 1):
                prefetch(lo + d)

            def step(k, carry):
                outer, outer_dirty = dict(values), set(dirty)
                values.update(zip(names, carry))
                dirty.update(names)
                prefetch(k + depth - 1)
                cursor["slot"] = k % depth
                for i in manual:
                    tile_copy(i, k, cursor["slot"]).wait()
                run_ops(pipe.body, LOOP, {pipe.var.name: k})
                out = tuple(get(b) for _, b in carried)
                values.clear()
                values.update(outer)
                dirty.clear()
                dirty.update(outer_dirty)
                return out

            init = tuple(get(b) for _, b in carried)
            values.update(zip(names, jax.lax.fori_loop(lo, hi, step, init)))
            dirty.update(names)

        run_ops(phases.pre, PRE, {})
        if walks:
            run_walk()
        elif pipe is not None:
            run_ops(pipe.body, LOOP, {})
        run_ops(phases.post, POST, {})

        # write back dirty scratch accumulators, in a fixed order: the
        # kernel's text is part of the persistent compile cache's key.  A
        # walk's cell starts afresh: nothing carries over to the next.
        if not walks:
            for name in sorted(dirty):
                scr_refs[scratch_pos[name]][...] = values[name].astype(
                    scr_refs[scratch_pos[name]].dtype
                )

    # the limit plan_vmem checked against, so Mosaic's smaller scoped
    # default cannot refuse a kernel the planner accepted
    compiler_params = pltpu.CompilerParams(
        dimension_semantics=dim_sem, vmem_limit_bytes=schedule.vmem_limit
    )
    if n_scalars:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_scalars,
            grid=grid,
            in_specs=in_specs + alias_in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch_shapes,
        )
        call = pl.pallas_call(
            body,
            grid_spec=grid_spec,
            out_shape=out_shape,
            input_output_aliases=input_output_aliases,
            interpret=schedule.interpret,
            compiler_params=compiler_params,
            name=program.name,
        )
    else:
        call = pl.pallas_call(
            body,
            grid=grid,
            in_specs=in_specs + alias_in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch_shapes,
            input_output_aliases=input_output_aliases,
            interpret=schedule.interpret,
            compiler_params=compiler_params,
            name=program.name,
        )

    n_aliased = len(alias_in_specs)
    # pallas_call returns one array per out *window* (store order); the
    # CompiledKernel contract is out *param* (declaration) order — the same
    # order the reference backend produces.
    out_perm = [
        next(j for j, w in enumerate(out_windows) if w.param is p)
        for p in out_params
    ]

    def fn(*arrays):
        # scalar-prefetch operands lead (PrefetchScalarGridSpec convention),
        # then one array per input window, then aliased in-out operands.
        operands = [arrays[i] for i in scalar_arg_idx]
        operands += [arrays[i] for i in window_param_idx]
        operands += list(arrays[len(arrays) - n_aliased :]) if n_aliased else []
        res = call(*operands)
        if len(out_windows) == 1:
            return res[0]
        return tuple(res[j] for j in out_perm)

    return CompiledKernel(
        program, fn, module.info(), arg_params, out_params, backend="pallas"
    )
