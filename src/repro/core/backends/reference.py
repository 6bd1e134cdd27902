"""Reference interpreter backend: an independent oracle for the lowering.

Walks every grid cell sequentially and interprets the traced ops over jnp
arrays — no Pallas, no BlockSpecs, no pipelining.  Tiny shapes only; its
entire value is being *structurally unrelated* to the Pallas emission so the
parity suite can cross-check them (DESIGN.md §4.2).

Two registered targets share the interpreter:

* ``reference`` — the oracle.  Concrete region starts and scalar-load
  indices are always bounds-checked: Python/NumPy negative-index wrap-around
  silently reads from the *end* of a buffer, and ``dynamic_slice`` silently
  clamps, so a corrupt block-table entry would otherwise produce plausible
  garbage instead of an error.
* ``sanitize`` — the oracle under instrumentation (DESIGN.md §5.8): pure
  outputs are poison-filled and tracked per element, duplicate writes from
  distinct grid cells, reads of never-written output regions, non-finite
  values escaping into outputs (with the op that introduced them), and
  vectorized-store bounds are all reported as :class:`SanitizeError`.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from ..buffer import GLOBAL, SCALAR, TileBuffer
from ..errors import LoweringError, SanitizeError
from ..expr import Expr, VarExpr, evaluate, loads_in
from ..lowering.indexing import no_loads
from ..lowering.module import CompiledKernel, LoweredInfo, LoweredModule
from ..tile_ops import (
    AtomicOp,
    CopyOp,
    CumsumOp,
    CustomOp,
    FillOp,
    GemmOp,
    ParallelOp,
    ReduceOp,
    ResolvedRegion,
    SerialOp,
    TileOp,
)
from . import register_backend


def _as_int(v) -> Optional[int]:
    """Concrete Python int, or None when the value is a tracer."""
    try:
        return int(v)
    except Exception:
        return None


def _check_region_starts(buffer: TileBuffer, starts, sizes, what: str):
    """Loud out-of-bounds error for concrete starts (always on): negative
    starts would wrap, over-large ones would be clamped — both silent."""
    for ax, (s, sz) in enumerate(zip(starts, sizes)):
        c = _as_int(s)
        if c is None:
            continue
        if c < 0 or c + sz > buffer.shape[ax]:
            raise SanitizeError(
                f"{what} out of bounds: {buffer.name} axis {ax} start {c} "
                f"block {sz} exceeds extent {buffer.shape[ax]}"
            )


def _check_scalar_index(buffer: TileBuffer, idx_values):
    for ax, v in enumerate(idx_values):
        c = _as_int(v)
        if c is None:
            continue
        if c < 0 or c >= buffer.shape[ax]:
            raise SanitizeError(
                f"scalar load out of bounds: {buffer.name} axis {ax} "
                f"index {c} not in [0, {buffer.shape[ax]})"
            )


class _Sanitizer:
    """Per-invocation instrumentation state for the ``sanitize`` target.

    ``writer[name]`` maps every element of a written global to the grid
    cell that last wrote it (-1 = never written).  Duplicate writes are
    judged at *cell* granularity: one cell may rewrite its own region
    (pipelined accumulation), two different cells may not — except the
    serving page-0 convention, where table-directed stores park dead rows
    on reserved page 0 (a sanctioned garbage sink).
    """

    def __init__(self, module: LoweredModule):
        self.module = module
        self.cell = -1
        self.writer: Dict[str, np.ndarray] = {}
        self.pure: set = set()
        self.taint: Dict[str, str] = {}
        aliased = {w.param.name for w in module.out_windows if w.aliased}
        for p in module.out_params:
            self.writer[p.name] = np.full(p.shape, -1, np.int64)
            if p.name not in aliased:
                self.pure.add(p.name)

    # -- helpers -----------------------------------------------------------
    def _slices(self, starts, sizes):
        out = []
        for s, sz in zip(starts, sizes):
            c = _as_int(s)
            if c is None:
                return None
            out.append(slice(c, c + sz))
        return tuple(out)

    @staticmethod
    def _page0_sink(region: ResolvedRegion, starts) -> bool:
        """A table-directed store whose dynamic axis landed on 0: the
        serving stack points every dead row at reserved page 0, so
        cross-cell duplicates there are sanctioned."""
        for ax, e in enumerate(region.starts):
            if any(ld.buffer.scope == SCALAR for ld in loads_in(e)):
                if _as_int(starts[ax]) == 0:
                    return True
        return False

    # -- events ------------------------------------------------------------
    def on_region_write(self, region: ResolvedRegion, starts, op: TileOp):
        mask = self.writer.get(region.buffer.name)
        if mask is None:
            return
        if self._page0_sink(region, starts):
            return
        sl = self._slices(starts, region.sizes)
        if sl is None:
            return
        prev = mask[sl]
        clash = prev[(prev >= 0) & (prev != self.cell)]
        if clash.size:
            raise SanitizeError(
                f"duplicate write: cells {int(clash[0])} and {self.cell} "
                f"both write {region.buffer.name}{[s for s in sl]} "
                f"({op.__class__.__name__}) — a lost write on parallel grids"
            )
        mask[sl] = self.cell

    def on_full_write(self, buf: TileBuffer):
        mask = self.writer.get(buf.name)
        if mask is None:
            return
        prev = mask
        clash = prev[(prev >= 0) & (prev != self.cell)]
        if clash.size:
            raise SanitizeError(
                f"duplicate write: cells {int(clash[0])} and {self.cell} "
                f"both write all of {buf.name}"
            )
        mask[...] = self.cell

    def on_scatter_write(self, buf: TileBuffer, idx_vals):
        mask = self.writer.get(buf.name)
        if mask is None:
            return
        try:
            idx = tuple(np.asarray(v) for v in idx_vals)
        except Exception:
            return  # traced indices: nothing concrete to mark
        prev = mask[idx]
        clash = prev[(prev >= 0) & (prev != self.cell)]
        if clash.size:
            raise SanitizeError(
                f"duplicate write: cells {int(clash[0])} and {self.cell} "
                f"both scatter into {buf.name}"
            )
        mask[idx] = self.cell

    def on_region_read(self, region: ResolvedRegion, starts):
        if region.buffer.name not in self.pure:
            return
        mask = self.writer[region.buffer.name]
        sl = self._slices(starts, region.sizes)
        if sl is None:
            return
        if (mask[sl] < 0).any():
            raise SanitizeError(
                f"read of uninitialized output region "
                f"{region.buffer.name}{[s for s in sl]} (never written)"
            )

    def note_value(self, buf: TileBuffer, val, op: TileOp, jnp):
        if buf.name not in self.writer or buf.name in self.taint:
            return
        if not jnp.issubdtype(jnp.asarray(val).dtype, jnp.floating):
            return
        if not bool(jnp.all(jnp.isfinite(val))):
            self.taint[buf.name] = (
                f"{op.__class__.__name__} at cell {self.cell}"
            )

    def check_parallel_indices(self, buf: TileBuffer, idx_vals, jnp):
        for ax, v in enumerate(idx_vals):
            arr = jnp.asarray(v)
            lo, hi = _as_int(jnp.min(arr)), _as_int(jnp.max(arr))
            if lo is None or hi is None:
                continue
            if lo < 0 or hi >= buf.shape[ax]:
                raise SanitizeError(
                    f"vectorized store out of bounds: {buf.name} axis {ax} "
                    f"indices span [{lo}, {hi}], extent {buf.shape[ax]}"
                )

    # -- verdict -----------------------------------------------------------
    def finalize(self, globals_: Dict[str, Any], jnp):
        for name in sorted(self.writer):
            val = globals_[name]
            if name in self.pure and (self.writer[name] < 0).any():
                n = int((self.writer[name] < 0).sum())
                raise SanitizeError(
                    f"output {name}: {n} element(s) never written "
                    "(poisoned values would escape to the caller)"
                )
            if jnp.issubdtype(jnp.asarray(val).dtype, jnp.floating):
                if not bool(jnp.all(jnp.isfinite(val))):
                    origin = self.taint.get(name, "unknown op")
                    raise SanitizeError(
                        f"output {name} contains non-finite values "
                        f"(first introduced by {origin})"
                    )


def _poison(shape, dtype, jnp):
    if jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        return jnp.full(shape, jnp.nan, jnp.dtype(dtype))
    return jnp.full(shape, jnp.iinfo(jnp.dtype(dtype)).min, jnp.dtype(dtype))


def _emit(module: LoweredModule, sanitize: bool) -> CompiledKernel:
    import itertools

    import jax.numpy as jnp

    program = module.program
    phases = module.phases
    pipe = phases.pipeline
    arg_params, out_params = module.arg_params, module.out_params
    kernel_axes = program.grid_axes

    def fn(*arrays):
        globals_: Dict[str, Any] = {}
        for p, a in zip(arg_params, arrays):
            globals_[p.name] = jnp.asarray(a)
        san = _Sanitizer(module) if sanitize else None
        for p in out_params:
            # In-out (aliased) params are already seeded from arg_params —
            # regions no grid cell writes must keep the caller's contents
            # (paged-KV pool semantics); pure outputs start at zero (or at
            # poison under the sanitizer, so an unwritten element can never
            # masquerade as a legitimate zero).
            if p.name not in globals_:
                globals_[p.name] = (
                    _poison(p.shape, p.dtype, jnp)
                    if sanitize
                    else jnp.zeros(p.shape, jnp.dtype(p.dtype))
                )

        for cell_id, cell in enumerate(
            itertools.product(*[range(e) for _, e in kernel_axes])
        ):
            if san is not None:
                san.cell = cell_id
            env0 = {v.name: idx for (v, _), idx in zip(kernel_axes, cell)}
            tiles: Dict[str, Any] = {}

            def run(ops, extra):
                for op in ops:
                    _ref_op(op, globals_, tiles, {**env0, **extra}, jnp, san)

            run(phases.pre, {})
            if pipe is not None:
                lo, hi = _loop_range(pipe, globals_, env0)
                if isinstance(lo, int) and isinstance(hi, int):
                    for k in range(lo, hi):
                        run(pipe.body, {pipe.var.name: k})
                else:
                    # bounds traced (under jit): walk to the bound, keeping
                    # a step's writes only where it lies in [lo, hi)
                    for k in range(pipe.extent):
                        before = dict(tiles), dict(globals_)
                        run(pipe.body, {pipe.var.name: k})
                        live = (k >= lo) & (k < hi)
                        for state, old in zip((tiles, globals_), before):
                            for name, val in state.items():
                                if name in old and val is not old[name]:
                                    state[name] = jnp.where(live, val, old[name])
            run(phases.post, {})
        if san is not None:
            san.finalize(globals_, jnp)
        outs = [globals_[p.name] for p in out_params]
        return outs[0] if len(outs) == 1 else tuple(outs)

    backend = "sanitize" if sanitize else "reference"
    info = LoweredInfo(
        grid=tuple(e for _, e in kernel_axes),
        dimension_semantics=(backend,),
        vmem=module.vmem,
        inference=module.inference,
        cost=module.cost,
        num_stages=1,
        n_windows_in=len(module.in_windows),
        n_windows_out=len(module.out_windows),
    )
    return CompiledKernel(
        program, fn, info, arg_params, out_params, backend=backend
    )


def _loop_range(pipe, globals_: Dict, env: Dict):
    """The ``k`` range one grid cell walks: ``[0, extent)`` for a static
    loop; a bounded loop's ``[max(start, 0), min(stop, extent))`` as
    Python ints where the scalar-prefetch params are concrete, else its
    traced ``(start, stop)`` (the caller then masks the steps outside)."""
    if pipe.bounds is None:
        return 0, pipe.extent

    def load(buffer, idx_values, idx_exprs):
        _check_scalar_index(buffer, idx_values)
        return globals_[buffer.name][tuple(idx_values)]

    lo, hi = (evaluate(e, env, load) for e in pipe.bounds)
    lo_i, hi_i = _as_int(lo), _as_int(hi)
    if lo_i is None or hi_i is None:
        return lo, hi
    return max(lo_i, 0), min(hi_i, pipe.extent)


@register_backend("reference")
def emit_reference(module: LoweredModule) -> CompiledKernel:
    return _emit(module, sanitize=False)


@register_backend("sanitize")
def emit_sanitize(module: LoweredModule) -> CompiledKernel:
    return _emit(module, sanitize=True)


def _ref_op(
    op: TileOp,
    globals_: Dict,
    tiles: Dict,
    env: Dict,
    jnp,
    san: Optional[_Sanitizer] = None,
):
    import jax

    def scalar_load(buffer, idx_values, idx_exprs):
        """Index-expression loads: only scalar-prefetch params are legal."""
        if buffer.scope != SCALAR:
            return no_loads(buffer, idx_values, idx_exprs)
        _check_scalar_index(buffer, idx_values)
        base = globals_[buffer.name]
        return base[tuple(jnp.asarray(v) for v in idx_values)]

    def ev(e: Expr, extra=None, load_fn=None):
        en = dict(env)
        if extra:
            en.update(extra)
        return evaluate(e, en, load_fn if load_fn is not None else scalar_load)

    def get(buf: TileBuffer):
        if buf.scope in (GLOBAL, SCALAR):
            return globals_[buf.name]
        if buf.name not in tiles:
            tiles[buf.name] = jnp.zeros(buf.shape, jnp.dtype(buf.dtype))
        return tiles[buf.name]

    def put(buf: TileBuffer, val):
        val = jnp.broadcast_to(val, buf.shape).astype(jnp.dtype(buf.dtype))
        if buf.scope == GLOBAL:
            if san is not None:
                san.on_full_write(buf)
                san.note_value(buf, val, op, jnp)
            globals_[buf.name] = val
        else:
            tiles[buf.name] = val

    def region_read(region: ResolvedRegion):
        base = get(region.buffer)
        starts = [jnp.asarray(ev(s), jnp.int32) for s in region.starts]
        _check_region_starts(region.buffer, starts, region.sizes, "region read")
        if san is not None and region.buffer.scope == GLOBAL:
            san.on_region_read(region, starts)
        val = jax.lax.dynamic_slice(base, starts, region.sizes)
        keep = tuple(i for i, c in enumerate(region.collapsed) if not c)
        return val.reshape(tuple(region.sizes[i] for i in keep))

    def region_write(region: ResolvedRegion, val):
        base = get(region.buffer)
        starts = [jnp.asarray(ev(s), jnp.int32) for s in region.starts]
        _check_region_starts(region.buffer, starts, region.sizes, "region write")
        upd = val.reshape(region.sizes).astype(base.dtype)
        if san is not None and region.buffer.scope == GLOBAL:
            san.on_region_write(region, starts, op)
            san.note_value(region.buffer, upd, op, jnp)
        out = jax.lax.dynamic_update_slice(base, upd, starts)
        if region.buffer.scope == GLOBAL:
            globals_[region.buffer.name] = out
        else:
            tiles[region.buffer.name] = out

    if isinstance(op, CopyOp):
        region_write(op.dst, region_read(op.src).astype(jnp.dtype(op.dst.buffer.dtype)))
    elif isinstance(op, FillOp):
        put(op.buffer, jnp.full(op.buffer.shape, ev(op.value), jnp.dtype(op.buffer.dtype)))
    elif isinstance(op, GemmOp):
        a, b = get(op.a), get(op.b)
        if op.transpose_a:
            a = jnp.swapaxes(a, -1, -2)
        if op.transpose_b:
            b = jnp.swapaxes(b, -1, -2)
        acc = get(op.c)
        prod = jax.lax.dot_general(
            a, b, (((a.ndim - 1,), (b.ndim - 2,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        put(op.c, acc + prod.astype(acc.dtype))
    elif isinstance(op, ReduceOp):
        src = get(op.src)
        fns = {
            "sum": jnp.sum,
            "max": jnp.max,
            "min": jnp.min,
            "prod": jnp.prod,
            "absmax": lambda x, axis: jnp.max(jnp.abs(x), axis=axis),
        }
        val = fns[op.kind](src, axis=op.axis)
        if not op.clear:
            comb = {
                "sum": jnp.add,
                "max": jnp.maximum,
                "min": jnp.minimum,
                "prod": jnp.multiply,
                "absmax": jnp.maximum,
            }[op.kind]
            val = comb(get(op.dst), val.astype(get(op.dst).dtype))
        put(op.dst, val)
    elif isinstance(op, CumsumOp):
        src = get(op.src)
        if op.reverse:
            src = jnp.flip(src, axis=op.axis)
        val = jnp.cumsum(src, axis=op.axis)
        if op.reverse:
            val = jnp.flip(val, axis=op.axis)
        put(op.dst, val)
    elif isinstance(op, ParallelOp):
        import jax.lax as lax

        nax = len(op.axes)
        iotas = {}
        for i, (v, e) in enumerate(zip(op.axes, op.extents)):
            shape = [1] * nax
            shape[i] = e
            iotas[v.name] = lax.broadcasted_iota(jnp.int32, tuple(shape), i)

        def load_fn(buffer, idx_values, idx_exprs):
            base = get(buffer)
            return base[tuple(jnp.asarray(v) for v in idx_values)]

        for buf, idx_exprs, val_expr in op.stores:
            val = ev(val_expr, extra=iotas, load_fn=load_fn)
            idx_vals = tuple(jnp.asarray(ev(e, extra=iotas, load_fn=load_fn)) for e in idx_exprs)
            direct = (
                len(idx_exprs) == nax
                and all(
                    isinstance(e, VarExpr) and e.name == op.axes[i].name
                    for i, e in enumerate(idx_exprs)
                )
                and tuple(buf.shape) == op.extents
            )
            if direct:
                put(buf, jnp.broadcast_to(val, op.extents))
            else:
                if san is not None:
                    san.check_parallel_indices(buf, idx_vals, jnp)
                cur = get(buf)
                new = cur.at[idx_vals].set(jnp.asarray(val).astype(cur.dtype))
                if buf.scope == GLOBAL:
                    if san is not None:
                        san.on_scatter_write(buf, idx_vals)
                        san.note_value(buf, new, op, jnp)
                    globals_[buf.name] = new
                else:
                    tiles[buf.name] = new
    elif isinstance(op, CustomOp):
        put(op.output, op.fn(*[get(b) for b in op.inputs]))
    elif isinstance(op, AtomicOp):
        base = get(op.dst.buffer)
        starts = [jnp.asarray(ev(s), jnp.int32) for s in op.dst.starts]
        _check_region_starts(op.dst.buffer, starts, op.dst.sizes, "atomic update")
        cur = jax.lax.dynamic_slice(base, starts, op.dst.sizes)
        val = get(op.src).reshape(op.dst.sizes).astype(cur.dtype)
        comb = {"add": jnp.add, "max": jnp.maximum, "min": jnp.minimum}[op.kind]
        globals_[op.dst.buffer.name] = jax.lax.dynamic_update_slice(
            base, comb(cur, val), starts
        )
    elif isinstance(op, SerialOp):
        for i in range(op.extent):
            for o in op.body:
                _ref_op(o, globals_, tiles, {**env, op.var.name: i}, jnp, san)
    else:
        raise LoweringError(f"reference: unhandled op {op!r}")
