"""Grid planning pass: kernel axes -> launch grid + scalar environment.

Kernel axes are reversed so the first-declared axis (``bx``) is the
fastest-varying parallel dimension (CUDA blockIdx.x convention), and the
pipelined axis is innermost overall so accumulators stay resident.  A
bounded ``T.Pipelined`` loop (``PipelinedOp.bounds``) gets no grid axis
where it can walk inside the kernel (:func:`walks_in_kernel`): each cell
then walks its own live range.  An
active ``T.use_swizzle`` flattens a 2-D parallel grid into one panel-raster
axis (see schedule.swizzle_decode).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

from ..layout import LANE
from ..schedule import Schedule, swizzle_decode, validate_swizzle
from .phases import LOOP, Phases


@dataclasses.dataclass
class GridPlan:
    grid: Tuple[int, ...]
    env_builder: Callable[..., Dict[str, Any]]
    kdim: Optional[int]  # grid position of the pipelined ("arbitrary") axis
    dimension_semantics: Tuple[str, ...]
    walk: bool = False  # a bounded loop walked inside the kernel


def walks_in_kernel(phases: Phases, in_windows) -> bool:
    """A bounded loop runs inside the kernel, its tiles DMA'd by hand, when
    every tile it reads has a lane-aligned minor dim: Mosaic slices an HBM
    operand only along whole (sublane, lane) tiles, so a ``(page_size, 1)``
    scale column cannot be copied by hand.  Otherwise the loop lowers as a
    static one, a grid axis over its bound."""
    pipe = phases.pipeline
    return pipe is not None and pipe.bounds is not None and all(
        w.region.sizes[-1] % LANE == 0 for w in in_windows if w.phase == LOOP
    )


def plan_grid(program, phases: Phases, schedule: Schedule, in_windows=()) -> GridPlan:
    kernel_axes = program.grid_axes  # declaration order
    n = len(kernel_axes)
    swz = schedule.grid_swizzle
    if swz is None:
        swz = program.annotations.swizzle

    pipe = phases.pipeline
    walk = walks_in_kernel(phases, in_windows)
    on_grid = pipe is not None and not walk
    kext = pipe.extent if on_grid else None
    kname = pipe.var.name if on_grid else None

    if swz is not None and n == 2:
        (v0, e0), (v1, e1) = kernel_axes
        # pallas-minor ordering: v1 (by) slower, v0 (bx) faster in raster;
        # flatten to one axis and decode with panel swizzling.  Clamp the
        # panel height to a divisor of the row extent (traced decode needs
        # uniform panels).
        factor = min(swz, e1)
        if e1 % factor != 0:
            factor = math.gcd(e1, factor) or 1
        validate_swizzle(e1, e0, factor)
        grid = (e1 * e0,) + ((kext,) if kext else ())
        sem = ("arbitrary",) * len(grid)

        def env_builder(*gids):
            flat = gids[0]
            i1, i0 = swizzle_decode(flat, e1, e0, factor)
            env = {v1.name: i1, v0.name: i0}
            if kname is not None:
                env[kname] = gids[1]
            return env

        kdim = 1 if kext else None
        return _with_override(grid, env_builder, kdim, sem, schedule, walk)

    grid = tuple(e for _, e in reversed(kernel_axes)) + ((kext,) if kext else ())
    sem = ("parallel",) * n + (("arbitrary",) if kext else ())

    def env_builder(*gids):
        env = {}
        for i, (v, _) in enumerate(kernel_axes):
            env[v.name] = gids[n - 1 - i]
        if kname is not None:
            env[kname] = gids[n]
        return env

    kdim = n if kext else None
    return _with_override(grid, env_builder, kdim, sem, schedule, walk)


def _with_override(grid, env_builder, kdim, sem, schedule: Schedule,
                   walk: bool) -> GridPlan:
    if schedule.dimension_semantics is not None:
        sem = tuple(schedule.dimension_semantics)
    return GridPlan(grid, env_builder, kdim, sem, walk)
