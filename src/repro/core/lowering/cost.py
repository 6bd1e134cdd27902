"""Cost-estimation pass (feeds autotune + benchmarks + roofline)."""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np

from ..buffer import dtype_bits
from ..tile_ops import CumsumOp, GemmOp, ParallelOp, ReduceOp, SerialOp, TileOp
from .phases import LOOP, Phases
from .windows import Window


@dataclasses.dataclass
class KernelCost:
    flops: int
    hbm_bytes: int
    grid: Tuple[int, ...]
    vmem_bytes: int

    def compute_seconds(self, peak_flops: float = 197e12) -> float:
        return self.flops / peak_flops

    def memory_seconds(self, hbm_bw: float = 819e9) -> float:
        return self.hbm_bytes / hbm_bw

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops / max(self.hbm_bytes, 1)

    def bound(self, peak_flops: float = 197e12, hbm_bw: float = 819e9) -> str:
        return (
            "compute" if self.compute_seconds(peak_flops) >= self.memory_seconds(hbm_bw)
            else "memory"
        )


def estimate_cost(
    program,
    phases: Phases,
    grid: Tuple[int, ...],
    in_windows: List[Window],
    out_windows: List[Window],
    vmem,
) -> KernelCost:
    pipe = phases.pipeline
    # a bounded loop is costed at its bound (the worst case)
    cells = int(np.prod([e for _, e in program.grid_axes]))
    total_steps = cells * (pipe.extent if pipe is not None else 1)

    flops = 0

    def op_flops(op: TileOp) -> int:
        if isinstance(op, GemmOp):
            return 2 * op.m * op.n * op.k
        if isinstance(op, ParallelOp):
            return int(np.prod(op.extents)) * max(1, len(op.stores)) * 2
        if isinstance(op, (ReduceOp,)):
            return op.src.size
        if isinstance(op, CumsumOp):
            return op.src.size
        if isinstance(op, SerialOp):
            return op.extent * sum(op_flops(o) for o in op.body)
        return 0

    for op in phases.pre + phases.post:
        flops += cells * op_flops(op)
    if pipe is not None:
        for op in pipe.body:
            flops += total_steps * op_flops(op)

    hbm = 0
    for w in in_windows:
        steps = total_steps if w.phase == LOOP else cells
        hbm += steps * int(np.prod(w.block_shape)) * dtype_bits(w.param.dtype) // 8
    for w in out_windows:
        steps = total_steps if w.phase == LOOP else cells
        hbm += steps * int(np.prod(w.block_shape)) * dtype_bits(w.param.dtype) // 8

    return KernelCost(flops=flops, hbm_bytes=hbm, grid=tuple(grid), vmem_bytes=vmem.total_bytes)
