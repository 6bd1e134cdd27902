"""Schedule space & VMEM planning (paper §4: everything that is *not* dataflow).

The four scheduling axes of the paper map onto the TPU target as:

=====================  =====================================================
paper axis             realization here
=====================  =====================================================
thread binding         vector-lane layout inference (infer.py) — no threads
memory layout          Layout/Fragment padding + alignment (layout.py/infer)
tensorization          T.gemm -> MXU dot_general; custom ops via CustomOp
pipeline               T.Pipelined -> `arbitrary` grid axis, multi-buffered
                       BlockSpec DMA (num_stages budgeted here)
=====================  =====================================================

``Schedule`` collects the knobs a caller (or the autotuner) can set without
touching the dataflow; ``plan_vmem`` validates the resulting on-chip
footprint against the hardware budget *before* any lowering happens.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from .buffer import FRAGMENT, GLOBAL, SHARED, TileBuffer, dtype_bits
from .errors import ScheduleError
from .layout import LANE, round_up, sublane

# VMEM per chip (bytes), keyed by ``jax.Device.device_kind``.  A kernel may
# plan VMEM_HEADROOM of it; the rest is headroom for Mosaic's own spills,
# semaphores and the grid pipeline's internal buffers.  The same number is
# handed to Mosaic as ``vmem_limit_bytes`` (backends/pallas_tpu.py), so a
# kernel the planner accepts is not refused by a smaller scoped default.
VMEM_BYTES = {
    "TPU v5 lite": 128 * 1024 * 1024,  # v5e
}
VMEM_HEADROOM = 0.85
# The chip kernels are planned for when none is attached (CPU interpret runs
# and ahead-of-time compiles against a described topology).
DEFAULT_DEVICE_KIND = "TPU v5 lite"


def vmem_limit_for(device_kind: str) -> int:
    """The scoped-VMEM limit (bytes) for one chip kind; unknown is an error."""
    if device_kind not in VMEM_BYTES:
        raise ScheduleError(
            f"no VMEM size known for device kind {device_kind!r}; add it to "
            f"repro.core.schedule.VMEM_BYTES (known: {sorted(VMEM_BYTES)})"
        )
    return int(VMEM_BYTES[device_kind] * VMEM_HEADROOM)


@dataclasses.dataclass
class Schedule:
    """User/autotuner-controllable scheduling knobs for one program."""

    interpret: bool = False  # run Pallas in interpreter (CPU validation)
    num_stages: Optional[int] = None  # override T.Pipelined's stage count
    grid_swizzle: Optional[int] = None  # override T.use_swizzle
    dimension_semantics: Optional[Tuple[str, ...]] = None  # rarely needed
    device_kind: str = DEFAULT_DEVICE_KIND  # chip the VMEM plan targets
    # Advisory: collected by lower.py for the cost model / roofline.
    notes: Dict[str, object] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        vmem_limit_for(self.device_kind)  # unknown chip: fail here, loudly

    @property
    def vmem_limit(self) -> int:
        return vmem_limit_for(self.device_kind)


@dataclasses.dataclass
class BufferPlan:
    name: str
    scope: str
    logical_shape: Tuple[int, ...]
    physical_shape: Tuple[int, ...]  # padded to (sublane, lane) tiling
    copies: int  # multi-buffering factor
    bytes: int

    @property
    def waste(self) -> float:
        import numpy as np

        log = int(np.prod(self.logical_shape)) or 1
        phys = int(np.prod(self.physical_shape))
        return 1.0 - log / phys


@dataclasses.dataclass
class VmemPlan:
    buffers: List[BufferPlan]
    total_bytes: int
    limit: int

    @property
    def ok(self) -> bool:
        return self.total_bytes <= self.limit

    def summary(self) -> str:
        lines = [f"VMEM plan: {self.total_bytes/2**20:.2f} MiB / {self.limit/2**20:.1f} MiB"]
        for b in self.buffers:
            lines.append(
                f"  {b.name:<16} {b.scope:<8} {str(b.logical_shape):<18} -> "
                f"{str(b.physical_shape):<18} x{b.copies} = {b.bytes/2**10:8.1f} KiB"
                + (f"  (pad waste {b.waste:.0%})" if b.waste > 0 else "")
            )
        return "\n".join(lines)


def physical_tile_shape(shape: Tuple[int, ...], dtype: str) -> Tuple[int, ...]:
    """Pad the last two dims to the Mosaic VMEM tiling ((sublane, lane))."""
    if not shape:
        return shape
    s = list(shape)
    s[-1] = round_up(s[-1], LANE)
    if len(s) >= 2:
        s[-2] = round_up(s[-2], sublane(dtype))
    else:
        # 1-D arrays occupy a (1, lane)-tiled row per sublane group
        pass
    return tuple(s)


def plan_vmem(
    program,
    schedule: Schedule,
    pipelined_inputs: Dict[str, int],
    check: bool = True,
) -> VmemPlan:
    """Compute the on-chip footprint of a traced program.

    ``pipelined_inputs`` maps buffer name -> multi-buffering depth for shared
    buffers fed by global copies inside a T.Pipelined loop (the grid
    pipeline double/multi-buffers those windows).

    ``check=False`` returns the (possibly over-budget) plan instead of
    raising — the pass pipeline uses this so the budget stays a *backend*
    feasibility concern (the reference interpreter has no VMEM).
    """
    plans: List[BufferPlan] = []
    total = 0
    for buf in program.allocs:
        phys = physical_tile_shape(buf.shape, buf.dtype)
        copies = pipelined_inputs.get(buf.name, 1)
        if schedule.num_stages is not None and buf.name in pipelined_inputs:
            copies = max(2, schedule.num_stages)
        import numpy as np

        nbytes = int(np.prod(phys)) * dtype_bits(buf.dtype) // 8 * copies
        plans.append(
            BufferPlan(buf.name, buf.scope, buf.shape, phys, copies, nbytes)
        )
        total += nbytes
    plan = VmemPlan(plans, total, schedule.vmem_limit)
    if check and not plan.ok:
        raise ScheduleError(
            f"{program.name}: VMEM budget exceeded —\n{plan.summary()}\n"
            "Reduce block shapes or num_stages."
        )
    return plan


# ---------------------------------------------------------------------------
# Grid swizzling (T.use_swizzle): reorder the sequential grid walk.
# ---------------------------------------------------------------------------


def swizzle_decode(flat, g0: int, g1: int, factor: int):
    """Decode a flattened 2-D grid step into (i0, i1) with panel rasterization.

    Walks ``factor`` consecutive i0 values per i1 before advancing i1 —
    consecutive grid steps then reuse the same operand-1 block, which the
    Pallas pipeline detects (identical block index => copy skipped).  This is
    the TPU analogue of the L2-locality thread-block swizzle: the "cache"
    being exploited is the VMEM window itself.

    Works on ints and traced int32 scalars alike.
    """
    panel = factor * g1
    group = flat // panel
    rem = flat % panel
    if isinstance(flat, int):
        # Last (possibly ragged) panel: clamp the panel height.
        rows = min(factor, g0 - group * factor)
        i0 = group * factor + rem % rows
        i1 = rem // rows
        return i0, i1
    # Traced path: require g0 % factor == 0 (checked by caller).
    i0 = group * factor + rem % factor
    i1 = rem // factor
    return i0, i1


def validate_swizzle(g0: int, g1: int, factor: int):
    if factor <= 0:
        raise ScheduleError(f"swizzle factor must be positive, got {factor}")
    if g0 % factor != 0:
        raise ScheduleError(
            f"use_swizzle({factor}): leading grid extent {g0} must be a "
            f"multiple of the factor on the TPU lowering"
        )
