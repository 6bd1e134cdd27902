"""Model step: useful FLOPs of the window's decode ticks of an MLA + MoE
model (``costs_mla_moe.token_flops`` per live slot and tick, plus the
routed (token, held expert) pairs the decode programs counted) over the
summed enqueue-to-ready time of the decode programs in the engine's
dispatch log, times the chip's peak, in %."""
import costs_mla_moe


def read(v):
    return costs_mla_moe.decode_mfu(v)
