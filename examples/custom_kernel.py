"""Advanced tile-DSL usage: a fused dequantize-GEMM with a custom layout
annotation, a tile-library escape hatch, grid swizzling, and the cost-model
autotuner — the paper's §4 machinery end to end.

    PYTHONPATH=src python examples/custom_kernel.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Schedule, autotune, compile as tl_compile, grid_configs
from repro.core import lang as T
from repro.kernels import ref

M, N, K = 128, 256, 512


def fused_dequant_gelu_matmul(block_M, block_N, block_K, num_stages=2):
    """C = gelu(A @ dequant(B)^T): weight-only int4 + fused activation."""

    @T.prim_func
    def Fused(
        A: T.Tensor((M, K), "float32"),
        B: T.Tensor((N, K // 2), "int8"),
        C: T.Tensor((N, M), "float32"),
    ):
        with T.Kernel(T.ceildiv(N, block_N), T.ceildiv(M, block_M)) as (bx, by):
            A_s = T.alloc_shared((block_M, block_K), "float32")
            B_s = T.alloc_shared((block_N, block_K // 2), "int8")
            B_q = T.alloc_fragment((block_N, block_K), "float32")
            acc = T.alloc_fragment((block_N, block_M), "float32")
            T.use_swizzle(2)  # rasterize the parallel grid for HBM reuse
            T.clear(acc)
            for k in T.Pipelined(T.ceildiv(K, block_K), num_stages=num_stages):
                T.copy(A[by * block_M, k * block_K], A_s)
                T.copy(B[bx * block_N, k * (block_K // 2)], B_s)
                # vectorized int4 unpack on the VPU (the PTX-conversion analogue)
                for i, j in T.Parallel(block_N, block_K):
                    v = (B_s[i, j // 2] >> ((j % 2) * 4)) & 15
                    B_q[i, j] = T.cast(T.if_then_else(v >= 8, v - 16, v), "float32")
                T.gemm(B_q, A_s, acc, transpose_B=True)
            # tile-library escape hatch: fuse the activation with jnp
            act = T.alloc_fragment((block_N, block_M), "float32")
            T.call_tile_lib(lambda x: 0.5 * x * (1 + jnp.tanh(0.7978845608 * (x + 0.044715 * x**3))),
                            act, acc, name="gelu")
            T.copy(act, C[bx * block_N, by * block_M])

    return Fused


# --- autotune over block shapes with the static cost model ------------------
kernel, winner = autotune(
    fused_dequant_gelu_matmul,
    grid_configs(block_M=[64, 128], block_N=[64, 128], block_K=[128, 256]),
    schedule=Schedule(interpret=jax.default_backend() != "tpu"),  # Mosaic on a TPU
)
print(f"autotuner picked {winner.config}  (predicted {winner.score*1e6:.1f} us, "
      f"mxu={winner.mxu_util:.0%})")

rng = np.random.default_rng(0)
a = rng.standard_normal((M, K), dtype=np.float32)
bp = rng.integers(-128, 128, size=(N, K // 2)).astype(np.int8)
out = np.asarray(kernel(a, bp))


def gelu(x):
    return 0.5 * x * (1 + np.tanh(0.7978845608 * (x + 0.044715 * x**3)))


expect = gelu(np.asarray(ref.dequant_matmul(a, bp, "int4")).T)
assert np.allclose(out, expect, atol=2e-2), np.abs(out - expect).max()
print("fused dequant+gelu matmul matches oracle ✓")
