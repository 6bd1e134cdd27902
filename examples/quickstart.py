"""Quickstart: write a tile-DSL kernel, compile it, run it, inspect the
schedule the compiler derived.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import numpy as np

from repro.core import Schedule, compile as tl_compile
from repro.core import lang as T

# ---------------------------------------------------------------------------
# 1. Dataflow only: a tiled matmul (paper Fig. 16).  No thread binding, no
#    layouts, no pipelining code — those are the compiler's job.
# ---------------------------------------------------------------------------
M = N = K = 512
bM = bN = bK = 128


@T.prim_func
def Matmul(
    A: T.Tensor((M, K), "float32"),
    B: T.Tensor((K, N), "float32"),
    C: T.Tensor((M, N), "float32"),
):
    with T.Kernel(T.ceildiv(N, bN), T.ceildiv(M, bM), threads=128) as (bx, by):
        A_shared = T.alloc_shared((bM, bK), "float32")
        B_shared = T.alloc_shared((bK, bN), "float32")
        C_local = T.alloc_fragment((bM, bN), "float32")
        T.clear(C_local)
        for k in T.Pipelined(T.ceildiv(K, bK), num_stages=2):
            T.copy(A[by * bM, k * bK], A_shared)
            T.copy(B[k * bK, bx * bN], B_shared)
            T.gemm(A_shared, B_shared, C_local)
        T.copy(C_local, C[by * bM, bx * bN])


# ---------------------------------------------------------------------------
# 2. Compile.  On a TPU host the program compiles to a Mosaic kernel;
#    anywhere else interpret=True runs the Pallas kernel body on the CPU.
# ---------------------------------------------------------------------------
kernel = tl_compile(Matmul, Schedule(interpret=jax.default_backend() != "tpu"))

print("grid:", kernel.info.grid)
print("dimension semantics:", kernel.info.dimension_semantics)
print(kernel.info.vmem.summary())
print(kernel.info.inference.summary())
print(
    f"cost model: {kernel.info.cost.flops:.3g} FLOPs, "
    f"{kernel.info.cost.hbm_bytes:.3g} HBM bytes, "
    f"AI = {kernel.info.cost.arithmetic_intensity:.1f} FLOP/B"
)

# ---------------------------------------------------------------------------
# 3. Run and check.
# ---------------------------------------------------------------------------
rng = np.random.default_rng(0)
a = rng.standard_normal((M, K), dtype=np.float32)
b = rng.standard_normal((K, N), dtype=np.float32)
c = np.asarray(kernel(a, b))
assert np.allclose(c, a @ b, atol=1e-3)
print("matmul matches numpy ✓")
