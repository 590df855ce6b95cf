# Pallas TPU kernels for the framework's compute hot-spots, each with a
# pure-jnp oracle in ref.py and a jit'd dispatch wrapper in ops.py:
#
#   block_matmul    — MXU-tiled matmul (the paper's mxmBlock, TPU-native)
#   flash_attention — fused causal/windowed/softcapped GQA attention (prefill)
#   linear_attn     — chunked decayed linear attention (RWKV6 / Mamba2 / GLA)
#   cholesky_tiles  — syrk / trsm tile kernels of the Fig. 4 Cholesky
#
# All kernels are written against pl.pallas_call + explicit BlockSpec VMEM
# tiling for TPU v5e and validated on CPU with interpret=True.
from . import ops, ref

__all__ = ["ops", "ref"]
