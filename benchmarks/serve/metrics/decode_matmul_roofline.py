"""Kernels: the decode projections' least time (QKV, O, gate/up, down and an
untied head, rows = active slots; the larger of operations over peak and
bytes over bandwidth) over the device time of the ops that implement them
inside the decode program, in the traced window.

A later kernel that implements these projections adds its op name here in
a benchmark PR; the work counted does not change with it."""

from readings import matmul_roofline

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tok_s"
MODULE = "jit_decode"
KERNELS = ("fused_gemv", "mmt4d_gemv", "mmt4d_gemm", "fused_pack_mmt4d",
           "tensor_pack", "tensor_unpack")


def read(run):
    return matmul_roofline(run, "decode", MODULE, KERNELS)
