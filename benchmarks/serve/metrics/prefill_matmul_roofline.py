"""Kernels: the prefill projections' least time (QKV, O, gate/up, down over
the real prompt tokens, and the untied head at each sequence's last
position; the larger of operations over peak and bytes over bandwidth) over
the device time of the ops that implement them inside the prefill program,
in the traced window.  The untied head runs through the same packed GEMM as
the projections.

A later kernel that implements these projections adds its op name here in
a benchmark PR; the work counted does not change with it."""

from prefill_costs import PREFILL_MODULE
from readings import matmul_roofline

LAYER = "kernels"
UNIT = "%"
MOVES = "out_tok_s"
KERNELS = ("fused_gemv", "mmt4d_gemv", "mmt4d_gemm", "fused_pack_mmt4d",
           "tensor_pack", "tensor_unpack")


def read(run):
    return matmul_roofline(run, "prefill", PREFILL_MODULE, KERNELS)
