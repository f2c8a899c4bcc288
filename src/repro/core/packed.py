"""PackedLinear — the paper's encoding as a first-class parameter format.

Weights of every dense projection are stored in the mmt4d packed layout
(N1, K1, N0, K0), packed ONCE at init/load (the paper packs at compile time;
same amortization).  Autodiff flows through the packed layout directly —
pack/unpack are linear, gradients and optimizer state share the packed shape,
and zero-padding regions provably stay zero under AdamW (sliced outputs give
them zero gradient).

`EncodingConfig.backend` picks the mmt4d implementation per DESIGN.md §3.
`enabled=False` stores plain (N, K) weights and runs the un-encoded reference
contraction — the upstream-IREE baseline benchmarks/table1_parity.py compares against.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import encoding
from repro.core import targets as targets_lib
from repro.kernels import ops


@dataclasses.dataclass(frozen=True)
class EncodingConfig:
    enabled: bool = True
    backend: str = "xla"        # xla | pallas | fused | reference
    # Attention op-class backend (kernels/registry.py select_attn): "xla"
    # (the jnp references), "pallas" (kernels/attn.py microkernels), or
    # "auto" (tuned table -> static policy -> xla fallback).  Mirrors
    # `backend`'s contract for the matmul class; serving (serve_llama
    # --attn-backend) defaults to "auto".
    attn_backend: str = "xla"
    # Pallas interpret mode: None = auto (interpret only when no TPU backend
    # is present — see targets.resolve_interpret); True/False force it.
    interpret: bool | None = None
    # Kernel target: None = the attached device's spec (targets.current()).
    target: targets_lib.TargetSpec | None = None
    # Pad packed tile counts to divide the mesh axes (16 in production).
    shard_multiple: int = 1
    # Serving weight quantization: "none" | "int8" (w8a8, per-channel/per-row
    # scales — kernels/mmt4d_q8.py) | "int4" (w4a8, per-K-group scales,
    # nibble-packed — kernels/mmt4d_q4.py).  Serving only.
    weight_quant: str = "none"
    # K elements per int4 scale group (weight_quant="int4" only).  Smaller
    # groups buy accuracy with more scale bytes — see docs/DESIGN.md §w4a8 group-quantized decode.
    quant_group: int = 16
    # Cross-shard reduction dtype for contracting-dim-sharded matmuls:
    # "bfloat16" halves the partial-sum all-reduce bytes (in-shard MXU
    # accumulation stays f32; only the K-shard partials are rounded).
    # Applied only when activations are bf16 (production), never in f32 tests.
    reduce_dtype: str = "float32"
    # Perf-hillclimb overrides (None = VMEM-model selection).
    gemm_blocks: tuple[int, int, int] | None = None

    def resolved_backend(self) -> str:
        return self.backend if self.enabled else "reference"


DEFAULT_ENCODING = EncodingConfig()


def linear_init(
    key: jax.Array,
    in_dim: int,
    out_dim: int,
    *,
    enc: EncodingConfig = DEFAULT_ENCODING,
    use_bias: bool = False,
    dtype: Any = jnp.float32,
    scale: float | None = None,
) -> dict:
    """Init a linear layer y = x @ W^T + b, stored packed when encoding is on."""
    scale = scale if scale is not None else in_dim**-0.5
    w_t = scale * jax.random.normal(key, (out_dim, in_dim), dtype=jnp.float32)
    w_t = w_t.astype(dtype)
    params = {}
    if enc.enabled and enc.weight_quant == "int4":
        w_q4, s_w4 = ops.pack_rhs_q4(
            w_t, group=enc.quant_group, shard_multiple=enc.shard_multiple
        )
        params["w_q4"] = w_q4
        params["w_scale4"] = s_w4
    elif enc.enabled and enc.weight_quant == "int8":
        w_q, s_w = ops.pack_rhs_q8(w_t, shard_multiple=enc.shard_multiple)
        params["w_q"] = w_q
        params["w_scale"] = s_w
    elif enc.enabled:
        params["w_packed"] = ops.pack_rhs(
            w_t, target=enc.target, shard_multiple=enc.shard_multiple
        )
    else:
        params["w_t"] = w_t
    if use_bias:
        params["b"] = jnp.zeros((out_dim,), dtype=dtype)
    return params


def linear_apply(
    params: dict,
    x: jnp.ndarray,
    *,
    n: int,
    phase: encoding.Phase,
    enc: EncodingConfig = DEFAULT_ENCODING,
    out_dtype: Any = None,
) -> jnp.ndarray:
    out_dtype = out_dtype or x.dtype
    acc_dtype = jnp.float32
    if enc.reduce_dtype == "bfloat16" and x.dtype == jnp.bfloat16:
        acc_dtype = jnp.bfloat16
    quant_backend = (
        enc.backend if enc.backend in ("pallas", "fused", "auto") else "xla"
    )
    if "w_q4" in params:
        y = ops.encoded_matmul_q4(
            x,
            params["w_q4"],
            params["w_scale4"],
            n=n,
            phase=phase,
            group=enc.quant_group,
            backend=quant_backend,
            target=enc.target,
            out_dtype=out_dtype,
            interpret=enc.interpret,
        )
    elif "w_q" in params:
        y = ops.encoded_matmul_q8(
            x,
            params["w_q"],
            params["w_scale"],
            n=n,
            phase=phase,
            backend=quant_backend,
            target=enc.target,
            out_dtype=out_dtype,
            interpret=enc.interpret,
        )
    elif "w_packed" in params:
        y = ops.encoded_matmul(
            x,
            params["w_packed"],
            n=n,
            phase=phase,
            backend=enc.resolved_backend(),
            blocks=enc.gemm_blocks,
            target=enc.target,
            out_dtype=out_dtype,
            acc_dtype=acc_dtype,
            interpret=enc.interpret,
            layer=params.get("layer"),
        )
    else:
        w_t = params["w_t"]
        y = jnp.einsum(
            "...k,nk->...n", x, w_t, preferred_element_type=jnp.float32
        ).astype(out_dtype)
    if "b" in params:
        y = y + params["b"].astype(out_dtype)
    return y


def linear_out_dim(params: dict) -> int:
    """Padded output width; callers pass the true `n` to linear_apply."""
    if "w_scale4" in params:  # nibbles pack N0 in halves; the scales do not
        n1, _, n0, _ = params["w_scale4"].shape
        return n1 * n0
    for key in ("w_packed", "w_q"):
        if key in params:
            n1, _, n0, _ = params[key].shape
            return n1 * n0
    return params["w_t"].shape[0]
