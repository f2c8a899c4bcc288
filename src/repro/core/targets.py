"""Hardware target descriptions.

The paper's `materialize-device-encoding` pass keys tile selection off the
target's vector parameters (VLEN for RVV).  We model the same idea as an
explicit TargetSpec consumed by `select_tile_sizes` and by the roofline
analysis.  TPU v5e is the primary target; the RVV entry documents the paper's
original hardware so the selection logic can be tested against the paper's
published tile sizes.
"""

from __future__ import annotations

import dataclasses
import functools


@dataclasses.dataclass(frozen=True)
class TargetSpec:
    name: str
    # Compute.
    peak_flops_bf16: float  # FLOP/s per chip
    peak_ops_int8: float    # OP/s per chip
    # Memory system.
    hbm_bytes_per_s: float
    hbm_bytes: int
    vmem_bytes: int  # scoped VMEM one kernel instance may use
    # Chip-to-chip interconnect, all links of one chip together.
    ici_bytes_per_s: float
    # Compute-unit geometry.
    mxu_dim: int  # systolic array edge (matmul native tile)
    lane_count: int  # VREG lanes
    sublane_count: int  # VREG sublanes for 32-bit types


# TPU v5e.  Peaks: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip
# interconnect.  vmem_bytes is Mosaic's default scoped-VMEM limit on v5e
# (a kernel that asks for more is refused at compile time).
TPU_V5E = TargetSpec(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    peak_ops_int8=393e12,
    hbm_bytes_per_s=819e9,
    hbm_bytes=16 * 1024**3,
    vmem_bytes=16 * 1024**2,
    ici_bytes_per_s=1600e9 / 8,
    mxu_dim=128,
    lane_count=128,
    sublane_count=8,
)

# Attached-device table: `jax.devices()[0].device_kind` -> TargetSpec.
# A v5e reports either kind (jax/_src/pallas/mosaic/tpu_info.py).
DEVICE_TARGETS = {
    "TPU v5 lite": TPU_V5E,
    "TPU v5e": TPU_V5E,
}

# The paper's board (MILK-V Jupiter, SpacemiT K1/X60): VLEN=256-bit RVV.
# Kept so tests can check that our selection rule reproduces the paper's
# published tiles when pointed at the paper's hardware.
RISCV_VLEN256 = TargetSpec(
    name="riscv-rvv-vlen256",
    peak_flops_bf16=2 * 1.66e9 * 16,   # 2 flop/FMA * clock * (VLEN/16 f16 lanes)
    peak_ops_int8=2 * 1.66e9 * 32,     # VLEN/8 int8 lanes
    hbm_bytes_per_s=10.6e9,            # LPDDR4x-ish
    hbm_bytes=8 * 1024**3,
    vmem_bytes=32 * 1024,              # register file + L1 working set proxy
    ici_bytes_per_s=0.0,
    mxu_dim=1,                         # no matrix unit: vector-only
    lane_count=16,                     # VLEN/16 f16 elements per vreg
    sublane_count=1,
)

# RVV VLEN in *bits* for the paper-rule check.
RISCV_VLEN_BITS = 256


def sublanes_for_dtype(target: TargetSpec, itemsize: int) -> int:
    """TPU packs narrow dtypes into deeper sublane tiles: f32→8, bf16→16, i8→32."""
    return target.sublane_count * max(1, 4 // itemsize)


@functools.lru_cache(maxsize=1)
def has_tpu_backend() -> bool:
    """True when JAX's default backend is a real TPU (not CPU/interpret host).

    A JAX that cannot start at all raises here: a broken runtime must not
    pass for a CPU host and turn interpret mode on in silence."""
    import jax

    return jax.default_backend() == "tpu"


@functools.lru_cache(maxsize=1)
def current() -> TargetSpec:
    """The TargetSpec of the attached device.

    On a TPU backend the device kind must be in DEVICE_TARGETS: an unknown
    chip is an error, never routed by another chip's figures.  Without a
    TPU, Pallas kernels run in the interpreter, which executes the v5e
    kernels as written, so the v5e spec keys their routing and tiles."""
    if not has_tpu_backend():
        return TPU_V5E
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_TARGETS:
        raise ValueError(
            f"no TargetSpec for device kind {kind!r}; add its published "
            f"peaks to core/targets.DEVICE_TARGETS (known: "
            f"{sorted(DEVICE_TARGETS)})"
        )
    return DEVICE_TARGETS[kind]


def resolve(target: TargetSpec | None) -> TargetSpec:
    """An explicit target, or the attached device's (None)."""
    return current() if target is None else target


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve a Pallas `interpret` request: None = auto-detect.

    Auto mode interprets only when no TPU backend is present, so real-hardware
    runs never silently fall back to interpreted kernels (and CPU containers
    never try to compile Mosaic).
    """
    if interpret is not None:
        return bool(interpret)
    return not has_tpu_backend()
