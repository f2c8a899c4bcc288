"""Dispatch wrappers — the analogue of IREE's microkernel ABI boundary.

`encoded_matmul` is the single entry point the model zoo calls for every dense
projection.  It performs the paper's rewrite (pack -> mmt4d -> unpack) with
phase/target-selected tiles and routes the mmt4d to one of:

    backend="reference" : plain contraction, NO encoding (upstream-IREE analogue)
    backend="xla"       : pack + einsum-mmt4d + unpack, pure jnp (dry-run path)
    backend="pallas"    : the Pallas microkernels (prefill GEMM / decode GEMV)
    backend="fused"     : beyond-paper fused pack+mmt4d+unpack Pallas kernel

Layout-unification decision (TPU adaptation, see DESIGN.md §2): weights are
packed ONCE, in the GEMM-native (N0=128, K0=128) tile layout, and shared by
prefill and decode.  The paper's phase-specific tile rule (decode N0=VLEN/4)
is honoured at the *kernel block* level instead: the decode GEMV kernel streams
`bn1` adjacent N tiles per grid step (bn1*128 ≈ the paper's wide-N), so serving
does not hold two packed copies of every weight.

The same unification extends to the fused decode fast path: because weights
stay in the one GEMM-native packed layout, `backend="fused"` can serve BOTH
regimes from the same rhs4 buffer — prefill routes to the fused GEMM
(`fused_pack_mmt4d.py`, 128-row slabs) and decode routes to the fused GEMV
(`fused_gemv.py`, sublane-padded row block, N-only weight-streaming grid).
Neither path materializes a packed activation or packed output in HBM: the
pack of the LHS and the unpack of the result live inside the kernel, which at
decode removes ~2*M*K*s + 2*M*N*4 bytes of HBM traffic per projection — the
dominant non-weight traffic of the paper's bandwidth-bound decode regime (see
docs/DESIGN.md §Fused GEMV).  The w8a8 path gets the same treatment:
`fused_gemv_q8_pallas` folds the factorized-scale epilogue into the dispatch.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import encoding
from repro.core import targets as targets_lib
from repro.kernels import fused_gemv as fused_gemv_lib
from repro.kernels import fused_pack_mmt4d as fused_lib
from repro.kernels import mmt4d as mmt4d_lib
from repro.kernels import mmt4d_gemv as gemv_lib
from repro.kernels import mmt4d_q4 as q4_lib
from repro.kernels import mmt4d_q8 as q8_lib
from repro.kernels import pack as pack_lib
from repro.kernels import ref
from repro.kernels import registry

Phase = encoding.Phase

# "auto" defers backend choice to the dispatch registry (kernels/registry.py):
# tuned table first, static policy second, reference fallback on unknown keys.
BACKENDS = ("reference", "xla", "pallas", "fused", "auto")

# Row ceiling for the fused decode GEMV: the full (M, K) activation block stays
# VMEM-resident across the whole grid, so M is bounded by the live decode slots
# (a few to a few dozen); larger fused matmuls take the 128-row GEMM slab path.
_FUSED_GEMV_MAX_ROWS = 256


def _largest_divisor_leq(n: int, cap: int) -> int:
    cap = max(1, min(n, cap))
    for d in range(cap, 0, -1):
        if n % d == 0:
            return d
    return 1


def pack_rhs(
    w_t: jnp.ndarray,
    *,
    tiles: encoding.TileSizes | None = None,
    target: targets_lib.TargetSpec | None = None,
    shard_multiple: int = 1,
) -> jnp.ndarray:
    """Pack a transposed weight (N, K) into (N1, K1, N0, K0). One-time cost.

    Always uses the GEMM-native layout (see layout-unification note above).
    `shard_multiple` pads the N1/K1 tile counts so they divide the mesh axes
    (production setting: 16); padding provably stays zero under training.
    """
    if tiles is None:
        tiles = encoding.select_tile_sizes(
            encoding.Phase.PREFILL, lhs_dtype=w_t.dtype,
            target=targets_lib.resolve(target),
        )
    p4 = ref.pack(w_t, (tiles.n0, tiles.k0))
    if shard_multiple > 1:
        n1, k1, n0, k0 = p4.shape
        pn = (-n1) % shard_multiple
        pk = (-k1) % shard_multiple
        if pn or pk:
            p4 = jnp.pad(p4, ((0, pn), (0, pk), (0, 0), (0, 0)))
    return p4


def _select_m0(
    phase: Phase, dtype, m: int, target: targets_lib.TargetSpec
) -> int:
    if target.mxu_dim == 1:
        return encoding.paper_tile_sizes(phase).m0
    if phase is Phase.DECODE:
        sub = targets_lib.sublanes_for_dtype(target, jnp.dtype(dtype).itemsize)
        return max(1, min(sub, m))
    return target.mxu_dim


def _pad_rows(x2d: jnp.ndarray, mult: int) -> jnp.ndarray:
    pad = (-x2d.shape[0]) % mult
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    return x2d


def encoded_matmul(
    x: jnp.ndarray,
    rhs4: jnp.ndarray,
    *,
    n: int,
    phase: Phase,
    backend: str = "xla",
    m0: int | None = None,
    blocks: tuple[int, int, int] | None = None,
    target: targets_lib.TargetSpec | None = None,
    out_dtype: Any = None,
    acc_dtype: Any = jnp.float32,
    interpret: bool | None = None,
    layer: jnp.ndarray | int | None = None,
) -> jnp.ndarray:
    """x (..., K) @ W^T where rhs4 is the packed (N1, K1, N0, K0) weight.

    With `layer`, rhs4 is a layer scan's stack of packed weights
    (L, N1, K1, N0, K0) and W is its layer `layer`: the fused decode GEMV
    streams it in place, every other path slices it out first.

    Returns (..., n) in `out_dtype` (default: x.dtype). `acc_dtype` is the
    cross-shard reduction dtype (see EncodingConfig.reduce_dtype); in-shard
    MXU accumulation is f32 regardless.  `blocks` overrides the VMEM-model
    block selection (perf hillclimb knob).  `interpret=None` auto-detects:
    interpreted Pallas only when no TPU backend is present.
    """
    assert backend in BACKENDS, backend
    target = targets_lib.resolve(target)
    interpret = targets_lib.resolve_interpret(interpret)
    out_dtype = out_dtype or x.dtype
    n1, k1, n0, k0 = rhs4.shape[-4:]
    k = x.shape[-1]
    assert k <= k1 * k0, (x.shape, rhs4.shape)
    lead = x.shape[:-1]
    x2d = x.reshape(-1, k)
    m = x2d.shape[0]
    choice = registry.select(
        quant="none", phase=phase, m=m, target=target,
        requested=backend, blocks=blocks,
    )
    backend, blocks = choice.backend, choice.blocks
    if k != k1 * k0:  # K padding lives in the packed weight; mirror it on lhs.
        x2d = jnp.pad(x2d, ((0, 0), (0, k1 * k0 - k)))

    if backend == "fused":
        if phase is Phase.DECODE and m <= _FUSED_GEMV_MAX_ROWS:
            # Decode fast path: fused GEMV — plain 2-D row block in, packed
            # weights streamed once, plain 2-D out. Rows pad to one sublane
            # group (8/16/32 by dtype), not the GEMM's 128-row slab.
            sub = targets_lib.sublanes_for_dtype(
                target, jnp.dtype(x.dtype).itemsize
            )
            xp = _pad_rows(x2d, sub)
            want_bn1 = (
                _gemv_bn1(n0, k0, k1, target, jnp.dtype(rhs4.dtype).itemsize)
                if blocks is None
                else blocks[1]
            )
            bn1 = _fused_gemv_plan(
                rows=xp.shape[0],
                n1=n1, k1=k1, n0=n0, k0=k0,
                lhs_itemsize=jnp.dtype(x.dtype).itemsize,
                rhs_itemsize=jnp.dtype(rhs4.dtype).itemsize,
                want_bn1=want_bn1,
                target=target,
            )
            if bn1 is not None:
                out2d = fused_gemv_lib.fused_gemv_pallas(
                    xp, rhs4, bn1=bn1, layer=layer, interpret=interpret
                )
                return out2d[:m, :n].astype(out_dtype).reshape(*lead, n)
            # VMEM can't hold the resident row block even at bn1=1:
            # fall through to the 128-row GEMM slab path below.

    if layer is not None:
        rhs4 = jax.lax.dynamic_index_in_dim(rhs4, layer, keepdims=False)

    if backend == "reference":
        w_t = ref.unpack(rhs4, (n, k1 * k0))[:, :k]
        out = ref.matmul_reference(x2d[:, :k], w_t).astype(out_dtype)
        return out.reshape(*lead, n)

    if backend == "fused":
        xp = _pad_rows(x2d, 128)
        want = blocks if blocks is not None else (4, 2, 4)
        # Clamp to divisors of this shape's tile counts: tuned/explicit blocks
        # are measured on representative shapes and must stay legal everywhere.
        bm1 = _largest_divisor_leq(xp.shape[0] // 128, want[0])
        bn1 = _largest_divisor_leq(n1, want[1])
        bk1 = _largest_divisor_leq(k1, want[2])
        out2d = fused_lib.fused_pack_mmt4d_pallas(
            xp,
            rhs4,
            blocks=(bm1, bn1, bk1),
            out_dtype=jnp.float32,
            interpret=interpret,
        )
        return out2d[:m, :n].astype(out_dtype).reshape(*lead, n)

    if m0 is None:
        m0 = _select_m0(phase, x.dtype, m, target)
    xp = _pad_rows(x2d, m0)
    m1 = xp.shape[0] // m0
    lhs4 = ref.pack(xp, (m0, k0))

    if backend == "xla":
        out4 = ref.mmt4d(lhs4, rhs4, acc_dtype=acc_dtype)
    elif phase is Phase.DECODE and m1 == 1:
        # The paper's decode GEMV microkernel: weight-streaming, wide-N blocks.
        want_bn1 = (
            _gemv_bn1(n0, k0, k1, target, jnp.dtype(rhs4.dtype).itemsize)
            if blocks is None
            else blocks[1]
        )
        bn1 = _largest_divisor_leq(n1, want_bn1)
        out4 = gemv_lib.mmt4d_gemv_pallas(lhs4, rhs4, bn1=bn1, interpret=interpret)
    else:
        # The paper's prefill GEMM microkernel (also used for skinny decode GEMM
        # when many batch rows are live).
        if blocks is None:
            tiles = encoding.TileSizes(m0=m0, n0=n0, k0=k0)
            kb = encoding.select_kernel_blocks(
                tiles,
                phase,
                m1=m1,
                n1=n1,
                k1=k1,
                lhs_itemsize=jnp.dtype(x.dtype).itemsize,
                rhs_itemsize=jnp.dtype(rhs4.dtype).itemsize,
                target=target,
            )
            blocks = (kb.bm1, kb.bn1, kb.bk1)
        bm1 = _largest_divisor_leq(m1, blocks[0])
        bn1 = _largest_divisor_leq(n1, blocks[1])
        bk1 = _largest_divisor_leq(k1, blocks[2])
        out4 = mmt4d_lib.mmt4d_pallas(
            lhs4, rhs4, blocks=(bm1, bn1, bk1), interpret=interpret
        )

    out2d = ref.unpack(out4, (xp.shape[0], n1 * n0))
    return out2d[:m, :n].astype(out_dtype).reshape(*lead, n)


def _fused_gemv_plan(
    *,
    rows: int,
    n1: int,
    k1: int,
    n0: int,
    k0: int,
    lhs_itemsize: int,
    rhs_itemsize: int,
    want_bn1: int,
    target: targets_lib.TargetSpec,
    per_tile_bytes: int | None = None,
) -> int | None:
    """VMEM-feasible bn1 for the fused GEMV, or None when none fits.

    Unlike the packed GEMV (whose lhs is one sublane-group row block), the
    fused kernel keeps the full (rows, K) activation block and an
    (rows, bn1*N0) f32 output slab resident alongside each streamed weight
    block.  Pallas double-buffers every operand (the resident lhs too), so
    the kernel holds two of each; the weight tiles feed the MXU as stored,
    with no relayouted copy (fused_gemv.py).  That footprint must fit the
    kernel's scoped VMEM less an eighth kept for Mosaic's own scratch (the
    bf16 kernel's per-slab (rows, bn1*N0) dot result among it): at
    qwen2-1.5b's down projection (K = 8960, one weight tile 2.2 MiB) this
    admits bn1 = 3 at 8 rows and 2 at 64, and the v5e compiler refuses 4.
    `per_tile_bytes` overrides the dense-rhs tile footprint for formats
    whose streamed bytes are not k1*n0*k0*itemsize (the nibble-packed w4a8
    tile + its scales).
    """
    budget = target.vmem_bytes * 7 // 8
    lhs_bytes = rows * k1 * k0 * lhs_itemsize
    per_tile = (
        per_tile_bytes if per_tile_bytes is not None else k1 * n0 * k0 * rhs_itemsize
    )

    def fits(bn1: int) -> bool:
        return 2 * (lhs_bytes + bn1 * per_tile + rows * bn1 * n0 * 4) <= budget

    bn1 = _largest_divisor_leq(n1, max(1, want_bn1))
    while bn1 > 1 and not fits(bn1):
        bn1 = _largest_divisor_leq(n1, bn1 - 1)
    return bn1 if fits(bn1) else None


def _gemv_bn1(
    n0: int,
    k0: int,
    k1: int,
    target: targets_lib.TargetSpec,
    rhs_itemsize: int = 2,
) -> int:
    """Decode streaming width: the paper's wide-N rule, VMEM-budgeted.

    select_tile_sizes(DECODE).n0 (=512 lanes on TPU) sets the *minimum* stream
    width; the ceiling is half of VMEM for the per-step weight block.
    """
    want = encoding.select_tile_sizes(Phase.DECODE, target=target).n0 // n0
    per_tile = k1 * n0 * k0 * rhs_itemsize
    cap = max(1, (target.vmem_bytes // 2) // max(per_tile, 1))
    return max(1, min(max(want, 1), cap))


# ---- int8 serving path (beyond paper) --------------------------------------


def pack_rhs_q8(
    w_t: jnp.ndarray, *, shard_multiple: int = 1
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize (per output channel) + pack. Returns (rhs4_q int8, s_w (N1,N0)).

    Weight rows use the MSE-optimal clip search (one-time cost at load);
    dynamic activation quantization stays absmax (encoded_matmul_q8)."""
    q, s = ref.quantize_rows_mse(w_t)
    rhs4 = pack_rhs(q, shard_multiple=shard_multiple)
    n1, _, n0, _ = rhs4.shape
    s_pad = jnp.zeros((n1 * n0,), jnp.float32).at[: s.shape[0]].set(s)
    return rhs4, s_pad.reshape(n1, n0)


def encoded_matmul_q8(
    x: jnp.ndarray,
    rhs4_q: jnp.ndarray,
    s_w: jnp.ndarray,
    *,
    n: int,
    phase: Phase,
    backend: str = "xla",
    blocks: tuple[int, int, int] | None = None,
    target: targets_lib.TargetSpec | None = None,
    out_dtype: Any = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """w8a8 encoded matmul: dynamic per-row activation quant, packed int8
    weights, s32 accumulation, factorized scales (see kernels/mmt4d_q8.py).

    backend="fused" at decode skips the activation pack and the output unpack
    entirely: quantized rows feed `fused_gemv_q8_pallas`, whose epilogue also
    folds in the s_a*s_w scale product (one dispatch, no HBM round-trips)."""
    target = targets_lib.resolve(target)
    interpret = targets_lib.resolve_interpret(interpret)
    out_dtype = out_dtype or x.dtype
    n1, k1, n0, k0 = rhs4_q.shape
    k = x.shape[-1]
    lead = x.shape[:-1]
    x2d = x.reshape(-1, k)
    m = x2d.shape[0]
    choice = registry.select(
        quant="w8a8", phase=phase, m=m, target=target,
        requested=backend, blocks=blocks,
    )
    backend, blocks = choice.backend, choice.blocks
    if k != k1 * k0:
        x2d = jnp.pad(x2d, ((0, 0), (0, k1 * k0 - k)))
    xq, s_a = ref.quantize_rows(x2d)

    if backend == "fused" and phase is Phase.DECODE and m <= _FUSED_GEMV_MAX_ROWS:
        sub = targets_lib.sublanes_for_dtype(target, 1)
        xqp = _pad_rows(xq, sub)
        rows = xqp.shape[0]
        bn1 = _fused_gemv_plan(
            rows=rows, n1=n1, k1=k1, n0=n0, k0=k0,
            lhs_itemsize=1, rhs_itemsize=1,
            want_bn1=(
                _gemv_bn1(n0, k0, k1, target, 1)
                if blocks is None
                else blocks[1]
            ),
            target=target,
        )
        if bn1 is not None:
            sa2 = jnp.zeros((rows, 1), jnp.float32).at[:m, 0].set(s_a)
            out2d = fused_gemv_lib.fused_gemv_q8_pallas(
                xqp, rhs4_q, sa2, s_w, bn1=bn1, interpret=interpret
            )
            return out2d[:m, :n].astype(out_dtype).reshape(*lead, n)
        # No VMEM-feasible fused plan: fall through to the packed q8 path.

    m0 = _select_m0(phase, jnp.int8, m, target)
    xq = _pad_rows(xq, m0)
    m1 = xq.shape[0] // m0
    lhs4 = ref.pack(xq, (m0, k0))
    sa_pad = jnp.zeros((m1 * m0,), jnp.float32).at[:m].set(s_a)
    sa2 = sa_pad.reshape(m1, m0)

    if backend in ("pallas", "fused"):
        # "fused" outside the GEMV regime (prefill, big M, VMEM-infeasible)
        # still runs the packed Pallas q8 kernel, not the reference einsum.
        want = blocks if blocks is not None else (4, 4, 4)
        bm1 = _largest_divisor_leq(m1, want[0])
        bn1 = _largest_divisor_leq(n1, want[1])
        bk1 = _largest_divisor_leq(k1, want[2])
        out4 = q8_lib.mmt4d_q8_pallas(
            lhs4, rhs4_q, sa2, s_w, blocks=(bm1, bn1, bk1), interpret=interpret
        )
    else:
        out4 = ref.mmt4d_q8(lhs4, rhs4_q, sa2, s_w)
    out2d = ref.unpack(out4, (xq.shape[0], n1 * n0))
    return out2d[:m, :n].astype(out_dtype).reshape(*lead, n)


# ---- int4 group-quantized serving path (w4a8) ------------------------------


def pack_rhs_q4(
    w_t: jnp.ndarray,
    *,
    group: int = ref.Q4_GROUP,
    shard_multiple: int = 1,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Group-quantize + pack a transposed weight (N, K) for the w4a8 path.

    Returns (rhs4_p (N1, K1, N0/2, K0) uint8 nibble-packed across N0,
             s_w4 (N1, K1, N0, K0/group) bf16 per-group scales).

    Quantization is per-(row, K-group) int4 with MSE-clip search (one-time
    load cost); the scales tensor mirrors the weight's tile structure so the
    kernels stream matching blocks.  Padded rows/columns carry zero scales
    and zero nibbles — their dequant is exactly 0."""
    assert 128 % group == 0, group  # groups must tile K0
    q, s = ref.quantize_rows_q4_grouped(w_t, group=group)
    tiles = encoding.select_tile_sizes(encoding.Phase.PREFILL)
    n0, k0 = tiles.n0, tiles.k0
    rhs4 = ref.pack(q, (n0, k0))          # (N1, K1, N0, K0) int8
    # Scales ship bf16: the scale stream is pure HBM overhead at decode and a
    # bf16 scale's rounding (<0.4% of the scale) is noise next to int4 error.
    s_w4 = ref.pack(s, (n0, k0 // group)).astype(jnp.bfloat16)
    if shard_multiple > 1:
        n1, k1, _, _ = rhs4.shape
        pn = (-n1) % shard_multiple
        pk = (-k1) % shard_multiple
        if pn or pk:
            rhs4 = jnp.pad(rhs4, ((0, pn), (0, pk), (0, 0), (0, 0)))
            s_w4 = jnp.pad(s_w4, ((0, pn), (0, pk), (0, 0), (0, 0)))
    return ref.pack_nibbles(rhs4, axis=-2), s_w4


def encoded_matmul_q4(
    x: jnp.ndarray,
    rhs4_p: jnp.ndarray,
    s_w4: jnp.ndarray,
    *,
    n: int,
    phase: Phase,
    group: int = ref.Q4_GROUP,
    backend: str = "xla",
    blocks: tuple[int, int, int] | None = None,
    target: targets_lib.TargetSpec | None = None,
    out_dtype: Any = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """w4a8 encoded matmul: dynamic per-row int8 activation quant, nibble-
    packed int4 weights with per-group scales (kernels/mmt4d_q4.py).

    The per-K-group scale rides inside the contraction (dequant fused into
    the kernel, per streamed tile); only the activation's per-row scale
    factors into the epilogue.  backend="fused" at decode is the
    pack/unpack-free GEMV; "pallas" (or fused outside the GEMV regime) is
    the blocked packed kernel; "xla" is the ref.mmt4d_q4 oracle."""
    target = targets_lib.resolve(target)
    interpret = targets_lib.resolve_interpret(interpret)
    out_dtype = out_dtype or x.dtype
    n1, k1, n0h, k0 = rhs4_p.shape
    n0 = 2 * n0h
    k = x.shape[-1]
    lead = x.shape[:-1]
    x2d = x.reshape(-1, k)
    m = x2d.shape[0]
    choice = registry.select(
        quant="w4a8", phase=phase, m=m, target=target,
        requested=backend, blocks=blocks,
    )
    backend, blocks = choice.backend, choice.blocks
    if k != k1 * k0:
        x2d = jnp.pad(x2d, ((0, 0), (0, k1 * k0 - k)))
    xq, s_a = ref.quantize_rows(x2d)

    # Streamed w4 tile: nibble bytes + group-scale bytes (not a dense tile).
    scale_itemsize = jnp.dtype(s_w4.dtype).itemsize
    q4_tile_bytes = k1 * (n0h * k0 + n0 * (k0 // group) * scale_itemsize)

    if backend == "fused" and phase is Phase.DECODE and m <= _FUSED_GEMV_MAX_ROWS:
        sub = targets_lib.sublanes_for_dtype(target, 1)
        xqp = _pad_rows(xq, sub)
        rows = xqp.shape[0]
        bn1 = _fused_gemv_plan(
            rows=rows, n1=n1, k1=k1, n0=n0, k0=k0,
            lhs_itemsize=1, rhs_itemsize=1,
            want_bn1=(
                _gemv_bn1(n0, k0, k1, target, 1)
                if blocks is None
                else blocks[1]
            ),
            target=target,
            per_tile_bytes=q4_tile_bytes,
        )
        if bn1 is not None:
            sa2 = jnp.zeros((rows, 1), jnp.float32).at[:m, 0].set(s_a)
            out2d = q4_lib.fused_gemv_q4_pallas(
                xqp, rhs4_p, sa2, s_w4, bn1=bn1, group=group,
                interpret=interpret,
            )
            return out2d[:m, :n].astype(out_dtype).reshape(*lead, n)
        # No VMEM-feasible fused plan: fall through to the packed q4 path.

    m0 = _select_m0(phase, jnp.int8, m, target)
    xq = _pad_rows(xq, m0)
    m1 = xq.shape[0] // m0
    lhs4 = ref.pack(xq, (m0, k0))
    sa_pad = jnp.zeros((m1 * m0,), jnp.float32).at[:m].set(s_a)
    sa2 = sa_pad.reshape(m1, m0)

    if backend in ("pallas", "fused"):
        want = blocks if blocks is not None else (4, 4, 4)
        bm1 = _largest_divisor_leq(m1, want[0])
        bn1 = _largest_divisor_leq(n1, want[1])
        bk1 = _largest_divisor_leq(k1, want[2])
        out4 = q4_lib.mmt4d_q4_pallas(
            lhs4, rhs4_p, sa2, s_w4, blocks=(bm1, bn1, bk1), group=group,
            interpret=interpret,
        )
    else:
        out4 = ref.mmt4d_q4(lhs4, rhs4_p, sa2, s_w4, group=group)
    out2d = ref.unpack(out4, (xq.shape[0], n1 * n0))
    return out2d[:m, :n].astype(out_dtype).reshape(*lead, n)


# Re-exports for benchmarks/tests.  (The attention op class lives in
# kernels/attn.py and is routed by registry.select_attn from
# models/layers.attention_apply — its callers import that module directly.)
pack_pallas = pack_lib.pack_pallas
unpack_pallas = pack_lib.unpack_pallas
mmt4d_pallas = mmt4d_lib.mmt4d_pallas
mmt4d_gemv_pallas = gemv_lib.mmt4d_gemv_pallas
fused_pack_mmt4d_pallas = fused_lib.fused_pack_mmt4d_pallas
fused_gemv_pallas = fused_gemv_lib.fused_gemv_pallas
fused_gemv_q8_pallas = fused_gemv_lib.fused_gemv_q8_pallas
fused_gemv_q4_pallas = q4_lib.fused_gemv_q4_pallas
mmt4d_q4_pallas = q4_lib.mmt4d_q4_pallas


@functools.lru_cache(maxsize=None)
def default_tiles(phase: Phase, dtype_name: str = "bfloat16") -> encoding.TileSizes:
    return encoding.select_tile_sizes(phase, lhs_dtype=jnp.dtype(dtype_name))
