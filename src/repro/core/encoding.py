"""Device-encoding materialization — the paper's compiler pass, as a JAX library.

IREE's `iree-codegen-materialize-device-encoding` pass rewrites contraction ops
into `tensor.pack -> linalg.mmt4d -> tensor.unpack` with target/phase-aware tile
sizes.  Here the same decision is made by `select_tile_sizes`, and the rewrite
is performed by `encode_matmul` / `PackedLinear` (core/packed.py): every dense
projection in the model zoo routes through this module.

Layouts (paper semantics, identical on TPU):
    pack(lhs, (M0, K0)) : (M, K)            -> (M1, K1, M0, K0)
    pack(rhs, (N0, K0)) : (N, K)  [= W^T]   -> (N1, K1, N0, K0)   # the 't' in mmt4d
    mmt4d(lhs4, rhs4)   :                   -> (M1, N1, M0, N0), f32 accumulate
    unpack(out4, (M,N)) : (M1, N1, M0, N0)  -> (M, N)

Two tiling levels (TPU adaptation):
  * the *pack tile* (M0, N0, K0) — the layout granularity, matched to the
    compute unit (MXU 128x128 for GEMM; VREG sublane x lane for GEMV).  This is
    the analogue of the paper's register tile.
  * the *kernel block* (BM1, BN1, BK1) — how many pack tiles one Pallas grid
    step keeps resident in VMEM.  The paper's ceiling is register spills; ours
    is the VMEM budget, encoded in `select_kernel_blocks`.
"""

from __future__ import annotations

import dataclasses
import enum
import math

import jax.numpy as jnp

from repro.core import targets as targets_lib


class Phase(enum.Enum):
    """Execution phase.  Matmul shape regime differs per phase (paper §Methodology)."""

    PREFILL = "prefill"   # GEMM: M = batch*seq rows
    DECODE = "decode"     # GEMV-class: M = batch rows (1 token each)
    TRAIN = "train"       # GEMM, fwd+bwd


@dataclasses.dataclass(frozen=True)
class TileSizes:
    m0: int
    n0: int
    k0: int

    def as_tuple(self) -> tuple[int, int, int]:
        return (self.m0, self.n0, self.k0)


@dataclasses.dataclass(frozen=True)
class KernelBlocks:
    """Pack-tile multiples held in VMEM per grid step."""

    bm1: int
    bn1: int
    bk1: int


def paper_tile_sizes(phase: Phase, vlen_bits: int = targets_lib.RISCV_VLEN_BITS) -> TileSizes:
    """The paper's published RVV rule (Methodology step 1):

        prefill: M,N,K = 6, VLEN/8, 1
        decode : M,N,K = 1, VLEN/4, 1
    """
    if phase in (Phase.PREFILL, Phase.TRAIN):
        return TileSizes(6, vlen_bits // 8, 1)
    return TileSizes(1, vlen_bits // 4, 1)


def select_tile_sizes(
    phase: Phase,
    *,
    lhs_dtype=jnp.bfloat16,
    m_hint: int | None = None,
    target: targets_lib.TargetSpec = targets_lib.TPU_V5E,
) -> TileSizes:
    """Target/phase-aware pack-tile selection (the VLEN-aware rule, re-solved for TPU).

    GEMM phases want MXU-native 128-multiples.  DECODE is bandwidth-bound: the
    M tile collapses to the (few) live batch rows, and N widens so the kernel
    streams weights with full lanes — the direct analogue of the paper widening
    N to VLEN/4 for GEMV.
    """
    if target.mxu_dim == 1:
        # Vector-only target: reproduce the paper's rule exactly.
        return paper_tile_sizes(phase)

    itemsize = jnp.dtype(lhs_dtype).itemsize
    sub = targets_lib.sublanes_for_dtype(target, itemsize)
    if phase in (Phase.PREFILL, Phase.TRAIN):
        return TileSizes(m0=target.mxu_dim, n0=target.mxu_dim, k0=target.mxu_dim)
    # DECODE: m0 covers the live rows, capped at one sublane group.
    rows = m_hint if m_hint is not None else 1
    m0 = max(1, min(sub, rows))
    return TileSizes(m0=m0, n0=4 * target.lane_count, k0=target.mxu_dim)


def select_kernel_blocks(
    tiles: TileSizes,
    phase: Phase,
    *,
    m1: int,
    n1: int,
    k1: int,
    lhs_itemsize: int = 2,
    rhs_itemsize: int = 2,
    acc_itemsize: int = 4,
    target: targets_lib.TargetSpec = targets_lib.TPU_V5E,
    vmem_fraction: float = 0.5,
) -> KernelBlocks:
    """VMEM-budgeted block selection — replaces the paper's register-spill ceiling.

    Per grid step the kernel holds:
        lhs block  BM1*BK1*M0*K0*lhs_itemsize
        rhs block  BN1*BK1*N0*K0*rhs_itemsize
        acc scratch BM1*BN1*M0*N0*acc_itemsize
    and the total must fit `vmem_fraction * target.vmem_bytes` (double-buffering
    headroom for the pipelined HBM->VMEM copies takes the rest).
    """
    budget = target.vmem_bytes * vmem_fraction
    m0, n0, k0 = tiles.as_tuple()

    def footprint(bm1: int, bn1: int, bk1: int) -> float:
        lhs = bm1 * bk1 * m0 * k0 * lhs_itemsize
        rhs = bn1 * bk1 * n0 * k0 * rhs_itemsize
        acc = bm1 * bn1 * m0 * n0 * acc_itemsize
        return lhs + rhs + acc

    bm1, bn1, bk1 = 1, 1, 1
    # Greedy doubling, largest-marginal-benefit first: K depth amortizes the
    # accumulator, then N (weight reuse), then M (activation reuse).
    order = ("bk1", "bn1", "bm1") if phase is not Phase.DECODE else ("bn1", "bk1", "bm1")
    grew = True
    while grew:
        grew = False
        for name in order:
            cand = dict(bm1=bm1, bn1=bn1, bk1=bk1)
            lim = dict(bm1=m1, bn1=n1, bk1=k1)
            if cand[name] * 2 > lim[name]:
                continue
            cand[name] *= 2
            if footprint(**cand) <= budget:
                bm1, bn1, bk1 = cand["bm1"], cand["bn1"], cand["bk1"]
                grew = True
    return KernelBlocks(bm1=bm1, bn1=bn1, bk1=bk1)


def quant_weight_stream_bytes(
    n: int,
    k: int,
    *,
    quant: str = "none",
    weight_itemsize: int = 2,
    group: int = 16,
    scale_itemsize: int = 2,
) -> int:
    """Bytes one decode step streams for a W (n, k) projection, per quant mode.

    This is THE decode roofline term (the weight is read once per token):
      none : n*k*weight_itemsize                        (bf16: 2 bytes/weight)
      w8a8 : n*k + n*4                                  (int8 + per-channel f32)
      w4a8 : n*k/2 + n*ceil(k/group)*scale_itemsize     (nibbles + group scales)
    With bf16 scales and g=16, w4a8 streams 0.625 bytes/weight — 1.6x less
    than w8a8 and 3.2x less than bf16 (docs/DESIGN.md §w4a8 group-quantized
    decode)."""
    if quant in ("none",):
        return n * k * weight_itemsize
    if quant in ("w8a8", "int8"):
        return n * k + n * 4
    if quant in ("w4a8", "int4"):
        return n * (k // 2) + n * math.ceil(k / group) * scale_itemsize
    raise ValueError(f"unknown quant mode {quant!r}")


# ---------------------------------------------------------------------------
# Quantized KV-cache layouts (kv8 / kv4).
#
# The same fuse-dequant-into-the-contraction move the mmt4d_q4 weight path
# proves out, applied to the OTHER decode HBM stream: K/V pages are stored
# int8 (kv8) or packed int4 nibbles (kv4) with a float32 per-token-per-head
# scale living in parallel *scale pages* (same page geometry as the data
# pages, so the BlockAllocator's page ids index both).  The attention kernels
# ride the scale pages as extra BlockSpec operands and dequantize tile-locally
# in VMEM before the online-softmax accumulate (kernels/attn.py).

KV_QUANTS = ("bf16", "kv8", "kv4")
KV_SCALE_ITEMSIZE = 4  # float32 scale per (token, kv-head)


def _unpack_nibbles(packed):
    """(…, hd//2) packed uint8 -> (…, hd) int32 in [-8, 7].

    Even head_dim elements live in the low nibble, odd in the high nibble
    (two's complement).  Pure jnp, safe inside Pallas kernel bodies."""
    b = packed.astype(jnp.int32)
    lo = b & 0xF
    hi = (b >> 4) & 0xF
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    return jnp.stack([lo, hi], axis=-1).reshape(*b.shape[:-1], b.shape[-1] * 2)


def _pack_nibbles(q):
    """(…, hd) int32 in [-8, 7] -> (…, hd//2) uint8 (inverse of _unpack_nibbles)."""
    lo = q[..., 0::2] & 0xF
    hi = q[..., 1::2] & 0xF
    return (lo | (hi << 4)).astype(jnp.uint8)


@dataclasses.dataclass(frozen=True)
class KVLayout:
    """One KV-cache storage layout: dtype, scale shape, codec, byte accounting.

    Every layer that touches K/V arrays goes through this object instead of
    assuming raw bf16: cache init sizes the leaves (`storage_head_dim`,
    `scale_shape`), the scatter-write paths quantize per page (`quantize`),
    the attention kernels / XLA fallback dequantize (`dequantize`), and the
    capacity math prices a cached token (`bytes_per_token_per_head`).

    Scales are per (token, kv-head) — decode scatters single tokens into
    pages with `.at[page, offset].set`, so a per-page *scalar* would
    retroactively re-scale previously written tokens; per-token scales kept
    in page-shaped scale arrays give page-granular alloc/free/COW with
    write-once token semantics.
    """

    name: str
    storage_dtype: object | None  # None = keep the model activation dtype
    pack_ratio: int               # head_dim elements per storage element
    qmax: int                     # symmetric integer clip bound (0 = unquantized)

    @property
    def quantized(self) -> bool:
        return self.qmax > 0

    def storage_head_dim(self, head_dim: int) -> int:
        if self.pack_ratio > 1 and head_dim % self.pack_ratio:
            raise ValueError(
                f"{self.name}: head_dim {head_dim} not divisible by pack "
                f"ratio {self.pack_ratio}"
            )
        return head_dim // self.pack_ratio

    def scale_shape(self, lead: tuple[int, ...], num_kv_heads: int) -> tuple[int, ...]:
        """Shape of the scale leaf matching data-leaf leading dims `lead`
        (e.g. (num_pages, block) or (batch, seq)) — heads stay at axis -2
        so the TP sharding rule for K/V applies unchanged."""
        return (*lead, num_kv_heads, 1)

    def bytes_per_token_per_head(self, head_dim: int) -> float:
        if not self.quantized:
            return float(head_dim * 2)  # bf16 storage, no scales
        return float(
            self.storage_head_dim(head_dim) * jnp.dtype(self.storage_dtype).itemsize
            + KV_SCALE_ITEMSIZE
        )

    def quantize(self, x):
        """(…, hd) float -> (q (…, hd / pack_ratio) storage_dtype,
        scale (…, 1) float32).  Symmetric absmax per (token, head) row.

        The scale multiplies by qmax's float32 reciprocal: XLA compiles a
        division by a constant to that multiply, so written out it gives
        the same bits eagerly as inside any jitted write path."""
        assert self.quantized, f"{self.name} has no codec"
        xf = x.astype(jnp.float32)
        amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
        scale = jnp.maximum(amax, 1e-8) * jnp.float32(1.0 / self.qmax)
        q = jnp.clip(jnp.round(xf / scale), -self.qmax, self.qmax).astype(jnp.int32)
        if self.pack_ratio > 1:
            return _pack_nibbles(q), scale
        return q.astype(self.storage_dtype), scale

    def dequantize(self, q, scale):
        """Inverse of `quantize` -> float32.  Pure jnp — the attention
        kernels call this on VMEM-resident tiles."""
        assert self.quantized, f"{self.name} has no codec"
        vals = _unpack_nibbles(q) if self.pack_ratio > 1 else q.astype(jnp.int32)
        return vals.astype(jnp.float32) * scale


_KV_LAYOUTS = {
    "bf16": KVLayout(name="bf16", storage_dtype=None, pack_ratio=1, qmax=0),
    "kv8": KVLayout(name="kv8", storage_dtype=jnp.int8, pack_ratio=1, qmax=127),
    "kv4": KVLayout(name="kv4", storage_dtype=jnp.uint8, pack_ratio=2, qmax=7),
}


def kv_layout(name: str) -> KVLayout:
    try:
        return _KV_LAYOUTS[name]
    except KeyError:
        raise ValueError(f"unknown kv_quant {name!r}; expected one of {KV_QUANTS}")


def kv_layout_for_storage(dtype) -> KVLayout:
    """Recover the layout from a cache leaf's dtype — caches are
    self-describing, so jitted model code never needs the config threaded
    through (int8 pools = kv8, packed uint8 = kv4, floats = bf16)."""
    dt = jnp.dtype(dtype)
    if dt == jnp.dtype(jnp.int8):
        return _KV_LAYOUTS["kv8"]
    if dt == jnp.dtype(jnp.uint8):
        return _KV_LAYOUTS["kv4"]
    return _KV_LAYOUTS["bf16"]


def kv_bytes_per_token(
    num_layers: int, num_kv_heads: int, head_dim: int, *, itemsize: int = 2,
    kv_quant: str = "bf16",
) -> int:
    """HBM bytes one cached token costs across all layers (K and V).

    For quantized layouts the per-head cost comes from the KVLayout codec
    (storage bytes + the float32 scale); `itemsize` only prices bf16."""
    if kv_quant in (None, "bf16"):
        return 2 * num_layers * num_kv_heads * head_dim * itemsize
    per_head = kv_layout(kv_quant).bytes_per_token_per_head(head_dim)
    return int(2 * num_layers * num_kv_heads * per_head)


def kv_capacity_requests(
    hbm_budget: int,
    *,
    max_seq: int,
    mean_tokens: int,
    block_size: int,
    num_layers: int,
    num_kv_heads: int,
    head_dim: int,
    itemsize: int = 2,
    kv_quant: str = "bf16",
) -> dict[str, int]:
    """Concurrent requests one KV HBM budget sustains, dense vs paged.

    Dense reserves max_seq tokens per slot regardless of use; paged holds
    ceil(mean_tokens / block_size) pages per in-flight request (mean_tokens =
    typical prompt + generated length), so the capacity ratio is roughly
    max_seq / round_up(mean_tokens, block_size) — the serving-plan headroom
    the paged engine converts into admitted requests (docs/DESIGN.md §Paged
    KV cache).  `kv_quant` shrinks bytes_per_token via the KVLayout,
    multiplying the pool a fixed budget sustains."""
    ptb = kv_bytes_per_token(
        num_layers, num_kv_heads, head_dim, itemsize=itemsize, kv_quant=kv_quant
    )
    dense = hbm_budget // max(1, max_seq * ptb)
    blocks_per_req = max(1, -(-mean_tokens // block_size))
    paged = hbm_budget // max(1, blocks_per_req * block_size * ptb)
    return {
        "dense": int(dense),
        "paged": int(paged),
        "bytes_per_token": ptb,
        "blocks_per_request": blocks_per_req,
    }


def _round_up(x: int, mult: int) -> int:
    return mult * math.ceil(x / mult) if mult > 0 else x


def padded_dim(dim: int, tile: int) -> int:
    return _round_up(dim, tile)


def packed_shape(rows: int, cols: int, t0: int, t1: int) -> tuple[int, int, int, int]:
    return (math.ceil(rows / t0), math.ceil(cols / t1), t0, t1)
