"""The serving path's Pallas kernels, compiled for a described TPU v5e chip.

Interpret mode runs a kernel as written; only Mosaic, the TPU kernel
compiler, refuses a relayout it cannot lower or a block that overflows the
scoped VMEM.  Each case lowers one kernel at qwen2-1.5b's published widths
(d_model 1536, 12/2 heads of 128, d_ff 8960) through the same entry point
the model calls, with the blocks the dispatch registry picks, and compiles
it for one chip of a `v5e:2x2` topology (~2 s each, no chip attached).

The topology is described inside a module fixture only: describing it loads
the TPU library, which one process at a time may hold.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.core import targets
from repro.core.encoding import Phase
from repro.core.packed import EncodingConfig
from repro.kernels import attn, ops
from repro.models import transformer as T
from repro.serving import engine

D, Q, KV, FF = 1536, 1536, 256, 8960  # qwen2-1.5b projection widths
HEADS, KV_HEADS, HEAD_DIM = 12, 2, 128
SLOTS, PROMPT, MAX_SEQ, BLOCK = 8, 512, 1024, 16  # chip_smoke.py's serving shape


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache out of it.
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _compile(fn, sharding, *args):
    """Compile `fn` for the described chip; args are ShapeDtypeStructs (or
    pytrees of them).  Returns the compiled program's HLO text."""
    placed = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), args
    )
    hlo = jax.jit(fn).lower(*placed).compile().as_text()
    assert "tpu_custom_call" in hlo  # a Mosaic kernel, not an XLA fallback
    return hlo


def _weight(pack, n, k):
    """Shapes of a packed (N, K) weight, as the model stores it."""
    return jax.eval_shape(pack, jax.ShapeDtypeStruct((n, k), jnp.bfloat16))


DECODE_MATMULS = [(Q, D, "q_o"), (KV, D, "k_v"), (FF, D, "gate_up"), (D, FF, "down")]


@pytest.mark.parametrize(
    "rows,n,k",
    [pytest.param(SLOTS, n, k, id=name) for n, k, name in DECODE_MATMULS]
    + [pytest.param(64, n, k, id=f"m64_{name}") for n, k, name in DECODE_MATMULS],
)
def test_decode_fused_gemv_compiles(one_chip, rows, n, k):
    """Decode matmul at 8 slots and at the benchmark's 64: the registry's
    fused GEMV with its tuned bn1, capped by the VMEM plan (down, K = 8960,
    is the widest weight tile)."""
    w = _weight(ops.pack_rhs, n, k)
    _compile(
        lambda x, w: ops.encoded_matmul(
            x, w, n=n, phase=Phase.DECODE, backend="auto",
            target=targets.TPU_V5E, interpret=False,
        ),
        one_chip, jax.ShapeDtypeStruct((rows, k), jnp.bfloat16), w,
    )


def test_prefill_fused_gemm_compiles(one_chip):
    """Prefill matmul over 8 prompts of 512: the fused pack+GEMM slab at
    the down projection's K."""
    w = _weight(ops.pack_rhs, D, FF)
    _compile(
        lambda x, w: ops.encoded_matmul(
            x, w, n=D, phase=Phase.PREFILL, backend="auto",
            target=targets.TPU_V5E, interpret=False,
        ),
        one_chip, jax.ShapeDtypeStruct((SLOTS * PROMPT, FF), jnp.bfloat16), w,
    )


@pytest.mark.parametrize("quant", ["w8a8", "w4a8"])
def test_decode_quantized_gemv_compiles(one_chip, quant):
    """The quantized decode GEMVs the registry selects (fused), at the down
    projection."""
    if quant == "w8a8":
        rhs, scale = _weight(ops.pack_rhs_q8, D, FF)
        fn = lambda x, w, s: ops.encoded_matmul_q8(  # noqa: E731
            x, w, s, n=D, phase=Phase.DECODE, backend="auto",
            target=targets.TPU_V5E, interpret=False,
        )
    else:
        rhs, scale = _weight(ops.pack_rhs_q4, D, FF)
        fn = lambda x, w, s: ops.encoded_matmul_q4(  # noqa: E731
            x, w, s, n=D, phase=Phase.DECODE, backend="auto",
            target=targets.TPU_V5E, interpret=False,
        )
    _compile(fn, one_chip, jax.ShapeDtypeStruct((SLOTS, FF), jnp.bfloat16), rhs, scale)


@pytest.mark.parametrize(
    "window,kv_quant", [(1, "bf16"), (4, "bf16"), (1, "kv8")],
    ids=["decode", "verify4", "decode_kv8"],
)
def test_paged_decode_attention_compiles(one_chip, window, kv_quant):
    """Paged decode attention, 12 query heads over 2 KV heads (groups of 6),
    block size 16; window 4 is a speculative-decode verify window.  bf16
    pages stream by page DMAs; kv8's int8 pages and scale pages (tiles the
    DMA cannot slice) through the BlockSpec page stream."""
    pages = 1 + SLOTS * MAX_SEQ // BLOCK
    q = jax.ShapeDtypeStruct((SLOTS, window, HEADS, HEAD_DIM), jnp.bfloat16)
    table = jax.ShapeDtypeStruct((SLOTS, MAX_SEQ // BLOCK), jnp.int32)
    pos = jax.ShapeDtypeStruct((SLOTS,), jnp.int32)
    if kv_quant == "bf16":
        pool = jax.ShapeDtypeStruct((pages, BLOCK, KV_HEADS, HEAD_DIM), jnp.bfloat16)
        _compile(
            lambda q, k, v, t, p: attn.paged_decode_attention(q, k, v, t, p),
            one_chip, q, pool, pool, table, pos,
        )
        return
    pool = jax.ShapeDtypeStruct((pages, BLOCK, KV_HEADS, HEAD_DIM), jnp.int8)
    scale = jax.ShapeDtypeStruct((pages, BLOCK, KV_HEADS, 1), jnp.float32)
    _compile(
        lambda q, k, v, t, p, ks, vs: attn.paged_decode_attention(
            q, k, v, t, p, k_scale=ks, v_scale=vs, kv_quant=kv_quant
        ),
        one_chip, q, pool, pool, table, pos, scale, scale,
    )


def test_flash_prefill_attention_compiles(one_chip):
    """Causal flash prefill over 8 prompts of 512 with the tuned 128/128
    chunks."""
    q = jax.ShapeDtypeStruct((SLOTS, PROMPT, HEADS, HEAD_DIM), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((SLOTS, PROMPT, KV_HEADS, HEAD_DIM), jnp.bfloat16)
    _compile(
        lambda q, k, v: attn.flash_prefill_attention(
            q, k, v, q_chunk=128, kv_chunk=128
        ),
        one_chip, q, kv, kv,
    )


def test_paged_decode_step_writes_the_pool_in_place(one_chip):
    """The engine's decode step for a 3-layer model at qwen2-1.5b's widths
    on a paged bf16 pool, donated as the engine donates it: the layer scan
    carries the pool whole, so the program holds no copy, dynamic slice or
    dynamic-update-slice of one layer's pool or of the whole pool (what a
    scan over the pool as xs/ys and the loop's whole-pool copies compile
    to), and each pool leaf is aliased input to output."""
    layers, pages = 3, 1 + SLOTS * MAX_SEQ // BLOCK
    cfg = dataclasses.replace(registry.get_config("qwen2-1.5b"), num_layers=layers)
    enc = EncodingConfig(enabled=True, backend="auto", attn_backend="auto",
                         target=targets.TPU_V5E, interpret=False)
    params = jax.eval_shape(lambda: T.model_init(jax.random.PRNGKey(0), cfg, enc))
    caches = jax.eval_shape(lambda: T.cache_init(
        cfg, SLOTS, MAX_SEQ, cache_mode="paged", block_size=BLOCK, num_pages=pages))
    args = (params, caches, jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32),
            jax.ShapeDtypeStruct((SLOTS,), jnp.int32))
    placed = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), args
    )
    step = jax.jit(engine.make_decode_step(cfg, enc), donate_argnums=(1,), keep_unused=True)
    hlo = step.lower(*placed).compile().as_text()
    assert "paged_decode_attention" in hlo

    layer_pool = pages * BLOCK * KV_HEADS * HEAD_DIM
    moves = []
    for name, dims, op in re.findall(
        r"%(\S+) = \(?\w+\[([\d,]*)\][^ ]* (copy|copy-start|dynamic-slice|"
        r"dynamic-update-slice)\(", hlo
    ):
        n = 1
        for dim in filter(None, dims.split(",")):
            n *= int(dim)
        if n in (layer_pool, layers * layer_pool):
            moves.append((name, op, dims))
    assert not moves, moves

    # Parameter and output numbers of the pool leaves: (params, caches, ...)
    # in, (token, logits, caches) out.
    cache_leaves = jax.tree_util.tree_flatten_with_path(caches)[0]
    first_in = len(jax.tree.leaves(params))
    alias = dict(
        (int(o), int(i)) for o, i in re.findall(r"\{(\d+)\}: \((\d+), \{\}", hlo)
    )
    for j, (path, leaf) in enumerate(cache_leaves):
        if leaf.ndim == 5:  # k, v: (layers, pages, bs, KV, D)
            assert alias.get(2 + j) == first_in + j, (jax.tree_util.keystr(path), alias)


def test_decode_step_streams_packed_weights_in_place(one_chip):
    """The engine's decode step at the benchmark's 64 slots, 3 layers at
    qwen2-1.5b's widths: each fused GEMV takes its projection's whole
    (layers, N1, K1, N0, K0) stack and streams one layer's blocks from it,
    so no op of the program makes a copy of one layer's packed weight."""
    layers, slots, pages = 3, 64, 1 + 64 * MAX_SEQ // BLOCK
    cfg = dataclasses.replace(registry.get_config("qwen2-1.5b"), num_layers=layers)
    enc = EncodingConfig(enabled=True, backend="auto", attn_backend="auto",
                         target=targets.TPU_V5E, interpret=False)
    params = jax.eval_shape(lambda: T.model_init(jax.random.PRNGKey(0), cfg, enc))
    caches = jax.eval_shape(lambda: T.cache_init(
        cfg, slots, MAX_SEQ, cache_mode="paged", block_size=BLOCK, num_pages=pages))
    args = (params, caches, jax.ShapeDtypeStruct((slots, 1), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32))
    placed = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), args
    )
    step = jax.jit(engine.make_decode_step(cfg, enc), donate_argnums=(1,), keep_unused=True)
    hlo = step.lower(*placed).compile().as_text()

    stacks = {
        ",".join(map(str, w.shape))
        for path, w in jax.tree_util.tree_flatten_with_path(params["groups"])[0]
        if jax.tree_util.keystr(path).endswith("['w_packed']")
    }
    assert len(stacks) == 4, stacks  # q/o, k/v, gate/up, down
    gemv_weights = set(re.findall(
        r"custom-call\(.*operand_layout_constraints=\{s32\[1\]\{0\}, bf16\[[\d,]+\]"
        r"\{[\d,]+\}, bf16\[([\d,]+)\].*fused_gemv", hlo
    ))
    assert gemv_weights == stacks, (gemv_weights, stacks)
    layer_copies = [
        (name, dims) for name, dims in re.findall(r"%(\S+) = bf16\[([\d,]+)\]", hlo)
        if any(dims == s.split(",", 1)[1] for s in stacks)
    ]
    assert not layer_copies, layer_copies


# qwen2.5-14b (benchmarks/serve/configs/qwen2.5-14b-d8.json): d_model 5120,
# 40 query heads over 8 KV heads of 128 (groups of 5), d_ff 13824, and an
# untied 152064-row head; 8 slots, prompts padded up to 4096, max_seq 4128.
D14, KV14, FF14, VOCAB14 = 5120, 1024, 13824, 152064
HEADS14, KV_HEADS14, SLOTS14, PROMPT14, MAX_SEQ14 = 40, 8, 8, 4096, 4128


@pytest.mark.parametrize(
    "n,k",
    [(D14, D14), (KV14, D14), (FF14, D14), (D14, FF14), (VOCAB14, D14)],
    ids=["q_o", "k_v", "gate_up", "down", "head"],
)
def test_qwen2_5_14b_decode_gemv_compiles(one_chip, n, k):
    """The m8 decode GEMV at the 14b's widths: its VMEM plan at K = 13824
    (down) and at the 152064-row untied head."""
    w = _weight(ops.pack_rhs, n, k)
    _compile(
        lambda x, w: ops.encoded_matmul(
            x, w, n=n, phase=Phase.DECODE, backend="auto",
            target=targets.TPU_V5E, interpret=False,
        ),
        one_chip, jax.ShapeDtypeStruct((SLOTS14, k), jnp.bfloat16), w,
    )


def test_qwen2_5_14b_attention_compiles(one_chip):
    """Paged decode attention with groups of 5 over the longdoc cell's pool
    and its widest block table, and causal flash prefill over 8 prompts
    padded to 4096."""
    blocks = -(-MAX_SEQ14 // BLOCK)
    pages = 1 + SLOTS14 * blocks
    q = jax.ShapeDtypeStruct((SLOTS14, 1, HEADS14, HEAD_DIM), jnp.bfloat16)
    pool = jax.ShapeDtypeStruct((pages, BLOCK, KV_HEADS14, HEAD_DIM), jnp.bfloat16)
    _compile(
        lambda q, k, v, t, p: attn.paged_decode_attention(q, k, v, t, p),
        one_chip, q, pool, pool, jax.ShapeDtypeStruct((SLOTS14, blocks), jnp.int32),
        jax.ShapeDtypeStruct((SLOTS14,), jnp.int32),
    )
    q = jax.ShapeDtypeStruct((SLOTS14, PROMPT14, HEADS14, HEAD_DIM), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((SLOTS14, PROMPT14, KV_HEADS14, HEAD_DIM), jnp.bfloat16)
    _compile(
        lambda q, k, v: attn.flash_prefill_attention(q, k, v, q_chunk=128, kv_chunk=128),
        one_chip, q, kv, kv,
    )
