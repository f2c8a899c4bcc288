"""Serving: phase-split prefill/decode steps (the paper's two regimes) and a
continuous-batching engine.

`make_prefill_step` / `make_decode_step` build the jit-able functions the
dry-run lowers (`serve_step` == one decode token against a seq_len KV cache).
The `Engine` drives them for real batched requests (examples/serve_llama.py):
slot-based continuous batching — new requests prefill into free slots while
existing slots keep decoding.

Decode path, chosen by the model (config.decode_path): for attention-only
models every active slot decodes in ONE jitted call per engine step
regardless of prompt-length skew — `pos` is a per-slot vector threaded
through the model's cache indexing, the step donates the cache buffers
(in-place update, no copy), and the returned caches replace the engine's
wholesale (no per-slot merge scatter).  Recurrent families (rec/rwkv), whose
state has no position mask, decode through the per-position-group dispatch
loop ("grouped") with a selective per-slot merge.

Paged KV cache (cache_mode="paged", the default for attention-only models):
KV memory is a global pool of fixed-size pages plus a per-slot block table
(serving/paged.py owns the host-side allocator; models/layers.py gathers
pages by table inside the decode dispatch).  Admission charges only the
blocks a prompt actually needs, shared prompt prefixes map to the same
physical pages (copy-on-write at the first divergent block), and decode
growth preempts the lowest-priority slot (latest admission ticket — its
request requeues and replays) when the pool is exhausted.  cache_mode="dense"
keeps the PR-1 worst-case (slots, max_seq) reservation as the parity
baseline; recurrent families (rec/rwkv) and sliding-window configs are
auto-routed to it.

Speculative decode (spec_decode=True): a model-free prompt-lookup drafter
(serving/spec.py) proposes up to draft_k tokens per slot per step; ONE
batched multi-token verify dispatch (make_verify_step — a decode-phase
forward over (B, L) tokens with per-row position vectors, masked-causal
inside the draft window, writing L cache positions per row) scores them; the
engine commits each slot's longest greedy-consistent draft prefix plus the
model's own next token, and rolls rejected tokens back (dense: masked until
overwritten; paged: trailing pages freed — audit() stays exact).  Output is
token-identical to plain greedy decode for any drafter; acceptance only buys
dispatch amortization (docs/DESIGN.md §Speculative decode).

Hardened request lifecycle (docs/ROBUSTNESS.md): every request carries a
status (queued -> running -> ok | cancelled | expired | error | rejected).
`submit` is backpressured — a bounded admission queue and up-front
serviceability checks return a structured `Rejected(reason)` instead of
admitting work that can only thrash — and step boundaries honour
`Request.cancel()` and per-request `deadline_ms` (pages freed through the
same `_finish_slot` path as normal completion, so `audit()` stays exact).
Committed logits pass a non-finite guard: a NaN/inf row quarantines only the
offending slot (finish-with-error; co-batched rows commit normally), and a
dispatch that raises demotes its registry key down the requested -> tuned ->
policy -> fallback ladder (kernels/registry.demote) for the rest of the
process, recorded in stats["degraded"].  A DecodeStepWatchdog
(runtime/watchdog.py) reads every step's `engine.step` span: EWMA step
latency and stall flags, with p50/p99 over the tracer's last 512 step
records — surfaced in stats["watchdog"].  All fault paths are driven through
injectable hooks (`fault_hooks`, `clock`) so the chaos layer
(serving/faults.py) needs no monkeypatching.

Tracing (serving/tracing.py): `Engine.tracer` puts spans at the step's phase
boundaries — engine.step, engine.reap, engine.admit (with engine.prefill and
engine.scatter inside it), engine.pages, engine.tables, engine.dispatch,
engine.wait (every host block on device results) and engine.commit — as
profiler TraceAnnotations timed on the engine clock, and keeps one record per
step with its counters.  stats["trace"] holds the per-span totals (count,
seconds, self seconds) and the counters' totals.  The step builders and the
model carry `jax.named_scope`s, so device ops in a trace name their region.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import encoding as encoding_lib
from repro.core import targets as targets_lib
from repro.core.encoding import Phase
from repro.core.packed import EncodingConfig
from repro.kernels import attn as attn_lib
from repro.kernels import registry as registry_lib
from repro.launch import mesh as mesh_lib
from repro.models import transformer as T
from repro.parallel import sharding as sharding_lib
from repro.runtime import watchdog as watchdog_lib
from repro.serving import faults as faults_lib
from repro.serving import paged as paged_lib
from repro.serving import spec as spec_lib
from repro.serving import tracing as tracing_lib
from repro.serving.config import EngineConfig, decode_path


def make_prefill_step(cfg, enc: EncodingConfig) -> Callable:
    def prefill(params, tokens, caches, extras=None):
        batch = {"tokens": tokens, **(extras or {})}
        # Serving prefill only needs the final position's logits (the first
        # sampled token); (B, S, V) is never materialized.
        with jax.named_scope("prefill"):
            logits, caches, _ = T.forward(
                params, batch, cfg=cfg, enc=enc, phase=Phase.PREFILL, caches=caches,
                last_logits_only=True,
            )
        return logits, caches

    return prefill


def make_suffix_prefill_step(cfg, enc: EncodingConfig) -> Callable:
    """Prefill ONLY the un-cached suffix of a prompt whose leading blocks
    were served by the radix prefix cache: the cached K/V is gathered into
    the temp dense cache first (engine._gather_prefix), then this step runs
    a PREFILL at static offset `pos` — the same prior-concat path chunked
    prefill uses, so suffix keys attend the gathered prefix exactly as a
    full prefill would.  `pos` must be a static int (jit static_argnums):
    the attention slice `cache[:, :pos]` needs a compile-time length."""

    def suffix_prefill(params, tokens, caches, pos):
        with jax.named_scope("prefill"):
            logits, caches, _ = T.forward(
                params, {"tokens": tokens}, cfg=cfg, enc=enc, phase=Phase.PREFILL,
                caches=caches, pos=pos, last_logits_only=True,
            )
        return logits, caches

    return suffix_prefill


def make_chunked_prefill_step(cfg, enc: EncodingConfig, *, chunk: int = 512) -> Callable:
    """Prefill long prompts in fixed chunks (bounded activation memory, the
    standard long-prompt serving pattern).  Each chunk runs as a PREFILL with
    `pos` offset; caches accumulate exactly as a single-shot prefill would.

    Returns prefill_chunked(params, tokens, caches) -> (last_logits, caches).
    Requires full attention or window <= chunk handling via the dense cache
    (positions are absolute)."""
    if 0 < chunk < cfg.sliding_window:
        # A window wider than the chunk needs keys from earlier chunks that
        # the windowed prefill path never concatenates back in — the result
        # would be silently wrong, not slow.
        raise ValueError(
            f"chunked prefill requires sliding_window <= chunk: window "
            f"{cfg.sliding_window} > chunk {chunk} would silently drop "
            "cross-chunk attention (grow chunk, or prefill single-shot)"
        )

    def one_chunk(params, tokens, caches, pos):
        logits, caches, _ = T.forward(
            params, {"tokens": tokens}, cfg=cfg, enc=enc, phase=Phase.PREFILL,
            caches=caches, pos=pos, last_logits_only=True,
        )
        return logits, caches

    def prefill_chunked(params, tokens, caches):
        b, s = tokens.shape
        logits = None
        for lo in range(0, s, chunk):
            hi = min(s, lo + chunk)
            logits, caches = one_chunk(params, tokens[:, lo:hi], caches, lo)
        return logits, caches

    return prefill_chunked


SAMPLE_MODES = ("greedy", "temperature")


def make_decode_step(cfg, enc: EncodingConfig, *, sample: str = "greedy") -> Callable:
    """One-token decode step.

    sample="greedy"      -> decode(params, caches, token, pos): argmax.
    sample="temperature" -> decode(params, caches, token, pos, key, temp):
        per-row temperature sampling — `temp` is (B,) float32, `key` a PRNG
        key for THIS step (the engine folds a step counter into its base
        key).  Rows with temp <= 0 take the argmax (per-slot greedy inside a
        sampled batch).
    """
    if sample not in SAMPLE_MODES:
        raise ValueError(f"sample must be one of {SAMPLE_MODES}, got {sample!r}")

    def _forward(params, caches, token, pos):
        logits, caches, _ = T.forward(
            params,
            {"tokens": token},
            cfg=cfg,
            enc=enc,
            phase=Phase.DECODE,
            caches=caches,
            pos=pos,
        )
        return logits, caches

    if sample == "greedy":

        def decode(params, caches, token, pos):
            """token: (B, 1) int32; pos: () or (B,) int32 — position of
            `token` (per-row when vectorized over slot positions)."""
            with jax.named_scope("decode"):
                logits, caches = _forward(params, caches, token, pos)
                nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            return nxt[:, None], logits, caches

        return decode

    def decode_sampled(params, caches, token, pos, key, temp):
        with jax.named_scope("decode"):
            logits, caches = _forward(params, caches, token, pos)
            last = logits[:, -1, :].astype(jnp.float32)
            greedy = jnp.argmax(last, axis=-1).astype(jnp.int32)
            scaled = last / jnp.maximum(temp, 1e-6)[:, None]
            sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
            nxt = jnp.where(temp > 0, sampled, greedy)
        return nxt[:, None], logits, caches

    return decode_sampled


def make_verify_step(cfg, enc: EncodingConfig) -> Callable:
    """Batched multi-token verify for speculative decode.

    verify(params, caches, tokens, pos) -> (logits, caches), where tokens is
    (B, L) int32 — row b's last committed token followed by its L-1 draft
    tokens — and pos is (B,) int32, the position of tokens[:, 0].  One
    decode-phase forward scores the whole draft window: the model's cache
    indexing writes all L positions per row and the decode mask is
    masked-causal within the window (models/layers.py attention_decode), so
    logits[:, j] is the next-token distribution given the committed history
    plus drafts 0..j — exactly what greedy acceptance compares against.
    """

    def verify(params, caches, tokens, pos):
        with jax.named_scope("verify"):
            logits, caches, _ = T.forward(
                params,
                {"tokens": tokens},
                cfg=cfg,
                enc=enc,
                phase=Phase.DECODE,
                caches=caches,
                pos=pos,
            )
        return logits, caches

    return verify


def make_mixed_step(cfg, enc: EncodingConfig) -> Callable:
    """Token-budget mixed step: chunked prefill and decode in ONE dispatch.

    mixed(params, caches, tokens, pos, logits_idx) -> (logits, caches).
    tokens is (B, L) int32 — row b's window is EITHER its last committed
    token plus draft tokens (a decoding slot; exactly make_verify_step's
    contract) OR the next chunk of its prompt (a prefilling slot) — and pos
    is (B,) int32, the absolute position of tokens[:, 0].  Both row kinds
    want the same decode-phase forward: the per-row multi-position cache
    scatter and the masked-causal window mask `slot <= pos_b + j` ARE
    chunked prefill when the window holds prompt tokens (models/layers.py
    attention_apply documents the contract).  logits_idx is (B, K) int32:
    per-row window indices whose hidden states are gathered BEFORE the
    output head (models/transformer.py), so a chunk row pays for K logit
    rows, never L — a 4k-token prompt chunk costs no (chunk, vocab) logits.
    """

    def mixed(params, caches, tokens, pos, logits_idx):
        with jax.named_scope("mixed"):
            logits, caches, _ = T.forward(
                params,
                {"tokens": tokens},
                cfg=cfg,
                enc=enc,
                phase=Phase.DECODE,
                caches=caches,
                pos=pos,
                logits_idx=logits_idx,
            )
        return logits, caches

    return mixed


def _batch_axis(path) -> int:
    """Cache leaves under "groups" carry a leading layer-stack dim: batch is
    axis 1 there, axis 0 in the tail."""
    first = path[0]
    name = getattr(first, "key", getattr(first, "idx", ""))
    return 1 if str(name) == "groups" else 0


def _is_table(path) -> bool:
    """A paged cache's block-table leaf (host-owned, refreshed per dispatch)."""
    return str(getattr(path[-1], "key", "")) == "table"


def slot_gather(caches, slots_sel: list[int]):
    """Batch rows `slots_sel` of every cache leaf, as one gather per leaf."""
    # Host-side index build (np, not jnp): these gathers run eagerly on
    # possibly-sharded cache leaves, and a committed device index array would
    # pin the op to the default device and clash with NamedSharding inputs.
    idx = np.asarray(slots_sel, np.int32)

    def one(path, c):
        return jnp.take(c, idx, axis=_batch_axis(path))

    return jax.tree_util.tree_map_with_path(one, caches)


def slot_slice(caches, s: int):
    return slot_gather(caches, [s])


def slot_merge(caches, part, slots_sel: list[int], src_idx: list[int] | None = None):
    """Write batch rows `src_idx` (default: same as slots_sel) of `part` into
    rows `slots_sel` of `caches` — one gather + one scatter per leaf (the
    per-slot .at[].set loop scaled O(slots) dispatches per leaf)."""
    src = np.asarray(src_idx if src_idx is not None else slots_sel, np.int32)
    dst = np.asarray(slots_sel, np.int32)

    def one(path, full, p):
        ax = _batch_axis(path)
        rows = jnp.take(p, src, axis=ax)
        if ax == 0:
            return full.at[dst].set(rows)
        return full.at[:, dst].set(rows)

    return jax.tree_util.tree_map_with_path(one, caches, part)


def scatter_prefill(pool, tmp, rows, blocks, pages, count):
    """Write prefilled K/V into the paged pool: for i < `count`, block
    `blocks[i]` of batch row `rows[i]` of the temporary dense prefill cache
    `tmp` lands in page `pages[i]` of every pool leaf.  `pool` is the
    engine's cache tree with its `table` leaves taken out (None).  The
    engine jits this once with `pool` donated, and sizes the index vectors
    to every block of `tmp` (entries from `count` on are never read), so
    there is one compiled program per (batch, padded length): the prefill's
    own shapes, whatever the number of pages written.

    A loop of one page per iteration, each a dynamic slice of `tmp` and a
    dynamic-update-slice of the pool: the pool is written in place in its
    own layout (a gather + scatter over the page axis makes XLA:TPU relayout
    the whole pool, and copy it back, around the scatter).

    The temp prefill cache is always raw bf16 (flash prefill computes
    full-precision K/V); under a quantized layout (kv8/kv4, read from the
    pool's storage dtype) each block is quantized HERE, and its per-token
    scales land in the sibling `k_scale`/`v_scale` leaf at the same page."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(pool)
    keys = [jax.tree_util.keystr(p) for p, _ in flat]
    at_key = {k: i for i, k in enumerate(keys)}
    tmp_by_path = {jax.tree_util.keystr(p): v
                   for p, v in jax.tree_util.tree_flatten_with_path(tmp)[0]}
    data = []  # (leaf index, scale leaf index or None, its tmp leaf, G axis?)
    for i, (path, leaf) in enumerate(flat):
        name = str(getattr(path[-1], "key", ""))
        if name in ("k_scale", "v_scale"):
            continue
        scale = at_key.get(keys[i].replace(f"['{name}']", f"['{name}_scale']"))
        data.append((i, scale, tmp_by_path[keys[i]], _batch_axis(path) == 1))

    def one_page(n, leaves):
        leaves = list(leaves)
        for i, scale, src, stacked in data:
            leaf = leaves[i]
            bs = leaf.shape[-3]
            lead = (0,) if stacked else ()
            block = jax.lax.dynamic_slice(
                src, (*lead, rows[n], blocks[n] * bs, 0, 0),
                (*src.shape[: len(lead)], 1, bs, *src.shape[-2:]),
            )
            at = (*lead, pages[n], 0, 0, 0)
            layout = encoding_lib.kv_layout_for_storage(leaf.dtype)
            if layout.quantized:
                block, sc = layout.quantize(block)
                leaves[scale] = jax.lax.dynamic_update_slice(leaves[scale], sc, at)
            leaves[i] = jax.lax.dynamic_update_slice(leaf, block.astype(leaf.dtype), at)
        return leaves

    leaves = jax.lax.fori_loop(0, count, one_page, [v for _, v in flat])
    return jax.tree_util.tree_unflatten(treedef, leaves)


def count_calls(fn):
    """Wrap `fn` with a dispatch counter (`fn.calls`) — instrumentation for
    the decode-dispatch invariants (benchmarks and tests)."""

    def wrapped(*args, **kwargs):
        wrapped.calls += 1
        return fn(*args, **kwargs)

    wrapped.calls = 0
    return wrapped


# Steps the watchdog's p50/p99 are taken over (the tracer's newest records).
WATCHDOG_WINDOW = 512

REQUEST_STATUSES = (
    "queued", "running", "ok", "cancelled", "expired", "error", "rejected",
)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray        # (S,) int32
    max_new_tokens: int
    generated: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # Decode finishes the slot early when this token is emitted (the EOS
    # itself is kept in `generated`; nothing past it is ever emitted).
    eos_id: int | None = None
    # Per-slot sampling temperature (engines built with sample="temperature"
    # only; <= 0 means greedy for this request inside a sampled batch).
    temperature: float = 1.0
    # Speculative-decode accounting (filled by the engine when spec decode
    # served this request): drafts offered / drafts accepted.
    draft_proposed: int = 0
    draft_accepted: int = 0
    # ---- lifecycle (docs/ROBUSTNESS.md) ------------------------------------
    # Wall-clock budget from submit() to last token, in ms of the ENGINE's
    # clock (injectable).  None = no deadline.  Checked at step boundaries:
    # an expired request finishes with status "expired", keeping whatever it
    # generated so far.
    deadline_ms: float | None = None
    status: str = "queued"
    error: str | None = None
    cancel_requested: bool = False
    submit_t: float | None = None     # engine clock at submit()
    # SLO class for the token-budget scheduler ("interactive" | "standard" |
    # "batch"; unknown values rank as "standard").  Queue ordering ages by
    # enqueued_step (stamped by submit()) so no class starves.
    slo_class: str = "standard"
    enqueued_step: int | None = None
    # Tenant for per-tenant page-quota accounting (paged engines with
    # EngineConfig.tenant_quota set): admission reserves this request's
    # worst-case page footprint against its tenant's quota, so one tenant's
    # long-context jobs cannot starve the pool for everyone else.
    tenant: str = "default"

    def cancel(self) -> None:
        """Ask the engine to drop this request.  Honoured at the next step
        boundary (and again at commit time, so a cancel landing while a
        draft window is in flight never emits another token)."""
        self.cancel_requested = True


@dataclasses.dataclass(frozen=True)
class Admitted:
    """submit() accepted the request into the admission queue."""

    uid: int

    def __bool__(self) -> bool:
        return True


@dataclasses.dataclass(frozen=True)
class Rejected:
    """submit() refused the request — structured backpressure, never an
    unbounded queue.  `reason` is machine-readable ("queue_full" |
    "unserviceable_seq" | "unserviceable_pool" | "unserviceable_quota");
    `detail` is for humans."""

    uid: int
    reason: str
    detail: str = ""

    def __bool__(self) -> bool:
        return False


# Lower rank = more urgent.  Unknown classes rank as "standard".
SLO_CLASSES = {"interactive": 0, "standard": 1, "batch": 2}


class TokenBudgetScheduler:
    """Admission / budget-split / preemption policy for the token-budget
    mixed step (`Engine(token_budget=...)`; docs/DESIGN.md §Token-budget
    scheduling).

    Admission order: SLO class rank (interactive < standard < batch) with
    starvation-free aging — every `aging_steps` engine steps a request
    spends queued promote it one class, so a batch request enqueued long
    enough eventually outranks a steady stream of fresh interactive ones.
    Ties break FIFO (enqueued_step, then submission order).

    Budget split per step: decode rows are funded first (1 token per row —
    the zero-stall floor), then spec drafts (spec.draft_budget), and
    chunked prefill takes what remains — never less than 1 token per
    prefill row, so an over-subscribed budget still makes prompt progress
    instead of livelocking admission.

    Preemption (pool pressure): victim = max (class rank, admission
    ticket) — batch rows evict before standard before interactive, ties to
    the latest admission.  Aging protects QUEUE order only; a running
    interactive row never loses its pages to an aged batch row.
    """

    def __init__(self, budget: int, *, aging_steps: int = 64):
        if budget < 1:
            raise ValueError(f"token_budget must be >= 1, got {budget}")
        self.budget = int(budget)
        self.aging_steps = max(1, int(aging_steps))

    def rank(self, req: Request) -> int:
        return SLO_CLASSES.get(req.slo_class, SLO_CLASSES["standard"])

    def queue_key(self, req: Request, now_step: int) -> tuple[int, int]:
        """Sort key for queued requests (lower = admitted first)."""
        enq = req.enqueued_step if req.enqueued_step is not None else now_step
        waited = max(0, now_step - enq)
        return (self.rank(req) - waited // self.aging_steps, enq)

    def victim_key(self, req: Request, ticket: int) -> tuple[int, int]:
        """Sort key for preemption victims (the MAX is evicted)."""
        return (self.rank(req), int(ticket))

    def split_chunks(
        self, decode_cost: int, remaining: dict[int, int], order: list[int],
    ) -> dict[int, int]:
        """Chunk sizes for this step's prefill rows.  `remaining[s]` prompt
        tokens are left on row s; `order` is priority order; decode rows
        (drafts included) already spent `decode_cost` of the budget.  Every
        row gets at least 1 token (forward progress), the leftover budget
        goes to the highest-priority rows first."""
        spare = max(self.budget - int(decode_cost), len(order))
        chunks = {s: 1 for s in order}
        spare -= len(order)
        for s in order:
            add = min(remaining[s] - 1, spare)
            if add > 0:
                chunks[s] += add
                spare -= add
        return chunks


class Engine:
    """Slot-based continuous batching on a fixed decode batch.

    decode_mode (read-only, from the model — config.decode_path):
      "vectorized" (attention-only models) — one jitted decode per step for
        ALL active slots: per-slot `pos` vector through the model, donated
        cache buffers, caches replaced wholesale (inactive rows absorb
        masked-off writes that the next admission's prefill overwrites).
      "grouped" (recurrent blocks) — the per-position-group dispatch loop
        with selective slot_merge.

    batch_prefill (read-only, from the model): admit every queued request
    that fits in one right-padded prefill call (attention-only,
    full-attention models; recurrent state and ring-buffer caches would
    absorb the pad garbage, so those families keep the exact per-slot
    prefill).  The paged path always batch-prefills — it prefills into a
    throwaway dense cache and scatters only real prompt blocks into the
    pool, so pad garbage never lands anywhere persistent.

    cache_mode:
      "paged" (default) — pool-of-pages KV with per-slot block tables,
        prefix reuse and preemption (module docstring).  Requires
        attention-only, no sliding window; anything else auto-routes to
        dense.
      "dense" — the worst-case (slots, max_seq) reservation (parity baseline).

    sample: "greedy" (default) or "temperature" — per-slot temperature
    sampling (Request.temperature; <= 0 rows stay greedy) with a PRNG key
    folded per engine step from `seed`.  Note: paged preemption REPLAYS a
    request from scratch; greedy replay is deterministic, sampled replay
    draws fresh keys, so sampled engines under pool pressure are not
    replay-deterministic.

    spec_decode: speculative decode fast path.  Each step, a model-free
    prompt-lookup drafter (serving/spec.py, or the `drafter` override)
    proposes up to `draft_k` tokens per slot out of the slot's own token
    history; ONE batched verify dispatch (make_verify_step — decode-phase
    forward over the (B, L) draft window with per-row positions) scores
    them, and the engine commits the longest draft prefix that matches the
    model's own greedy argmax, plus the model's next token after it (1 to
    draft_k + 1 tokens per slot per dispatch).  Output is token-identical to
    plain greedy decode for ANY drafter; only throughput depends on draft
    quality.  Rejected draft positions need no dense-cache surgery (their
    K/V stays masked until overwritten) — but paged slots truncate back to
    the pages their committed length needs, returning draft-only pages to
    the pool (`audit()` stays exact).  Requires attention-only, no sliding
    window, greedy sampling; anything else switches it off.

    token_budget: unified continuous batching (Sarathi-style).  Every step
    runs ONE mixed decode-phase dispatch whose (B, L) window packs decode
    rows (1 token each, or their spec-verify window) beside chunked-prefill
    rows (each spending a slice of the remaining budget on its prompt), so
    a long prompt admitted mid-decode streams into the cache WITHOUT ever
    pausing decode — zero decode-stall steps by construction
    (tests/test_token_budget.py).  Admission order, per-step budget
    split, and preemption ordering come from TokenBudgetScheduler
    (Request.slo_class + starvation-free aging).  A prefill row's final
    chunk yields its first generated token in the same dispatch, so output
    is token-identical to the phase-split engine.  Needs the spec-verify
    machinery (attention-only, no sliding window, greedy); anything else
    turns it off and the phase-split path remains.

    stream_cb: optional callable (req, token) invoked synchronously as each
    token is committed — streaming output for servers (launch/serve.py).
    """

    def __init__(
        self,
        params,
        cfg,
        enc: EncodingConfig,
        config: EngineConfig | None = None,
        *,
        drafter: Callable | None = None,
        clock: Callable[[], float] | None = None,
        fault_hooks=None,
        stream_cb: Callable[[Request, int], None] | None = None,
        **kwargs,
    ):
        # ---- configuration (serving/config.py) -----------------------------
        # The engine's knobs live in one frozen, validated EngineConfig.
        # `Engine(params, cfg, enc, slots=8, ...)` remains supported as a
        # deprecation shim — the legacy kwargs are folded into
        # EngineConfig(**kwargs) — but config= is the first-class path.
        # Cross-field auto-downgrades (paged->dense, spec-off-under-sampling)
        # happen in config.resolve(), not here; the applied rules are
        # surfaced in stats["downgrades"].
        if config is None:
            config = EngineConfig(**kwargs)
        elif kwargs:
            raise TypeError(
                "pass either config=EngineConfig(...) or the legacy engine "
                f"kwargs, not both (got extra kwargs: {sorted(kwargs)})"
            )
        config = config.resolve(cfg)
        self.config = config
        self.cfg, self.enc = cfg, enc
        self.slots = config.slots
        self.max_seq = config.max_seq
        # ---- tensor parallelism (docs/DESIGN.md §Tensor-parallel serving) ----
        # mesh_shape=(N,) with N > 1 shards the serving step across a device
        # mesh: weight streams column/row-parallel (parallel/sharding.py),
        # KV caches head-parallel (serving_cache_shardings), dispatch still
        # ONE jitted SPMD program per step — GSPMD inserts the single psum
        # per layer at the row-parallel wo/w_down matmuls.  Pallas custom
        # calls are not GSPMD-partitionable, so both op classes are routed
        # to the partitionable XLA paths under tp > 1 (recorded below).
        self.tp_shards = config.tp_shards
        self.mesh = None
        enc_downgrades: list[str] = []
        if config.mesh_devices > 1:
            self.mesh = mesh_lib.build_serving_mesh(
                config.mesh_shape, tp_axis=config.tp_axis
            )
        if self.tp_shards > 1:
            repl = {}
            if enc.backend not in ("xla", "reference"):
                repl["backend"] = "xla"
                enc_downgrades.append(f"backend:xla(tp,was={enc.backend})")
            if getattr(enc, "attn_backend", "xla") != "xla":
                repl["attn_backend"] = "xla"
                enc_downgrades.append(
                    f"attn_backend:xla(tp,was={enc.attn_backend})"
                )
            if repl:
                self.enc = enc = dataclasses.replace(enc, **repl)
        self.enc_downgrades = tuple(enc_downgrades)
        # kv4 packs two values per byte; only the pallas decode kernels
        # unpack nibbles tile-locally in VMEM.  Under an xla/reference
        # attention fallback (including the forced-xla tp path above) the
        # gather-and-dequant of packed nibbles is not worth the capacity win,
        # so kv4 rides the kv8 layout there — recorded like any other
        # resolve()-time downgrade.
        if (config.kv_quant == "kv4"
                and getattr(enc, "attn_backend", "xla")
                in ("xla", "reference")):
            config = dataclasses.replace(
                config, kv_quant="kv8",
                downgrades=config.downgrades + (
                    f"kv_quant:kv8(attn_backend="
                    f"{getattr(enc, 'attn_backend', 'xla')})",
                ),
            )
            self.config = config
        self.kv_quant = config.kv_quant
        self.params = params
        if self.mesh is not None:
            self.params = jax.device_put(
                params,
                sharding_lib.params_shardings(params, self.mesh, fsdp=False),
            )
        # ---- lifecycle / robustness (docs/ROBUSTNESS.md) -------------------
        # max_queue: admission-queue bound — submit() returns Rejected
        #   ("queue_full") past it instead of growing without bound.
        # clock: injectable monotonic clock (seconds) for deadlines, the
        #   tracer's spans and so the step watchdog; perf_counter by default,
        #   the clock a benchmark's window bounds use.  The chaos layer
        #   passes FaultSchedule.clock so clock-skew faults are visible.
        # fault_hooks: object with on_step_begin / pre_dispatch /
        #   corrupt_slots / held_pages (serving/faults.FaultSchedule) —
        #   injection points, all no-ops when None.
        # logits_guard: non-finite check on committed logits; quarantines the
        #   offending slot only (docs/ROBUSTNESS.md §Guard overhead).
        self.max_queue = config.max_queue
        self.clock = clock if clock is not None else time.perf_counter
        self.hooks = fault_hooks
        self.logits_guard = bool(config.logits_guard)
        self.tracer = tracing_lib.Tracer(self.clock)
        self.watchdog = watchdog_lib.DecodeStepWatchdog()
        self.rejected: list[Request] = []
        self.degraded: list[dict] = []
        self.lifecycle = {
            "rejected": 0, "cancelled": 0, "expired": 0,
            "kernel_faults": 0, "guard_trips": 0,
        }
        self.step_count = 0
        slots = config.slots
        max_seq = config.max_seq
        # Model-dependent mode downgrades (paged->dense for sliding windows
        # and recurrent families, spec/budget off where the verify window
        # cannot run) were applied by config.resolve(); the decode path and
        # prefill batching follow from the model alone.
        self._decode_mode, self._batch_prefill = decode_path(cfg)
        self.cache_mode = config.cache_mode
        self.sample = config.sample
        self._base_key = jax.random.PRNGKey(config.seed)
        self._step_idx = 0
        self.draft_k = int(config.draft_k)
        self.spec_decode = bool(config.spec_decode)
        self.drafter = drafter if drafter is not None else spec_lib.propose
        self.token_budget = config.token_budget
        self.scheduler = (
            TokenBudgetScheduler(
                self.token_budget, aging_steps=config.slo_aging_steps
            )
            if self.token_budget is not None
            else None
        )
        self.stream_cb = stream_cb
        self._mixed_m = slots        # M of the imminent mixed dispatch
        self._window_blocks = 0      # table width the mixed window needs
        if self.scheduler is not None:
            self.continuous = {
                "token_budget": self.token_budget,
                "mixed_steps": 0,
                "decode_tokens": 0,        # decode-row window tokens dispatched
                "prefill_tokens": 0,       # prompt chunk tokens dispatched
                "decode_stall_steps": 0,   # steps where live decode rows emitted 0
                "chunked_admissions": 0,
                "completed_prefills": 0,
            }
        self._rebuild_dispatch_fns()
        if self.spec_decode:
            self.spec_stats = {
                "steps": 0,          # engine steps served by a verify dispatch
                "slot_steps": 0,     # per-slot verify participations
                "proposed": 0,       # draft tokens offered to verify
                "accepted": 0,       # draft tokens matching the greedy target
                "committed": 0,      # tokens emitted by spec steps (incl. bonus)
                "pool_deferred": 0,  # spec steps skipped: draft pages won't fit
            }
            self.slot_proposed = np.zeros(slots, np.int64)
            self.slot_accepted = np.zeros(slots, np.int64)
        if self.cache_mode == "paged":
            block_size = config.block_size
            pool_pages = config.pool_pages
            self.block_size = block_size
            self.num_blocks = -(-max_seq // block_size)
            if pool_pages is None:
                # Parity default: the pool covers the dense worst case, so
                # nothing preempts unless the caller shrinks it.
                pool_pages = 1 + slots * self.num_blocks
            # Tensor-parallel pools mirror one allocator per shard (page
            # identity must agree; COW/preemption/audit stay shard-local —
            # serving/paged.ShardedBlockAllocator).
            self.prefix_cache = bool(config.prefix_cache)
            self.tenant_quota = config.tenant_quota
            # Suffix-only prefill needs the bf16 prior-concat path: a kv8/kv4
            # gather would dequantize-requantize (bitwise drift vs cache
            # off), and sharded pools would gather per-shard head slices.
            # Those layouts still get the write-skip half of the cache win.
            self._suffix_ok = (
                self.kv_quant == "bf16" and self.tp_shards == 1
                and not cfg.sliding_window
            )
            self.alloc = (
                paged_lib.ShardedBlockAllocator(
                    pool_pages, block_size, shards=self.tp_shards,
                    kv_quant=self.kv_quant,
                    prefix_cache=self.prefix_cache,
                    tenant_quota=self.tenant_quota,
                )
                if self.tp_shards > 1
                else paged_lib.BlockAllocator(
                    pool_pages, block_size, self.kv_quant,
                    prefix_cache=self.prefix_cache,
                    tenant_quota=self.tenant_quota,
                )
            )
            self.caches = T.cache_init(
                cfg, slots, max_seq, cache_mode="paged",
                block_size=block_size, num_pages=pool_pages,
                kv_quant=self.kv_quant,
            )
            # Admission's two programs around the prefill: the temp dense
            # cache, one per (batch, padded length), and the write of its
            # blocks into the pool, donated (in place, no pool copy).
            def prefill_cache(batch, length):
                return T.cache_init(cfg, batch, length)

            self.prefill_cache_fn = jax.jit(prefill_cache, static_argnums=(0, 1))
            self.scatter_fn = jax.jit(scatter_prefill, donate_argnums=(0,))
            self.block_table = np.full(
                (slots, self.num_blocks), paged_lib.SCRATCH_PAGE, np.int32
            )
            self.slot_pages: list[list[int]] = [[] for _ in range(slots)]
            # Written-content tracking lives in the ALLOCATOR now
            # (BlockAllocator.written / mark_written): chunked prefill writes
            # lazily, but commit_prompt registers pages for prefix sharing
            # immediately — a later admission may only treat a shared page as
            # valid history once its owner's chunks have covered it (see
            # _admit_budget), and only written pages may be RETAINED in the
            # radix cache at refcount 0.  alloc() clears the marker on
            # recycle, so a re-allocated page can never carry a stale marker
            # into a future share.
            # Per-tenant worst-case page reservations for live admissions
            # (quota gate): tenant -> pages reserved by running requests.
            self._tenant_reserved: dict[str, int] = {}
            # Satellite-2 accounting: admissions that earlier DEFERRED on an
            # unwritten shared prefix and later re-planned into extra shared
            # blocks once the writer's chunks landed.
            self.deferred_hits = 0
            self.slot_ticket = np.zeros(slots, np.int64)
            self._ticket = 0
            self._tables_dirty = True
            self.preemptions = 0
            self.peak_active = 0
        else:
            self.caches = T.cache_init(cfg, slots, max_seq)
            # Prefix caching and page quotas are properties of the paged
            # pool; dense engines carry the neutral values so the shared
            # admission paths (e.g. _admit_budget's quota gate) stay
            # branch-free.
            self.prefix_cache = False
            self.tenant_quota = None
            self._tenant_reserved = {}
            self.deferred_hits = 0
        if self.mesh is not None:
            # Head-parallel KV: each shard holds its kv-head slice of every
            # cache page/row; block tables replicate (they mirror the host
            # table).  GSPMD propagates these shardings through the jitted
            # step, so attention runs collective-free until the per-layer
            # psum at the row-parallel output projection.
            self.caches = jax.device_put(
                self.caches,
                sharding_lib.serving_cache_shardings(self.caches, self.mesh),
            )
        self.slot_req: list[Request | None] = [None] * slots
        self.slot_pos = np.zeros(slots, np.int32)
        # Prompt tokens already in the slot's cache — equals len(prompt) the
        # moment (batch) prefill runs; strictly less only mid-chunked-prefill
        # under the token-budget scheduler.
        self.slot_prefill_done = np.zeros(slots, np.int64)
        self.queue: collections.deque[Request] = collections.deque()
        self.finished: list[Request] = []

    @property
    def decode_mode(self) -> str:
        return self._decode_mode

    @property
    def batch_prefill(self) -> bool:
        return self._batch_prefill

    def _reject(self, req: Request, reason: str, detail: str) -> Rejected:
        req.status = "rejected"
        req.error = detail
        req.done = True
        self.rejected.append(req)
        self.lifecycle["rejected"] += 1
        return Rejected(req.uid, reason, detail)

    def submit(self, req: Request) -> Admitted | Rejected:
        """Admit `req` into the bounded queue, or refuse it with a structured
        reason — backpressure (queue_full) and up-front serviceability checks
        (a request that cannot ever fit the cache or the pool is rejected
        here, not admitted to preempt-thrash; the pool bound is the
        kv_capacity_requests math from core/encoding.py, applied to one
        request).  The result is truthy iff admitted."""
        req.submit_t = self.clock()
        req.enqueued_step = self.step_count
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            return self._reject(
                req, "queue_full",
                f"admission queue at max_queue={self.max_queue}; retry later",
            )
        if len(req.prompt) > self.max_seq:
            return self._reject(
                req, "unserviceable_seq",
                f"prompt of {len(req.prompt)} tokens exceeds max_seq "
                f"{self.max_seq}",
            )
        if self.cache_mode == "paged" and req.max_new_tokens > 0:
            # The most pages the request can ever hold (decode stops at
            # max_seq) must fit the pool, or admission could never run it —
            # this is blocks_per_request from encoding.kv_capacity_requests
            # evaluated at the request's own worst case.
            worst = self._worst_pages(req)
            if worst > self.alloc.capacity:
                return self._reject(
                    req, "unserviceable_pool",
                    f"request can need {worst} pages but the pool holds "
                    f"{self.alloc.capacity}; grow pool_pages or shrink the "
                    "request",
                )
            if self.tenant_quota is not None and worst > self.tenant_quota:
                # Same up-front serviceability logic as the pool bound: a
                # request whose worst case exceeds its tenant's whole quota
                # could never pass the admission gate — reject instead of
                # queuing it to starve.
                return self._reject(
                    req, "unserviceable_quota",
                    f"request can need {worst} pages but tenant "
                    f"{req.tenant!r} is capped at {self.tenant_quota}; raise "
                    "tenant_quota or shrink the request",
                )
        self.queue.append(req)
        return Admitted(req.uid)

    def _worst_pages(self, req: Request) -> int:
        """Worst-case page footprint of one request (decode stops at
        max_seq) — the quantity submit() checks against the pool and the
        quota gate reserves per tenant at admission."""
        worst_pos = min(len(req.prompt) + req.max_new_tokens, self.max_seq) - 1
        return worst_pos // self.block_size + 1

    def _quota_blocked(self, req: Request) -> bool:
        """Per-tenant admission gate: reserving this request's worst-case
        pages must keep its tenant within quota.  Reservations (not live
        usage) are the gated quantity so a tenant cannot over-admit on pages
        its running requests merely have not grown into yet."""
        if self.tenant_quota is None:
            return False
        reserved = self._tenant_reserved.get(req.tenant, 0)
        return reserved + self._worst_pages(req) > self.tenant_quota

    def _reserve_quota(self, req: Request) -> None:
        if self.tenant_quota is None:
            return
        pages = self._worst_pages(req)
        req._quota_pages = pages
        self._tenant_reserved[req.tenant] = (
            self._tenant_reserved.get(req.tenant, 0) + pages
        )

    def _release_quota(self, req: Request) -> None:
        pages = getattr(req, "_quota_pages", 0)
        if self.tenant_quota is None or not pages:
            return
        req._quota_pages = 0
        left = self._tenant_reserved.get(req.tenant, 0) - pages
        if left > 0:
            self._tenant_reserved[req.tenant] = left
        else:
            self._tenant_reserved.pop(req.tenant, None)

    # ---- guarded dispatch + kernel quarantine ------------------------------

    def _rebuild_dispatch_fns(self) -> None:
        """(Re)jit the serving dispatches.  Called at construction and after
        a kernel quarantine: a fresh jit object retraces on next call, so the
        model re-resolves its registry keys against the demoted ladder."""
        self.prefill_fn = jax.jit(make_prefill_step(self.cfg, self.enc))
        # Vectorized mode replaces the caches wholesale each step, so the old
        # buffers can be donated (in-place update on device, no copy).  The
        # grouped path re-reads self.caches after the call (merge) — no donate.
        donate = (1,) if self.decode_mode == "vectorized" else ()
        self.decode_fn = jax.jit(
            make_decode_step(self.cfg, self.enc, sample=self.sample),
            donate_argnums=donate,
        )
        if self.spec_decode:
            self.verify_fn = jax.jit(
                make_verify_step(self.cfg, self.enc), donate_argnums=(1,)
            )
        if getattr(self, "token_budget", None) is not None:
            self.mixed_fn = jax.jit(
                make_mixed_step(self.cfg, self.enc), donate_argnums=(1,)
            )
        if self.cache_mode == "paged":
            # Radix-cache suffix prefill: `pos` (tokens already served by
            # cached pages) is static — each distinct cached-prefix length
            # compiles once, like the chunked-prefill offsets.
            self.suffix_prefill_fn = jax.jit(
                make_suffix_prefill_step(self.cfg, self.enc),
                static_argnums=(3,),
            )

    def _attn_s(self, phase: Phase) -> int:
        """The logical KV length the next dispatch of `phase` attends — the
        S that keys its attention registry entry (mirrors stats)."""
        if phase is Phase.PREFILL:
            return self.max_seq
        if self.cache_mode == "paged":
            return self._live_table_width() * self.block_size
        if self.cfg.sliding_window:
            return min(self.max_seq, self.cfg.sliding_window)
        return self.max_seq

    def _dispatch_keys(self, kind: str) -> tuple[str, ...]:
        """Registry keys the imminent dispatch resolves through: its
        attention key plus its matmul key (quant mode x phase x M-bucket).
        These are what pre_dispatch faults match and what a quarantine
        demotes."""
        phase = Phase.PREFILL if kind == "prefill" else Phase.DECODE
        target_name = targets_lib.resolve(self.enc.target).name
        quant = {"none": "none", "int8": "w8a8", "int4": "w4a8"}.get(
            getattr(self.enc, "weight_quant", "none"), "none"
        )
        m = {
            "prefill": self.slots * self.max_seq,
            "decode": self.slots,
            "verify": self.slots * (1 + self.draft_k),
            # The mixed window's M is slots x L, set per step — wide chunk
            # windows land in the "big" bucket, which routes to the packed
            # mmt4d GEMM (kernels/registry.py default policy).
            "mixed": self._mixed_m,
        }[kind]
        return (
            registry_lib.attn_dispatch_key(
                phase, self._attn_s(phase), target_name,
                kv=getattr(self, "kv_quant", "bf16"),
            ),
            registry_lib.dispatch_key(quant, phase, m, target_name),
        )

    def _requested_for(self, key: str) -> str | None:
        """The caller-pinned backend for a key's op class (the `requested`
        rung of its ladder) — attn_backend for attention keys, the encoding
        backend for matmul keys."""
        if key.startswith(registry_lib.ATTN_OP + "|"):
            return getattr(self.enc, "attn_backend", None)
        return getattr(self.enc, "backend", None)

    def _quarantine_kernel(
        self, key: str, reason: str, shard: int | None = None
    ) -> dict:
        """Demote `key` to the next rung of its dispatch ladder for the rest
        of the process (kernels/registry.demote), record it in
        stats["degraded"], and rebuild the jitted dispatches so the next
        trace resolves the demoted backend.  A shard-tagged fault demotes
        only that shard's ladder entry; the SPMD dispatch still honours it
        (select takes the max level over shards) but healthy shards keep
        their own observability rung."""
        requested = self._requested_for(key)
        before = registry_lib.resolve_key(key, requested=requested, shard=shard)
        record = registry_lib.demote(
            key, failing=before.backend, reason=reason,
            requested=requested, shard=shard,
        )
        entry = {"key": key, "step": self.step_count, **record}
        self.degraded.append(entry)
        self.lifecycle["kernel_faults"] += 1
        self._rebuild_dispatch_fns()
        if self.cache_mode == "paged":
            self._tables_dirty = True
        return entry

    def _dispatch(self, kind: str, fn_attr: str, *args):
        """Run one jitted dispatch through the fault/quarantine boundary:
        pre_dispatch hooks may raise a (simulated) KernelFaultError; a raise
        quarantines the named key and retries on the demoted rung.  Bounded
        by the ladder depth — a dispatch that still fails at the fallback
        rung propagates (there is nothing left to degrade to)."""
        for _attempt in range(4):
            keys = self._dispatch_keys(kind)
            try:
                if self.hooks is not None:
                    self.hooks.pre_dispatch(self, kind, keys)
                return getattr(self, fn_attr)(*args)
            except faults_lib.KernelFaultError as exc:
                self._quarantine_kernel(
                    exc.key, reason=str(exc),
                    shard=getattr(exc, "shard", None),
                )
                continue
        raise faults_lib.KernelFaultError(
            keys[0], "kernel dispatch still failing at the fallback rung"
        )

    # ---- lifecycle: deadlines, cancellation, the non-finite guard ----------

    def _past_deadline(self, req: Request) -> bool:
        return (
            req.deadline_ms is not None
            and req.submit_t is not None
            and (self.clock() - req.submit_t) * 1e3 > req.deadline_ms
        )

    def _finish_queued(self, req: Request, status: str, error: str | None) -> None:
        req.done = True
        req.status = status
        req.error = error
        self.finished.append(req)
        self.tracer.note("finished", req.uid)
        self.lifecycle[status] = self.lifecycle.get(status, 0) + 1

    def _admission_reap(self, req: Request) -> None:
        """Companion to the _reap_lifecycle sweep: the sweep reads the clock
        ONCE at the step boundary, but admission runs later in the same step
        (after prefill planning and page commits), so a deadline can lapse —
        or a cancel land — in between.  Without this re-check an
        already-dead request is admitted, prefilled, and only reaped a full
        step later: wasted dispatch work and, paged, pool pages committed to
        a corpse that can preempt a live request.  Caller has already popped
        `req` from the queue."""
        if req.cancel_requested:
            self._finish_queued(req, "cancelled", "cancelled while queued")
        else:
            self._finish_queued(
                req, "expired",
                f"deadline_ms={req.deadline_ms} exceeded at admission",
            )

    def _reap_lifecycle(self) -> None:
        """Step-boundary lifecycle sweep: cancelled and deadline-expired
        requests finish NOW, queued or running — running slots free their
        pages through the same _finish_slot path as normal completion, so
        the allocator audit stays exact."""
        if self.queue and any(
            r.cancel_requested or self._past_deadline(r) for r in self.queue
        ):
            kept: collections.deque[Request] = collections.deque()
            for req in self.queue:
                if req.cancel_requested:
                    self._finish_queued(req, "cancelled", "cancelled while queued")
                elif self._past_deadline(req):
                    self._finish_queued(
                        req, "expired",
                        f"deadline_ms={req.deadline_ms} exceeded while queued",
                    )
                else:
                    kept.append(req)
            self.queue = kept
        for s in range(self.slots):
            req = self.slot_req[s]
            if req is None:
                continue
            if req.cancel_requested:
                self._finish_slot(s, status="cancelled", error="cancelled mid-flight")
            elif self._past_deadline(req):
                self._finish_slot(
                    s, status="expired",
                    error=f"deadline_ms={req.deadline_ms} exceeded mid-flight",
                )

    def _guard_slots(self, logits, active: list[int]) -> frozenset[int]:
        """The non-finite guard: slots whose logit rows this step are not
        finite (hook-injected corruption included — the chaos layer NaNs
        rows here so the REAL guard sees real non-finite data).  One (B,)
        device reduction + host transfer per step; overhead measured in
        benchmarks (docs/ROBUSTNESS.md)."""
        if self.hooks is not None:
            forced = self.hooks.corrupt_slots(self, active)
            if forced:
                logits = logits.at[jnp.asarray(forced, jnp.int32)].set(jnp.nan)
        if not self.logits_guard:
            return frozenset()
        with self.tracer.span("engine.wait"):
            ok = np.asarray(
                jnp.all(jnp.isfinite(logits), axis=tuple(range(1, logits.ndim)))
            )
        bad = frozenset(s for s in active if not ok[s])
        if bad:
            self.lifecycle["guard_trips"] += len(bad)
        return bad

    def poison_slot_kv(self, s: int) -> None:
        """Overwrite slot `s`'s most recent KV storage with NaN — the chaos
        layer's cache-poisoning injection (a kernel writing garbage K/V).
        The slot's next logits go non-finite and the guard quarantines it;
        pages are slot-private unless prefix-shared, so co-batched slots
        only see the poison when they genuinely share the page.

        Quantized layouts (kv8/kv4) store integer page data, which cannot
        hold a NaN — the data pages get a saturating garbage sentinel and
        the float32 scale pages get the NaN, so dequantize (int * NaN
        scale) still produces the non-finite logits the guard trips on."""
        nan = jnp.nan
        if self.cache_mode == "paged":
            if not self.slot_pages[s]:
                return
            page = self.slot_pages[s][-1]

            def one(path, leaf):
                if _is_table(path):
                    return leaf
                poison = (
                    jnp.iinfo(leaf.dtype).max
                    if jnp.issubdtype(leaf.dtype, jnp.integer) else nan
                )
                if _batch_axis(path) == 1:
                    return leaf.at[:, page].set(poison)
                return leaf.at[page].set(poison)

        else:
            pos = max(int(self.slot_pos[s]) - 1, 0)

            def one(path, leaf):
                if _is_table(path):
                    return leaf
                if leaf.ndim < 2:
                    return leaf
                if _batch_axis(path) == 1:
                    return leaf.at[:, s, pos % leaf.shape[2]].set(nan)
                return leaf.at[s, pos % leaf.shape[1]].set(nan)

        self.caches = jax.tree_util.tree_map_with_path(one, self.caches)

    # ---- paged admission / page management ---------------------------------

    def _finish_degenerate(self, req: Request) -> None:
        req.done = True
        req.status = "ok"
        self.finished.append(req)
        self.tracer.note("finished", req.uid)

    def _admit_paged(self):
        free = [s for s in range(self.slots) if self.slot_req[s] is None]
        batch: list[tuple[int, Request, paged_lib.PagePlan]] = []
        # Radix-cache admissions whose whole shared run is already WRITTEN:
        # prefill computes only the un-cached suffix ((slot, req, plan, lead)).
        suffix: list[tuple[int, Request, paged_lib.PagePlan, int]] = []
        for req in list(self.queue):
            if not free:
                break
            if req.max_new_tokens <= 0:
                self.queue.remove(req)
                self._finish_degenerate(req)
                continue
            if req.cancel_requested or self._past_deadline(req):
                # Deadline/cancel re-check at admission time (the _reap
                # sweep's snapshot can lapse within the same step).
                self.queue.remove(req)
                self._admission_reap(req)
                continue
            if self._quota_blocked(req):
                # Tenant over quota: skip THIS request but keep scanning —
                # one tenant's quota pressure must not become head-of-line
                # blocking for every other tenant's queued work.
                continue
            nblocks, shared = self.alloc.plan_prompt(req.prompt)
            if not self.alloc.plan_fits(nblocks, shared):
                break  # pool pressure: stop admitting (FIFO order preserved)
            # Leading run of shared blocks whose K/V has LANDED in the pool:
            # those token ranges can skip prefill compute entirely.  Shares
            # of unwritten pages (an admission earlier in this same batch)
            # still reuse the pages — the batched prefill below writes them
            # this very step — but force the full-prefill path.
            lead = 0
            while lead in shared and self.alloc.is_written(shared[lead]):
                lead += 1
            plan = self.alloc.commit_prompt(
                req.prompt, nblocks, shared, tenant=req.tenant
            )
            assert plan is not None
            self.queue.remove(req)
            self._reserve_quota(req)
            s = free.pop(0)
            if lead == len(shared) and lead > 0 and self._suffix_ok:
                suffix.append((s, req, plan, lead))
            else:
                batch.append((s, req, plan))
        if not batch and not suffix:
            return
        if batch:
            # ONE right-padded batched prefill into a TEMPORARY dense cache
            # (pad rounds to a power of two >= block_size, so padded lengths
            # are block-aligned and compiled shapes stay
            # O(slots * log(max_seq))), then scatter the computed K/V blocks
            # into their pool pages.  Shared prefix pages are NOT rewritten:
            # suffix zero-padding is exact in the chunked attention, so the
            # original owner's prefill already wrote bitwise-identical
            # content (the conformance tests pin this).
            maxlen = max(len(r.prompt) for _, r, _ in batch)
            lp = max(
                self.block_size,
                min(1 << (maxlen - 1).bit_length(),
                    self.num_blocks * self.block_size),
            )
            with self.tracer.span("engine.prefill", seqs=len(batch), padded=lp):
                toks = np.zeros((len(batch), lp), np.int32)
                for i, (_, r, _) in enumerate(batch):
                    toks[i, : len(r.prompt)] = r.prompt
                tmp = self.prefill_cache_fn(len(batch), lp)
                _, tmp = self._dispatch(
                    "prefill", "prefill_fn", self.params, jnp.asarray(toks), tmp
                )
            self.tracer.add("prefill_tokens", sum(len(r.prompt) for _, r, _ in batch))
            self.tracer.add("prefill_padded_tokens", len(batch) * lp)
            self._scatter_prefill(tmp, batch)
        for s, r, plan, lead in suffix:
            self._prefill_suffix(r, plan, lead)
        for s, r, plan in batch + [(s, r, p) for s, r, p, _ in suffix]:
            self.tracer.note("admitted", r.uid)
            self.slot_req[s] = r
            r.status = "running"
            self.slot_pos[s] = len(r.prompt)
            self.slot_prefill_done[s] = len(r.prompt)
            self.slot_pages[s] = list(plan.pages)
            self.alloc.claim_owner(plan.pages, s)
            self.alloc.mark_written(plan.pages)
            self.block_table[s, :] = paged_lib.SCRATCH_PAGE
            self.block_table[s, : len(plan.pages)] = plan.pages
            self.slot_ticket[s] = self._ticket
            self._ticket += 1
        self._tables_dirty = True

    def _scatter_prefill(self, tmp, batch) -> None:
        """Write each admitted request's non-shared prompt blocks from the
        temporary dense prefill cache into their pool pages, inside the
        `engine.scatter` span, as one call of the compiled, pool-donating
        `scatter_prefill`.  Its (row, block, page) index vectors hold one
        entry per block of the temp cache (`padded`), so an admission never
        compiles a scatter its prefill's shape has not.  The step record
        counts the pages written (`scatter_pages`); the span's args give
        them and `padded`.  Shared prefix pages are never rewritten."""
        idx = [(i, j, pg) for i, (_, _r, plan) in enumerate(batch)
               for j, (pg, sh) in enumerate(zip(plan.pages, plan.shared))
               if not sh]
        n = len(idx)
        padded = len(batch) * jax.tree.leaves(tmp)[0].shape[-3] // self.block_size
        self.tracer.add("scatter_pages", n)
        with self.tracer.span("engine.scatter", pages=n, padded=padded):
            if not n:
                return
            # Host-side numpy indices: a committed device array would pin
            # the call to one device and clash with a sharded pool.
            rows, blocks, pages = np.zeros((3, padded), np.int32)
            rows[:n], blocks[:n], pages[:n] = zip(*idx)
            pool = jax.tree_util.tree_map_with_path(
                lambda p, x: None if _is_table(p) else x, self.caches
            )
            flat, _ = jax.tree_util.tree_flatten_with_path(
                self.scatter_fn(pool, tmp, rows, blocks, pages, np.int32(n))
            )
            written = {jax.tree_util.keystr(p): v for p, v in flat}
            self.caches = jax.tree_util.tree_map_with_path(
                lambda p, x: written.get(jax.tree_util.keystr(p), x), self.caches
            )

    def _gather_prefix(self, tmp, pages: list[int]):
        """Copy written pool pages into the leading rows of a temp dense
        prefill cache — the cached-prefix K/V a suffix prefill attends
        through the prior-concat path.  bf16 layout only (the suffix path is
        gated off under kv8/kv4: a dequantize-requantize round trip would
        break bitwise identity with the cache-off run)."""
        if not pages:
            return tmp
        bs = self.block_size
        n = len(pages)
        pga = jnp.asarray(pages, jnp.int32)
        flat, _ = jax.tree_util.tree_flatten_with_path(self.caches)
        pool_by_path = {jax.tree_util.keystr(p): v for p, v in flat}

        def one(path, leaf):
            name = str(getattr(path[-1], "key", ""))
            if name not in ("k", "v"):
                return leaf
            pool = pool_by_path[jax.tree_util.keystr(path)]
            if _batch_axis(path) == 1:  # stacked groups (G, P, bs, KV, HD)
                blocks = pool[:, pga]   # (G, n, bs, KV, HD)
                seq = blocks.reshape(
                    blocks.shape[0], 1, n * bs, *blocks.shape[3:]
                )
                return leaf.at[:, :, : n * bs].set(seq.astype(leaf.dtype))
            blocks = pool[pga]          # (n, bs, KV, HD)
            seq = blocks.reshape(1, n * bs, *blocks.shape[2:])
            return leaf.at[:, : n * bs].set(seq.astype(leaf.dtype))

        return jax.tree_util.tree_map_with_path(one, tmp)

    def _prefill_suffix(self, req: Request, plan: paged_lib.PagePlan,
                        lead: int) -> None:
        """Radix-cache admission with the first `lead` blocks' K/V already
        in the pool: prefill computes ONLY the un-cached suffix.  The cached
        prefix is gathered into a temp dense cache, the suffix runs as a
        PREFILL at static offset lead*block_size (the prior-concat resume
        path chunked prefill uses), and the computed suffix blocks scatter
        into the plan's private pages — the prefill-FLOPs-saved half of the
        cache win (the write-skip half applies on every layout; see
        docs/DESIGN.md §Prefix caching)."""
        bs = self.block_size
        skip = lead * bs
        plen = len(req.prompt)
        lp = max(bs, min(1 << (plen - 1).bit_length(), self.num_blocks * bs))
        with self.tracer.span("engine.prefill", seqs=1, padded=lp - skip):
            tmp = self.prefill_cache_fn(1, lp)
            tmp = self._gather_prefix(tmp, plan.pages[:lead])
            toks = np.zeros((1, lp - skip), np.int32)
            toks[0, : plen - skip] = np.asarray(req.prompt[skip:], np.int32)
            _, tmp = self._dispatch(
                "prefill", "suffix_prefill_fn", self.params, jnp.asarray(toks),
                tmp, skip,
            )
        self.tracer.add("prefill_tokens", plen - skip)
        self.tracer.add("prefill_padded_tokens", lp - skip)
        self._scatter_prefill(tmp, [(None, req, plan)])

    def _live_table_width(self) -> int:
        """Logical block-table width the NEXT decode dispatch needs: the max
        allocated page count over active slots, bucketed to a power of two
        (compiled decode shapes stay O(log num_blocks)).  Short sequences
        then stop paying for empty trailing table entries — the paged
        attention kernel's grid and the fallback `paged_gather` both scale
        with the table width they are handed.  Tiny tables skip the
        narrowing entirely: each width bucket is a fresh decode compile,
        and below ~8 blocks the recompiles cost more than the few spare
        block reads they save."""
        if self.num_blocks <= 8:
            return self.num_blocks
        # The mixed step widens the table to cover its whole window, pads
        # included: a pad past the table width would clamp onto the row's
        # LAST REAL page (models/layers.py) and corrupt committed history,
        # while inside the width it lands on scratch or a masked future
        # offset of a private page.  _window_blocks is 0 outside mixed steps.
        live = max(1, self._window_blocks)
        for s in range(self.slots):
            if self.slot_req[s] is not None:
                live = max(live, len(self.slot_pages[s]))
        return min(self.num_blocks, 1 << (live - 1).bit_length())

    def _with_tables(self, caches):
        """Refresh every `table` cache leaf from the host block table,
        narrowed to the live-width bucket (_live_table_width)."""
        tbl = self.block_table[:, : self._live_table_width()]
        self._table_width = tbl.shape[-1]

        def one(path, leaf):
            if _is_table(path):
                shape = leaf.shape[:-1] + (tbl.shape[-1],)
                return jnp.asarray(np.broadcast_to(tbl, shape))
            return leaf

        return jax.tree_util.tree_map_with_path(one, caches)

    def _note_attn_blocks(self, rows: list[int]) -> None:
        """Count the paged decode kernel's blocks for one dispatch over
        `rows` into the step record: `attn_blocks_live`, the blocks of
        pages_per_block pages that hold a row's pages, and
        `attn_blocks_grid`, the blocks its grid spans at the dispatch's
        table width (the width `_with_tables` last set)."""
        if self.cache_mode != "paged":
            return
        width = self._table_width
        ppb = attn_lib.pages_per_block(self.block_size, width)
        self.tracer.add("attn_blocks_live", sum(
            -(-min(len(self.slot_pages[s]), width) // ppb) for s in rows))
        self.tracer.add("attn_blocks_grid", self.slots * -(-width // ppb))

    def _preempt(self, s: int) -> None:
        """Evict slot `s`: free its pages and requeue its request at the
        queue front.  Greedy decode is deterministic, so the replay emits
        the same tokens the uninterrupted run would have."""
        req = self.slot_req[s]
        req.generated.clear()
        req.draft_proposed = req.draft_accepted = 0  # replay re-accounts
        req.status = "queued"
        self._release_quota(req)
        self.alloc.free_pages(self.slot_pages[s], owner=s, tenant=req.tenant)
        self.slot_pages[s] = []
        self.block_table[s, :] = paged_lib.SCRATCH_PAGE
        self.slot_req[s] = None
        self.slot_pos[s] = 0
        self.slot_prefill_done[s] = 0  # replay re-runs (chunked) prefill
        self.queue.appendleft(req)
        self._tables_dirty = True
        self.preemptions += 1
        self.tracer.note("preempted", req.uid)

    def _victim_key(self, v: int):
        """Preemption priority — the MAX of this key over live slots is
        evicted.  Phase-split engines keep the original rule (latest
        admission ticket).  Under the token-budget scheduler, SLO class
        outranks ticket: batch rows evict before standard before
        interactive, ties to the latest admission (aging protects queue
        order only; a running interactive row never loses its pages to an
        aged batch row — docs/ROBUSTNESS.md)."""
        if self.scheduler is not None:
            return self.scheduler.victim_key(self.slot_req[v], self.slot_ticket[v])
        return self.slot_ticket[v]

    def _ensure_decode_pages(self, extra: int = 0) -> None:
        """Decode growth: each active slot must own the page its next token
        writes into — and, with `extra` > 0 (the speculative-decode verify
        window), the pages of the `extra` draft positions after it too."""
        self._ensure_pages({
            s: max(int(self.slot_pos[s]) - 1, 0) + extra
            for s in range(self.slots)
            if self.slot_req[s] is not None
        })

    def _ensure_pages(self, ends: dict[int, int]) -> None:
        """Grow each slot's pages to cover its last write position
        (`ends[s]`, absolute — the mixed step passes per-row window ends).
        Allocate at block boundaries in admission order; when the pool is
        dry, preempt the lowest-priority slot (_victim_key) until a page
        frees — possibly the requesting slot itself."""
        order = sorted(ends, key=lambda s: self.slot_ticket[s])
        for s in order:
            if self.slot_req[s] is None:
                continue  # preempted while serving an earlier slot
            need = ends[s] // self.block_size + 1
            while self.slot_req[s] is not None and len(self.slot_pages[s]) < need:
                page = self.alloc.alloc(
                    owner=s, tenant=self.slot_req[s].tenant
                )
                if page is None:
                    victims = [
                        v for v in range(self.slots) if self.slot_req[v] is not None
                    ]
                    victim = max(victims, key=self._victim_key)
                    self._preempt(victim)
                    continue
                self.slot_pages[s].append(page)
                self.block_table[s, len(self.slot_pages[s]) - 1] = page
                self._tables_dirty = True

    @property
    def stats(self) -> dict:
        out = {
            "cache_mode": self.cache_mode,
            "decode_mode": self.decode_mode,
            "sample": self.sample,
            # KV-cache storage layout (core/encoding.kv_layout): bf16, or a
            # quantized paged layout (kv8/kv4) with per-page scales.
            "kv_quant": getattr(self, "kv_quant", "bf16"),
            # Serving weight format (drives the decode weight-stream roofline;
            # see encoding.quant_weight_stream_bytes and docs/DESIGN.md).
            "weight_quant": self.enc.weight_quant,
            # Resolved attention op-class backend for this engine's CURRENT
            # decode regime (kernels/registry.py select_attn; "pallas" = the
            # kernels/attn.py microkernels, "xla" = the jnp references).
            # The S the dispatches actually see: the live-narrowed table
            # width for paged caches, the ring width for sliding windows.
            "attn_backend": registry_lib.select_attn(
                phase=Phase.DECODE,
                s=(
                    self._live_table_width() * self.block_size
                    if self.cache_mode == "paged"
                    else min(self.max_seq, self.cfg.sliding_window)
                    if self.cfg.sliding_window
                    else self.max_seq
                ),
                target=self.enc.target,
                requested=getattr(self.enc, "attn_backend", "xla"),
                kv=getattr(self, "kv_quant", "bf16"),
            ).backend,
            # ---- robustness observables (docs/ROBUSTNESS.md) ---------------
            "steps": self.step_count,
            "watchdog": self.watchdog.summary(
                [r.seconds for r in self.tracer.recent(WATCHDOG_WINDOW)]
            ),
            # Per-span lifetime totals (count, seconds, self seconds, parent)
            # and the step counters' totals (serving/tracing.py).
            "trace": self.tracer.summary(),
            "lifecycle": dict(self.lifecycle),
            # Kernel-quarantine events this process: [{key, step, level,
            # from, to, reason}] — the degradation ladder's audit trail.
            "degraded": [dict(d) for d in self.degraded],
        }
        if self.tp_shards > 1:
            # Per-shard observability under tensor parallelism: the resolved
            # attention backend each shard's ladder would pick (the SPMD
            # dispatch itself runs the max-quarantined rung over shards), and
            # each shard's slice of the degradation trail (global events
            # appear in every shard's list).  Legacy string/list forms are
            # preserved at tp==1 so single-device callers are untouched.
            attn_s = (
                self._live_table_width() * self.block_size
                if self.cache_mode == "paged"
                else min(self.max_seq, self.cfg.sliding_window)
                if self.cfg.sliding_window
                else self.max_seq
            )
            out["attn_backend"] = {
                k: registry_lib.select_attn(
                    phase=Phase.DECODE,
                    s=attn_s,
                    target=self.enc.target,
                    requested=getattr(self.enc, "attn_backend", "xla"),
                    kv=getattr(self, "kv_quant", "bf16"),
                    shard=k,
                ).backend
                for k in range(self.tp_shards)
            }
            out["degraded"] = {
                k: [
                    dict(d) for d in self.degraded
                    if d.get("shard") in (None, k)
                ]
                for k in range(self.tp_shards)
            }
            out["tp"] = {
                "shards": self.tp_shards,
                "mesh_shape": list(self.config.mesh_shape),
                "tp_axis": self.config.tp_axis,
                "enc_downgrades": list(self.enc_downgrades),
            }
        if self.config.downgrades:
            out["config_downgrades"] = list(self.config.downgrades)
        if self.spec_decode:
            st = dict(self.spec_stats)
            # Amortization terms (docs/DESIGN.md §Speculative decode): a slot's
            # verify commits mean_accepted_len tokens per dispatch, so decode
            # dispatches per token is its reciprocal.
            st["acceptance_rate"] = st["accepted"] / max(st["proposed"], 1)
            st["mean_accepted_len"] = st["committed"] / max(st["slot_steps"], 1)
            st["per_slot_proposed"] = self.slot_proposed.tolist()
            st["per_slot_accepted"] = self.slot_accepted.tolist()
            out["spec"] = st
            out["draft_k"] = self.draft_k
        if self.scheduler is not None:
            out["continuous"] = dict(self.continuous)
        if self.cache_mode == "paged":
            astats = self.alloc.stats
            out.update(astats)
            out.update(
                pages_total=self.alloc.capacity,
                pages_in_use=self.alloc.in_use(),
                pages_free=self.alloc.available(),
                preemptions=self.preemptions,
                peak_active=self.peak_active,
                block_size=self.block_size,
            )
            # Radix prefix-cache observability, one shape-stable dict at
            # every tp degree (the PR-9 normalization rule: reporting code
            # must not care about the mesh — per-shard copies of these
            # counters are asserted identical by ShardedBlockAllocator and
            # also appear under tp.per_shard_pages).
            out["prefix_cache"] = {
                "enabled": self.prefix_cache,
                "hit_blocks": astats["hit_blocks"],
                "hit_tokens": astats["hit_tokens"],
                "lookup_blocks": astats["lookup_blocks"],
                "hit_rate": (
                    astats["hit_blocks"] / astats["lookup_blocks"]
                    if astats["lookup_blocks"] else 0.0
                ),
                "evictions": astats["evictions"],
                "cached_pages": astats["cached_pages"],
                "deferred_hits": self.deferred_hits,
            }
            if self.tenant_quota is not None:
                out["prefix_cache"]["tenant_quota"] = self.tenant_quota
                out["prefix_cache"]["tenant_usage"] = self.alloc.tenant_usage()
            if self.tp_shards > 1:
                out["tp"]["per_shard_pages"] = self.alloc.per_shard_stats()
        return out

    def stats_view(self) -> dict:
        """`stats` with a SHAPE-STABLE schema across tp degrees.

        The raw `stats` property keeps its legacy forms — a scalar
        `attn_backend` string and a flat `degraded` list at tp==1, per-shard
        dicts at tp>1 — because both shapes are pinned by existing callers
        and tests.  Reporting code that must not care about the mesh (e.g.
        launch/serve.py) uses this accessor instead: `attn_backend` and
        `degraded` are ALWAYS {shard -> value} dicts, with the single-device
        engine presented as shard 0."""
        out = self.stats
        if self.tp_shards == 1:
            out["attn_backend"] = {0: out["attn_backend"]}
            out["degraded"] = {0: out["degraded"]}
        return out

    def audit(self) -> None:
        """Assert allocator/table consistency (tests call this every step).
        Pages seized by an active fault schedule (pool_spike holds) are
        legitimate references, not leaks — fold them in as one extra table
        so the exact-partition check keeps holding under chaos."""
        if self.cache_mode != "paged":
            return
        tables = [
            self.slot_pages[s] for s in range(self.slots)
            if self.slot_req[s] is not None
        ]
        held = (
            list(self.hooks.held_pages())
            if self.hooks is not None and hasattr(self.hooks, "held_pages")
            else []
        )
        if held:
            tables = tables + [held]
        self.alloc.audit(tables)

    # ---- dense admission ---------------------------------------------------

    def _admit(self):
        if self.scheduler is not None:
            return self._admit_budget()
        if self.cache_mode == "paged":
            return self._admit_paged()
        free = [s for s in range(self.slots) if self.slot_req[s] is None]
        batch: list[tuple[int, Request]] = []
        while free and self.queue:
            req = self.queue.popleft()
            if req.max_new_tokens <= 0:
                # Degenerate request: nothing to decode — never occupies a slot.
                self._finish_degenerate(req)
                continue
            if req.cancel_requested or self._past_deadline(req):
                # Deadline/cancel re-check at admission time (the _reap
                # sweep's snapshot can lapse within the same step).
                self._admission_reap(req)
                continue
            batch.append((free.pop(0), req))
        if not batch:
            return
        if self.batch_prefill and len(batch) > 1:
            # One right-padded prefill for every admitted request.  Pad tokens
            # only write cache positions the decode mask (slot <= pos) never
            # reads before a real token overwrites them.  The pad length
            # rounds up to a power of two so the jitted prefill compiles for
            # O(slots * log(max_seq)) shapes, not one per distinct maxlen.
            slots_sel = [s for s, _ in batch]
            maxlen = max(len(r.prompt) for _, r in batch)
            maxlen = min(1 << (maxlen - 1).bit_length(), self.max_seq)
            with self.tracer.span("engine.prefill", seqs=len(batch), padded=maxlen):
                toks = np.zeros((len(batch), maxlen), np.int32)
                for i, (_, r) in enumerate(batch):
                    toks[i, : len(r.prompt)] = r.prompt
                part = slot_gather(self.caches, slots_sel)
                _, part = self._dispatch(
                    "prefill", "prefill_fn", self.params, jnp.asarray(toks), part
                )
            with self.tracer.span("engine.scatter"):
                self.caches = slot_merge(
                    self.caches, part, slots_sel, list(range(len(batch)))
                )
            self.tracer.add("prefill_padded_tokens", len(batch) * maxlen)
        else:
            for s, r in batch:
                # Per-slot prefill: batch of 1 through a slot-sliced cache view.
                with self.tracer.span("engine.prefill", seqs=1, padded=len(r.prompt)):
                    toks = jnp.asarray(r.prompt, jnp.int32)[None]
                    slot_cache = slot_slice(self.caches, s)
                    _, slot_cache = self._dispatch(
                        "prefill", "prefill_fn", self.params, toks, slot_cache
                    )
                with self.tracer.span("engine.scatter"):
                    self.caches = slot_merge(self.caches, slot_cache, [s], [0])
                self.tracer.add("prefill_padded_tokens", len(r.prompt))
        self.tracer.add("prefill_tokens", sum(len(r.prompt) for _, r in batch))
        for s, r in batch:
            self.tracer.note("admitted", r.uid)
            self.slot_req[s] = r
            r.status = "running"
            self.slot_pos[s] = len(r.prompt)
            self.slot_prefill_done[s] = len(r.prompt)

    # ---- token-budget admission (no prefill dispatch) ----------------------

    def _admit_budget(self) -> None:
        """Admission under the token-budget scheduler: NO prefill dispatch
        here — an admitted request's prompt streams into the cache through
        the mixed step's chunk rows (slot_prefill_done tracks progress), so
        admitting a 4k-token prompt costs this step nothing.  Candidates
        are taken in SLO priority order (TokenBudgetScheduler.queue_key)
        instead of FIFO; pool pressure stops admission at the first
        candidate that does not fit, so a smaller request never jumps a
        starved larger one.  Paged prompts commit their whole page plan up
        front; leading prefix-shared pages are reused VERBATIM —
        slot_prefill_done starts past them, so a chunk row never rewrites a
        shared page and the COW boundary stays exact even when the shared
        prefix is not chunk- or block-aligned (the partial boundary block
        was already COW-split by plan_prompt/commit_prompt)."""
        free = [s for s in range(self.slots) if self.slot_req[s] is None]
        if not free or not self.queue:
            return
        candidates = sorted(
            self.queue,
            key=lambda r: self.scheduler.queue_key(r, self.step_count),
        )
        for req in candidates:
            if not free:
                break
            if req.max_new_tokens <= 0:
                self.queue.remove(req)
                self._finish_degenerate(req)
                continue
            if req.cancel_requested or self._past_deadline(req):
                # Deadline/cancel re-check at admission time (the _reap
                # sweep's snapshot can lapse within the same step).
                self.queue.remove(req)
                self._admission_reap(req)
                continue
            if self._quota_blocked(req):
                # Tenant over quota: skip (never `break` — other tenants'
                # queued work must keep flowing past a capped tenant).
                continue
            done = 0
            if self.cache_mode == "paged":
                nblocks, shared = self.alloc.plan_prompt(req.prompt)
                # Share only pages whose content has actually LANDED:
                # commit_prompt registers pages before any chunk writes
                # them (chunked prefill is lazy), and a row prefilling
                # from INSIDE a shared block sprays its window-pad writes
                # (positions past its chunk, garbage K/V) across the
                # owner's history.  Truncating the plan at the first
                # unwritten page keeps this row's entire write range —
                # real chunks AND pads — inside private pages: written
                # shared pages are skipped outright (slot_prefill_done
                # starts past them), unwritten ones are never shared.
                lead = 0
                while (lead in shared
                       and self.alloc.is_written(shared[lead])):
                    lead += 1
                if lead < len(shared) and self._defer_for_writer(req, lead):
                    # Declined unwritten shares need not be FORFEITED: the
                    # writer's chunks are still landing, so re-check the
                    # tree at this request's next admission opportunity
                    # instead of committing a recomputed private copy now.
                    # Bounded (_DEFER_CAP) so a stalled writer cannot
                    # park a candidate forever.
                    continue
                if getattr(req, "_defer_lead", None) is not None:
                    # Admitted after deferring: every block the wait turned
                    # from an unwritten decline into a real share is a hit
                    # the old code silently forfeited.
                    self.deferred_hits += max(0, lead - req._defer_lead)
                    req._defer_lead = None
                shared = {j: p for j, p in shared.items() if j < lead}
                if not self.alloc.plan_fits(nblocks, shared):
                    break  # pool pressure: the head candidate waits
                plan = self.alloc.commit_prompt(
                    req.prompt, nblocks, shared, tenant=req.tenant
                )
                assert plan is not None
                s = free.pop(0)
                self.slot_pages[s] = list(plan.pages)
                self.alloc.claim_owner(plan.pages, s)
                self.block_table[s, :] = paged_lib.SCRATCH_PAGE
                self.block_table[s, : len(plan.pages)] = plan.pages
                self.slot_ticket[s] = self._ticket
                self._ticket += 1
                self._tables_dirty = True
                done = lead * self.block_size
                self._reserve_quota(req)
            else:
                s = free.pop(0)
            self.queue.remove(req)
            self.tracer.note("admitted", req.uid)
            self.slot_req[s] = req
            req.status = "running"
            self.slot_prefill_done[s] = done
            self.slot_pos[s] = done
            self.continuous["chunked_admissions"] += 1

    # A candidate declining unwritten prefix shares re-checks the tree for at
    # most this many admission opportunities before giving up and recomputing
    # the prefix privately (satellite: deferred_hits).
    _DEFER_CAP = 4

    def _defer_for_writer(self, req: Request, lead: int) -> bool:
        """Whether to hold `req` out of this admission round because part of
        its tree-matched prefix is still unwritten (the writer's chunks are
        in flight).  Records the written lead at defer time so the eventual
        admission can count the blocks the wait recovered."""
        count = getattr(req, "_defer_count", 0)
        if count >= self._DEFER_CAP:
            return False
        req._defer_count = count + 1
        req._defer_lead = lead
        return True

    def _finish_slot(self, s: int, *, status: str = "ok",
                     error: str | None = None) -> None:
        """Retire slot `s` with a terminal status.  EVERY slot exit — normal
        completion, cancel, deadline expiry, guard trip — funnels through
        here, so page release and table reset are a single code path the
        allocator audit can hold exactly."""
        req = self.slot_req[s]
        req.done = True
        req.status = status
        req.error = error
        self.finished.append(req)
        self.tracer.note("finished", req.uid)
        if status != "ok":
            self.lifecycle[status] = self.lifecycle.get(status, 0) + 1
        self.slot_req[s] = None
        self.slot_pos[s] = 0  # freed rows decode (discarded) at pos 0
        self.slot_prefill_done[s] = 0
        if self.cache_mode == "paged":
            # Released-on-finish: every page's refcount drops; table row back
            # to scratch.  With the prefix cache on, registered+written
            # blocks whose refcount hits 0 are PARKED in the radix tree
            # (state "cached") instead of freed — this release IS the
            # insert-on-finish the radix cache lives on.  Everything else
            # (trailing decode pages, partial blocks) frees as before.
            self._release_quota(req)
            self.alloc.free_pages(self.slot_pages[s], owner=s,
                                  tenant=req.tenant)
            self.slot_pages[s] = []
            self.block_table[s, :] = paged_lib.SCRATCH_PAGE
            self._tables_dirty = True

    def _commit_tokens(self, s: int, toks: list[int]) -> int:
        """Append `toks` to slot s in order, honouring EOS / max_new_tokens /
        max_seq mid-list (spec decode commits several tokens per dispatch; a
        finish condition truncates the rest — post-EOS tokens are never
        emitted).  Returns how many tokens were emitted."""
        req = self.slot_req[s]
        emitted = 0
        for t in toks:
            req.generated.append(t)
            self.slot_pos[s] += 1
            emitted += 1
            if self.stream_cb is not None:
                self.stream_cb(req, int(t))
            if (
                (req.eos_id is not None and t == req.eos_id)
                or len(req.generated) >= req.max_new_tokens
                or self.slot_pos[s] >= self.max_seq
            ):
                self._finish_slot(s)
                break
        return emitted

    def _commit(
        self, slots_sel: list[int], nxt: np.ndarray,
        bad: frozenset[int] = frozenset(),
    ) -> int:
        """Commit this dispatch's tokens.  `bad` slots (non-finite logits)
        finish with status "error" and emit nothing; a cancel that landed
        while the dispatch was in flight is honoured HERE — the request
        never sees a token sampled after its cancel."""
        emitted = 0
        for s in slots_sel:
            if self.slot_req[s] is None:
                continue
            if s in bad:
                self._finish_slot(
                    s, status="error",
                    error="non-finite logits (guard tripped)",
                )
                continue
            if self.slot_req[s].cancel_requested:
                self._finish_slot(
                    s, status="cancelled", error="cancelled mid-dispatch"
                )
                continue
            emitted += self._commit_tokens(s, [int(nxt[s, 0])])
        return emitted

    # ---- speculative decode (prompt-lookup draft + batched verify) ---------

    def _last_tokens(self, active: list[int]) -> np.ndarray:
        last_tokens = np.zeros((self.slots, 1), np.int32)
        for s in active:
            req = self.slot_req[s]
            last_tokens[s, 0] = req.generated[-1] if req.generated else int(req.prompt[-1])
        return last_tokens

    def _sample_args(self, active: list[int]):
        """(key, temp) extras for sample="temperature" decode dispatches —
        one fresh key per dispatch, per-slot temperature from the request."""
        key = jax.random.fold_in(self._base_key, self._step_idx)
        self._step_idx += 1
        temp = np.zeros(self.slots, np.float32)
        for s in active:
            temp[s] = self.slot_req[s].temperature
        return key, jnp.asarray(temp)

    def _refresh_tables(self) -> None:
        if self.cache_mode == "paged" and self._tables_dirty:
            # Thread the (host-maintained) block tables into the cache
            # leaves; the decode dispatch gathers K/V pages by table.
            # Unchanged tables flow through the donated decode call, so
            # steady-state steps skip the host->device refresh.
            with self.tracer.span("engine.tables"):
                self.caches = self._with_tables(self.caches)
            self._tables_dirty = False
            self.tracer.add("tables_refreshed")

    def _plan_drafts(self, active: list[int], k_max: int | None = None):
        """(L, {slot: draft}) for this step's verify window, or None to take
        the plain one-token path (no headroom, or nothing to propose).
        `k_max` caps drafts below draft_k (the token-budget mixed step
        shares its budget between drafts and prefill chunks)."""
        # One shared window length L: every row's last verify write lands at
        # pos-1 + L-1, which must stay inside max_seq even for padded rows
        # (pads scatter real cache writes), so the most constrained slot caps
        # the batch.  Compiled verify shapes stay O(draft_k) distinct.
        k = self.draft_k if k_max is None else min(self.draft_k, int(k_max))
        head = min(self.max_seq - int(self.slot_pos[s]) + 1 for s in active)
        L = min(1 + k, head)
        if L <= 1:
            return None
        drafts: dict[int, np.ndarray] = {}
        any_draft = False
        for s in active:
            req = self.slot_req[s]
            # A commit is at most (accepted drafts + 1 bonus) tokens — never
            # draft past the request's remaining budget.
            room = req.max_new_tokens - len(req.generated) - 1
            kk = min(L - 1, max(room, 0))
            d = spec_lib._EMPTY
            if kk > 0:
                ctx = np.concatenate([
                    np.asarray(req.prompt, np.int32),
                    np.asarray(req.generated, np.int32),
                ])
                d = np.asarray(self.drafter(ctx, kk), np.int32).ravel()[:kk]
            drafts[s] = d
            any_draft = any_draft or d.size > 0
        return (L, drafts) if any_draft else None

    def _draft_pages_fit(self, active: list[int], L: int) -> bool:
        """True when every active slot's draft window (positions through
        pos-1 + L-1) fits the free pool as-is.  Speculation is an
        optimization: it must NEVER preempt a live request to fund pages
        that only unverified drafts need — when the window doesn't fit, the
        step falls back to plain one-token decode (which allocates at most
        the baseline growth page and may legitimately preempt for that).
        `available()` counts free plus EVICTABLE cached pages: funding a
        draft window may drain cold prefix cache, but never live requests —
        the same eviction-before-preemption ordering the radix cache keeps
        everywhere (docs/ROBUSTNESS.md §Eviction vs preemption)."""
        need = 0
        for s in active:
            pos = max(int(self.slot_pos[s]) - 1, 0) + L - 1
            need += max(0, pos // self.block_size + 1 - len(self.slot_pages[s]))
        return need <= self.alloc.available()

    def _truncate_slot_pages(self, s: int) -> None:
        """Spec-decode rollback: return the pages only rejected drafts
        touched.  The committed history plus the next write position
        (slot_pos - 1) define what the slot still needs; trailing pages go
        back to the pool and their table entries back to scratch.  The stale
        draft K/V inside KEPT pages needs no scrubbing — the decode mask
        (slot <= pos) hides it until a later write replaces it.

        Rollback never frees tree-cached content: draft pages are trailing
        DECODE growth, past the prompt's immutable blocks, so none of them
        can be registered in the radix tree (commit_prompt only registers
        blocks j < shareable_blocks(plen)).  The assert keeps that contract
        explicit — serving/spec.py documents the other half."""
        need = (int(self.slot_pos[s]) - 1) // self.block_size + 1
        extra = self.slot_pages[s][need:]
        if not extra:
            return
        assert not any(self.alloc.is_registered(p) for p in extra), (
            "spec rollback would free radix-registered pages"
        )
        self.slot_pages[s] = self.slot_pages[s][:need]
        req = self.slot_req[s]
        self.alloc.free_pages(
            extra, owner=s,
            tenant=req.tenant if req is not None else paged_lib.DEFAULT_TENANT,
        )
        self.block_table[s, need:] = paged_lib.SCRATCH_PAGE
        self._tables_dirty = True

    def _spec_step(self, active: list[int], L: int, drafts: dict) -> int:
        """ONE batched verify dispatch scores every slot's draft window;
        commit each slot's longest greedy-consistent prefix + bonus token."""
        tr = self.tracer
        with tr.span("engine.dispatch"):
            mat = np.zeros((self.slots, L), np.int32)
            mat[:, :1] = self._last_tokens(active)
            for s in active:
                mat[s, 1 : 1 + drafts[s].size] = drafts[s]
            pos_vec = np.maximum(self.slot_pos.astype(np.int32) - 1, 0)
            logits, self.caches = self._dispatch(
                "verify", "verify_fn",
                self.params, self.caches, jnp.asarray(mat), jnp.asarray(pos_vec),
            )
        tr.add("decode_rows", len(active))
        self._note_attn_blocks(active)
        bad = self._guard_slots(logits, active)
        # tgt[s, j]: the model's greedy token AFTER consuming mat[s, :j+1] —
        # the acceptance target for draft j and the bonus token at the cut.
        with tr.span("engine.wait"):
            tgt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        st = self.spec_stats
        st["steps"] += 1
        emitted = 0
        with tr.span("engine.commit"):
            for s in active:
                if s in bad:
                    self._finish_slot(
                        s, status="error",
                        error="non-finite logits (guard tripped, verify)",
                    )
                    continue
                if self.slot_req[s].cancel_requested:
                    # The cancel landed while the draft window was in flight: no
                    # token from this verify is ever emitted; the slot's pages
                    # (draft positions included) free through _finish_slot.
                    self._finish_slot(
                        s, status="cancelled", error="cancelled mid-dispatch"
                    )
                    continue
                d = drafts[s]
                a = 0
                while a < d.size and int(d[a]) == int(tgt[s, a]):
                    a += 1
                commit = [int(t) for t in d[:a]] + [int(tgt[s, a])]
                req = self.slot_req[s]
                got = self._commit_tokens(s, commit)
                # A finish condition inside the window (EOS among the accepted
                # drafts, max_new_tokens, max_seq) truncates the commit.  The
                # draft tail past the cut was scored but never influenced
                # output — counting it inflated draft_proposed and skewed
                # acceptance_rate low on EOS-heavy workloads.  Count only the
                # drafts actually consumed: on truncation every emitted token
                # IS an accepted draft (the bonus never lands), so proposed ==
                # accepted == got for that row.
                if got == len(commit):
                    scored, used = int(d.size), a
                else:
                    scored = used = min(got, a)
                req.draft_proposed += scored
                req.draft_accepted += used
                self.slot_proposed[s] += scored
                self.slot_accepted[s] += used
                st["slot_steps"] += 1
                st["proposed"] += scored
                st["accepted"] += used
                st["committed"] += got
                emitted += got
                if self.cache_mode == "paged" and self.slot_req[s] is not None:
                    self._truncate_slot_pages(s)
        return emitted

    # ---- token-budget mixed step (chunked prefill beside decode) -----------

    def _mixed_step(self) -> int:
        """ONE token-budget-bounded decode-phase dispatch for every active
        slot: decode rows spend 1 token each (or their spec-verify window)
        and prefill rows spend a chunk of their remaining prompt — a long
        prompt admitted mid-decode streams into the cache beside the
        decoding slots instead of pausing them (zero decode-stall steps by
        construction; tests/test_token_budget.py).

        The window generalizes the spec-verify machinery: row r holds
        tokens for positions start_r .. start_r + L - 1, where start_r is
        slot_pos - 1 (decode: the last committed token re-presented) or
        prefill_done (prefill: the next chunk).  The masked-causal window
        mask on top of the full committed history IS chunked-prefill
        masking when the window holds prompt tokens.  Window pads write
        garbage K/V strictly BEYOND every row's real content — masked until
        a later real write lands first (the spec-rollback contract) — and
        the shared width L is head-capped so no pad reaches max_seq, while
        the paged table is widened to cover the window so no pad clamps
        onto committed pages (_live_table_width).  A prefill row's final
        chunk yields its first generated token in the same dispatch: the
        logits at the chunk's last window index are the same computation
        the phase-split path runs as its first decode, so output is
        token-identical to sequential prefill-then-decode."""
        active = [s for s in range(self.slots) if self.slot_req[s] is not None]
        if not active:
            return 0
        cont = self.continuous
        decode_rows = [
            s for s in active
            if self.slot_prefill_done[s] >= len(self.slot_req[s].prompt)
        ]
        prefill_rows = [s for s in active if s not in set(decode_rows)]

        # Per-row window start; L is capped so start + L <= max_seq for
        # EVERY row (pads scatter real cache writes).  Live decode rows
        # always have slot_pos <= max_seq - 1 (the finish funnel retires
        # them at max_seq) and prefill rows have done < plen <= max_seq,
        # so head >= 1 and decode rows always fit their 1 real token.
        start = {
            s: (
                max(int(self.slot_pos[s]) - 1, 0)
                if s in set(decode_rows)
                else int(self.slot_prefill_done[s])
            )
            for s in active
        }
        head = min(self.max_seq - start[s] for s in active)

        # Spec drafts for decode rows, capped by the budget's spare share.
        drafts: dict[int, np.ndarray] = {}
        if self.spec_decode and decode_rows:
            k_cap = spec_lib.draft_budget(
                self.draft_k, len(decode_rows), self.token_budget
            )
            plan = (
                self._plan_drafts(decode_rows, k_max=min(k_cap, head - 1))
                if k_cap > 0 and head > 1
                else None
            )
            if plan is not None:
                drafts = {s: d for s, d in plan[1].items() if d.size}
            if drafts and self.cache_mode == "paged":
                # Never preempt a live request for pages only unverified
                # drafts need (the _draft_pages_fit contract, per-row).
                need = sum(
                    max(
                        0,
                        (start[s] + int(drafts[s].size)) // self.block_size
                        + 1 - len(self.slot_pages[s]),
                    )
                    for s in drafts
                )
                if need > self.alloc.available():
                    self.spec_stats["pool_deferred"] += 1
                    drafts = {}

        # Budget split: decode rows first (their windows), prefill chunks
        # take the rest — at least 1 token per prefill row.
        decode_cost = sum(
            1 + int(drafts.get(s, spec_lib._EMPTY).size) for s in decode_rows
        )
        chunks: dict[int, int] = {}
        if prefill_rows:
            remaining = {
                s: len(self.slot_req[s].prompt) - int(self.slot_prefill_done[s])
                for s in prefill_rows
            }
            order = sorted(
                prefill_rows,
                key=lambda s: (
                    self.scheduler.rank(self.slot_req[s]),
                    int(self.slot_ticket[s]) if self.cache_mode == "paged" else s,
                ),
            )
            chunks = self.scheduler.split_chunks(decode_cost, remaining, order)
            chunks = {s: min(c, head) for s, c in chunks.items()}

        # Shared window width, bucketed to a power of two so compiled mixed
        # shapes stay O(log budget) distinct; the head cap still rules
        # (real content never exceeds head, so the min never truncates it).
        width = 1
        for s in decode_rows:
            width = max(width, 1 + int(drafts.get(s, spec_lib._EMPTY).size))
        for s in prefill_rows:
            width = max(width, chunks[s])
        L = min(1 << (width - 1).bit_length(), head)

        if self.cache_mode == "paged":
            ends = {}
            for s in decode_rows:
                ends[s] = start[s] + int(drafts.get(s, spec_lib._EMPTY).size)
            for s in prefill_rows:
                ends[s] = start[s] + chunks[s] - 1  # within the admitted plan
            with self.tracer.span("engine.pages"):
                self._ensure_pages(ends)
            if any(self.slot_req[s] is None for s in active):
                # Pool growth preempted someone mid-plan: replan the whole
                # window against the surviving slots rather than reason
                # about a half-evicted layout.  Bounded by slot count.
                return self._mixed_step()
            self.peak_active = max(self.peak_active, len(active))
            # Widen the table to the window (pad-write safety; see
            # _live_table_width) and refresh if the width bucket moved.
            wb = max((start[s] + L - 1) // self.block_size + 1 for s in active)
            if wb != self._window_blocks:
                self._window_blocks = wb
                self._tables_dirty = True
        self._refresh_tables()

        tr = self.tracer
        with tr.span("engine.dispatch"):
            k_cols = 1 + self.draft_k if self.spec_decode else 1
            mat = np.zeros((self.slots, L), np.int32)
            pos_vec = np.zeros(self.slots, np.int32)
            idx = np.zeros((self.slots, k_cols), np.int32)
            for s in decode_rows:
                req = self.slot_req[s]
                mat[s, 0] = (
                    req.generated[-1] if req.generated else int(req.prompt[-1])
                )
                d = drafts.get(s, spec_lib._EMPTY)
                if d.size:
                    mat[s, 1 : 1 + d.size] = d
                pos_vec[s] = start[s]
                idx[s] = np.minimum(np.arange(k_cols), L - 1)
            for s in prefill_rows:
                req = self.slot_req[s]
                done, c = int(self.slot_prefill_done[s]), chunks[s]
                mat[s, :c] = np.asarray(req.prompt[done : done + c], np.int32)
                pos_vec[s] = done
                idx[s] = c - 1  # the final chunk's bonus logit; unused otherwise

            self._mixed_m = self.slots * L
            cont["mixed_steps"] += 1
            cont["decode_tokens"] += decode_cost
            cont["prefill_tokens"] += sum(chunks.values())
            logits, self.caches = self._dispatch(
                "mixed", "mixed_fn",
                self.params, self.caches,
                jnp.asarray(mat), jnp.asarray(pos_vec), jnp.asarray(idx),
            )
        tr.add("decode_rows", len(decode_rows))
        tr.add("prefill_tokens", sum(chunks.values()))
        tr.add("prefill_padded_tokens", len(prefill_rows) * L)
        self._note_attn_blocks(active)
        bad = self._guard_slots(logits, active)
        # tgt[s, j]: the greedy token after consuming mat[s, :idx[s, j]+1].
        with tr.span("engine.wait"):
            tgt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        st = self.spec_stats if (self.spec_decode and drafts) else None
        if st is not None:
            st["steps"] += 1
        emitted = 0
        decode_emitted = 0
        with tr.span("engine.commit"):
            for s in active:
                if self.slot_req[s] is None:
                    continue
                if s in bad:
                    self._finish_slot(
                        s, status="error",
                        error="non-finite logits (guard tripped, mixed)",
                    )
                    continue
                req = self.slot_req[s]
                if req.cancel_requested:
                    self._finish_slot(
                        s, status="cancelled", error="cancelled mid-dispatch"
                    )
                    continue
                if s in chunks:
                    # Prefill row: the chunk's K/V landed in cache this dispatch.
                    done = int(self.slot_prefill_done[s]) + chunks[s]
                    self.slot_prefill_done[s] = done
                    self.slot_pos[s] = done
                    if self.cache_mode == "paged":
                        # Fully covered prompt blocks are now valid prefix
                        # content for later prefix-sharing admissions — and
                        # retainable in the radix cache once released.
                        self.alloc.mark_written(
                            self.slot_pages[s][: done // self.block_size]
                        )
                    if done >= len(req.prompt):
                        # Final chunk: its last window index scored position
                        # plen - 1 — the first decode.  Committing it here keeps
                        # prefill completion and first token in one dispatch.
                        cont["completed_prefills"] += 1
                        got = self._commit_tokens(s, [int(tgt[s, 0])])
                        emitted += got
                    continue
                # Decode row: greedy-consistent draft prefix + bonus token
                # (plain decode is the d.size == 0 degenerate: bonus only).
                d = drafts.get(s, spec_lib._EMPTY)
                a = 0
                while a < d.size and int(d[a]) == int(tgt[s, a]):
                    a += 1
                commit = [int(t) for t in d[:a]] + [int(tgt[s, a])]
                got = self._commit_tokens(s, commit)
                emitted += got
                decode_emitted += got
                if st is not None:
                    # Same truncation-aware accounting as _spec_step.
                    if got == len(commit):
                        scored, used = int(d.size), a
                    else:
                        scored = used = min(got, a)
                    req.draft_proposed += scored
                    req.draft_accepted += used
                    self.slot_proposed[s] += scored
                    self.slot_accepted[s] += used
                    st["slot_steps"] += 1
                    st["proposed"] += scored
                    st["accepted"] += used
                    st["committed"] += got
                if self.cache_mode == "paged" and self.slot_req[s] is not None:
                    self._truncate_slot_pages(s)
            if decode_rows and decode_emitted == 0 and any(
                self.slot_req[s] is not None for s in decode_rows
            ):
                # A live decode row emitted nothing this step — the stall the
                # token budget exists to prevent (0 by construction; the bench
                # gate pins it).
                cont["decode_stall_steps"] += 1
        return emitted

    # ---- the engine loop ---------------------------------------------------

    def step(self) -> int:
        """One engine iteration: fire fault hooks, reap cancelled/expired
        requests, admit, then ONE decode (or ONE speculative verify)
        dispatch for every active slot — one `engine.step` span and step
        record (serving/tracing.py), whose duration the watchdog reads
        (exception-safe: a dispatch that raises still records its
        latency)."""
        self.step_count += 1
        step = self.tracer.step(self.step_count, self.slots)
        try:
            with step as rec:
                try:
                    rec.emitted = self._step_inner()
                finally:
                    if self.cache_mode == "paged":
                        rec.pages_in_use = self.alloc.in_use()
                        rec.pages_total = self.alloc.capacity
        finally:
            self.watchdog.observe(step.record.seconds)
        return rec.emitted

    def _step_inner(self) -> int:
        tr = self.tracer
        if self.hooks is not None:
            self.hooks.on_step_begin(self)
        with tr.span("engine.reap"):
            self._reap_lifecycle()
        with tr.span("engine.admit"):
            self._admit()
        if self.scheduler is not None:
            # Token-budget continuous batching: one mixed dispatch serves
            # decode AND chunked prefill; the phase-split paths below never
            # run for this engine.
            return self._mixed_step()
        active = [s for s in range(self.slots) if self.slot_req[s] is not None]
        if not active:
            return 0
        spec_plan = self._plan_drafts(active) if self.spec_decode else None
        if self.cache_mode == "paged":
            if spec_plan is not None and not self._draft_pages_fit(active, spec_plan[0]):
                self.spec_stats["pool_deferred"] += 1
                spec_plan = None
            with tr.span("engine.pages"):
                self._ensure_decode_pages(extra=(spec_plan[0] - 1) if spec_plan else 0)
            # Decode growth may have preempted slots (requests requeued).
            active = [s for s in range(self.slots) if self.slot_req[s] is not None]
            if not active:
                return 0
            self.peak_active = max(self.peak_active, len(active))
            if spec_plan is not None:
                L, drafts = spec_plan
                live = set(active)
                drafts = {s: d for s, d in drafts.items() if s in live}
                spec_plan = (
                    (L, drafts) if any(d.size for d in drafts.values()) else None
                )
        if spec_plan is not None:
            self._refresh_tables()
            return self._spec_step(active, *spec_plan)
        if self.decode_mode == "vectorized":
            self._refresh_tables()
            with tr.span("engine.dispatch"):
                # One dispatch serves all active slots regardless of position
                # skew: each row decodes at its own pos.  Inactive rows decode
                # (and write their cache row at pos 0) with token 0; that write
                # is harmless because every cache position is written before
                # it is attended — the next admission's prefill rewrites the
                # row from position 0 up.
                last_tokens = self._last_tokens(active)
                pos_vec = np.maximum(self.slot_pos.astype(np.int32) - 1, 0)
                args = (
                    self.params, self.caches,
                    jnp.asarray(last_tokens), jnp.asarray(pos_vec),
                )
                if self.sample == "temperature":
                    args = args + self._sample_args(active)
                nxt, logits, self.caches = self._dispatch("decode", "decode_fn", *args)
            tr.add("decode_rows", len(active))
            self._note_attn_blocks(active)
            bad = self._guard_slots(logits, active)
            with tr.span("engine.wait"):
                nxt = np.asarray(nxt)
            with tr.span("engine.commit"):
                return self._commit(active, nxt, bad)
        # Grouped (recurrent state): slots admitted with different prompt
        # lengths decode on their own pos via per-pos grouping; each group's
        # cache rows merge back selectively so other groups' histories stay
        # untouched.
        with tr.span("engine.dispatch"):
            last_tokens = self._last_tokens(active)
        tr.add("decode_rows", len(active))
        groups: dict[int, list[int]] = {}
        for s in active:
            groups.setdefault(int(self.slot_pos[s]), []).append(s)
        emitted = 0
        for p, slots in groups.items():
            with tr.span("engine.dispatch"):
                args = (
                    self.params, self.caches,
                    jnp.asarray(last_tokens), jnp.asarray(p - 1, jnp.int32),
                )
                if self.sample == "temperature":
                    args = args + self._sample_args(slots)
                nxt, logits, new_caches = self._dispatch("decode", "decode_fn", *args)
                self.caches = slot_merge(self.caches, new_caches, slots)
            bad = self._guard_slots(logits, slots)
            with tr.span("engine.wait"):
                nxt = np.asarray(nxt)
            with tr.span("engine.commit"):
                emitted += self._commit(slots, nxt, bad)
        return emitted

    def run(self) -> list[Request]:
        while self.queue or any(r is not None for r in self.slot_req):
            self.step()
        return self.finished
