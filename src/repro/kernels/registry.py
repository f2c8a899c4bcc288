"""Unified microkernel dispatch registry — the analogue of IREE's ukernel
selection boundary (TinyIREE's "clean selection/deployment seam").

Every encoded matmul used to pick its implementation through scattered
`backend="fused"/"pallas"/"q8"` branching in ops.py call sites.  This module
centralizes the decision behind one key.  Two op classes share the table:

matmul (select()):

    (quant mode, phase, M-bucket, target name)  ->  KernelChoice(backend, blocks)

* quant mode : "none" (bf16/f32), "w8a8" (int8), "w4a8" (group int4)
* M-bucket   : live-row regime — "m1" (pure GEMV), "m8" (decode slots),
               "m32" (spec-decode verify: slots x draft window), "m64"
               (skinny GEMM), "big" (prefill slab); buckets keep the table
               finite while still separating the paper's two regimes.
* target     : TargetSpec.name from core/targets.py

attention (select_attn()):

    ("attn", phase, S-bucket[, kv-quant], target name)
        ->  KernelChoice(backend, blocks)

* S-bucket   : context-length regime — "s256"/"s1k"/"s4k"/"sbig" over the
               logical KV length the dispatch attends (cache width at
               decode, key length at prefill).  Attention cost scales with
               S the way matmul cost scales with M, so S plays the bucket
               role here.
* kv-quant   : the KV-cache storage layout the kernel streams ("bf16",
               "kv8", "kv4" — core/encoding.KV_QUANTS).  bf16 keys keep
               the legacy 4-segment form `attn|{phase}|{S}|{target}` so
               every checked-in tuned entry, fault-schedule fnmatch
               pattern, and quarantine record stays valid; quantized
               layouts insert the axis: `attn|{phase}|{S}|{kv}|{target}`.
               A kv-quant key with no tuned entry inherits the bf16
               entry's blocks (chunking geometry is dtype-independent
               until a retune says otherwise).
* backend    : "xla" (the jnp references layers.attention_decode /
               attention_chunked) or "pallas" (kernels/attn.py — paged or
               dense decode kernel, flash prefill).
* blocks     : (q_chunk, kv_chunk) streaming granularity for the Pallas
               kernels (decode uses kv_chunk only; the paged kernel streams
               blocks of attn.pages_per_block pages and ignores blocks).

Resolution order (both classes):
  1. an explicit `requested` backend always wins (tests/benches pin paths);
  2. a tuned-table entry for the key (blocks measured by
     `benchmarks/kernel_bench.py --tune`, persisted to the checked-in
     tuned_table.json next to this file);
  3. the static default policy (the routing ops.py used to hard-code);
  4. unknown key (unrecognized quant/phase/target): the reference path —
     dispatch must never crash on a target it has no data for.

Quarantine tier (docs/ROBUSTNESS.md): a dispatch that raises, or whose
output fails the serving engine's finite check, demotes its key down that
same ladder for the rest of the process — `demote(key, failing_backend)`
walks the rung list until the resolved backend CHANGES (a rung that would
re-pick the failing kernel is no mitigation), and `select`/`select_attn`
honour the recorded demotion level before anything else, including an
explicit `requested` backend.  Quarantine stores only a rung offset per
key, never code; `quarantine_snapshot()` is what Engine.stats surfaces as
`stats["degraded"]`.

The tuned table stores only data (backend name + kernel blocks), never code:
deployment-time dispatch is a dict lookup, and re-tuning is a JSON diff.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any

from repro.core import encoding
from repro.core import targets as targets_lib

Phase = encoding.Phase

QUANTS = ("none", "w8a8", "w4a8")
M_BUCKETS = ("m1", "m8", "m32", "m64", "big")

# Backends each quant mode understands (ops.py contract).  "auto" is the
# registry sentinel, resolved here and never passed to a kernel.
BACKENDS_BY_QUANT = {
    "none": ("reference", "xla", "pallas", "fused"),
    "w8a8": ("xla", "pallas", "fused"),
    "w4a8": ("xla", "pallas", "fused"),
}

# The no-data escape hatch per quant mode.  For quantized modes "xla" IS the
# reference oracle (ref.mmt4d_q8 / ref.mmt4d_q4 on the packed operands).
FALLBACK_BACKEND = {"none": "reference", "w8a8": "xla", "w4a8": "xla"}

DEFAULT_TABLE_PATH = os.path.join(os.path.dirname(__file__), "tuned_table.json")

_TABLE_VERSION = 1


@dataclasses.dataclass(frozen=True)
class KernelChoice:
    """One resolved dispatch decision."""

    backend: str
    # matmul: (BM1, BN1, BK1) kernel blocks (GEMV uses BN1).
    # attn  : (q_chunk, kv_chunk) streaming granularity.
    blocks: tuple[int, ...] | None = None
    source: str = "default"  # "requested" | "tuned" | "default" | "fallback"


def m_bucket(m: int) -> str:
    if m <= 1:
        return "m1"
    if m <= 8:
        return "m8"
    if m <= 32:
        return "m32"
    if m <= 64:
        return "m64"
    return "big"


def dispatch_key(quant: str, phase: Phase, m: int, target_name: str) -> str:
    return f"{quant}|{phase.value}|{m_bucket(m)}|{target_name}"


def default_backend(quant: str, phase: Phase, bucket: str = "") -> str:
    """The static policy — the routing formerly hard-coded across ops.py.

    Decode at GEMV-like row counts ("m1", "m8" — one to a batch of slots)
    takes the fused path (pack/unpack-free, the bandwidth regime's win).
    Past that ("m32": the speculative-decode verify window, slots x
    (draft_k+1) rows; "m64": many-slot decode; "big": the token-budget
    mixed step, slots x window rows when chunked-prefill tokens pack into
    the decode dispatch) the fused GEMV's premise breaks — it keeps the
    whole (M, K) activation block VMEM-resident per streamed weight tile,
    a footprint that grows with M — so multi-row decode routes to the
    packed mmt4d GEMM, the same kernel the prefill slab uses (one verify
    kernel path, TinyIREE's keep-dispatch-small argument).  The policy is
    monotonic in M by design ("big" included — it used to fall through to
    "fused", which silently handed a GEMM-shaped mixed window to the
    row-resident GEMV); a target where the fused GEMV measures faster at
    some bucket says so through its tuned entry (tpu-v5e's m64 entries pin
    "fused"), which outranks this policy.
    Prefill takes the fused GEMM slab for unquantized weights and the
    packed Pallas kernel for quantized ones (their fused slab does not
    exist — the packed kernel already streams int operands).

    This is also what `kernel_bench --tune` records as each entry's backend:
    retuning re-measures blocks against the POLICY backend, never copying a
    backend out of the table being regenerated (a stale entry must not
    self-perpetuate across retunes)."""
    if phase is Phase.DECODE:
        return "pallas" if bucket in ("m32", "m64", "big") else "fused"
    return "fused" if quant == "none" else "pallas"


def _known_key(quant: str, phase: Phase, target_name: str) -> bool:
    known_targets = {targets_lib.TPU_V5E.name, targets_lib.RISCV_VLEN256.name}
    return quant in QUANTS and isinstance(phase, Phase) and target_name in known_targets


# ---- tuned-table persistence ------------------------------------------------

_table_cache: dict[str, dict] = {}


def load_table(path: str | None = None) -> dict:
    """Load (and cache) a tuned table.  Missing/corrupt file -> empty table:
    dispatch falls back to the static policy rather than failing."""
    path = path or DEFAULT_TABLE_PATH
    if path in _table_cache:
        return _table_cache[path]
    table: dict[str, Any] = {"version": _TABLE_VERSION, "entries": {}}
    try:
        with open(path) as f:
            raw = json.load(f)
        if isinstance(raw, dict) and raw.get("version") == _TABLE_VERSION:
            entries = raw.get("entries", {})
            if isinstance(entries, dict):
                table = {"version": _TABLE_VERSION, "entries": entries}
    except (OSError, ValueError):
        pass
    _table_cache[path] = table
    return table


def save_table(table: dict, path: str | None = None) -> str:
    """Persist a tuned table (sorted keys — stable diffs) and refresh the
    cache.  Returns the path written."""
    path = path or DEFAULT_TABLE_PATH
    out = {
        "version": _TABLE_VERSION,
        "entries": dict(sorted(table.get("entries", {}).items())),
    }
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    _table_cache[path] = out
    return path


def clear_cache() -> None:
    """Drop cached tables (tests swap table files underneath the registry)."""
    _table_cache.clear()


# ---- kernel quarantine ------------------------------------------------------
#
# Process-lifetime demotions: dispatch key -> how many rungs of the
# requested -> tuned -> policy -> fallback ladder to skip.  Populated by the
# serving engine when a dispatch raises or fails the finite-output check
# (engine._quarantine_kernel); consulted by select()/select_attn() below.
#
# Tensor-parallel serving makes the table SHARD-AWARE: a fault attributed to
# one shard (a single bad device/core) demotes only that shard's entry —
# stored under "key@shardN" — never the key globally.  Because the serving
# dispatch is one SPMD program executed by every shard, resolution with
# shard=None (what select()/select_attn() do at trace time) takes the MAX
# level over the base key and all its shard entries: the shared program must
# avoid a kernel any shard cannot run.  Per-shard observability
# (Engine.stats["degraded"]/["attn_backend"]) resolves with an explicit
# shard, which consults only that shard's entry (plus any global one) — so a
# shard-0 query stays clean after a shard-1 demotion.

_quarantine: dict[str, dict] = {}

_SHARD_SEP = "@shard"


def _shard_key(key: str, shard: int) -> str:
    return f"{key}{_SHARD_SEP}{int(shard)}"


def quarantine_level(key: str, shard: int | None = None) -> int:
    """Demotion level for `key`.  shard=None: the EFFECTIVE level the single
    SPMD dispatch must honour (max over global + every shard).  shard=k: the
    level as seen from shard k only (global + that shard's entry)."""
    entry = _quarantine.get(key)
    lvl = entry["level"] if entry else 0
    if shard is None:
        prefix = key + _SHARD_SEP
        for k, e in _quarantine.items():
            if k.startswith(prefix):
                lvl = max(lvl, e["level"])
    else:
        e = _quarantine.get(_shard_key(key, shard))
        if e is not None:
            lvl = max(lvl, e["level"])
    return lvl


def quarantine_snapshot() -> dict[str, dict]:
    """{key: {"level", "from", "to", "reason"[, "shard"]}} for every demoted
    key; shard-local demotions appear under their "key@shardN" entry."""
    return {k: dict(v) for k, v in _quarantine.items()}


def clear_quarantine() -> None:
    """Reset all demotions (tests; a real process never un-quarantines)."""
    _quarantine.clear()


def _apply_quarantine(
    key: str, ladder: list[tuple[str, str]], shard: int | None = None
) -> tuple[str, str]:
    """Pick the ladder rung the key's demotion level points at.  Levels past
    the bottom clamp to the last rung (the fallback can't be demoted)."""
    lvl = quarantine_level(key, shard)
    backend, source = ladder[min(lvl, len(ladder) - 1)]
    if lvl > 0:
        source = f"quarantined:{source}"
    return backend, source


def _demote_ladder(key: str, ladder: list[tuple[str, str]], failing: str,
                   reason: str, shard: int | None = None) -> dict:
    """Record a demotion for `key` (shard-local when `shard` is given):
    advance the level until the resolved backend differs from `failing` (or
    the bottom rung is reached).  Returns the quarantine record
    ({"level", "from", "to", "reason"[, "shard"]})."""
    lvl = quarantine_level(key, shard)
    start = min(lvl, len(ladder) - 1)
    new = start
    while new < len(ladder) - 1:
        new += 1
        if ladder[new][0] != failing:
            break
    record = {
        "level": new,
        "from": ladder[start][0],
        "to": ladder[new][0],
        "reason": reason,
    }
    if shard is not None:
        record["shard"] = int(shard)
    _quarantine[key if shard is None else _shard_key(key, shard)] = record
    return record


def _tuned_entry(key: str, path: str | None) -> dict | None:
    entry = load_table(path)["entries"].get(key)
    return entry if isinstance(entry, dict) else None


# ---- the one resolution function -------------------------------------------


def _matmul_ladder(
    quant: str,
    phase: Phase,
    bucket: str,
    target_name: str,
    requested: str | None,
    table_path: str | None,
) -> list[tuple[str, str]]:
    """The full (backend, source) rung list for one matmul key, in resolution
    order.  Rung 0 is what select() returns with no quarantine; demotions
    index further down."""
    valid = BACKENDS_BY_QUANT.get(quant, ())
    ladder: list[tuple[str, str]] = []
    if requested not in (None, "auto"):
        # An explicit backend is a caller decision: a name this quant mode
        # does not understand is a bug at the call site, not a routing
        # question — fail loudly instead of silently running the oracle.
        if requested not in valid:
            raise ValueError(
                f"backend {requested!r} is not valid for quant={quant!r} "
                f"(valid: {valid}); use 'auto' for registry routing"
            )
        ladder.append((requested, "requested"))
    if _known_key(quant, phase, target_name):
        key = f"{quant}|{phase.value}|{bucket}|{target_name}"
        entry = _tuned_entry(key, table_path)
        if entry is not None and entry.get("backend") in valid:
            ladder.append((entry["backend"], "tuned"))
        ladder.append((default_backend(quant, phase, bucket), "default"))
    ladder.append((FALLBACK_BACKEND.get(quant, "reference"), "fallback"))
    return ladder


def select(
    *,
    quant: str,
    phase: Phase,
    m: int,
    target: targets_lib.TargetSpec | None = None,
    requested: str | None = None,
    blocks: tuple[int, int, int] | None = None,
    table_path: str | None = None,
    shard: int | None = None,
) -> KernelChoice:
    """Resolve one dispatch.  `requested` is the caller's backend= argument:
    "auto"/None defer to the registry; anything else is honoured verbatim
    (still picking up tuned blocks when the caller passed none) — unless the
    key is quarantined, which outranks even an explicit request (a pinned
    kernel that failed the finite check must not keep serving).  `shard`
    scopes the quarantine lookup: None = the effective (SPMD) level, k =
    shard k's own view (per-shard observability)."""
    target_name = targets_lib.resolve(target).name
    key = dispatch_key(quant, phase, m, target_name)
    entry = _tuned_entry(key, table_path)
    tuned_blocks = None
    if entry is not None and isinstance(entry.get("blocks"), (list, tuple)):
        b = entry["blocks"]
        if len(b) == 3 and all(isinstance(v, int) and v >= 1 for v in b):
            tuned_blocks = (b[0], b[1], b[2])
    resolved_blocks = blocks if blocks is not None else tuned_blocks

    ladder = _matmul_ladder(
        quant, phase, m_bucket(m), target_name, requested, table_path,
    )
    backend, source = _apply_quarantine(key, ladder, shard)
    if source == "fallback":
        resolved_blocks = (
            None if quarantine_level(key, shard) == 0 else resolved_blocks
        )
    return KernelChoice(backend, resolved_blocks, source)


def resolve_key(
    key: str,
    *,
    requested: str | None = None,
    table_path: str | None = None,
    shard: int | None = None,
) -> KernelChoice:
    """Resolve a dispatch key string directly (either op class) — what
    select()/select_attn() would return for it, quarantine included.  The
    serving engine uses this to learn which backend is CURRENTLY serving a
    key before demoting it, and (with `shard`) to report per-shard
    resolution in stats."""
    op = key.split("|", 1)[0]
    if op == ATTN_OP:
        phase_val, bucket, kv, target_name = split_attn_key(key)
        ladder = _attn_ladder(
            Phase(phase_val), bucket, kv, target_name, requested, table_path
        )
    else:
        op, phase_val, bucket, target_name = key.split("|", 3)
        ladder = _matmul_ladder(
            op, Phase(phase_val), bucket, target_name, requested, table_path
        )
    backend, source = _apply_quarantine(key, ladder, shard)
    return KernelChoice(backend, None, source)


def demote(
    key: str,
    *,
    failing: str,
    reason: str = "",
    requested: str | None = None,
    table_path: str | None = None,
    shard: int | None = None,
) -> dict:
    """Quarantine `key` (either op class — the key string carries its class):
    advance its demotion level past every rung that would re-resolve to the
    `failing` backend.  With `shard`, the demotion is SHARD-LOCAL (stored
    under "key@shardN"): other shards' views stay clean, though the shared
    SPMD dispatch honours the max level across shards.  Idempotent per rung:
    demoting an already-demoted key moves it further down; the bottom rung
    clamps.  Returns the quarantine record the engine surfaces in
    stats["degraded"]."""
    op = key.split("|", 1)[0]
    if op == ATTN_OP:
        phase_val, bucket, kv, target_name = split_attn_key(key)
        ladder = _attn_ladder(
            Phase(phase_val), bucket, kv, target_name, requested, table_path
        )
    else:
        op, phase_val, bucket, target_name = key.split("|", 3)
        ladder = _matmul_ladder(
            op, Phase(phase_val), bucket, target_name, requested, table_path
        )
    return _demote_ladder(key, ladder, failing, reason, shard)


# ---- the attention op class -------------------------------------------------

ATTN_OP = "attn"

# "xla" is the jnp reference pair (layers.attention_decode /
# attention_chunked) — also the no-data fallback; "pallas" is kernels/attn.py.
ATTN_BACKENDS = ("xla", "pallas")
ATTN_FALLBACK_BACKEND = "xla"

S_BUCKETS = ("s256", "s1k", "s4k", "sbig")

# KV-cache storage layouts forming the third attn-key axis (the canonical
# tuple lives with the KVLayout codec in core/encoding.py).
KV_QUANTS = encoding.KV_QUANTS


def s_bucket(s: int) -> str:
    """Context-length bucket: the logical KV length the dispatch attends."""
    if s <= 256:
        return "s256"
    if s <= 1024:
        return "s1k"
    if s <= 4096:
        return "s4k"
    return "sbig"


def attn_dispatch_key(
    phase: Phase, s: int, target_name: str, kv: str = "bf16"
) -> str:
    """Attention dispatch key.  bf16 emits the legacy 4-segment form
    (backward-compatible with every checked-in tuned entry and fault
    pattern); kv8/kv4 insert the kv-quant axis before the target."""
    if kv in (None, "bf16"):
        return f"{ATTN_OP}|{phase.value}|{s_bucket(s)}|{target_name}"
    if kv not in encoding.KV_QUANTS:
        raise ValueError(
            f"unknown kv_quant {kv!r}; expected one of {encoding.KV_QUANTS}"
        )
    return f"{ATTN_OP}|{phase.value}|{s_bucket(s)}|{kv}|{target_name}"


def split_attn_key(key: str) -> tuple[str, str, str, str]:
    """attn key -> (phase value, S-bucket, kv-quant, target name).  Accepts
    both the legacy 4-segment form (implied kv=bf16) and the 5-segment
    kv-quant form."""
    parts = key.split("|")
    if parts[0] != ATTN_OP:
        raise ValueError(f"not an attn key: {key!r}")
    if len(parts) == 4:
        return parts[1], parts[2], "bf16", parts[3]
    if len(parts) == 5 and parts[3] in encoding.KV_QUANTS:
        return parts[1], parts[2], parts[3], parts[4]
    raise ValueError(f"malformed attn key: {key!r}")


def default_attn_backend(phase: Phase, bucket: str = "") -> str:
    """Static attention policy: every phase of a known target takes the
    Pallas kernel — decode because the paged kernel streams only the slot's
    live pages (no materialized `paged_gather` view) and the dense kernel
    bounds its chunk scan at the newest written slot; prefill because the
    flash kernel skips upper-triangle KV chunks the reference visits-and-
    masks.  There is no S-bucket below which the reference wins on traffic
    (the gather view costs O(pool) at every context length), so the policy
    is constant; a target where the reference measures faster at some bucket
    says so through its tuned entry, which outranks this."""
    return "pallas"


def _attn_tuned_blocks(entry: dict | None) -> tuple[int, ...] | None:
    if entry is None or not isinstance(entry.get("blocks"), (list, tuple)):
        return None
    b = entry["blocks"]
    if len(b) in (2, 3) and all(isinstance(v, int) and v >= 1 for v in b):
        return tuple(b[:2])  # (q_chunk, kv_chunk)
    return None


def _attn_tuned_lookup(
    phase: Phase, bucket: str, kv: str, target_name: str, table_path: str | None
) -> dict | None:
    """Tuned entry for an attn key: the exact (possibly 5-segment) key
    first; a kv-quant key with no entry of its own inherits the legacy bf16
    entry — blocks are chunk geometry, independent of the streamed dtype,
    so a fresh kv axis never silently loses the measured chunking."""
    key = f"{ATTN_OP}|{phase.value}|{bucket}|{target_name}"
    if kv not in (None, "bf16"):
        exact = _tuned_entry(
            f"{ATTN_OP}|{phase.value}|{bucket}|{kv}|{target_name}", table_path
        )
        if exact is not None:
            return exact
    return _tuned_entry(key, table_path)


def _attn_ladder(
    phase: Phase,
    bucket: str,
    kv: str,
    target_name: str,
    requested: str | None,
    table_path: str | None,
) -> list[tuple[str, str]]:
    """The (backend, source) rung list for one attention key — the attn
    op-class analogue of _matmul_ladder."""
    ladder: list[tuple[str, str]] = []
    if requested not in (None, "auto"):
        if requested not in ATTN_BACKENDS:
            raise ValueError(
                f"attention backend {requested!r} is not valid "
                f"(valid: {ATTN_BACKENDS}); use 'auto' for registry routing"
            )
        ladder.append((requested, "requested"))
    known_targets = {targets_lib.TPU_V5E.name, targets_lib.RISCV_VLEN256.name}
    if isinstance(phase, Phase) and target_name in known_targets:
        entry = _attn_tuned_lookup(phase, bucket, kv, target_name, table_path)
        if entry is not None and entry.get("backend") in ATTN_BACKENDS:
            ladder.append((entry["backend"], "tuned"))
        ladder.append((default_attn_backend(phase, bucket), "default"))
    ladder.append((ATTN_FALLBACK_BACKEND, "fallback"))
    return ladder


def select_attn(
    *,
    phase: Phase,
    s: int,
    target: targets_lib.TargetSpec | None = None,
    requested: str | None = None,
    blocks: tuple[int, ...] | None = None,
    table_path: str | None = None,
    shard: int | None = None,
    kv: str = "bf16",
) -> KernelChoice:
    """Resolve one attention dispatch — the second op class, mirroring
    select(): `requested` is the caller's attn_backend (EncodingConfig /
    serve_llama --attn-backend); "auto"/None defer to tuned table -> static
    policy -> "xla" fallback on unknown targets.  A quarantined key outranks
    everything, including an explicit request; `shard` scopes the lookup as
    in select().  `kv` is the KV-cache storage layout axis: quarantine and
    tuning are tracked per kv-quant key (a kernel that fails on int4 pages
    must not quarantine the bf16 path), with tuned blocks inherited from
    the bf16 entry when the kv-quant key has none of its own."""
    target_name = targets_lib.resolve(target).name
    key = attn_dispatch_key(phase, s, target_name, kv)
    bucket = s_bucket(s) if isinstance(phase, Phase) else ""
    entry = _attn_tuned_lookup(phase, bucket, kv, target_name, table_path)
    resolved_blocks = blocks if blocks is not None else _attn_tuned_blocks(entry)

    ladder = _attn_ladder(phase, bucket, kv, target_name, requested, table_path)
    backend, source = _apply_quarantine(key, ladder, shard)
    if source == "fallback" and quarantine_level(key, shard) == 0:
        resolved_blocks = None
    return KernelChoice(backend, resolved_blocks, source)
