"""Operations, bytes and model FLOPs of a dense GQA decoder, from its
configuration's shapes alone, and the chip peaks they are held against.

Every count here is computed from the configuration file (published key
names: `hidden_size`, `intermediate_size`, ...), never read from the
program, so that a change to the program cannot change the yardstick.
"""

from __future__ import annotations

import dataclasses
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
BF16 = 2
F32 = 4


@dataclasses.dataclass(frozen=True)
class Peaks:
    kind: str
    bf16_flops: float
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


def peaks(device_kind: str, path: str = os.path.join(HERE, "peaks.json")) -> Peaks:
    """Published peaks of one chip of `device_kind`; an unknown kind is an
    error, never another chip's figures."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table["kinds"]:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in {path} "
            f"(known: {sorted(table['kinds'])})"
        )
    row = table["kinds"][device_kind]
    return Peaks(device_kind, float(row["bf16_flops"]), float(row["hbm_bytes_per_s"]),
                 float(row["hbm_bytes"]), table["source"])


def head_dim(cfg: dict) -> int:
    return int(cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"])


def projections(cfg: dict) -> list[tuple[str, int, int]]:
    """(name, N, K) of each dense projection of one layer: y = x W^T with W
    of shape (N, K)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = head_dim(cfg)
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return [("q", q, d), ("k", kv, d), ("v", kv, d), ("o", d, q),
            ("gate", f, d), ("up", f, d), ("down", d, f)]


def layer_params(cfg: dict) -> int:
    """Parameters of one decoder layer: projections, QKV biases, two norms."""
    mats = sum(n * k for _, n, k in projections(cfg))
    hd = head_dim(cfg)
    bias = (cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]) * hd
    return mats + (bias if cfg.get("attention_bias", True) else 0) + 2 * cfg["hidden_size"]


def param_count(cfg: dict) -> int:
    """All parameters: layers, embedding, final norm, untied head."""
    emb = cfg["vocab_size"] * cfg["hidden_size"]
    head = 0 if cfg["tie_word_embeddings"] else emb
    return cfg["num_hidden_layers"] * layer_params(cfg) + emb + head + cfg["hidden_size"]


def kv_bytes_per_token(cfg: dict, itemsize: int = BF16) -> int:
    """K and V of one token over all layers."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * cfg["num_hidden_layers"] * itemsize


def matmul_cost(m: int, n: int, k: int, *, w_itemsize: int = BF16,
                x_itemsize: int = BF16, out_itemsize: int = F32) -> tuple[float, float]:
    """(operations, HBM bytes) of one (m, k) x (k, n) product that reads its
    weight once: 2mnk operations; weight, input and output bytes."""
    return 2.0 * m * n * k, float(n * k * w_itemsize + m * k * x_itemsize + m * n * out_itemsize)


def projection_cost(cfg: dict, rows: int) -> tuple[float, float]:
    """(operations, bytes) of one call through every layer's projections at
    `rows` rows; each weight is read once per call."""
    ops = byts = 0.0
    for _, n, k in projections(cfg):
        o, b = matmul_cost(rows, n, k)
        ops += o
        byts += b
    ops *= cfg["num_hidden_layers"]
    byts *= cfg["num_hidden_layers"]
    return ops, byts


def head_cost(cfg: dict, rows: int) -> tuple[float, float]:
    """(operations, bytes) of the output head at `rows` rows."""
    return matmul_cost(rows, cfg["vocab_size"], cfg["hidden_size"])


def least_time(ops: float, byts: float, pk: Peaks) -> tuple[float, str]:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bandwidth, and which bound it."""
    t_c, t_m = ops / pk.bf16_flops, byts / pk.hbm_bytes_per_s
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def model_flops_per_token(cfg: dict, context: int) -> float:
    """Model FLOPs of one generated token at `context` positions of history
    (itself included): 2 x matmul parameters, plus the output head, plus
    attention (QK^T and PV, 2 x context x head_dim each, per query head and
    layer)."""
    mats = sum(n * k for _, n, k in projections(cfg)) * cfg["num_hidden_layers"]
    flops = 2.0 * mats + 2.0 * cfg["vocab_size"] * cfg["hidden_size"]
    flops += 4.0 * context * head_dim(cfg) * cfg["num_attention_heads"] * cfg["num_hidden_layers"]
    return flops

