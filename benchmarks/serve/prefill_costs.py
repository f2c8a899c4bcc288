"""Operations, bytes and model FLOPs of a dense GQA decoder's prefill, from
its configuration's shapes and the real prompt lengths alone, and the
window readings that the prefill's metric readers share.

As in `costs.py`, every count is computed from the configuration file and
the lengths the traffic drew, never read from the program.  Only real
tokens are counted: the engine pads a prefill batch to a power of two, and
the rows it computes for padding are no work here, so padding shows as a
lower share of a roofline.
"""

from __future__ import annotations

import costs
from engine_spans import window_records

PREFILL_MODULE = "jit_prefill"   # the engine's jitted prefill step


def _mats(cfg: dict) -> int:
    """Weights of every layer's projections."""
    return sum(n * k for _, n, k in costs.projections(cfg)) * cfg["num_hidden_layers"]


def attention_cost(cfg: dict, lengths) -> tuple[float, float]:
    """(operations, HBM bytes) of causal attention over sequences of
    `lengths` real tokens: position p attends p positions, QK^T and PV
    2 x head_dim each per query head and layer, so 4 x head_dim x heads x
    layers x sum of L(L+1)/2; Q, K, V and O read or written once in bf16."""
    hd, layers = costs.head_dim(cfg), cfg["num_hidden_layers"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    pairs = sum(n * (n + 1) // 2 for n in lengths)
    ops = 4.0 * hd * heads * layers * pairs
    byts = float(costs.BF16 * layers * sum(lengths) * hd * (2 * heads + 2 * kv))
    return ops, byts


def prefill_flops(cfg: dict, lengths) -> float:
    """Model FLOPs of prefilling sequences of `lengths` real tokens: every
    position through every layer's projections and its causal attention,
    and the head at each sequence's last position only."""
    head = 2.0 * cfg["vocab_size"] * cfg["hidden_size"]
    return (2.0 * _mats(cfg) * sum(lengths) + attention_cost(cfg, lengths)[0]
            + head * len(lengths))


def window_flops(run) -> float:
    """Model FLOPs of the window's tokens: a request's first token is its
    prompt's prefill (`prefill_flops`), each later one a decode step at its
    context (`costs.model_flops_per_token`)."""
    total = 0.0
    for r in run.requests:
        for j, t in enumerate(r.token_times):
            if run.in_window(t):
                total += (prefill_flops(run.cfg, [r.prompt_len]) if j == 0 else
                          costs.model_flops_per_token(run.cfg, r.prompt_len + j))
    return total


def traced_prefill_steps(run) -> dict[int, int]:
    """Step index -> real prompt tokens, of the traced window's steps that
    called the prefill."""
    return {c.step: c.rows for c in run.calls
            if c.traced and c.phase == "prefill" and c.rows > 0}


def traced_prefill_lengths(run) -> list[list[int]] | None:
    """The real prompt lengths of each traced step's prefill: the uids that
    the engine's step record lists as admitted in that step, looked up in
    the run's requests.  None where the program keeps no step records."""
    recs = window_records(run)
    if recs is None:
        return None
    length = {r.uid: r.prompt_len for r in run.requests}
    out = []
    for i in sorted(traced_prefill_steps(run)):
        st = run.steps[i]
        uids = [u for rec in recs if st.t0 <= rec.t0 and rec.t1 <= st.t1
                for u in rec.admitted]
        lens = [length[u] for u in uids if u in length]
        if lens:
            out.append(lens)
    return out


def module_s(run, module: str) -> float:
    """Device seconds of every op of the programs named `module` in the
    traced window."""
    return sum(s for (mod, _), s in run.summary.module_op_s.items() if mod == module)


def on_device(run) -> bool:
    """Whether the run holds a device trace to read."""
    return run.summary is not None and bool(run.summary.devices)
