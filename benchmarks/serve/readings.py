"""Shared arithmetic of the metric readers (`metrics/<name>.py`)."""

from __future__ import annotations

import numpy as np

import costs


def percentile(values, q: float) -> float | None:
    """The q-th percentile (linear interpolation), or None for no values."""
    values = list(values)
    return float(np.percentile(np.asarray(values, np.float64), q)) if values else None


def window_tokens(run):
    """(request, index of the token, its time) of every token committed in
    the window."""
    for r in run.requests:
        for j, t in enumerate(r.token_times):
            if run.in_window(t):
                yield r, j, t


def generated_flops(run) -> float:
    """Model FLOPs of the window's generated tokens, each at its context."""
    return sum(costs.model_flops_per_token(run.cfg, r.prompt_len + j)
               for r, j, _ in window_tokens(run))


def mfu(run, flops: float) -> float | None:
    """Percent of the chips' bf16 peak that `flops` over the window is; None
    off a TPU (a CPU rehearsal), which has no peak to be a share of."""
    if run.device.get("platform") != "tpu" or run.window_s <= 0 or flops <= 0:
        return None
    pk = costs.peaks(run.device["kind"])
    return 100.0 * flops / run.window_s / (pk.bf16_flops * run.device["count"])


def matmul_roofline(run, phase: str, module: str, kernels) -> float | None:
    """Percent: the least time of the traced `phase` calls' projections
    (QKV, O, gate/up, down, and an untied head) over the device time of the
    ops named in `kernels` inside programs named `module`.  None where the
    trace holds no such call or no such op."""
    if run.summary is None or not run.summary.devices:
        return None
    cfg = run.cfg
    pk = costs.peaks(run.device["kind"])
    least = 0.0
    for call in run.calls:
        if not call.traced or call.phase != phase or call.rows <= 0:
            continue
        ops, byts = costs.projection_cost(cfg, call.rows)
        if not cfg["tie_word_embeddings"]:
            o, b = costs.head_cost(cfg, call.rows if phase == "decode" else call.seqs)
            ops, byts = ops + o, byts + b
        least += costs.least_time(ops, byts, pk)[0]
    spent = run.summary.kernel_s(set(kernels), module)
    if least <= 0 or spent <= 0:
        return None
    return 100.0 * least / spent


def device_idle(run) -> float | None:
    s = run.summary
    if s is None or not s.devices or s.window_s <= 0:
        return None
    return 1.0 - s.busy_s / s.window_s
