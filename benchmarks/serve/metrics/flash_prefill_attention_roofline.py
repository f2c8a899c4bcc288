"""Kernels: causal attention's least time over the real prompt lengths of
the traced prefill calls (`prefill_costs.attention_cost`; the larger of
operations over peak and bytes over bandwidth, per call) over the device
time of `flash_prefill_attention` inside the prefill program."""

import costs
from prefill_costs import PREFILL_MODULE, attention_cost, on_device, traced_prefill_lengths

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
KERNEL = "flash_prefill_attention"


def read(run):
    if not on_device(run):
        return None
    calls = traced_prefill_lengths(run)
    if not calls:
        return None
    pk = costs.peaks(run.device["kind"])
    least = sum(costs.least_time(*attention_cost(run.cfg, lens), pk)[0] for lens in calls)
    spent = run.summary.kernel_s({KERNEL}, PREFILL_MODULE)
    return 100.0 * least / spent if spent > 0 else None
