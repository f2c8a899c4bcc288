"""Whole step: model FLOPs of the window's tokens per second, over the
chips' bf16 peak: each request's first token as its prompt's prefill (every
position at its position, the head on the last only), each later token at
its context."""

from prefill_costs import window_flops
from readings import mfu

LAYER = "whole step"
UNIT = "%"
MOVES = "out_tok_s"


def read(run):
    return mfu(run, window_flops(run))
