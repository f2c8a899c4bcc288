"""Whole step: model FLOPs of the window's generated tokens (each at its
context) per second, over the chips' bf16 peak."""

from readings import generated_flops, mfu

LAYER = "whole step"
UNIT = "%"
MOVES = "out_tok_s"


def read(run):
    return mfu(run, generated_flops(run))
