"""Device: 1 - the union of device op intervals over the traced window."""

from readings import device_idle

LAYER = "device"
UNIT = "frac"
MOVES = "out_tok_s"


def read(run):
    return device_idle(run)
