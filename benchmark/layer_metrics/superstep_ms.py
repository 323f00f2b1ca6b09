"""Milliseconds of one superstep on the device: the sum of the warm
``superstep`` spans' durations (analysis/symbolic.py: each spans one
``sym_run`` call from its enqueue to the read of its results, which
ends when the device does) over the sum of their ``steps_run`` (the
loop counter ``sym_run`` keeps on the frontier). Host clock around
device work, so the enqueue and the transfer of the results are inside
it. Layer: kernels. Moves ``contracts_per_min``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _seam import warm_seconds_per_superstep  # noqa: E402


def read(obs: dict):
    sec = warm_seconds_per_superstep(obs)
    return None if sec is None else 1e3 * sec
