"""Seconds from a device phase's start to its first ``sym_run`` call:
per ``device_phase`` span, the ``mono`` of the first ``superstep`` span
on its thread inside it, less its own; mean over the window's phases.
The host builds the batch meanwhile (``batch_build`` spans) and the
device has nothing to run. Layer: exploration driver. Moves
``contracts_per_min``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _lead_in import feeder_phases, inside  # noqa: E402


def read(obs: dict):
    got = []
    for d in feeder_phases(obs):
        calls = inside(obs, "superstep", d)
        if calls:
            got.append(calls[0]["mono"] - d["mono"])
    return sum(got) / len(got) if got else None
