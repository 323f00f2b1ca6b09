"""Share of the lead-ins' wall clock in which the feeder thread was on
a CPU: sum of the ``batch_build`` spans' ``cpu_s`` over the sum of their
``dur`` (the spans on a device phase's thread, inside it). What is left
is waiting: for a lock, the interpreter's or the runtime's, or for the
device. A program from before those spans gives nothing to read.
Layer: exploration driver. Moves ``contracts_per_min``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _lead_in import feeder_phases, inside  # noqa: E402


def read(obs: dict):
    builds = [b for d in feeder_phases(obs)
              for b in inside(obs, "batch_build", d) if "cpu_s" in b]
    total = sum(b["dur"] for b in builds)
    if not total:
        return None
    return 100.0 * sum(b["cpu_s"] for b in builds) / total
