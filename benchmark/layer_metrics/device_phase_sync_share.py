"""Share of the device phases' wall clock that their thread stood
blocked in device reads OTHER than the ends of its ``sym_run`` calls:
the ``device_wait_s`` of the ``device_phase`` spans less that of the
``superstep`` spans inside them (same thread, inside the phase's
interval). What is left is the harvest and seam syncs: the drain's
fetch, the per-tx harvest, the quiescence check between transactions.
Layer: exploration driver. Moves ``contracts_per_min``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _seam import counted_supersteps, phases  # noqa: E402


def read(obs: dict):
    devs = phases(obs, "device_phase")
    total = sum(s["dur"] for s in devs)
    if not total:
        return None
    steps = counted_supersteps(obs)
    sync = 0.0
    for d in devs:
        end = d["mono"] + d["dur"]
        inside = sum(s.get("device_wait_s", 0.0) for s in steps
                     if s.get("tid") == d.get("tid")
                     and d["mono"] <= s["mono"] <= end)
        sync += max(0.0, d["device_wait_s"] - inside)
    return 100.0 * sync / total
