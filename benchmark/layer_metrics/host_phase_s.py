"""Mean ``host_phase`` span per batch of the window (detection modules,
witness search, report merge). Layer: host phase. Moves
``contracts_per_min``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import spans  # noqa: E402


def read(obs: dict):
    if obs.get("kind") != "campaign":
        return None
    durs = [s["dur"] for s in spans(obs, "host_phase")]
    return sum(durs) / len(durs) if durs else None
