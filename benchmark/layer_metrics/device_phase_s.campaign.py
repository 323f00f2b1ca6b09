"""Mean ``device_phase`` span per batch of the window (packing, the
``sym_run`` chunks and the per-transaction harvest fetches; the span
ends after the last fetch). Layer: exploration driver. Moves
``contracts_per_min``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import spans  # noqa: E402


def read(obs: dict):
    if obs.get("kind") != "campaign":
        return None
    durs = [s["dur"] for s in spans(obs, "device_phase")]
    return sum(durs) / len(durs) if durs else None
