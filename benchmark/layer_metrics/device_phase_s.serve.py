"""Median of stage ``device`` in the daemon's stage ledger (the batch's
device phase, which each request of the batch waited through) over the
window's requests that were analysed. Layer: exploration driver. Moves
``verdict_p95_s``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import median  # noqa: E402


def read(obs: dict):
    if obs.get("kind") != "serve":
        return None
    return median([r["timings"]["device"] for r in obs["requests"]
                   if "device" in (r.get("timings") or {})])
