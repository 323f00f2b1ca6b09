"""Median of stage ``sched_wait`` (admission to the start of the batch
that ran it) over the window's requests that were analysed, from the
``timings`` block the daemon attaches to each result. Layer: serve
scheduler. Moves ``verdict_p95_s``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import median  # noqa: E402


def read(obs: dict):
    if obs.get("kind") != "serve":
        return None
    return median([r["timings"]["sched_wait"] for r in obs["requests"]
                   if "sched_wait" in (r.get("timings") or {})])
