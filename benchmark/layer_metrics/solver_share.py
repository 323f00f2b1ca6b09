"""Share of the window's host-phase seconds spent inside the solver
portfolio: the ``solver_stage_seconds_<stage>`` histograms of
``obs.metrics.REGISTRY`` (smt/portfolio.py), summed over the stages and
differenced over the window, over the sum of ``host_phase`` spans.
Layer: host phase. Moves ``contracts_per_min``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import spans  # noqa: E402

PREFIX = "solver_stage_seconds_"


def _total(snapshot: dict) -> float:
    return sum(h["sum"] for k, h in snapshot.get("histograms", {}).items()
               if k.startswith(PREFIX))


def read(obs: dict):
    if obs.get("kind") != "campaign":
        return None
    host = sum(s["dur"] for s in spans(obs, "host_phase"))
    if not host:
        return None
    solver = _total(obs["registry_after"]) - _total(obs["registry_before"])
    return 100.0 * solver / host
