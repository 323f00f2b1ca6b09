"""Seconds a batch spends joining its systems' constructors into one
world a system: the mean of the ``system_world`` spans (inside the
creation transaction's ``tx_seam``; attributes ``systems``, ``rows``,
``failed``). A program without linked systems emits none: nothing to
read. Layer: exploration driver. Moves ``contracts_per_min``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import spans  # noqa: E402


def read(obs: dict):
    if obs.get("kind") != "campaign":
        return None
    got = spans(obs, "system_world")
    if not got:
        return None
    return sum(s["dur"] for s in got) / len(got)
