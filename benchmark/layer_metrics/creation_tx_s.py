"""Seconds a batch of the window spends deploying: the spans of the
creation transaction (``tx_kind="creation"``: its ``superstep`` calls
outside the drain, its ``drain``, its ``harvest``) and the ``tx_seam``
that hands its lanes to the first message call, summed and divided by
the number of those handoffs. A program that does not deploy, or one
from before ``tx_kind``, gives no such span: nothing to read. Layer:
exploration driver. Moves ``contracts_per_min``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import spans  # noqa: E402


def read(obs: dict):
    if obs.get("kind") != "campaign":
        return None
    seams = spans(obs, "tx_seam", tx_kind="creation")
    if not seams:
        return None
    parts = seams + spans(obs, "drain", tx_kind="creation") + spans(
        obs, "harvest", tx_kind="creation") + [
        s for s in spans(obs, "superstep", tx_kind="creation")
        if not s.get("drain")]
    return sum(s["dur"] for s in parts) / len(seams)
