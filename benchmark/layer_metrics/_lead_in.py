"""Helpers of the readers that lay the feeder thread's spans beside the
host phases: the thread that feeds the device is the one the
``device_phase`` spans are on (``tid``), its ``sym_run`` calls are its
``superstep`` spans (open from the enqueue to the read of the results),
and a device phase's lead-in is the time from its start to its first
call. Spans on other threads (a worker's, the host phase's own) are
nobody's lead-in."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(HERE)]
from _spans import spans  # noqa: E402
from trace_reduce import union_length  # noqa: E402


def interval(s: dict) -> tuple:
    return s["mono"], s["mono"] + s["dur"]


def feeder_phases(obs: dict) -> list:
    """The ``device_phase`` spans of a campaign run, in time order."""
    if obs.get("kind") != "campaign":
        return []
    return sorted(spans(obs, "device_phase"), key=lambda s: s["mono"])


def feeder_calls(obs: dict) -> list:
    """``(start, end)`` of every ``sym_run`` call of the feeder thread,
    in time order."""
    tids = {d.get("tid") for d in feeder_phases(obs)}
    return sorted(interval(s) for s in spans(obs, "superstep")
                  if s.get("tid") in tids)


def inside(obs: dict, name: str, phase: dict) -> list:
    """The ``name`` spans on ``phase``'s thread that start inside it
    (one that starts where it ends is the next phase's), in time
    order."""
    lo, hi = interval(phase)
    return sorted((s for s in spans(obs, name)
                   if s.get("tid") == phase.get("tid")
                   and lo <= s["mono"] < hi), key=lambda s: s["mono"])


def overlap(lo: float, hi: float, intervals) -> float:
    """Seconds of ``[lo, hi]`` that the union of ``intervals`` covers."""
    return union_length([(max(a, lo), min(b, hi)) for a, b in intervals
                         if min(b, hi) > max(a, lo)])
