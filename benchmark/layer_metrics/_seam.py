"""Helpers the readers of the host/device seam share: ``superstep``
spans that say how many supersteps ran, and phase spans (``host_phase``,
``device_phase``) that carry ``device_fetches`` / ``device_wait_s`` /
``cpu_s``. A program from before those attributes gives empty lists,
and the readers then find nothing to read."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import spans  # noqa: E402


def phases(obs: dict, name: str) -> list:
    """The ``name`` spans of a campaign run that carry the split of
    their wall clock."""
    if obs.get("kind") != "campaign":
        return []
    return [s for s in spans(obs, name)
            if "device_wait_s" in s and "cpu_s" in s
            and "device_fetches" in s]


def share(obs: dict, name: str, key: str):
    """Sum of ``key`` over the ``name`` phase spans as a percentage of
    the sum of their durations."""
    got = phases(obs, name)
    total = sum(s["dur"] for s in got)
    if not total:
        return None
    return 100.0 * sum(s[key] for s in got) / total


def counted_supersteps(obs: dict) -> list:
    """``superstep`` spans that end when the device does: they carry
    the supersteps that ran (``steps_run``)."""
    if obs.get("kind") != "campaign":
        return []
    return [s for s in spans(obs, "superstep") if "steps_run" in s]


def warm_seconds_per_superstep(obs: dict):
    """Sum of the warm ``superstep`` spans' durations over the sum of
    the supersteps they ran (a cold call's span holds the compile)."""
    warm = [s for s in counted_supersteps(obs) if not s.get("cold")]
    ran = sum(s["steps_run"] for s in warm)
    if not ran:
        return None
    return sum(s["dur"] for s in warm) / ran
