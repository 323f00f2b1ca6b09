"""Reads of device arrays a host phase makes (``device_fetches`` of the
``host_phase`` spans), mean per phase: those that dispatch a kernel
wait for the chip when the next batch's device phase runs beside it.
Layer: host phase. Moves ``contracts_per_min``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _seam import phases  # noqa: E402


def read(obs: dict):
    got = phases(obs, "host_phase")
    if not got:
        return None
    return sum(s["device_fetches"] for s in got) / len(got)
