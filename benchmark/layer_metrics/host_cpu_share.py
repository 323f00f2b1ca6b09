"""Share of the host phases' wall clock in which their thread was on a
CPU (``cpu_s`` of the ``host_phase`` spans: ``time.thread_time()``
outside the device reads). What is neither this nor
``host_device_wait_share`` is waiting for a lock or a pool. Layer: host
phase. Moves ``contracts_per_min``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _seam import share  # noqa: E402


def read(obs: dict):
    return share(obs, "host_phase", "cpu_s")
