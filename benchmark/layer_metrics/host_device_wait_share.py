"""Share of the host phases' wall clock spent blocked in reads of
device arrays (``device_wait_s`` of the ``host_phase`` spans: the tally
of ``obs.device.fetch`` on the phase's thread): on one chip a read that
dispatches a kernel queues behind the ``sym_run`` call the next batch
is running. Layer: host phase. Moves ``contracts_per_min``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _seam import share  # noqa: E402


def read(obs: dict):
    return share(obs, "host_phase", "device_wait_s")
