"""Share of the host phases' seconds that passed while a ``sym_run``
call was in flight: the overlap of the ``host_phase`` spans with the
feeder thread's ``superstep`` spans over the sum of the ``host_phase``
spans' ``dur``. A host phase that runs while the next batch is still
being built hides nothing: the device waits through it. Layer: entry /
campaign. Moves ``contracts_per_min``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _lead_in import (feeder_calls, feeder_phases, interval,  # noqa: E402
                      overlap)
from _spans import spans  # noqa: E402


def read(obs: dict):
    if not feeder_phases(obs):
        return None
    hosts = spans(obs, "host_phase")
    total = sum(h["dur"] for h in hosts)
    if not total:
        return None
    calls = feeder_calls(obs)
    return 100.0 * sum(overlap(*interval(h), calls) for h in hosts) / total
