"""Share of the WHOLE window in which the feeder thread had no
``sym_run`` call in flight: 1 - the union of its ``superstep`` spans
inside the window over ``window_s``. Unlike ``device_idle_share.
campaign`` it does not depend on where the profiler's slice falls.
Layer: exploration driver. Moves ``contracts_per_min``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _lead_in import feeder_calls, feeder_phases, overlap  # noqa: E402


def read(obs: dict):
    if not feeder_phases(obs) or not obs.get("window_s"):
        return None
    lo, hi = obs["window"]
    return 100.0 * (1.0 - overlap(lo, hi, feeder_calls(obs)) / (hi - lo))
