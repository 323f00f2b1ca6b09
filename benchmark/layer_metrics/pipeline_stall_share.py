"""Share of the window in which the campaign's loop stood blocked on an
unfinished host phase (``pipeline_stall`` spans with
``wait=device-waits-host``, mythril/campaign.py): the device had nothing
to do. Layer: entry / campaign. Moves ``contracts_per_min``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import spans  # noqa: E402


def read(obs: dict):
    if obs.get("kind") != "campaign" or not obs.get("spans"):
        return None
    stall = sum(s["dur"] for s in spans(obs, "pipeline_stall",
                                        wait="device-waits-host"))
    return 100.0 * stall / obs["window_s"]
