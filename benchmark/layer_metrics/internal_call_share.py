"""Of the CALL-family instructions of the message calls (the paths that
ended a transaction alive and without error; ``tx`` 0 is the creation
transaction), the share that entered a member's code as a frame: 100 x
``internal`` / all fates of ``engine_calls_total{tx,fate}`` (``internal``
/ ``eoa`` / ``precompile`` / ``external``), differenced over the window.
A program without the counter, or a window without a call, gives
nothing to read. Layer: engine. Moves ``contracts_per_min``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _counters import fate_share  # noqa: E402


def read(obs: dict):
    return fate_share(obs, "engine_calls_total", "internal", message_calls=True)
