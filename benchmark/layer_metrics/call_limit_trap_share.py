"""Of the message calls' calls to a member of the lane's world that has
code, the share a limit sent down the external path (the call depth,
the calldata window, a symbolic window or value) where a frame would
have run: 100 x ``trapped`` / (``framed`` + ``trapped``) of
``engine_member_calls_total{tx,fate}``, ``tx`` 0 (the creation
transaction) left out, differenced over the window: the trap rate of
two of ``ROADMAP.md``'s limits that trap. A program without the counter,
or a window without such a call, gives nothing to read. Layer: engine.
Moves ``contracts_per_min``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _counters import fate_share  # noqa: E402


def read(obs: dict):
    return fate_share(obs, "engine_member_calls_total", "trapped", message_calls=True)
