"""Of the symbolic words read across a frame boundary (a callee's
``CALLDATALOAD`` of the call window's bytes, a caller's read of a
return word), the share that was the other frame's tape node and not a
havoc leaf tied to nothing: 100 x ``exact`` / (``exact`` + ``havoc``)
of ``engine_hop_words_total{side,fate}``, both sides, differenced over
the window. A program without the counter, or a window in which no
symbolic word crossed, gives nothing to read. Layer: engine. Moves
``contracts_per_min``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _counters import fate_share  # noqa: E402


def read(obs: dict):
    return fate_share(obs, "engine_hop_words_total", "exact")
