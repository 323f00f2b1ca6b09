"""Seconds a batch of the window spends in its last transaction: the
``superstep`` spans outside a drain, the ``drain`` spans (a drain's own
calls lie inside it) and the ``rebalance`` spans of the highest ``tx``,
summed and divided by the number of that transaction's ``harvest``
spans (one a batch), as ``creation_tx_s`` is built. Beside ``device_phase_s.campaign`` it says whether the last call
is most of the phase. A program from before ``tx`` on those spans gives
nothing to read. Layer: exploration driver. Moves
``contracts_per_min``."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import spans  # noqa: E402


def read(obs: dict):
    if obs.get("kind") != "campaign":
        return None
    steps = [s for s in spans(obs, "superstep") if "tx" in s]
    if not steps:
        return None
    last = max(s["tx"] for s in steps)
    ended = len(spans(obs, "harvest", tx=last))
    if not ended:
        return None
    parts = [s for s in steps if s["tx"] == last and not s.get("drain")]
    parts += spans(obs, "drain", tx=last) + spans(obs, "rebalance", tx=last)
    return sum(s["dur"] for s in parts) / ended
