"""How many of the end states that passed the pruners at the last seam
between two message calls got to start the next call: 100 x (admitted +
merged) / passed of the highest transaction index in
``engine_seam_states_total{fate,tx}`` (analysis/symbolic.py counts a
state as ``admitted`` when its lane starts, at the seam or in a later
round of the same call, as ``merged`` when a state with the same
storage stands for it, as ``dropped`` when its lane is given up),
differenced over the window. A program without the counter gives
nothing to read. Layer: exploration driver. Moves
``contracts_per_min``."""

import re

TX = re.compile(r'^engine_seam_states_total\{fate="passed",tx="(\d+)"\}$')
FATE = 'engine_seam_states_total{fate="%s",tx="%d"}'


def read(obs: dict):
    if obs.get("kind") != "campaign":
        return None
    after = (obs.get("registry_after") or {}).get("counters", {})
    before = (obs.get("registry_before") or {}).get("counters", {})
    txs = [int(m.group(1)) for m in map(TX.match, after) if m]
    if not txs:
        return None
    passed, admitted, merged = (
        after.get(FATE % (fate, max(txs)), 0.0)
        - before.get(FATE % (fate, max(txs)), 0.0)
        for fate in ("passed", "admitted", "merged"))
    if passed <= 0:
        return None
    return 100.0 * (admitted + merged) / passed
