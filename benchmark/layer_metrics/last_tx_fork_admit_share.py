"""How much of the last message transaction the lane budget covers:
100 x paths / (paths + dropped forks) of the highest transaction index,
from ``engine_paths_total{tx}`` and ``engine_dropped_forks_total{tx}``
(analysis/symbolic.py counts both at each transaction's harvest),
differenced over the window. A program without the counters gives
nothing to read. Layer: engine. Moves ``contracts_per_min``."""

import re

TX = re.compile(r'^engine_paths_total\{tx="(\d+)"\}$')
PATHS = 'engine_paths_total{tx="%d"}'
DROPPED = 'engine_dropped_forks_total{tx="%d"}'


def read(obs: dict):
    if obs.get("kind") != "campaign":
        return None
    after = (obs.get("registry_after") or {}).get("counters", {})
    before = (obs.get("registry_before") or {}).get("counters", {})
    txs = [int(m.group(1)) for m in map(TX.match, after) if m]
    if not txs:
        return None
    last = max(txs)
    paths, dropped = (after.get(k, 0.0) - before.get(k, 0.0)
                      for k in (PATHS % last, DROPPED % last))
    if paths + dropped <= 0:
        return None
    return 100.0 * paths / (paths + dropped)
