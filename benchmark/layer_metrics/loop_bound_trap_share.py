"""How many lanes the bounded-loops policy retired: 100 x lanes trapped
at the loop bound / (paths that ended a transaction + those lanes), all
transactions of the window, from ``engine_loop_bound_traps_total{tx}``
and ``engine_paths_total{tx}`` (analysis/symbolic.py counts both at
each harvest), differenced over the window. A loop over a dynamic
argument forks at every test of its length; what the bound cuts is
coverage the rate does not show. A program without the counter gives
nothing to read. Layer: engine. Moves ``contracts_per_min``."""

import re

KEY = re.compile(
    r'^engine_(loop_bound_traps|paths)_total\{tx="\d+"\}$')


def read(obs: dict):
    if obs.get("kind") != "campaign":
        return None
    after = (obs.get("registry_after") or {}).get("counters", {})
    before = (obs.get("registry_before") or {}).get("counters", {})
    total = {"loop_bound_traps": 0.0, "paths": 0.0}
    seen = set()
    for key, value in after.items():
        m = KEY.match(key)
        if m:
            seen.add(m.group(1))
            total[m.group(1)] += value - before.get(key, 0.0)
    if "loop_bound_traps" not in seen or sum(total.values()) <= 0:
        return None
    return 100.0 * total["loop_bound_traps"] / sum(total.values())
