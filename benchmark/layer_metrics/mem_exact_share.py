"""How many of the paths that ended the last message call (without
error or revert) still had their scratch words and their free pointer,
memory words 0-2, exact: 100 x (exact + floored) / all of the highest
transaction index in ``engine_paths_memory_total{state,tx}``
(analysis/symbolic.py counts a path at the harvest as ``exact`` where
no write or copy at a symbolic offset invalidated any of its memory, as
``floored`` where one did from a word above those three, as ``havoc``
where one did from one of them down), differenced over the window.
solc hashes every mapping slot in words 0-1 and allocates from word 2:
a path past them explores over junk. A program without the counter
gives nothing to read. Layer: engine. Moves ``contracts_per_min``."""

import re

TX = re.compile(r'^engine_paths_memory_total\{state="exact",tx="(\d+)"\}$')
STATE = 'engine_paths_memory_total{state="%s",tx="%d"}'


def read(obs: dict):
    if obs.get("kind") != "campaign":
        return None
    after = (obs.get("registry_after") or {}).get("counters", {})
    before = (obs.get("registry_before") or {}).get("counters", {})
    txs = [int(m.group(1)) for m in map(TX.match, after) if m]
    if not txs:
        return None
    exact, floored, havoc = (
        after.get(STATE % (state, max(txs)), 0.0)
        - before.get(STATE % (state, max(txs)), 0.0)
        for state in ("exact", "floored", "havoc"))
    if exact + floored + havoc <= 0:
        return None
    return 100.0 * (exact + floored) / (exact + floored + havoc)
