"""Supersteps the engine ran for one batch of the window: the
difference of ``engine_supersteps_total`` (analysis/symbolic.py adds
each ``sym_run`` call's own loop count) over the batches the window
committed. A program without ``engine_supersteps_budget_total`` still
adds the calls' budgets to that counter, not what ran: nothing to
read. Layer: engine. Moves ``contracts_per_min``."""

NAME = "engine_supersteps_total"
BUDGET = "engine_supersteps_budget_total"


def read(obs: dict):
    if obs.get("kind") != "campaign" or not obs.get("batches"):
        return None
    after = (obs.get("registry_after") or {}).get("counters", {})
    before = (obs.get("registry_before") or {}).get("counters", {})
    if BUDGET not in after or NAME not in after:
        return None
    return (after[NAME] - before.get(NAME, 0.0)) / obs["batches"]
