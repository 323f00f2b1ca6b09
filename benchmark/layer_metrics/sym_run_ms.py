"""Device-plane duration of the ``sym_run`` XLA module in the profiler
trace, mean per call (one call per batch, transaction and 64-step
chunk). The traced slice starts and ends inside a call and cuts both
short, so with three calls or more the first and the last are left out.
Layer: engine. Moves ``contracts_per_min``."""


def read(obs: dict):
    prof = obs.get("profile")
    if not prof:
        return None
    calls = [sec for name, durs in prof["module_calls"].items()
             if "sym_run" in name for sec in durs]
    if len(calls) >= 3:
        calls = calls[1:-1]
    return 1e3 * sum(calls) / len(calls) if calls else None
