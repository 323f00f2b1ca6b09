"""1 - (union of the device's leaf-operation intervals / traced window),
from the profiler trace the harness records around warm batches of the
window. Layer: device. Moves ``contracts_per_min``."""


def read(obs: dict):
    prof = obs.get("profile")
    if not prof or not prof["window_s"]:
        return None
    return 100.0 * (1.0 - prof["busy_s"] / prof["window_s"])
