"""Share of the chip's memory bandwidth that a superstep's least
traffic takes: 2 x ``frontier_bytes`` (one read and one write of the
frontier: the LEAST a superstep can move, its temporaries and the
corpus gather not counted, so the true share is higher) over
``superstep_ms``, against ``hbm_bytes_per_s`` of ``benchmark/peaks.json``
for the device kind. ``frontier_bytes`` is the gauge the exploration
driver sets from the leaves' shapes and dtypes. Layer: kernels. Moves
``contracts_per_min``."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from _seam import warm_seconds_per_superstep  # noqa: E402


def read(obs: dict):
    sec = warm_seconds_per_superstep(obs)
    gauges = (obs.get("registry_after") or {}).get("gauges", {})
    nbytes = gauges.get("frontier_bytes")
    kind = ((obs.get("engine_setup") or {}).get("device") or {}).get("kind")
    with open(os.path.join(os.path.dirname(HERE), "peaks.json")) as fh:
        peak = (json.load(fh).get(kind) or {}).get("hbm_bytes_per_s")
    if not sec or not nbytes or not peak:
        return None
    return 100.0 * (2.0 * nbytes / sec) / peak
