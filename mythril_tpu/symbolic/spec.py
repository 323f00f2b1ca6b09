"""SymSpec: which inputs of a transaction are symbolic.

A frozen description of shapes and switches, nothing more — it lives in
its own module so a process that only *describes* an engine (the
supervisor of an engine worker, the serve scheduler) can build one
without importing ``state``/``engine``, whose module-level jnp tables
initialize a JAX backend and so take the accelerator.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any


@dataclass(frozen=True)
class SymSpec:
    """Static (trace-time) choice of which inputs are symbolic.

    Mirrors the reference's symbolic tx setup (``execute_message_call``
    builds symbolic calldata/callvalue/caller ⚠unv, SURVEY.md §2
    "Transaction models")."""

    calldata: bool = True
    callvalue: bool = True
    caller: bool = False       # reference default: concrete ATTACKER address
    storage: bool = True       # unknown initial storage -> fresh STORAGE leaves
    block_env: bool = True     # timestamp/number/... symbolic (PredictableVars)
    # When the frontier's lane axis is sharded over a device mesh, the
    # precompile host callbacks must round-trip only shard-local lanes —
    # a bare pure_callback inside pjit gets a {maximal device=0} sharding
    # and XLA inserts a full gather/rescatter ("Involuntary full
    # rematerialization") that would serialize every superstep on a pod.
    # Setting ``mesh`` (a hashable jax.sharding.Mesh; part of the jit
    # cache key via static spec) routes them through jax.shard_map over
    # ``lane_axis`` instead. None = single-device path, no shard_map.
    mesh: Any = None
    lane_axis: str = "dp"
    # numeric storage-alias probe: demote symbolic
    # keys with fully-known bits to their value at SSTORE/SLOAD so
    # provably-equal keys connect. Trace-time static: False compiles the
    # probe out entirely (~0-15% cost on storage-heavy CPU workloads,
    # noise-limited, never measured on the chip; the soundness win is
    # the default).
    alias_probe: bool = True
