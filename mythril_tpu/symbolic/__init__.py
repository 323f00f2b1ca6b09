"""Symbolic execution layer: SSA tape, forking engine, constraint propagation.

TPU-first replacement for the reference's Z3-object symbolic state
(``mythril/laser/smt`` + symbolic values threaded through
``mythril/laser/ethereum/state`` ⚠unv, SURVEY.md §2): symbolic values are
integer node ids into a per-lane bounded SSA tape; path conditions are
(node, sign) pairs; feasibility is decided by batched abstract
interpretation over the tape (known-bits + unsigned intervals), with a
model-search fallback instead of Z3 (not available in this image).
"""

import importlib

#: export -> submodule. Resolved on first access (PEP 562): ``state`` and
#: ``engine`` build jnp tables at import, which initializes a JAX backend
#: — and on a machine whose accelerator belongs to one process, a
#: supervisor that only needs ``SymSpec`` must leave it to its worker.
_EXPORTS = {
    "SymOp": "ops", "FreeKind": "ops", "WELL_KNOWN": "ops",
    "N_WELL_KNOWN": "ops", "calldata_arg_offsets": "ops",
    "SymSpec": "spec",
    "SymFrontier": "state", "make_sym_frontier": "state",
    "sym_superstep": "engine", "sym_run": "engine",
    "expand_forks": "engine", "append_node": "engine",
    "between_txs": "engine", "migrate_parked_device": "engine",
    "propagate_feasibility": "propagate", "kill_infeasible": "propagate",
}

__all__ = list(_EXPORTS)


def __getattr__(name):
    if name in _EXPORTS:
        return getattr(importlib.import_module(
            f"{__name__}.{_EXPORTS[name]}"), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
