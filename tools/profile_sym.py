#!/usr/bin/env python
"""Symbolic-superstep profiler: where does sym_run time go?

Variants (PROF_SYM_VARIANTS, comma list; one big XLA compile each):
  - sym:        production sym_run (forking + propagation sweeps)
  - sym_noprop: propagate_every=0 (no feasibility sweeps) — the delta
                against `sym` is the incremental-propagation cost
  - sym_nofork: SymSpec with nothing symbolic (calldata/value/storage
                concrete) — no forks, no tape growth: the floor of the
                sym overlay on top of the concrete interpreter
  - sym_noalias: SymSpec(alias_probe=False) — the storage-alias probe
                compiled OUT; the delta against `sym` is the probe's
                cost (opt-in: add it to PROF_SYM_VARIANTS for the A/B)

Prints ONE JSON object. PROF_SYM_P / PROF_SYM_STEPS / PROF_REPS size it.
Run one variant per process when compiles are slow.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import mythril_tpu  # noqa: F401
import jax
import numpy as np

from mythril_tpu.config import DEFAULT_LIMITS
from mythril_tpu.core import Corpus, make_env
from mythril_tpu.disassembler import ContractImage
from mythril_tpu.disassembler.asm import erc20_like
from mythril_tpu.symbolic import SymSpec, make_sym_frontier, sym_run

P = int(os.environ.get("PROF_SYM_P", "4096"))
MAX_STEPS = int(os.environ.get("PROF_SYM_STEPS", "128"))
REPS = int(os.environ.get("PROF_REPS", "3"))


def timed(fn, *args, reps=REPS):
    out = fn(*args)
    jax.block_until_ready(jax.tree.leaves(out)[0])
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(jax.tree.leaves(out)[0])
    return (time.perf_counter() - t0) / reps, out


def tree_bytes(t) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(t) if hasattr(x, "nbytes"))


def main():
    L = DEFAULT_LIMITS
    code = erc20_like()
    img = ContractImage.from_bytecode(code, L.max_code)
    corpus = Corpus.from_images([img])
    active = np.zeros(P, dtype=bool)
    active[::2] = True  # half seeds, half fork head-room
    env = make_env(P)

    res = {"backend": jax.default_backend(), "P": P, "max_steps": MAX_STEPS}
    sel = [v for v in os.environ.get(
        "PROF_SYM_VARIANTS", "sym,sym_noprop,sym_nofork").split(",") if v]

    variants = {
        "sym": (SymSpec(), None),
        "sym_noprop": (SymSpec(), 0),
        "sym_nofork": (SymSpec(calldata=False, callvalue=False,
                               storage=False, block_env=False), None),
        # alias-probe A/B: the round-5
        # numeric storage-alias probe is a trace-time gate — "sym" above
        # IS the alias_probe=True arm; this is the compiled-out arm
        "sym_noalias": (SymSpec(alias_probe=False), None),
    }
    prof = {}
    for name in sel:
        if name not in variants:  # tolerate typos: never lose the JSON line
            prof[f"{name}_error"] = "unknown variant"
            continue
        spec, prop = variants[name]
        sf = make_sym_frontier(P, L, active=active)
        if name == "sym" and "frontier_bytes" not in res:
            res["frontier_bytes"] = tree_bytes(sf)

        def runner(s, _spec=spec, _prop=prop):
            return sym_run(s, env, corpus, _spec, L, max_steps=MAX_STEPS,
                           propagate_every=_prop)

        t_c0 = time.perf_counter()
        dt, out = timed(runner, sf)
        prof[f"{name}_compile_s"] = round(time.perf_counter() - t_c0 - dt * REPS, 1)
        supersteps = int(np.asarray(out.base.n_steps).max())
        steps_sum = int(np.asarray(out.base.n_steps).sum())
        prof[f"{name}_wall_s"] = round(dt, 4)
        prof[f"{name}_superstep_ms"] = round(dt / max(supersteps, 1) * 1e3, 3)
        prof[f"{name}_lane_steps_per_sec"] = round(steps_sum / dt, 1)
        prof[f"{name}_supersteps"] = supersteps
        prof[f"{name}_live_paths"] = int(
            (np.asarray(out.base.active) & ~np.asarray(out.base.error)).sum())
    res["profile"] = prof
    print(json.dumps(res))


if __name__ == "__main__":
    main()
