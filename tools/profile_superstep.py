#!/usr/bin/env python
"""Superstep profiler: where does concrete-interpreter time go?

Times, on the current default backend:
  - full `run` (per-superstep cost on the ERC-20 workload),
  - prologue / epilogue alone,
  - each class handler standalone (all lanes executing that class),
  - the 16 `jnp.any(mask)` dispatch predicates,
so the dispatch restructuring is driven by
measurements instead of guesses. Prints ONE JSON object.

Run in its own process (the XLA:CPU JIT segfault appears after ~50 large
compiles in one process — see pytest.ini).
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# Optional backend gate (PROF_INIT_TIMEOUT=<sec>): probe backend init in
# a subprocess BEFORE the heavy imports below build jnp tables — on a
# wedged TPU runtime those imports hang this process forever. bench.py probes on its own before spawning
# this tool, so the gate is opt-in to avoid double-probing.
_INIT_TIMEOUT = float(os.environ.get("PROF_INIT_TIMEOUT", "0") or 0)
if _INIT_TIMEOUT > 0:
    from mythril_tpu.resilience import BackendManager

    _bm = BackendManager(init_timeout=_INIT_TIMEOUT)
    _ok, _diag = _bm.probe()
    if not _ok:
        print(json.dumps({"error": "backend unavailable: " + _diag,
                          "backend_events": _bm.events}))
        sys.exit(1)

import mythril_tpu  # noqa: F401
import jax
import jax.numpy as jnp
import numpy as np

from mythril_tpu.config import DEFAULT_LIMITS
from mythril_tpu.core import run
from mythril_tpu.core import interpreter as ci
from mythril_tpu.obs import trace as obs_trace
from mythril_tpu.workloads import erc20_transfer_workload

# PROF_TRACE=FILE: record every timed section as a span in a
# Perfetto-loadable trace (same spine the campaign's --trace uses)
if os.environ.get("PROF_TRACE"):
    obs_trace.configure(os.environ["PROF_TRACE"])

P = int(os.environ.get("PROF_P", "4096"))
MAX_STEPS = int(os.environ.get("PROF_STEPS", "256"))
REPS = int(os.environ.get("PROF_REPS", "20"))

CLASS_NAMES = [
    "STACK", "ALU", "MUL", "DIVMOD", "MODARITH", "EXP", "SHA3", "ENV",
    "COPY", "MEM", "STORAGE", "JUMP", "HALT", "LOG", "CALL", "CREATE",
]

# a representative opcode per class to fill the op vector with
CLASS_OP = {
    "STACK": 0x60, "ALU": 0x01, "MUL": 0x02, "DIVMOD": 0x04,
    "MODARITH": 0x08, "EXP": 0x0A, "SHA3": 0x20, "ENV": 0x33,
    "COPY": 0x37, "MEM": 0x51, "STORAGE": 0x54, "JUMP": 0x56,
    "HALT": 0x00, "LOG": 0xA1, "CALL": 0xF1, "CREATE": 0xF0,
}


def timed(fn, *args, reps=REPS, label="timed"):
    out = fn(*args)
    jax.block_until_ready(out)
    with obs_trace.timer(f"profile.{label}", reps=reps) as sp:
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
    return sp.elapsed / reps


def tree_bytes(t) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(t) if hasattr(x, "nbytes"))


def main():
    limits = DEFAULT_LIMITS
    if os.environ.get("PROF_STACK") or os.environ.get("PROF_MEM"):
        import dataclasses

        limits = dataclasses.replace(
            DEFAULT_LIMITS,
            max_stack=int(os.environ.get("PROF_STACK",
                                         DEFAULT_LIMITS.max_stack)),
            mem_bytes=int(os.environ.get("PROF_MEM",
                                         DEFAULT_LIMITS.mem_bytes)),
        )
    code, f, env, corpus = erc20_transfer_workload(P, limits)
    res = {"backend": jax.default_backend(), "P": P, "max_steps": MAX_STEPS,
           "frontier_bytes": tree_bytes(f), "corpus_bytes": tree_bytes(corpus)}

    from jax import lax

    def make_runner(cond_classes, skeleton=False):
        def step(fr):
            fr, op, run_m, old_pc = ci.prologue(fr, corpus)
            if not skeleton:
                fr = ci.dispatch(fr, env, corpus, op, run_m, old_pc,
                                 cond_classes=cond_classes)
            return ci.epilogue(fr, op, run_m, old_pc)

        @jax.jit
        def go(fr):
            def cond(st):
                i, x = st
                return (i < MAX_STEPS) & jnp.any(x.running)

            def body(st):
                i, x = st
                return i + 1, step(x)

            return lax.while_loop(cond, body, (jnp.int32(0), fr))[1]

        return go

    variants = {
        "split": tuple(ci.COND_CLASSES),          # cheap classes fused
        "all_cond": tuple(range(ci.N_CLASSES)),   # current default
        "none_cond": (),                          # everything unconditional
    }

    def make_empty_cond_runner():
        """Same 16-cond structure as all_cond but every handler replaced
        by identity: isolates fixed per-cond overhead from handler
        compute (if this ~equals all_cond, the conds ARE the cost)."""
        def step(fr):
            fr, op, run_m, old_pc = ci.prologue(fr, corpus)
            cls_v = ci._J_CLASS[op]
            present = jnp.any(
                (cls_v[:, None] == jnp.arange(ci.N_CLASSES,
                                              dtype=cls_v.dtype)[None, :])
                & run_m[:, None], axis=0)
            for cid in range(ci.N_CLASSES):
                names = ci.WRITE_FIELDS[cid]
                outs = lax.cond(
                    present[cid],
                    lambda fr=fr, names=names: tuple(
                        getattr(fr, n) for n in names),
                    lambda fr=fr, names=names: tuple(
                        getattr(fr, n) for n in names),
                )
                fr = fr.replace(**dict(zip(names, outs)))
            return ci.epilogue(fr, op, run_m, old_pc)

        @jax.jit
        def go(fr):
            # fixed-trip loop: with handlers disabled lanes trap on stack
            # arity almost immediately, so the usual `running` exit would
            # end after ~2 supersteps and time nothing
            def body(st):
                i, x = st
                return i + 1, step(x)

            return lax.while_loop(lambda st: st[0] < MAX_STEPS, body,
                                  (jnp.int32(0), fr))[1]

        return go
    # PROF_VARIANTS selects a subset (slow compiles can
    # make the full 4-variant sweep blow a wall-clock budget — one
    # variant per process keeps each session to a single big compile)
    sel = [v for v in os.environ.get(
        "PROF_VARIANTS", "split,all_cond,none_cond,skeleton").split(",") if v]
    prof = {}
    out = None
    for name, cc in variants.items():
        if name not in sel:
            continue
        runner = make_runner(cc)
        dt = timed(runner, f, reps=REPS, label=name)
        out = runner(f)
        steps = int(np.asarray(out.n_steps).max())
        prof[f"{name}_wall_s"] = round(dt, 4)
        prof[f"{name}_superstep_ms"] = round(dt / max(steps, 1) * 1e3, 4)
        # sanity: a dispatch variant that broke execution produces absurd
        # timings — record enough to see it
        prof[f"{name}_ok_lanes"] = int(np.asarray(
            out.halted & ~out.error).sum())
        prof[f"{name}_steps_max"] = steps
    if "skeleton" in sel:
        sk = make_runner((), skeleton=True)
        dt = timed(sk, f, reps=REPS, label="skeleton")
        prof["skeleton_superstep_ms"] = round(dt / MAX_STEPS * 1e3, 4)
    if "empty_conds" in sel:
        ec = make_empty_cond_runner()
        dt = timed(ec, f, reps=REPS, label="empty_conds")
        prof["empty_conds_superstep_ms"] = round(dt / MAX_STEPS * 1e3, 4)

    if out is not None:
        steps_sum = int(np.asarray(out.n_steps).sum())
        supersteps = int(np.asarray(out.n_steps).max())
        name0 = next(n for n in variants if n in sel)
        dt = prof[f"{name0}_wall_s"]
        res["supersteps"] = supersteps
        res["lane_steps_per_sec"] = round(steps_sum / dt, 1)
        # bandwidth floor: each superstep reads+writes the frontier once
        res["est_min_GBps"] = round(
            2 * res["frontier_bytes"] * supersteps / dt / 1e9, 2)
    res["profile"] = prof
    print(json.dumps(res))


if __name__ == "__main__":
    try:
        main()
    finally:
        obs_trace.close()  # writes the PROF_TRACE Chrome file, if any
