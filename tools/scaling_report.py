#!/usr/bin/env python
"""Compiled-cost attribution: how the engine's program grows with P.

Traces the symbolic engine's jaxprs at several lane counts P — WITHOUT
executing or allocating anything at those sizes (inputs are
``ShapeDtypeStruct`` skeletons) — buckets primitive op / output-element /
output-byte counts by phase, and fits a log-log growth exponent per
bucket. A bucket whose fitted exponent is ~1.0 scales linearly in P
(flat per-lane cost); anything materially above 1 is a superlinear term,
and the report names the dominant one. This is how a throughput cliff
between 4096 and 16384 lanes was once attributed to a dense
``[G, B, B]`` destination map in ``expand_forks`` from a CPU-only box:
the op-count model needs no hardware, only traces.

Phases bucketed:

- ``superstep``      one :func:`sym_superstep` (dispatch + overlay +
                     claimed handlers + gas + pop seam)
- ``expand_forks``   the fork compaction pass
- ``fork_plan``      its source→slot map alone (``plan_fork_map``)
- ``rebalance``      the in-jit migration tier (``migrate_parked_device``)
- ``sym_run_body``   one full while-loop body of :func:`sym_run` — the
                     unit the CI smoke (tests/test_scaling.py) holds to a
                     per-lane exponent budget
- ``cond_carry``     analytic: elements carried across the superstep's
                     cond boundaries per step (the expand gate's full
                     frontier plus the narrow pop_frames write set)
- ``observe_fetch``  analytic: device→host bytes per chunk seam

``--write-mode dense`` pins the TPU-style slot-write lowering while
tracing on CPU (it patches ``interpreter._use_scatter`` for the trace)
so the accelerator cost curve is attributable from any box.

Usage:
  python tools/scaling_report.py                      # dense writes
  python tools/scaling_report.py --p 256,1024 --json  # CI-sized, JSON only

One JSON document on stdout with ``--json``; human table otherwise.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

if __name__ == "__main__":
    # host-side analysis: tracing needs no accelerator, and a wedged
    # accelerator must not hang the report (same guard as gen_corpus)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_P = (1024, 4096, 16384)

# committed per-lane growth budget for the superstep body (the CI smoke
# asserts against THIS value — a future PR reintroducing an O(P·x) term
# fails tests/test_scaling.py without TPU hardware)
PER_LANE_EXPONENT_BUDGET = 1.05


def all_eqns(jaxpr):
    """Every equation of a (Closed)Jaxpr, sub-jaxprs included (cond
    branches, while bodies, pjit calls, scans: each once)."""
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        yield eqn
        # params hold sub-jaxprs under many names (branches, jaxpr,
        # body_jaxpr, ...) and inside tuples — duck-type on .eqns
        stack = list(eqn.params.values())
        while stack:
            val = stack.pop()
            if isinstance(val, (tuple, list)):
                stack.extend(val)
            elif hasattr(getattr(val, "jaxpr", val), "eqns"):
                yield from all_eqns(val)


def jaxpr_cost(jaxpr) -> dict:
    """Op/element/byte totals over a (Closed)Jaxpr. Sub-jaxprs count
    ONCE — the model measures program size per trip, not trip counts,
    which is the right units for a growth-in-P fit."""
    ops = 0
    elems = 0
    nbytes = 0
    for eqn in all_eqns(jaxpr):
        ops += 1
        for ov in eqn.outvars:
            aval = getattr(ov, "aval", None)
            shape = getattr(aval, "shape", None)
            if shape is None:
                continue
            n = 1
            for d in shape:
                n *= int(d)
            elems += n
            dt = getattr(aval, "dtype", None)
            nbytes += n * (dt.itemsize if dt is not None else 4)
    return {"ops": ops, "elems": elems, "bytes": nbytes}


def _skeleton(tree, p_from: int, p_to: int):
    """Map a concrete pytree to ShapeDtypeStructs with the lane axis
    rescaled p_from→p_to. Only leading-dim matches rescale — the lane
    axis is the leading axis on every per-lane leaf by construction
    (``p_from`` is chosen not to collide with any other dimension)."""
    import jax

    def one(x):
        shape = tuple(x.shape)
        if shape and shape[0] == p_from:
            shape = (p_to,) + shape[1:]
        return jax.ShapeDtypeStruct(shape, x.dtype)

    return jax.tree.map(one, tree)


def _build_inputs(p_base: int):
    """One concrete (sf, env, corpus) at the BASE lane count; larger P
    variants are abstract skeletons (nothing big is ever allocated)."""
    import numpy as np

    from mythril_tpu.config import DEFAULT_LIMITS as L
    from mythril_tpu.core import Corpus, make_env
    from mythril_tpu.disassembler import ContractImage
    from mythril_tpu.disassembler.asm import erc20_like
    from mythril_tpu.symbolic import make_sym_frontier

    img = ContractImage.from_bytecode(erc20_like(), L.max_code)
    corpus = Corpus.from_images([img])
    active = np.zeros(p_base, dtype=bool)
    active[::2] = True
    sf = make_sym_frontier(p_base, L, active=active)
    env = make_env(p_base)
    return sf, env, corpus, L


def _carry_elems(sf, declared=None) -> int:
    """Elements crossing a cond boundary that carries ``sf`` (or only
    its ``declared`` dotted paths)."""
    import jax.tree_util as jtu

    kl, _ = jtu.tree_flatten_with_path(sf)

    def name(path):
        out = []
        for k in path:
            for attr in ("name", "key", "idx"):
                v = getattr(k, attr, None)
                if v is not None:
                    out.append(str(v))
                    break
        return ".".join(out)

    total = 0
    for path, leaf in kl:
        if leaf is None or not hasattr(leaf, "shape"):
            continue
        if declared is not None:
            n = name(path)
            if not any(n == d or n.startswith(d + ".") for d in declared):
                continue
        sz = 1
        for d in leaf.shape:
            sz *= int(d)
        total += sz
    return total


def fit_exponent(ps, ys) -> float:
    """Least-squares slope of log(y) on log(P); 0.0 when degenerate."""
    pts = [(math.log(p), math.log(y)) for p, y in zip(ps, ys) if y > 0]
    if len(pts) < 2:
        return 0.0
    n = len(pts)
    mx = sum(x for x, _ in pts) / n
    my = sum(y for _, y in pts) / n
    num = sum((x - mx) * (y - my) for x, y in pts)
    den = sum((x - mx) ** 2 for x, _ in pts)
    return num / den if den else 0.0


def attribution(p_list=DEFAULT_P, write_mode: str = "dense",
                fork_policy: str = "shallow",
                steps: int = 8,
                only=None) -> dict:
    """The report body: per-bucket cost at each P + fitted exponents.

    ``fork_policy`` defaults to a sorting policy ("shallow") because the
    fifo fast path skips the rank machinery under attribution — the
    sweep wants the worst case the campaign actually runs.

    ``only`` restricts tracing to the named buckets (tests/
    test_scaling.py traces just the bucket it asserts on — a full
    attribution traces six jaxprs per P, too slow for tier-1).
    """
    import jax

    from mythril_tpu.core import interpreter as ci
    from mythril_tpu.symbolic import SymSpec
    from mythril_tpu.symbolic.engine import (_POP_FRAME_WRITES,
                                             _sym_run_impl, expand_forks,
                                             migrate_parked_device,
                                             plan_fork_map, sym_superstep)

    p_base = min(p_list)
    sf0, env0, corpus, L = _build_inputs(p_base)
    spec = SymSpec()

    names = ("superstep", "expand_forks", "fork_plan", "rebalance",
             "sym_run_body", "cond_carry", "observe_fetch")
    if only is not None:
        names = tuple(n for n in names if n in set(only))
    buckets = {name: {"elems": {}, "bytes": {}, "ops": {}}
               for name in names}

    if write_mode not in ("dense", "scatter"):
        raise ValueError(f"unknown write mode: {write_mode!r}")
    real = ci._use_scatter
    ci._use_scatter = lambda: write_mode == "scatter"
    try:
        for p in p_list:
            sf = _skeleton(sf0, p_base, p)
            env = _skeleton(env0, p_base, p)

            def rec(name, mk):
                if name not in buckets:
                    return
                c = jaxpr_cost(mk())
                buckets[name]["elems"][p] = c["elems"]
                buckets[name]["bytes"][p] = c["bytes"]
                buckets[name]["ops"][p] = c["ops"]

            rec("superstep", lambda: jax.make_jaxpr(
                lambda s, e: sym_superstep(s, e, corpus, spec, L))(sf, env))
            rec("expand_forks", lambda: jax.make_jaxpr(
                lambda s: expand_forks(s, L.loop_bound, 0, fork_policy,
                                       True, None))(sf))
            # the mapping machinery alone — inside the full expand_forks
            # trace the whole-frontier copy (linear, ~hundreds of kB per
            # lane) drowns this term; isolated, a [G, B, B] one-hot
            # shows its P² directly
            import numpy as _np
            req2 = jax.ShapeDtypeStruct((1, p), bool)
            free2 = jax.ShapeDtypeStruct((1, p), bool)
            key2 = jax.ShapeDtypeStruct((1, p), _np.int32)
            if fork_policy == "fifo":
                rec("fork_plan", lambda: jax.make_jaxpr(
                    lambda r, f: plan_fork_map(r, f, None, fork_policy))(
                        req2, free2))
            else:
                rec("fork_plan", lambda: jax.make_jaxpr(
                    lambda r, f, k: plan_fork_map(r, f, k, fork_policy))(
                        req2, free2, key2))
            # the in-jit migration tier needs G > 1 blocks to exist
            rec("rebalance", lambda: jax.make_jaxpr(
                lambda s: migrate_parked_device(s, max(1, p // 4)))(sf))
            rec("sym_run_body", lambda: jax.make_jaxpr(
                lambda s, e: _sym_run_impl(
                    s, e, corpus, spec, L, max_steps=steps,
                    fork_policy=fork_policy, defer_starved=True))(sf, env))
            # analytic buckets: cond-boundary carry (the expand gate
            # carries the full frontier; the pop seam carries only its
            # write set) and the chunk-seam host fetch
            if "cond_carry" in buckets:
                full = _carry_elems(sf)
                narrow = _carry_elems(sf, _POP_FRAME_WRITES)
                buckets["cond_carry"]["elems"][p] = full + narrow
                buckets["cond_carry"]["bytes"][p] = 0
                buckets["cond_carry"]["ops"][p] = 2
            if "observe_fetch" in buckets:
                # (active, fork_req, running) — one bool each per lane
                buckets["observe_fetch"]["elems"][p] = 3 * p
                buckets["observe_fetch"]["bytes"][p] = 3 * p
                buckets["observe_fetch"]["ops"][p] = 1
    finally:
        ci._use_scatter = real

    ps = list(p_list)
    for name, b in buckets.items():
        ys = [b["elems"][p] for p in ps]
        b["exponent"] = round(fit_exponent(ps, ys), 4)
        b["per_lane_exponent"] = round(b["exponent"] - 1.0, 4)

    # dominant superlinear bucket: worst exponent, ties broken by size
    # at the deepest P (cond_carry/observe_fetch are informational)
    cands = [(b["exponent"], b["elems"][ps[-1]], n)
             for n, b in buckets.items()
             if n in ("superstep", "expand_forks", "fork_plan",
                      "rebalance", "sym_run_body")]
    cands.sort(reverse=True)
    dominant = cands[0][2] if cands and cands[0][0] > 1.05 else None

    return {
        "P": ps,
        "write_mode": write_mode,
        "fork_policy": fork_policy,
        "per_lane_exponent_budget": PER_LANE_EXPONENT_BUDGET,
        "buckets": buckets,
        "dominant_superlinear": dominant,
        "superstep_body_exponent": buckets.get(
            "sym_run_body", {}).get("exponent"),
    }


def _table(rep: dict) -> str:
    ps = rep["P"]
    lines = ["scaling attribution  write_mode=%s policy=%s"
             % (rep["write_mode"], rep["fork_policy"]),
             "%-14s %s %10s" % ("bucket",
                                " ".join("%14s" % ("elems@%d" % p)
                                         for p in ps), "exponent")]
    for name, b in rep["buckets"].items():
        lines.append("%-14s %s %10.3f"
                     % (name,
                        " ".join("%14d" % b["elems"][p] for p in ps),
                        b["exponent"]))
    dom = rep["dominant_superlinear"]
    lines.append("dominant superlinear bucket: %s"
                 % (dom if dom else "none (all ≤ 1.05)"))
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--p", default=",".join(str(p) for p in DEFAULT_P),
                    help="comma-separated lane counts")
    ap.add_argument("--write-mode", default="dense",
                    choices=["dense", "scatter"],
                    help="slot-write lowering to attribute (dense = the "
                         "TPU path, traceable from a CPU box)")
    ap.add_argument("--policy", default="shallow",
                    help="fork admission policy to trace")
    ap.add_argument("--json", action="store_true",
                    help="one JSON document on stdout")
    args = ap.parse_args()
    ps = tuple(int(x) for x in args.p.split(",") if x.strip())
    rep = attribution(ps, write_mode=args.write_mode,
                      fork_policy=args.policy)
    if args.json:
        print(json.dumps(rep))
    else:
        print(_table(rep))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
