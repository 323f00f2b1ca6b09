"""Compiled-cost scaling smoke (tools/scaling_report.py).

Holds the engine to its committed growth budget WITHOUT hardware: the
attribution traces jaxprs (no execution), so a CPU-only CI round still
catches a PR that reintroduces an O(P·x) term into the superstep body —
the class of regression behind the 4096→16384 throughput cliff. Small
P values keep the traces tier-1 fast; exponents are shape-derived, so
they are exactly what the 16k-lane trace would fit.
"""

import os
import sys

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import scaling_report  # noqa: E402  (tools/ is not a package)

from fork_map_ref import fork_map_ref  # noqa: E402

P_SMOKE = (256, 1024)


def test_superstep_body_growth_within_budget():
    # the committed threshold (≈1.05 total ⇔ ≈0.05 per-lane): the whole
    # while-loop body — superstep, expand gate, pop seam, carry — must
    # cost O(P^1.05) or less with the packed fork map
    rep = scaling_report.attribution(P_SMOKE, only=("sym_run_body",))
    e = rep["superstep_body_exponent"]
    assert e is not None
    assert e <= scaling_report.PER_LANE_EXPONENT_BUDGET, (
        f"superstep body op growth fit P^{e}: a superlinear term is back "
        f"(budget {scaling_report.PER_LANE_EXPONENT_BUDGET}; run "
        f"tools/scaling_report.py to name the bucket)")
    assert rep["dominant_superlinear"] is None


def test_cost_counter_sees_a_dense_inverse_map():
    # the counter must still SEE the old cliff: the reference map in its
    # one-hot form ([G, B, B] compare) fits ~P², far above the product's
    def elems(p):
        mask = jax.ShapeDtypeStruct((1, p), bool)
        key = jax.ShapeDtypeStruct((1, p), np.int32)
        return scaling_report.jaxpr_cost(jax.make_jaxpr(
            lambda r, f, k: fork_map_ref(r, f, k, "shallow", dense=True))(
                mask, mask, key))["elems"]

    e = scaling_report.fit_exponent(P_SMOKE, [elems(p) for p in P_SMOKE])
    assert e > 1.5, (
        f"the [G,B,B] one-hot map fit P^{e}: the cost counter lost sight "
        "of the term it exists to name")


def test_packed_fork_plan_is_linear():
    rep = scaling_report.attribution(P_SMOKE, only=("fork_plan",))
    b = rep["buckets"]["fork_plan"]
    assert b["exponent"] <= 1.05, (
        f"packed fork map fit P^{b['exponent']}, expected linear")
    assert rep["dominant_superlinear"] is None


def test_feasibility_sweep_is_dense_on_the_tpu_write_mode(monkeypatch):
    # what the TPU traces (interpreter._use_scatter False) must hold no
    # per-lane scatter into, and no single-element gather from, a
    # [P, T, 8] domain array: the chip serializes both (four such gathers
    # were 36-41% of its busy time in every cell until PR 37)
    from mythril_tpu.config import TEST_LIMITS
    from mythril_tpu.core import interpreter as ci
    from mythril_tpu.symbolic import kill_infeasible, make_sym_frontier

    P = 16
    sf = make_sym_frontier(P, TEST_LIMITS)
    wide = sf.iv_lo.shape
    assert wide == (P, TEST_LIMITS.tape_len, 8)

    def faults(scatter):
        monkeypatch.setattr(ci, "_use_scatter", lambda: scatter)
        found = []
        # a new function a trace: make_jaxpr keeps what it traced before
        for eqn in scaling_report.all_eqns(
                jax.make_jaxpr(lambda s: kill_infeasible(s))(sf)):
            name = eqn.primitive.name
            operand = tuple(eqn.invars[0].aval.shape) if eqn.invars else ()
            if operand != wide:
                continue
            if name.startswith("scatter"):
                found.append(name)
            elif (name == "gather"
                  and tuple(eqn.params["slice_sizes"]) == (1, 1, 1)):
                found.append(name)
        return found

    assert faults(scatter=False) == []
    # the walker sees the CPU's form: four row scatters, no element gather
    assert faults(scatter=True) == ["scatter"] * 4


def _byte_gathers_of(jaxpr, lanes):
    """Gathers of single bytes (all-ones ``slice_sizes``) from a
    ``u8[lanes, L]`` operand, made inside ``_gather_bytes``."""
    found = []
    for eqn in scaling_report.all_eqns(jaxpr):
        if eqn.primitive.name != "gather":
            continue
        aval = eqn.invars[0].aval
        if (aval.dtype != np.uint8 or len(aval.shape) != 2
                or aval.shape[0] != lanes
                or set(eqn.params["slice_sizes"]) != {1}):
            continue
        if any(fr.function_name == "_gather_bytes"
               for fr in eqn.source_info.traceback.frames):
            found.append(tuple(aval.shape))
    return found


def test_gather_bytes_reads_rows_on_the_tpu_write_mode(monkeypatch):
    # what the TPU traces must gather no single byte in _gather_bytes:
    # the chip serializes an element gather (~12 ns a byte; seven such
    # fusions were 22-23% of its busy time in every cell until PR 41).
    # A lane's window is read as whole rows and shifted into place.
    from mythril_tpu.core import interpreter as ci
    from mythril_tpu.symbolic import SymSpec
    from mythril_tpu.symbolic.engine import sym_superstep

    P = 16
    sf0, env0, corpus, L = scaling_report._build_inputs(P)

    def faults(scatter):
        monkeypatch.setattr(ci, "_use_scatter", lambda: scatter)
        # a new function a trace: make_jaxpr keeps what it traced before
        step = jax.make_jaxpr(
            lambda f, e: ci.superstep(f, e, corpus))(sf0.base, env0)
        sym = jax.make_jaxpr(
            lambda s, e: sym_superstep(s, e, corpus, SymSpec(), L))(sf0, env0)
        return _byte_gathers_of(step, P), _byte_gathers_of(sym, P)

    step, sym = faults(scatter=False)
    assert step == [] and sym == [], (
        f"{len(step)} single-byte gathers in the interpreter's step and "
        f"{len(sym)} in sym_superstep come from _gather_bytes: {step + sym}")
    # the walker sees the CPU's form: one element gather a call site
    step, sym = faults(scatter=True)
    assert len(step) >= 7 and len(sym) > len(step)


def _whole_memory_byte_gathers_of(jaxpr, lanes, mem_bytes):
    """Gathers of ``lanes x mem_bytes`` single bytes (all-ones
    ``slice_sizes``) from a ``u8[lanes, L]`` operand, whoever made them:
    one source byte for every position of every lane's memory."""
    found = []
    for eqn in scaling_report.all_eqns(jaxpr):
        if eqn.primitive.name != "gather":
            continue
        aval, out = eqn.invars[0].aval, eqn.outvars[0].aval
        if (aval.dtype == np.uint8 and len(aval.shape) == 2
                and aval.shape[0] == lanes
                and set(eqn.params["slice_sizes"]) == {1}
                and int(np.prod(out.shape)) == lanes * mem_bytes):
            found.append(tuple(aval.shape))
    return found


def test_copies_read_rows_on_the_tpu_write_mode(monkeypatch):
    # what the TPU traces must not gather a source byte for every
    # position of every lane's memory: the four copy opcodes' five
    # sources, a returning frame's data and a precompile's output did
    # (4,194,304 single bytes a gather at the cells' shapes; six of the
    # seven were 46% of linked.campaign's busy time until PR 43)
    from mythril_tpu.core import interpreter as ci
    from mythril_tpu.symbolic import SymSpec
    from mythril_tpu.symbolic.engine import sym_superstep

    P = 16
    sf0, env0, corpus, L = scaling_report._build_inputs(P)
    M = sf0.base.memory.shape[1]

    def faults(scatter):
        monkeypatch.setattr(ci, "_use_scatter", lambda: scatter)
        # a new function a trace: make_jaxpr keeps what it traced before
        step = jax.make_jaxpr(
            lambda f, e: ci.superstep(f, e, corpus))(sf0.base, env0)
        sym = jax.make_jaxpr(
            lambda s, e: sym_superstep(s, e, corpus, SymSpec(), L))(sf0, env0)
        return (_whole_memory_byte_gathers_of(step, P, M),
                _whole_memory_byte_gathers_of(sym, P, M))

    step, sym = faults(scatter=False)
    assert step == [] and sym == [], (step, sym)
    # the walker sees the CPU's form: _h_copy's five sources, and in
    # sym_superstep the precompile's output and pop_frames' data too
    step, sym = faults(scatter=True)
    assert len(step) == 5 and len(sym) == 7, (step, sym)
