"""The run-total superstep counter on the frontier (``steps_total``):
``sym_run`` adds its loop counter where the loop ran, so a call that
ended on quiescence says how many supersteps it took."""

import pathlib
import re

import numpy as np
import pytest

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.core import Corpus, make_env
from mythril_tpu.disassembler import ContractImage
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.symbolic import SymSpec, make_sym_frontier, sym_run

# halts after five instructions
QUIESCES = assemble(1, 2, "ADD", "POP", "STOP")
# JUMPDEST; PUSH1 0; JUMP: runs until the bounded-loops policy ends it
SPINS = bytes([0x5B, 0x60, 0x00, 0x56])


def build(code: bytes, n_lanes: int = 4):
    img = ContractImage.from_bytecode(code, TEST_LIMITS.max_code)
    active = np.zeros(n_lanes, dtype=bool)
    active[0] = True
    sf = make_sym_frontier(n_lanes, TEST_LIMITS, active=active)
    return sf, make_env(n_lanes), Corpus.from_images([img])


def steps_of(sf) -> int:
    return int(np.asarray(sf.steps_total))


def executed(sf) -> int:
    """Instructions the one seeded lane executed: it runs one a
    superstep until it stops, so this is the number of loop iterations
    in which any lane ran, counted by the interpreter itself."""
    return int(np.asarray(sf.base.n_steps).max())


BUDGET = 8


def run(sf, env, corpus):
    return sym_run(sf, env, corpus, SymSpec(), TEST_LIMITS,
                   max_steps=BUDGET, propagate_every=0)


def test_counts_the_loop_of_a_program_that_quiesces_early():
    sf, env, corpus = build(QUIESCES)
    assert steps_of(sf) == 0
    out = run(sf, env, corpus)
    assert 0 < executed(out) < BUDGET
    assert steps_of(out) == executed(out)


def test_counts_the_budget_of_a_program_that_outlasts_it():
    sf, env, corpus = build(SPINS)
    out = run(sf, env, corpus)
    assert bool(np.asarray(out.base.running).any())
    assert steps_of(out) == executed(out) == BUDGET


def test_adds_up_over_chunked_calls():
    # the bounded-loops policy ends SPINS inside the second chunk
    sf, env, corpus = build(SPINS)
    sf = run(sf, env, corpus)
    assert steps_of(sf) == executed(sf) == BUDGET
    sf = run(sf, env, corpus)
    total = executed(sf)
    assert BUDGET < total < 2 * BUDGET
    assert steps_of(sf) == total
    # a call on a quiescent frontier runs nothing and adds nothing
    sf = run(sf, env, corpus)
    assert steps_of(sf) == total


def test_sym_run_has_one_program():
    """One jit entry, and no static argument or environment variable
    that picks another compiled program: every static argument left has
    two values in use somewhere in the product."""
    from mythril_tpu.symbolic import engine

    assert engine._SYM_RUN_STATIC == (
        "spec", "limits", "max_steps", "propagate_every", "fork_block",
        "track_coverage", "fork_policy", "defer_starved", "migrate_every")
    assert not hasattr(engine, "sym_run_donated")
    pkg = pathlib.Path(mythril_tpu.__file__).parent
    readers = [str(f.relative_to(pkg))
               for d in ("symbolic", "core", "analysis")
               for f in sorted((pkg / d).rglob("*.py"))
               if re.search(r"os\.environ|getenv", f.read_text())]
    assert readers == []


def _loop_of(monkeypatch, rule_raises=False, **static):
    """``_sym_run_impl`` traced (nothing compiles): the carry of its
    superstep loop and the frontier it returns."""
    import jax

    from mythril_tpu.symbolic import engine

    if rule_raises:
        def never(*a, **kw):
            raise AssertionError("pool_fixpoint traced")

        monkeypatch.setattr(engine, "pool_fixpoint", never)
    sf, env, corpus = build(SPINS)
    closed, out = jax.make_jaxpr(
        lambda sf, env, corpus: engine._sym_run_impl(
            sf, env, corpus, SymSpec(), TEST_LIMITS, max_steps=BUDGET,
            **static),
        return_shape=True)(sf, env, corpus)
    (loop,) = [e for e in closed.jaxpr.eqns if e.primitive.name == "while"]
    return len(loop.outvars), len(jax.tree.leaves(sf)), out


@pytest.mark.parametrize("case", ["bare", "spill", "spill_without_sweeps"])
def test_the_pools_rule_is_compiled_in_only_where_lanes_park(
        case, monkeypatch):
    """Without ``defer_starved`` nothing parks: the rule is not even
    traced, the loop carries what it always did and the frontier comes
    back without a ``fixpoint`` leaf, so the program is the one it was
    before the rule (``tools/sym_run_digest.py``'s ``bare`` is the
    byte-for-byte check against another checkout). With it the carry
    also holds the observation of the last sweep (six parts) and the
    flag; without sweeps nothing can be proven, and only the flag comes
    back, clear."""
    if case == "bare":
        carry, leaves, out = _loop_of(monkeypatch, rule_raises=True)
        # (JAX keeps what the body hands through unchanged out of the
        # carry, so it holds fewer than every leaf)
        assert carry <= 1 + leaves + 1
        assert out.fixpoint is None
        return
    plain, _, out = _loop_of(monkeypatch, rule_raises=True,
                             defer_starved=True, propagate_every=0)
    assert out.fixpoint.shape == () and out.fixpoint.dtype == bool
    if case == "spill":
        with pytest.raises(AssertionError, match="pool_fixpoint traced"):
            _loop_of(monkeypatch, rule_raises=True, defer_starved=True)
        monkeypatch.undo()
        carry, _, out = _loop_of(monkeypatch, defer_starved=True)
        # the observation's six parts and the flag (and what a sweep
        # writes, which the run without sweeps handed through)
        assert carry >= plain + 6 + 1
        assert out.fixpoint.shape == () and out.fixpoint.dtype == bool
