"""Backend-adaptive slot writes: the scatter (XLA:CPU) and one-hot (TPU)
formulations of `_set_slot` / `_write_slot` must be bit-identical — the
TPU path is chosen at trace time (`_use_scatter`), so CI (CPU-only) pins
the two against each other and against a numpy oracle here.

Context: round 3's scatter rewrite was a 7x TPU regression (1.05M ->
0.149M lane-steps/s on the same chip); the fix keeps both formulations
behind one helper, and this test keeps them from drifting.
"""

import numpy as np
import jax.numpy as jnp

import mythril_tpu  # noqa: F401
import mythril_tpu.core.interpreter as ci

rng = np.random.default_rng(7)


def both_paths(fn):
    real = ci._use_scatter
    try:
        ci._use_scatter = lambda: True
        a = fn()
        ci._use_scatter = lambda: False
        b = fn()
    finally:
        ci._use_scatter = real
    return np.asarray(a), np.asarray(b)


def ref_write(arr, idx, val):
    out = np.array(arr)
    P, K = arr.shape[0], arr.shape[1]
    val = np.broadcast_to(np.asarray(val, arr.dtype), (P,) + arr.shape[2:])
    for p in range(P):
        if 0 <= idx[p] < K:
            out[p, idx[p]] = val[p]
    return out


def test_set_slot_paths_match():
    P, S = 16, 8
    stack = rng.integers(0, 2**32, (P, S, 8), dtype=np.uint32)
    val = rng.integers(0, 2**32, (P, 8), dtype=np.uint32)
    pos = rng.integers(-2, S + 2, P).astype(np.int32)
    mask = rng.random(P) < 0.6
    a, b = both_paths(lambda: ci._set_slot(
        jnp.asarray(stack), jnp.asarray(pos), jnp.asarray(val),
        jnp.asarray(mask)))
    want = ref_write(stack, np.where(mask & (pos >= 0), pos, S), val)
    assert (a == b).all() and (a == want).all()


def test_write_slot_paths_match_2d_3d_4d():
    P = 12
    for shape, vshape in (((P, 5), (P,)), ((P, 5, 8), (P, 8)),
                          ((P, 3, 4, 8), (P, 4, 8))):
        arr = rng.integers(0, 2**31, shape).astype(np.int32)
        val = rng.integers(0, 2**31, vshape).astype(np.int32)
        idx = rng.integers(0, shape[1] + 1, P).astype(np.int32)  # K = drop
        a, b = both_paths(lambda: ci._write_slot(
            jnp.asarray(arr), jnp.asarray(idx), jnp.asarray(val)))
        want = ref_write(arr, idx, val)
        assert (a == b).all() and (a == want).all(), shape


def test_expand_forks_paths_match():
    """The dense inverse-map formulation of expand_forks' fork-slot
    assignment (TPU path) must produce the same survivors as the scatter
    formulation, including under saturation (drops) and non-fifo rank."""
    from mythril_tpu.config import TEST_LIMITS
    from mythril_tpu.core import Corpus, make_env
    from mythril_tpu.disassembler import ContractImage
    from mythril_tpu.disassembler.asm import assemble
    from mythril_tpu.symbolic import SymSpec, make_sym_frontier, sym_run

    L = TEST_LIMITS
    toks = []
    for i in range(4):  # 2^4 paths against 12 lanes: saturates
        toks += [32 * i, "CALLDATALOAD", ("ref", f"L{i}"), "JUMPI",
                 ("label", f"L{i}"), "JUMPDEST"]
    toks += [1, 0, "SSTORE", "STOP"]
    code = assemble(*toks)
    img = ContractImage.from_bytecode(code, L.max_code)
    corpus = Corpus.from_images([img])

    def run_mode(scatter, policy):
        real = ci._use_scatter
        ci._use_scatter = lambda: scatter
        try:
            active = np.zeros(12, dtype=bool)
            active[0] = True
            sf = make_sym_frontier(12, L, active=active)
            out = sym_run(sf, make_env(12), corpus, SymSpec(), L,
                          max_steps=64, fork_policy=policy)
            return (np.asarray(out.base.active) & ~np.asarray(out.base.error),
                    np.asarray(out.con_sign), np.asarray(out.con_len),
                    int(np.asarray(out.dropped_total)))
        finally:
            ci._use_scatter = real

    for policy in ("fifo", "shallow"):
        a = run_mode(True, policy)
        b = run_mode(False, policy)
        assert (a[0] == b[0]).all(), policy
        assert (a[1] == b[1]).all() and (a[2] == b[2]).all(), policy
        assert a[3] == b[3], policy


def test_write_slot_scalar_and_bool():
    P, K = 10, 6
    arr = np.zeros((P, K), dtype=bool)
    idx = rng.integers(0, K + 1, P).astype(np.int32)
    a, b = both_paths(lambda: ci._write_slot(
        jnp.asarray(arr), jnp.asarray(idx), True))
    want = ref_write(arr, idx, True)
    assert (a == b).all() and (a == want).all()


def test_narrow_cond_aux_defaults_and_taken():
    """narrow_cond's aux channel: defaults when the cond is untaken,
    handler values when taken (the mechanism the shared stack writeback
    rides — dispatch AUX_KEYS / sym claimed storage)."""
    from mythril_tpu.config import TEST_LIMITS
    from mythril_tpu.core import make_frontier

    f = make_frontier(4, TEST_LIMITS)
    defaults = {"r": jnp.zeros((4, 8), dtype=jnp.uint32),
                "w": jnp.zeros(4, dtype=bool)}

    def handler(fr):
        return fr.replace(pc=fr.pc + 1), {
            "r": jnp.ones((4, 8), dtype=jnp.uint32),
            "w": jnp.ones(4, dtype=bool),
        }

    taken, aux_t = ci.narrow_cond(jnp.bool_(True), handler, f,
                                  ("pc",), aux_defaults=defaults)
    untaken, aux_f = ci.narrow_cond(jnp.bool_(False), handler, f,
                                    ("pc",), aux_defaults=defaults)
    assert np.asarray(taken.pc).tolist() == (np.asarray(f.pc) + 1).tolist()
    assert np.asarray(untaken.pc).tolist() == np.asarray(f.pc).tolist()
    assert bool(np.asarray(aux_t["w"]).all())
    assert not bool(np.asarray(aux_f["w"]).any())
    assert np.asarray(aux_t["r"]).max() == 1
    assert np.asarray(aux_f["r"]).max() == 0


def test_narrow_cond_undeclared_aux_raises():
    from mythril_tpu.config import TEST_LIMITS
    from mythril_tpu.core import make_frontier

    f = make_frontier(2, TEST_LIMITS)

    def handler(fr):
        return fr, {"bogus": jnp.zeros(2)}

    try:
        ci.narrow_cond(jnp.bool_(True), handler, f, (),
                       aux_defaults={"r": jnp.zeros(2)})
    except AssertionError as e:
        assert "undeclared aux" in str(e)
    else:
        raise AssertionError("undeclared aux key must raise at trace time")


def test_dispatch_rejects_undeclared_write(monkeypatch):
    """A class handler that writes a field outside its WRITE_FIELDS entry
    fails when ``dispatch`` is traced: the gated cond returns only the
    declared leaves, so an undeclared write would otherwise be dropped
    without a word."""
    import jax
    import pytest

    from mythril_tpu.config import TEST_LIMITS
    from mythril_tpu.core import Corpus, make_env, make_frontier
    from mythril_tpu.disassembler import ContractImage
    from mythril_tpu.disassembler.asm import assemble

    img = ContractImage.from_bytecode(assemble(1, "POP", "STOP"),
                                      TEST_LIMITS.max_code)
    corpus = Corpus.from_images([img])
    f, env = make_frontier(2, TEST_LIMITS), make_env(2)

    def rogue(fr, env, corpus, op, mask, old_pc):
        return fr.replace(gas_min=fr.gas_min + 1), {}

    assert "gas_min" not in ci.WRITE_FIELDS[0]
    monkeypatch.setattr(ci, "_HANDLERS", [rogue])

    def step(fr):
        fr, op, run, old_pc = ci.prologue(fr, corpus)
        return ci.dispatch(fr, env, corpus, op, run, old_pc)

    with pytest.raises(AssertionError,
                       match="rogue wrote undeclared field 'gas_min'"):
        jax.eval_shape(step, f)


def test_shared_writeback_swap_and_veto_semantics():
    """SWAP16-at-depth and the ok-veto: the dispatch shared writeback must
    reproduce the per-handler writes the oracle suites pin, including the
    second write port and a vetoed MLOAD (oob) leaving the stack slot
    untouched."""
    from mythril_tpu.config import TEST_LIMITS
    from mythril_tpu.core import Corpus, make_env, make_frontier, run
    from mythril_tpu.disassembler import ContractImage
    from mythril_tpu.disassembler.asm import assemble

    # push 17 distinct values, SWAP16, store top and the swapped-to slot
    prog = []
    for k in range(17):
        prog.append(("push1", k + 1))
    prog += ["SWAP16",
             ("push1", 0), "MSTORE",            # writes top (was slot 16)
             ("push1", 0), ("push1", 0), "RETURN"]
    code = assemble(*prog)
    img = ContractImage.from_bytecode(code, TEST_LIMITS.max_code)
    corpus = Corpus.from_images([img])
    f = make_frontier(2, TEST_LIMITS)
    out = run(f, make_env(2), corpus, max_steps=64)
    assert bool(out.halted[0]) and not bool(out.error[0])
    # after SWAP16 the top is the value pushed FIRST (1); MSTORE@0 wrote it
    mem0 = np.asarray(out.memory)[0, :32]
    assert int(mem0[31]) == 1 and int(mem0[:31].sum()) == 0

    # veto: MLOAD at an offset past the memory cap errors the lane and
    # must NOT write the stack slot (w1_mask = run & PUSHES & ~veto)
    code2 = assemble(("push4", 0x7FFFFFFF), "MLOAD", "STOP")
    img2 = ContractImage.from_bytecode(code2, TEST_LIMITS.max_code)
    corpus2 = Corpus.from_images([img2])
    f2 = make_frontier(1, TEST_LIMITS)
    out2 = run(f2, make_env(1), corpus2, max_steps=8)
    assert bool(out2.error[0])  # OOB_MEM trap
    # the MLOAD destination slot (sp-1, slot 0) still holds the pushed
    # offset, not a zero-fill gather result
    top = np.asarray(out2.stack)[0, 0]
    assert int(top[0]) == 0x7FFFFFFF and int(top[1:].sum()) == 0
