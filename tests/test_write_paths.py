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


# ---------------------------------------------------------------------------
# The feasibility sweep (symbolic/propagate.py) under both write modes,
# against an oracle in Python integers: one lane at a time, one node at a
# time, ``out[p, widx[p]] = r[p]`` where ``widx[p] < T``.
# ---------------------------------------------------------------------------

import functools  # noqa: E402

import pytest  # noqa: E402

from mythril_tpu.config import TEST_LIMITS  # noqa: E402
from mythril_tpu.ops import u256  # noqa: E402
from mythril_tpu.symbolic.ops import (  # noqa: E402
    FreeKind, SymOp, WK_CALLDATA0 as _WK_CD0, WK_CALLDATASIZE as _WK_CDSIZE,
    WK_CALLER as _WK_CALLER, WK_CALLVALUE as _WK_CALLVALUE)

M256 = (1 << 256) - 1
SWEEP_P = 8
_ADDR_KINDS = (int(FreeKind.CALLER), int(FreeKind.ORIGIN))
_SMALL_KINDS = (int(FreeKind.CALLDATASIZE), int(FreeKind.TIMESTAMP),
                int(FreeKind.NUMBER))


def _ints(arr):
    """u32[P, T, 8] limbs -> [P][T] Python ints."""
    P, T = arr.shape[:2]
    flat = u256.to_ints(arr)
    return [flat[p * T:(p + 1) * T] for p in range(P)]


def _shr(s, a):
    return a >> s if s < 256 else 0


def _shl(s, a):
    return (a << s) & M256 if s < 256 else 0


def _oracle_node(op, kind, imm, A, B):
    """One node's ``(lo, hi, km, kv)`` from its operands' (the transfer
    functions of ``propagate_feasibility``'s body, in plain integers)."""
    O = SymOp
    (la, ha, ka, va), (lb, hb, kb, vb) = A, B
    sing_a, sing_b = la == ha, lb == hb
    lo, hi, km, kv = 0, M256, 0, 0
    if op == O.CONST:
        lo = hi = kv = imm
        km = M256
    elif op == O.FREE:
        hi = ((1 << 160) - 1 if kind in _ADDR_KINDS
              else (1 << 64) - 1 if kind in _SMALL_KINDS else M256)
        km = M256 ^ hi if hi != M256 else 0
    elif op == O.ADD:
        if ha + hb <= M256:
            lo, hi = (la + lb) & M256, ha + hb
    elif op == O.SUB:
        if la >= hb:
            lo, hi = la - hb, (ha - lb) & M256
    elif op == O.MUL:
        if ha * hb <= M256:
            lo, hi = (la * lb) & M256, ha * hb
    elif op == O.DIV:
        hi = ha
    elif op == O.MOD:
        hi = 0 if hb == 0 else min(ha, hb - 1)
    elif op == O.AND:
        hi = min(ha, hb)
        km = (ka & kb) | (ka & ~va) | (kb & ~vb)
        kv = va & vb & km
    elif op == O.OR:
        lo = max(la, lb)
        km = (ka & kb) | (ka & va) | (kb & vb)
        kv = (va | vb) & km
    elif op == O.XOR:
        km = ka & kb
        kv = (va ^ vb) & km
    elif op == O.NOT:
        lo, hi = M256 ^ ha, M256 ^ la
        km, kv = ka, ~va & ka
    elif op == O.BYTE:
        hi = 255
    elif op == O.SHR:
        lo, hi = (_shr(la, lb), _shr(la, hb)) if sing_a else (0, hb)
        if sing_a and la < 256:
            km = _shr(la, kb) | (M256 ^ _shr(la, M256))
            kv = _shr(la, vb)
    elif op == O.SHL:
        top = _shl(la, hb)
        if sing_a and la < 256 and _shr(la, top) == hb:
            lo, hi = _shl(la, lb), top
        if sing_a and la < 256:
            km = _shl(la, kb) | (M256 ^ _shl(la, M256))
            kv = _shl(la, vb)
    elif op in (O.LT, O.GT, O.EQ, O.ISZERO, O.SLT, O.SGT):
        t = f = False
        if op == O.LT:
            t, f = ha < lb, la >= hb
        elif op == O.GT:
            t, f = la > hb, ha <= lb
        elif op == O.EQ:
            t = sing_a and sing_b and la == lb
            f = ha < lb or hb < la
        elif op == O.ISZERO:
            t, f = ha == 0, la != 0
        lo, hi = int(t), int(not f)
        km, kv = M256 ^ 1, 0
        a_full, b_full = ka == M256, kb == M256
        if op == O.EQ:
            surely = a_full and b_full and va == vb
            if surely or (va ^ vb) & ka & kb:
                km, kv = M256, int(surely)
        elif op == O.ISZERO:
            surely = a_full and va == 0
            if surely or va & ka:
                km, kv = M256, int(surely)
    return lo, hi & M256, km & M256, kv & M256


def oracle_sweep(sf):
    """``kill_infeasible`` on numpy copies of ``sf``'s leaves: returns the
    four domain arrays, ``prop_len`` and the lanes killed."""
    g = lambda x: np.asarray(x)  # noqa: E731
    t_op, t_a, t_b = g(sf.tape_op), g(sf.tape_a), g(sf.tape_b)
    t_imm = _ints(sf.tape_imm)
    tape_len, prop_len = g(sf.tape_len), g(sf.prop_len)
    dom = [_ints(x) for x in (sf.iv_lo, sf.iv_hi, sf.kb_m, sf.kb_v)]
    P, T = t_op.shape
    base = np.maximum(prop_len, 1)
    trips = max(int((tape_len - base).max()), 0)
    at = lambda i: min(max(int(i), 0), T - 1)  # noqa: E731
    for p in range(P):
        for idx in range(int(base[p]), int(base[p]) + trips):
            n = at(idx)
            op = int(t_op[p, n])
            if not (idx < tape_len[p] and op != SymOp.NULL):
                continue  # widx == T: written nowhere
            a, b = at(t_a[p, n]), at(t_b[p, n])
            r = _oracle_node(op, int(t_a[p, n]), t_imm[p][n],
                             tuple(d[p][a] for d in dom),
                             tuple(d[p][b] for d in dom))
            for d, v in zip(dom, r):
                d[p][idx] = v
    lo, hi, km, kv = dom
    con_node, con_sign = g(sf.con_node), g(sf.con_sign)
    inf = np.zeros(P, dtype=bool)
    for p in range(P):
        for c in range(min(int(g(sf.con_len)[p]), con_node.shape[1])):
            if con_node[p, c] == 0:
                continue
            n = at(con_node[p, c])
            if con_sign[p, c]:   # asserted nonzero
                bad = hi[p][n] == 0 or (km[p][n] == M256 and kv[p][n] == 0)
            else:                # asserted zero
                bad = lo[p][n] != 0 or (kv[p][n] & km[p][n]) != 0
            inf[p] |= bad
    inf &= g(sf.base.active) & ~g(sf.base.error)
    arrs = [np.stack([u256.from_ints(row) for row in d]) for d in dom]
    return arrs, np.maximum(prop_len, tape_len), inf


@functools.lru_cache(maxsize=None)
def _sweep_fn(scatter):
    import jax
    from mythril_tpu.symbolic import kill_infeasible

    def traced(sf):  # _use_scatter is read while this traces
        real, ci._use_scatter = ci._use_scatter, lambda: scatter
        try:
            return kill_infeasible(sf)
        finally:
            ci._use_scatter = real
    return jax.jit(traced)


def _frontier(lanes):
    """A ``SWEEP_P``-lane frontier at TEST_LIMITS. ``lanes[p]`` is a dict:
    ``nodes`` [(op, a, b, imm)] appended after the well-known leaves (a
    negative a / b counts back from the node itself), ``fresh`` how many
    of the tape's last nodes no sweep has seen (default: all of it),
    ``cons`` [(node, sign)] (node < 0: from the tape's end), ``active``,
    ``error``. Rows past a lane's ``prop_len`` hold garbage, so a write
    that lands where it must not, or is lost, shows."""
    from mythril_tpu.symbolic import make_sym_frontier

    sf = make_sym_frontier(SWEEP_P, TEST_LIMITS)
    P, T = sf.tape_op.shape
    C = sf.con_node.shape[1]
    n_wk = int(sf.tape_len[0])
    t_op, t_a, t_b = (np.array(x) for x in (sf.tape_op, sf.tape_a, sf.tape_b))
    t_imm = np.zeros((P, T, 8), dtype=np.uint32)
    tape_len = np.full(P, n_wk, dtype=np.int32)
    fresh = tape_len.copy()
    con_node = np.zeros((P, C), dtype=np.int32)
    con_sign = np.zeros((P, C), dtype=bool)
    con_len = np.zeros(P, dtype=np.int32)
    active = np.ones(P, dtype=bool)
    error = np.zeros(P, dtype=bool)
    for p, lane in enumerate(lanes):
        n = n_wk
        for op, a, b, imm in lane.get("nodes", ()):
            t_op[p, n] = int(op)
            t_a[p, n] = n + a if a < 0 else a
            t_b[p, n] = n + b if b < 0 else b
            t_imm[p, n] = u256.from_int(imm)
            n += 1
        assert n <= T
        tape_len[p] = n
        fresh[p] = lane.get("fresh", n)
        for c, (node, sign) in enumerate(lane.get("cons", ())):
            con_node[p, c] = n + node if node < 0 else node
            con_sign[p, c] = sign
            con_len[p] = c + 1
        con_len[p] = lane.get("con_len", con_len[p])
        active[p] = lane.get("active", True)
        error[p] = lane.get("error", False)
    sf = sf.replace(
        tape_op=jnp.asarray(t_op), tape_a=jnp.asarray(t_a),
        tape_b=jnp.asarray(t_b), tape_imm=jnp.asarray(t_imm),
        tape_len=jnp.asarray(tape_len), con_node=jnp.asarray(con_node),
        con_sign=jnp.asarray(con_sign), con_len=jnp.asarray(con_len),
        base=sf.base.replace(active=jnp.asarray(active),
                             error=jnp.asarray(error)))
    # everything a lane calls fresh is one sweep away: sweep the rest first
    seen = np.maximum(tape_len - fresh, 1)
    if (seen > 1).any():
        pre = sf.replace(tape_len=jnp.asarray(seen),
                         con_len=jnp.zeros(P, dtype=jnp.int32))
        done = _sweep_fn(True)(pre)
        sf = sf.replace(iv_lo=done.iv_lo, iv_hi=done.iv_hi, kb_m=done.kb_m,
                        kb_v=done.kb_v, prop_len=done.prop_len)
    g = np.random.default_rng(11)
    keep = (np.arange(T)[None, :] < np.asarray(sf.prop_len)[:, None])

    def fill(x):
        return jnp.where(keep[:, :, None], x, jnp.asarray(
            g.integers(0, 2**32, (P, T, 8), dtype=np.uint32)))
    return sf.replace(iv_lo=fill(sf.iv_lo), iv_hi=fill(sf.iv_hi),
                      kb_m=fill(sf.kb_m), kb_v=fill(sf.kb_v))


def _check_sweep(sf):
    (lo, hi, km, kv), prop_len, inf = oracle_sweep(sf)
    outs = {}
    for scatter in (True, False):
        out = _sweep_fn(scatter)(sf)
        outs[scatter] = out
        mode = "scatter" if scatter else "dense"
        for name, want in (("iv_lo", lo), ("iv_hi", hi), ("kb_m", km),
                           ("kb_v", kv), ("prop_len", prop_len)):
            got = np.asarray(getattr(out, name))
            bad = np.argwhere(got != want)
            assert not len(bad), (mode, name, bad[:4].tolist())
        was = np.asarray(sf.base.active)
        assert (np.asarray(out.base.active) == (was & ~inf)).all(), mode
        assert (np.asarray(out.killed_infeasible) == inf).all(), mode
        assert int(out.killed_total) == int(inf.sum()), mode
    return outs[False], inf


def _C(v):
    return (SymOp.CONST, 0, 0, v)



# every arm of the body, each on concrete operands, on leaves and on a
# mix (a: node -2, b: node -1 unless the arm says otherwise)
_BIN = [SymOp.ADD, SymOp.SUB, SymOp.MUL, SymOp.DIV, SymOp.SDIV, SymOp.MOD,
        SymOp.SMOD, SymOp.EXP, SymOp.SIGNEXTEND, SymOp.LT, SymOp.GT,
        SymOp.SLT, SymOp.SGT, SymOp.EQ, SymOp.AND, SymOp.OR, SymOp.XOR,
        SymOp.BYTE, SymOp.SHL, SymOp.SHR, SymOp.SAR, SymOp.KECCAK_ABS]
_UN = [SymOp.ISZERO, SymOp.NOT, SymOp.KECCAK, SymOp.KECCAK_SEED,
       SymOp.CD_SELECT]     # a select is the top of both domains
_PAIRS = [(7, 3), (3, 7), (5, 5), (0, 9), (9, 0), (M256, 2), (2, M256),
          (1 << 255, 1 << 255), (255, 0xFF00), (256, 0xFF00), (8, 0xFF00),
          (4, M256 >> 4), (4, M256), ((1 << 128) - 1, (1 << 128) + 1),
          ((1 << 128), (1 << 128)), (0xF0, 0x0F)]


def _arm_lanes(ops, operands):
    """One lane an operand pattern: every op of ``ops`` on it."""
    lanes = []
    for a, b in operands:
        nodes = []
        for op in ops:
            nodes += [a, b, (op, -2, -1, 0)]
        lanes.append({"nodes": nodes})
    return lanes


def _consts(pairs):
    return [(_C(a), _C(b)) for a, b in pairs]


def _LEAF(wk):
    return (SymOp.ADD, wk, 0, 0)   # leaf + node 0: the leaf's domains


def _MASKED(wk, m):
    return [_C(m), (SymOp.AND, wk, -1, 0)]


def _n_well_known():
    from mythril_tpu.symbolic.ops import N_WELL_KNOWN
    return N_WELL_KNOWN(TEST_LIMITS.calldata_bytes)


def _brimful(op):
    """A constant and ``op`` on it until the tape is full."""
    return [_C(1)] + [(op, -1, -1, 0)] * (
        TEST_LIMITS.tape_len - _n_well_known() - 1)


SWEEP_CASES = {
    # all 27 ops on 16 concrete operand pairs, 8 lanes at a time
    "every_op_concrete_0": lambda: _arm_lanes(_BIN + _UN, _consts(_PAIRS[:8])),
    "every_op_concrete_1": lambda: _arm_lanes(_BIN + _UN, _consts(_PAIRS[8:])),
    # the same ops where one or both operands are bounded leaves: the
    # undecided and the interval-only arms
    "every_op_on_leaves": lambda: _arm_lanes(_BIN + _UN, [
        (_LEAF(_WK_CALLER), _C(5)), (_C(5), _LEAF(_WK_CALLER)),
        (_LEAF(_WK_CDSIZE), _LEAF(_WK_CALLER)),
        (_LEAF(_WK_CALLVALUE), _C(0)), (_C(300), _LEAF(_WK_CD0)),
        (_LEAF(_WK_CDSIZE), _LEAF(_WK_CDSIZE)),
        (_C(1 << 200), _LEAF(_WK_CALLER)), (_C(3), _LEAF(_WK_CDSIZE))]),
    # a FREE leaf of every kind (160-bit, 64-bit and unbounded), used
    "free_leaf_kinds": lambda: [
        {"nodes": [n for k in kinds for n in (
            (SymOp.FREE, int(k), 0, 0), (SymOp.ADD, -1, -1, 0),
            (SymOp.NOT, -2, 0, 0))], **fresh}
        for kinds, fresh in ((list(FreeKind)[:9], {}),
                             (list(FreeKind)[9:], {"fresh": 10}))],
    # known bits decide what intervals cannot: (x | 1) == 2, x & 0xF0 == 1,
    # iszero(x | 4), shifts of a masked word
    "known_bits_decide": lambda: [
        {"nodes": [_C(1), (SymOp.OR, _WK_CD0, -1, 0), _C(2),
                   (SymOp.EQ, -2, -1, 0)], "cons": [(-1, True)]},
        {"nodes": _MASKED(_WK_CD0, 0xF0) + [_C(1), (SymOp.EQ, -2, -1, 0)],
         "cons": [(-1, True)]},
        {"nodes": [_C(4), (SymOp.OR, _WK_CD0, -1, 0),
                   (SymOp.ISZERO, -1, 0, 0)], "cons": [(-1, True)]},
        {"nodes": _MASKED(_WK_CD0, 0xFF00) + [_C(8), (SymOp.SHR, -1, -2, 0),
                                              _C(4), (SymOp.SHL, -1, -3, 0),
                                              (SymOp.XOR, -1, -3, 0),
                                              (SymOp.NOT, -1, 0, 0)]},
        {"nodes": [_C(7), _C(7), (SymOp.EQ, -2, -1, 0),
                   (SymOp.ISZERO, -1, 0, 0)], "cons": [(-1, True)]},
        {"nodes": [_C(0), (SymOp.ISZERO, -1, 0, 0)], "cons": [(-1, False)]},
        {"nodes": _MASKED(_WK_CALLER, M256) + [(SymOp.NOT, -1, 0, 0)]},
        {"nodes": [_C(300), (SymOp.SHR, -1, _WK_CD0, 0),
                   (SymOp.SHL, -2, _WK_CD0, 0)]},
    ],
    # 0, 1 and many new nodes in one sweep (the trip count is the longest
    # lane's), the earlier part of each tape swept before
    "new_nodes_0_1_many": lambda: [
        {"nodes": [_C(3), _C(4), (SymOp.ADD, -2, -1, 0)], "fresh": 0},
        {"nodes": [_C(3), _C(4), (SymOp.ADD, -2, -1, 0)], "fresh": 1},
        {"nodes": [_C(3)] + [(SymOp.ADD, -1, -1, 0)] * 40, "fresh": 30},
        {"nodes": [], "fresh": 0},
        {"nodes": [_C(9), (SymOp.LT, -1, _WK_CDSIZE, 0)], "fresh": 2},
        {"nodes": [_C(2)] + [(SymOp.MUL, -1, -1, 0)] * 12, "fresh": 13},
        {"nodes": [_C(1), (SymOp.SUB, _WK_CDSIZE, -1, 0)], "fresh": 1},
        {"nodes": [_C(5)], "fresh": 1},
    ],
    # an operand computed an earlier trip of the SAME sweep, chains of it
    "operand_from_same_sweep": lambda: [
        {"nodes": [_C(2), (SymOp.ADD, -1, -1, 0), (SymOp.MUL, -1, -2, 0),
                   (SymOp.SUB, -1, -3, 0), (SymOp.EQ, -1, -1, 0),
                   (SymOp.ISZERO, -1, 0, 0)], "fresh": 6,
         "cons": [(-1, True)]},
        {"nodes": [_C(1)] + [(SymOp.SHL, -1, -1, 0)] * 9, "fresh": 10},
        {"nodes": [(SymOp.AND, _WK_CALLER, _WK_CDSIZE, 0),
                   (SymOp.OR, -1, _WK_CD0, 0), (SymOp.GT, -1, -2, 0)],
         "fresh": 3},
    ],
    # a NULL node between live ones: its row keeps what it held
    "null_node": lambda: [
        {"nodes": [_C(6), (SymOp.NULL, 0, 0, 0), (SymOp.ADD, -2, -2, 0)],
         "fresh": 3},
        {"nodes": [(SymOp.NULL, 0, 0, 77), (SymOp.NULL, -1, -1, 0),
                   (SymOp.NOT, -1, 0, 0)], "fresh": 3},
        {"nodes": [_C(6), (SymOp.NULL, 0, 0, 0)], "fresh": 1},
    ],
    # tape_len == T: the last row is written, nothing past it; beside a
    # lane with one new node (it idles for the rest of the trips)
    "tape_full": lambda: [
        {"nodes": _brimful(SymOp.ADD)},
        {"nodes": [_C(1)], "fresh": 1},
        {"nodes": _brimful(SymOp.XOR), "fresh": 5},
    ],
    # who is killed: either domain, either sign; node 0, slots past
    # con_len, inactive and errored lanes are not
    "kills": lambda: [
        {"nodes": [_C(0)], "cons": [(-1, True)]},              # 0 != 0
        {"nodes": [_C(5)], "cons": [(-1, False)]},             # 5 == 0
        {"nodes": [_C(5)], "cons": [(-1, True), (0, True)]},   # fine
        {"nodes": [_C(0)], "cons": [(-1, True)], "con_len": 0},
        {"nodes": [_C(0)], "cons": [(-1, True)], "active": False},
        {"nodes": [_C(0)], "cons": [(-1, True)], "error": True},
        {"nodes": [_C(1 << 70), (SymOp.LT, _WK_CDSIZE, -1, 0)],
         "cons": [(_WK_CD0, True), (-1, False)]},    # !(size < 2^70)
        {"nodes": [(SymOp.LT, _WK_CALLER, _WK_CDSIZE, 0)],
         "cons": [(-1, True), (_WK_CD0, False)]},              # undecided
    ],
}


@pytest.mark.parametrize("case", sorted(SWEEP_CASES))
def test_sweep_paths_match_oracle(case):
    out, inf = _check_sweep(_frontier(SWEEP_CASES[case]()))
    if case == "tape_full":
        assert out.prop_len[:3].tolist() == [
            TEST_LIMITS.tape_len, _n_well_known() + 1, TEST_LIMITS.tape_len]
    if case == "kills":
        assert inf.tolist() == [True, True, False, False, False, False,
                                True, False]
    if case == "known_bits_decide":
        assert inf[:6].tolist() == [True, True, True, False, True, True]


def test_sweep_cases_reach_every_op():
    seen = set()
    for case in SWEEP_CASES.values():
        for lane in case():
            seen |= {int(n[0]) for n in lane.get("nodes", ())}
    assert seen == {int(op) for op in SymOp}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_sweep_paths_match_oracle_random_tapes(seed):
    """Random tapes over every op, operands anywhere earlier on the tape,
    a second sweep resuming where the first stopped."""
    g = np.random.default_rng(seed)
    n_wk = _n_well_known()
    words = [0, 1, 2, 255, 256, 1 << 160, M256, M256 - 1, 1 << 255]

    def nodes(k, start):
        out = []
        for j in range(k):
            op = int(g.integers(0, len(SymOp)))
            if op == SymOp.FREE:   # a is the kind there
                a, b = int(g.integers(0, 17)), 0
            else:
                a, b = (int(x) for x in g.integers(0, start + j, 2))
            imm = (words[int(g.integers(len(words)))] if g.random() < 0.5
                   else int.from_bytes(g.bytes(32), "big"))
            out.append((op, a, b, imm))
        return out

    lanes = []
    for p in range(SWEEP_P):
        k = int(g.integers(0, 60))
        cons = [(int(g.integers(0, n_wk + max(k, 1))), bool(g.random() < 0.5))
                for _ in range(int(g.integers(0, 12)))]
        lanes.append({"nodes": nodes(k, n_wk), "cons": cons})
        if p % 2:
            lanes[-1]["fresh"] = int(g.integers(0, k + 1))
    _check_sweep(_frontier(lanes))


# ---------------------------------------------------------------------------
# _gather_bytes: the TPU reads a lane's window as rows, the CPU as bytes
# ---------------------------------------------------------------------------


def _wrap64(v):
    """A Python integer as int64 arithmetic leaves it."""
    return (v + 2**63) % 2**64 - 2**63


def _gather_ref(buf, start, n, limit):
    """out[p, k] = buf[p, start[p] + k] where that index (wrapped to
    int64, as the device adds it) lies in [0, min(limit[p], L)), else 0."""
    P, L = buf.shape
    out = np.zeros((P, n), np.uint8)
    for p in range(P):
        for k in range(n):
            idx = _wrap64(int(start[p]) + k)
            if 0 <= idx < min(int(limit[p]), L):
                out[p, k] = buf[p, idx]
    return out


def _gather_starts(L, n):
    from mythril_tpu.ops import u256
    big = np.zeros((1, 8), np.uint32)
    big[0, 5] = 1   # an offset past 2**64: saturates, then wraps to -1
    saturated = int(np.asarray(
        u256.to_u64_saturating(jnp.asarray(big)).astype(jnp.int64))[0])
    assert saturated == -1
    return [-(2**63), -n - 1, -n, -n + 1, -129, -128, -33, -1, saturated, 0,
            1, 5, 31, 32, 33, 127, 128, 129, L // 2 + 3, L - n - 1, L - n,
            L - n + 1, L - 33, L - 1, L, L + 1, L + n, 2**31 - 1, 2**31 + 7,
            2**63 - n, 2**63 - 1]


GATHER_LIMITS = ("zero", "inside_window", "equal_L", "beyond_L")


@pytest.mark.parametrize("limit_kind", GATHER_LIMITS)
@pytest.mark.parametrize("L", [256, 1024, 4096, 24576])
@pytest.mark.parametrize("n", [32, 200, 256, 1024, 4096])   # 4096: a copy's
# window, the whole memory (33 rows a lane; L 256 / 1024 / 4096 have fewer)
def test_gather_bytes_paths_match_oracle(n, L, limit_kind):
    g = np.random.default_rng(n * 31 + L)
    start = np.asarray(_gather_starts(L, n), np.int64)
    P = len(start)
    buf = g.integers(1, 256, (P, L), dtype=np.uint8)   # no zero byte:
    # a position that reads 0 was masked, one that does not was read
    limit = {
        "zero": np.zeros(P, np.int64),
        # a different cut in every lane, from before the window to past it
        "inside_window": np.clip(start, -n, L) + g.integers(-2, n + 2, P),
        "equal_L": np.full(P, L, np.int32),   # as jnp.full_like(off, L)
        "beyond_L": L + g.integers(1, 2**40, P),
    }[limit_kind]
    a, b = both_paths(lambda: ci._gather_bytes(
        jnp.asarray(buf), jnp.asarray(start), n, jnp.asarray(limit)))
    want = _gather_ref(buf, start, n, limit)
    assert a.dtype == b.dtype == np.uint8 and a.shape == b.shape == (P, n)
    assert (a == want).all(), np.argwhere(a != want)[:5]
    assert (b == want).all(), np.argwhere(b != want)[:5]
    if limit_kind != "zero":
        assert want.any()


def test_gather_bytes_odd_widths_and_narrow_starts():
    """Buffers that are no whole number of rows, windows wider than the
    buffer, and the int32 starts the PUSH window passes (pc + 1)."""
    g = np.random.default_rng(5)
    for L, n in ((100, 32), (33, 32), (31, 32), (8, 32), (448, 32),
                 (200, 200), (96, 256), (4096, 7)):
        start = np.asarray([-n, -1, 0, 1, 30, L - n, L - 1, L, L + 40, 2**31 - 1],
                           np.int64).clip(-2**31, 2**31 - 1).astype(np.int32)
        P = len(start)
        buf = g.integers(1, 256, (P, L), dtype=np.uint8)
        limit = g.integers(0, L + 3, P).astype(np.int32)
        a, b = both_paths(lambda: ci._gather_bytes(
            jnp.asarray(buf), jnp.asarray(start), n, jnp.asarray(limit)))
        want = _gather_ref(buf, start, n, limit)
        assert (a == want).all() and (b == want).all(), (L, n)


# ---------------------------------------------------------------------------
# A copy's source window: memory[:, j] <- source[:, j + (src - dst)] over all
# M positions (CALLDATACOPY, CODECOPY, EXTCODECOPY, RETURNDATACOPY, a
# returning frame's data, a precompile's output)
# ---------------------------------------------------------------------------


def _take_ref(buf, dst, src, M, limit):
    """The index the copies used until PR 43, position by position:
    ``idx = j - dst + src`` wrapped to int64 as the device adds it, the
    byte there if ``0 <= idx < min(limit, L)``, else 0."""
    P, L = buf.shape
    out = np.zeros((P, M), np.uint8)
    for p in range(P):
        for j in range(M):
            idx = _wrap64(_wrap64(j - int(dst[p])) + int(src[p]))
            if 0 <= idx < min(int(limit[p]), L):
                out[p, j] = buf[p, idx]
    return out


@pytest.mark.parametrize("L", [256, 1024, 24576])
def test_window_start_is_the_index_the_copies_used(L):
    """``start = src - dst`` (wrapping) reads what ``j - dst + src`` read,
    for every pair of operands as the handlers cast them: a saturated
    ``u64`` is -1, one from 2**63 up is negative."""
    M = 4096
    ops = [0, 1, 5, 127, 128, 129, 255, 256, M - 1, M, L - 1, L, L + 1,
           2**31 + 7, 2**63 - 1, -1, -(2**63), -(2**63) + 5]
    dst = np.repeat(np.asarray(ops, np.int64), len(ops))
    src = np.tile(np.asarray(ops, np.int64), len(ops))
    P = len(dst)
    g = np.random.default_rng(L)
    buf = g.integers(1, 256, (P, L), dtype=np.uint8)
    limit = np.where(g.random(P) < 0.5, L, g.integers(0, L + 1, P))
    with np.errstate(over="ignore"):
        start = src - dst
    a, b = both_paths(lambda: ci._gather_bytes(
        jnp.asarray(buf), jnp.asarray(start), M, jnp.asarray(limit)))
    want = _take_ref(buf, dst, src, M, limit)
    assert (a == want).all(), np.argwhere(a != want)[:5]
    assert (b == want).all(), np.argwhere(b != want)[:5]
    assert want.any()


_COPY_OPS = {"CALLDATACOPY": 0x37, "CODECOPY": 0x39, "EXTCODECOPY": 0x3C,
             "RETURNDATACOPY": 0x3E}
_BIG = 2**256 - 1


def _copy_cases(M, n_cd, n_code, n_rd, n_ext):
    """(opcode, dst, src, len): inside, to the last byte of memory, one
    past it, length 0, a source that ends inside the window, one past
    its end, and operands past 2**64."""
    out = []
    for name, n_src in (("CALLDATACOPY", n_cd), ("CODECOPY", n_code),
                        ("EXTCODECOPY", n_ext), ("RETURNDATACOPY", n_rd)):
        out += [(name, 0, 0, 8), (name, 3, 10, 40), (name, 129, 1, 127),
                (name, 0, n_src - 5, 70),           # runs off the source
                (name, 64, n_src, 32), (name, 64, n_src + 9, 32),
                (name, 7, 0, 0), (name, _BIG, 0, 0),   # length 0
                (name, M - 24, 0, 24),              # to memory's last byte
                (name, M - 24, 0, 25),              # one past it: traps
                (name, M, 0, 1), (name, 0, 0, M + 1),
                (name, _BIG, 0, 1), (name, 0, 0, _BIG), (name, 2**64, 0, 1),
                (name, _BIG, 3, 40), (name, 5, _BIG, 40),   # -1 as int64
                (name, 5, 2**63, 40),               # negative as int64
                (name, 5, 2**63 - 1, 40)]
    return out


def _run_concrete(f, env, corpus, scatter):
    """``core.run`` traced anew under one lowering."""
    import jax

    real, ci._use_scatter = ci._use_scatter, lambda: scatter
    try:
        return jax.jit(ci.run.__wrapped__, static_argnames=("max_steps",))(
            f, env, corpus, max_steps=16)
    finally:
        ci._use_scatter = real


def test_copy_opcodes_match_the_plain_evm_under_both_lowerings():
    """``_h_copy`` end to end: one lane a case, the operands read from
    calldata, memory and return data seeded with a pattern, every lane
    diffed against ``tests/pyevm_ref.py`` (EXTCODECOPY of a known account
    against its CODECOPY of that account's code)."""
    from mythril_tpu.core import Corpus, make_env, make_frontier
    from mythril_tpu.core.frontier import contract_address
    from mythril_tpu.disassembler import ContractImage
    from mythril_tpu.disassembler.asm import assemble

    from pyevm_ref import RefEVM

    L = TEST_LIMITS
    M = L.mem_bytes
    g = np.random.default_rng(43)
    target = bytes(g.integers(1, 256, 300, dtype=np.uint8))
    segs, at = b"", {}
    for name, op in _COPY_OPS.items():
        at[name] = len(segs)
        segs += assemble(64, "CALLDATALOAD", 32, "CALLDATALOAD",
                         0, "CALLDATALOAD",
                         *((96, "CALLDATALOAD") if name == "EXTCODECOPY"
                           else ()), name, "STOP")
    prog = segs + bytes(g.integers(1, 256, 200, dtype=np.uint8))
    rd = bytes(g.integers(1, 256, 100, dtype=np.uint8))
    cases = _copy_cases(M, L.calldata_bytes, len(prog), len(rd), len(target))
    P = len(cases)
    cd = np.zeros((P, L.calldata_bytes), np.uint8)
    for i, (name, dst, src, ln) in enumerate(cases):
        words = [dst, src, ln,
                 contract_address(1) if name == "EXTCODECOPY"
                 else int.from_bytes(bytes(g.integers(1, 256, 32,
                                                      dtype=np.uint8)), "big")]
        cd[i] = np.frombuffer(b"".join(w.to_bytes(32, "big") for w in words),
                              np.uint8)
    pattern = g.integers(1, 256, M, dtype=np.uint8)
    corpus = Corpus.from_images([ContractImage.from_bytecode(c, L.max_code)
                                 for c in (prog, target)])
    f = make_frontier(P, L, calldata=cd,
                      calldata_len=np.full(P, L.calldata_bytes, np.int32),
                      n_contracts=2)
    returndata = np.zeros((P, L.returndata_bytes), np.uint8)
    returndata[:, :len(rd)] = np.frombuffer(rd, np.uint8)
    f = f.replace(
        pc=jnp.asarray([at[c[0]] for c in cases], jnp.int32),
        memory=jnp.asarray(np.tile(pattern, (P, 1))),
        mem_words=jnp.full(P, M // 32, jnp.int32),
        returndata=jnp.asarray(returndata),
        returndata_len=jnp.full(P, len(rd), jnp.int32))
    outs = [_run_concrete(f, make_env(P), corpus, scatter)
            for scatter in (True, False)]
    sources = {"CALLDATACOPY": None, "CODECOPY": prog, "EXTCODECOPY": target,
               "RETURNDATACOPY": rd}
    trapped = by_ref = 0
    for i, (name, dst, src, ln) in enumerate(cases):
        tag = f"lane {i} {cases[i]}"
        source = sources[name] or bytes(cd[i])
        want_err, want_mem = _copy_as_cast(pattern, source, dst, src, ln)
        if max(dst, src, ln) < 2**63 and not want_err:
            # the casts change nothing: the plain EVM says the same
            ref = RefEVM(prog, calldata=bytes(cd[i]))
            ref.pc, ref.returndata = at[name], rd
            ref.memory, ref.mem_words = bytearray(pattern.tobytes()), M // 32
            got = ref.run(max_steps=16)
            assert not got.error and len(got.memory) == M, tag
            if name == "EXTCODECOPY":
                # the reference answers every EXTCODECOPY with zeros: lay
                # the account's code over them as its CODECOPY lays its own
                got.memory[dst:dst + ln] = bytes(
                    target[src + k] if src + k < len(target) else 0
                    for k in range(ln))
            assert bytes(got.memory) == want_mem, tag
            for out in outs:
                assert int(np.asarray(out.gas_min)[i]) == got.gas_min, tag
            by_ref += 1
        trapped += want_err
        for out in outs:
            assert bool(np.asarray(out.error)[i]) == want_err, tag
            assert bool(np.asarray(out.halted)[i]) != want_err, tag
            # a lane that trapped at the memory model's end wrote nothing
            assert bytes(np.asarray(out.memory)[i]) == want_mem, tag
    assert trapped == 3 * len(_COPY_OPS) and by_ref == 9 * len(_COPY_OPS)


def _copy_as_cast(memory, source, dst, src, ln):
    """(trapped, memory after) of one copy as ``_h_copy`` casts its
    operands: saturated to ``u64``, then read as ``int64`` (2**64 and
    more is -1, 2**63 and more negative: ``PERF.md`` section 7, "found by
    PR 41's tests"; below 2**63 this is the EVM). The sums wrap."""
    d, s, n = (_wrap64(min(v, 2**64 - 1)) for v in (dst, src, ln))
    M = len(memory)
    end = _wrap64(d + n)
    if n > 0 and end > M:
        return True, memory.tobytes()
    out = bytearray(memory.tobytes())
    for j in range(max(d, 0), min(end, M)):
        k = _wrap64(_wrap64(j - d) + s)
        out[j] = source[k] if 0 <= k < len(source) else 0
    return False, bytes(out)


def _window_as_cast(memory, data, r_off, r_len):
    """(trapped, memory after) of a call's output window as
    ``_h_sym_call`` casts its operands (see :func:`_copy_as_cast`): the
    first ``min(r_len, len(data))`` bytes of ``data`` at ``r_off``."""
    d, n = (_wrap64(min(v, 2**64 - 1)) for v in (r_off, r_len))
    M = len(memory)
    if n > 0 and _wrap64(d + n) > M:
        return True, memory.tobytes()
    out = bytearray(memory.tobytes())
    for j in range(max(d, 0), min(_wrap64(d + min(n, len(data))), M)):
        out[j] = data[j - d]
    return False, bytes(out)


def test_returned_and_precompile_data_land_as_in_the_plain_evm():
    """``pop_frames``' and ``_apply_precompiles``' window under both
    lowerings: one lane a case, (r_off, r_len, callee, a_len) read from
    concrete calldata, the caller's memory seeded with a pattern whose
    first two words tell the callee how much to return and whether to
    revert. The callee's data comes from ``tests/pyevm_world.py``."""
    import hashlib

    import jax

    from mythril_tpu.core import Corpus, make_env
    from mythril_tpu.core.frontier import ATTACKER_ADDRESS, contract_address
    from mythril_tpu.disassembler import ContractImage
    from mythril_tpu.disassembler.asm import assemble
    from mythril_tpu.symbolic import SymSpec, make_sym_frontier
    from mythril_tpu.symbolic import engine

    from pyevm_world import Account, World

    L = TEST_LIMITS
    M = L.mem_bytes
    g = np.random.default_rng(44)
    word = lambda: int.from_bytes(bytes(g.integers(1, 256, 32,
                                                   dtype=np.uint8)), "big")
    caller = assemble(32, "CALLDATALOAD", 0, "CALLDATALOAD",
                      96, "CALLDATALOAD", 0, 0, 64, "CALLDATALOAD",
                      ("push2", 50_000), "CALL", "STOP")
    callee = assemble(
        *[t for k in range(4) for t in (("push32", word()), 32 * k, "MSTORE")],
        32, "CALLDATALOAD", ("ref", "rev"), "JUMPI",
        0, "CALLDATALOAD", 0, "RETURN",
        ("label", "rev"), "JUMPDEST", 0, "CALLDATALOAD", 0, "REVERT")
    bad = bytes([0xFE])                                  # INVALID
    me, you, other = (contract_address(i) for i in range(3))
    IDENTITY, SHA256 = 4, 2
    # (r_off, r_len, to, a_len, the callee's return length, reverts)
    cases = [(0, 32, you, 64, 32, 0), (5, 20, you, 64, 32, 0),
             (40, 64, you, 64, 32, 0),          # data ends inside the window
             (131, 128, you, 64, 127, 0), (100, 0, you, 64, 32, 0),
             (64, 32, you, 64, 0, 0),           # nothing returned
             (M - 32, 32, you, 64, 32, 0),      # to memory's last byte
             (M - 16, 32, you, 64, 32, 0),      # one past it: traps
             (M, 1, you, 64, 32, 0), (_BIG, 0, you, 64, 32, 0),
             (_BIG, 32, you, 64, 32, 0), (2**64, 40, you, 64, 64, 0),
             (7, _BIG, you, 64, 32, 0), (7, 2**63 - 1, you, 64, 32, 0),
             (3, 64, you, 64, 50, 1),           # REVERT carries its data
             (3, 64, other, 64, 0, 0),          # INVALID: nothing comes back
             (0, 32, IDENTITY, 64, 0, 0), (70, 100, IDENTITY, 40, 0, 0),
             (9, 10, IDENTITY, 128, 0, 0), (M - 8, 8, IDENTITY, 128, 0, 0),
             (M - 8, 9, IDENTITY, 128, 0, 0), (300, 0, IDENTITY, 64, 0, 0),
             (_BIG, 20, IDENTITY, 64, 0, 0),
             (0, 32, SHA256, 64, 0, 0), (77, 64, SHA256, 5, 0, 0),
             (200, 7, SHA256, 0, 0, 0), (M - 32, 32, SHA256, 64, 0, 0)]
    P = len(cases)
    cd = np.zeros((P, L.calldata_bytes), np.uint8)
    memory = np.tile(g.integers(1, 256, M, dtype=np.uint8), (P, 1))
    for i, (r_off, r_len, to, a_len, n_ret, reverts) in enumerate(cases):
        cd[i] = np.frombuffer(b"".join(
            w.to_bytes(32, "big") for w in (r_off, r_len, to, a_len)), np.uint8)
        memory[i, :64] = np.frombuffer(
            n_ret.to_bytes(32, "big") + reverts.to_bytes(32, "big"), np.uint8)
    corpus = Corpus.from_images([ContractImage.from_bytecode(c, L.max_code)
                                 for c in (caller, callee, bad)])
    import dataclasses

    L5 = dataclasses.replace(L, max_accounts=5)
    sf = make_sym_frontier(P, L5, contract_id=np.zeros(P, np.int32),
                           calldata=cd, n_contracts=3)
    sf = sf.replace(base=sf.base.replace(
        memory=jnp.asarray(memory),
        mem_words=jnp.full(P, M // 32, jnp.int32)))
    spec = SymSpec(calldata=False, callvalue=False, storage=False,
                   block_env=False)

    def run(scatter):
        real, ci._use_scatter = ci._use_scatter, lambda: scatter
        try:
            return jax.jit(engine._sym_run_impl,
                           static_argnames=engine._SYM_RUN_STATIC)(
                sf, make_env(P), corpus, spec, L5, max_steps=48)
        finally:
            ci._use_scatter = real

    outs = [run(True), run(False)]
    trapped = 0
    for i, (r_off, r_len, to, a_len, n_ret, reverts) in enumerate(cases):
        tag = f"lane {i} {cases[i]}"
        data = bytes(memory[i, :a_len])
        if to == IDENTITY:
            ok, ret = True, data
        elif to == SHA256:
            ok, ret = True, hashlib.sha256(data).digest()
        else:
            world = World(eoas=(ATTACKER_ADDRESS,))
            world.accounts.update({me: Account(caller), you: Account(callee),
                                   other: Account(bad)})
            ok, ret = world.message(me, to, 0, data, ATTACKER_ADDRESS)
            assert (ok, len(ret)) == (not reverts and to == you, n_ret), tag
        want_err, want_mem = _window_as_cast(memory[i], ret, r_off, r_len)
        trapped += want_err
        for out in outs:
            b = out.base
            assert bool(np.asarray(b.error)[i]) == want_err, tag
            assert bytes(np.asarray(b.memory)[i]) == want_mem, tag
            if not want_err:
                assert bool(np.asarray(b.halted)[i]), tag
                assert int(np.asarray(b.depth)[i]) == 0, tag
                top = np.asarray(b.stack)[i, int(np.asarray(b.sp)[i]) - 1]
                assert u256.to_int(top) == int(ok), tag
    assert trapped == 3
