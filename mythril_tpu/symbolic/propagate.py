"""Batched feasibility propagation: unsigned-interval + known-bits
abstract interpretation over the per-lane SSA tapes.

This is the on-device replacement for the cheap majority of the
reference's ``Solver.check()`` calls (``mythril/laser/smt/solver`` ⚠unv,
SURVEY.md §2.2): one forward pass assigns every tape node an unsigned
interval [lo, hi] (u256 as 8xu32 limbs) AND a known-bits pair
(mask, value) — bit positions proven constant. A path constraint
``(node, sign)`` is contradicted when either domain proves the node
can't be nonzero (sign=true) or can't be zero (sign=false). Lanes with
any contradicted constraint are provably infeasible and get killed.

The two domains are complementary: intervals decide magnitude reasoning
(LT/GT bounds, dispatcher ranges); known-bits decide mask/alignment
reasoning intervals cannot — e.g. ``(x | 1) == 2`` is unsat because bit 0
of the LHS is known 1.

Soundness direction: both domains only ever over-approximate, so a kill
is always correct; undecided lanes stay alive (the reference keeps unsat
paths alive until a solver call too). The expensive exact residue goes to
the host model search only when a detection module needs a witness.

Access pattern. The four domain arrays are ``[P, T, 8]`` and every
indexed access to them is per lane. A lane reads an operand as one row
of 8 limbs (a row gather, like the tape reads of ``engine.append_node``)
and writes its node through ``interpreter._write_slot``: a per-lane
scatter on XLA:CPU, one dense compare-select pass over the array on the
TPU, which runs per-lane scatters and single-element gathers as
serialized updates (``interpreter._use_scatter`` decides when the sweep
is traced; the four 1,048,576-element gathers the constraint check used
to make were 36-41% of the device's busy time in every benchmark cell,
PERF.md PR 37). The constraint check computes both verdicts of every
node in one dense pass and reads them at the constraints' nodes.
``tests/test_write_paths.py`` holds the two write modes to one oracle,
``tests/test_scaling.py`` the TPU's trace to no scatter into, and no
element gather from, a domain array.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..core import interpreter as ci
from ..ops import u256
from .ops import SymOp, FreeKind
from .state import SymFrontier

I32 = jnp.int32
U32 = jnp.uint32

_MAX = jnp.full(8, 0xFFFFFFFF, dtype=U32)


def _full_like(x, top: bool):
    tgt = _MAX if top else jnp.zeros(8, U32)
    return jnp.broadcast_to(tgt, x.shape)


def _bound_2exp(shape, bits: int):
    """Inclusive upper bound 2^bits - 1 as limbs."""
    out = jnp.zeros(shape[:-1] + (8,), dtype=U32)
    full, rem = bits // 32, bits % 32
    for limb in range(8):
        if limb < full:
            out = out.at[..., limb].set(0xFFFFFFFF)
        elif limb == full and rem:
            out = out.at[..., limb].set((1 << rem) - 1)
    return out


def propagate_feasibility(sf: SymFrontier):
    """INCREMENTAL forward pass over every lane's tape.

    The tape is SSA append-only, so a node's domains never change once
    computed: the pass resumes from ``prop_len`` (the persistent
    ``iv_lo``/``iv_hi``/``kb_m``/``kb_v`` arrays hold earlier nodes) and
    walks only to the current ``tape_len`` — typically a handful of new
    nodes per sweep instead of the full static tape capacity, which
    measured as ~96% of symbolic runtime before this change.

    Returns ``(sf, infeasible)``: the frontier with updated domain arrays
    + ``prop_len``, and the per-lane infeasibility verdict (intervals AND
    known-bits combined)."""
    P, T = sf.tape_op.shape
    lo, hi = sf.iv_lo, sf.iv_hi   # node 0 == concrete zero: [0, 0]
    # known-bits: bit set in km -> that bit of the node equals the same
    # bit of kv. Node 0 is concrete zero: all bits known zero.
    km, kv = sf.kb_m, sf.kb_v

    def gather(arr, ids):
        # one row of 8 limbs a lane (the [P, 1, 1] index broadcasts over
        # the limb axis), not 8 single elements
        return jnp.take_along_axis(
            arr, jnp.clip(ids, 0, T - 1)[:, None, None].astype(I32),
            axis=1)[:, 0]

    def body(idx, carry):
        # idx is PER-LANE (i32[P]): lane p processes its own node
        # prop_len[p] + j this iteration — the loop trip count is the max
        # NEW-node count, not the global tape span (SSA order guarantees
        # operands were processed in an earlier sweep or iteration)
        lo, hi, km, kv = carry
        at = jnp.clip(idx, 0, T - 1)[:, None]
        op = jnp.take_along_axis(sf.tape_op, at, axis=1)[:, 0]
        a_id = jnp.take_along_axis(sf.tape_a, at, axis=1)[:, 0]
        b_id = jnp.take_along_axis(sf.tape_b, at, axis=1)[:, 0]
        imm = gather(sf.tape_imm, idx)
        la, ha = gather(lo, a_id), gather(hi, a_id)
        lb, hb = gather(lo, b_id), gather(hi, b_id)
        ka, va = gather(km, a_id), gather(kv, a_id)
        kb, vb = gather(km, b_id), gather(kv, b_id)

        top_lo = jnp.zeros_like(la)
        top_hi = _full_like(ha, True)

        # --- leaves ---
        r_lo, r_hi = top_lo, top_hi  # default TOP
        is_const = op == int(SymOp.CONST)
        r_lo = jnp.where(is_const[:, None], imm, r_lo)
        r_hi = jnp.where(is_const[:, None], imm, r_hi)
        is_free = op == int(SymOp.FREE)
        kind = a_id  # FREE stores kind in a
        addr_hi = _bound_2exp(ha.shape, 160)
        small_hi = _bound_2exp(ha.shape, 64)
        free_hi = top_hi
        free_hi = jnp.where(
            ((kind == int(FreeKind.CALLER)) | (kind == int(FreeKind.ORIGIN)))[:, None],
            addr_hi, free_hi)
        free_hi = jnp.where(
            ((kind == int(FreeKind.CALLDATASIZE)) | (kind == int(FreeKind.TIMESTAMP))
             | (kind == int(FreeKind.NUMBER)))[:, None],
            small_hi, free_hi)
        r_lo = jnp.where(is_free[:, None], 0, r_lo)
        r_hi = jnp.where(is_free[:, None], free_hi, r_hi)

        # --- helpers over operand intervals ---
        sing_a = jnp.all(la == ha, axis=-1)
        sing_b = jnp.all(lb == hb, axis=-1)
        b_can_zero = u256.is_zero(lb)
        b_all_zero = u256.is_zero(hb)

        # ADD: exact unless the hi sum wraps
        s_lo, c_lo = u256.add_carry(la, lb)
        s_hi, c_hi = u256.add_carry(ha, hb)
        add_exact = ~c_hi
        r = (jnp.where(add_exact[:, None], s_lo, 0),
             jnp.where(add_exact[:, None], s_hi, top_hi))
        r_lo = jnp.where((op == int(SymOp.ADD))[:, None], r[0], r_lo)
        r_hi = jnp.where((op == int(SymOp.ADD))[:, None], r[1], r_hi)

        # SUB: exact when a surely >= b
        no_wrap = u256.gte(la, hb)
        d_lo = u256.sub(la, hb)
        d_hi = u256.sub(ha, lb)
        r_lo = jnp.where((op == int(SymOp.SUB))[:, None],
                         jnp.where(no_wrap[:, None], d_lo, 0), r_lo)
        r_hi = jnp.where((op == int(SymOp.SUB))[:, None],
                         jnp.where(no_wrap[:, None], d_hi, top_hi), r_hi)

        # MUL: exact when hi*hi fits 256 bits
        wide = u256.mul_wide(ha, hb)
        fits = jnp.all(wide[:, 8:] == 0, axis=-1)
        m_lo = u256.mul(la, lb)
        m_hi = wide[:, :8]
        r_lo = jnp.where((op == int(SymOp.MUL))[:, None],
                         jnp.where(fits[:, None], m_lo, 0), r_lo)
        r_hi = jnp.where((op == int(SymOp.MUL))[:, None],
                         jnp.where(fits[:, None], m_hi, top_hi), r_hi)

        # DIV: b>=1 -> result <= a_hi (no 256-step division here: too slow)
        r_lo = jnp.where((op == int(SymOp.DIV))[:, None], 0, r_lo)
        r_hi = jnp.where((op == int(SymOp.DIV))[:, None], ha, r_hi)

        # MOD: < b_hi (and <= a_hi); b identically 0 -> result 0
        one = jnp.zeros_like(hb).at[:, 0].set(1)
        b_minus_1 = u256.sub(hb, one)
        mod_cap = jnp.where(u256.lt(ha, b_minus_1)[:, None], ha, b_minus_1)
        mod_hi = jnp.where(b_all_zero[:, None], 0, mod_cap)
        r_lo = jnp.where((op == int(SymOp.MOD))[:, None], 0, r_lo)
        r_hi = jnp.where((op == int(SymOp.MOD))[:, None], mod_hi, r_hi)

        # AND: <= min(a_hi, b_hi)
        and_hi = jnp.where(u256.lt(ha, hb)[:, None], ha, hb)
        r_lo = jnp.where((op == int(SymOp.AND))[:, None], 0, r_lo)
        r_hi = jnp.where((op == int(SymOp.AND))[:, None], and_hi, r_hi)

        # OR: >= max(a_lo, b_lo)
        or_lo = jnp.where(u256.gt(la, lb)[:, None], la, lb)
        r_lo = jnp.where((op == int(SymOp.OR))[:, None], or_lo, r_lo)

        # NOT: exact complement flip
        r_lo = jnp.where((op == int(SymOp.NOT))[:, None], u256.bit_not(ha), r_lo)
        r_hi = jnp.where((op == int(SymOp.NOT))[:, None], u256.bit_not(la), r_hi)

        # BYTE: [0, 255]
        byte_hi = jnp.zeros_like(ha).at[:, 0].set(255)
        r_lo = jnp.where((op == int(SymOp.BYTE))[:, None], 0, r_lo)
        r_hi = jnp.where((op == int(SymOp.BYTE))[:, None], byte_hi, r_hi)

        # SHR by singleton shift: exact; else [0, value_hi]
        shr_exact = sing_a
        shr_lo = jnp.where(shr_exact[:, None], u256.shr(la, lb), 0)
        shr_hi = jnp.where(shr_exact[:, None], u256.shr(la, hb), hb)
        r_lo = jnp.where((op == int(SymOp.SHR))[:, None], shr_lo, r_lo)
        r_hi = jnp.where((op == int(SymOp.SHR))[:, None], shr_hi, r_hi)

        # SHL by singleton shift: exact when hi<<k doesn't lose bits
        k_small = sing_a & u256.lt(la, jnp.zeros_like(la).at[:, 0].set(256))
        shifted_hi = u256.shl(la, hb)
        back = u256.shr(la, shifted_hi)
        shl_ok = k_small & u256.eq(back, hb)
        r_lo = jnp.where((op == int(SymOp.SHL))[:, None],
                         jnp.where(shl_ok[:, None], u256.shl(la, lb), 0), r_lo)
        r_hi = jnp.where((op == int(SymOp.SHL))[:, None],
                         jnp.where(shl_ok[:, None], shifted_hi, top_hi), r_hi)

        # --- boolean producers: result in [0,1], sharpened when decidable ---
        t_lo = jnp.zeros_like(ha)
        t_one = jnp.zeros_like(ha).at[:, 0].set(1)

        def bool_iv(surely_true, surely_false):
            blo = jnp.where(surely_true[:, None], t_one, t_lo)
            bhi = jnp.where(surely_false[:, None], t_lo, t_one)
            return blo, bhi

        lt_t = u256.lt(ha, lb)   # a_hi < b_lo -> surely a<b
        lt_f = u256.gte(la, hb)  # a_lo >= b_hi -> surely not
        blo, bhi = bool_iv(lt_t, lt_f)
        r_lo = jnp.where((op == int(SymOp.LT))[:, None], blo, r_lo)
        r_hi = jnp.where((op == int(SymOp.LT))[:, None], bhi, r_hi)

        gt_t = u256.gt(la, hb)
        gt_f = u256.lte(ha, lb)
        blo, bhi = bool_iv(gt_t, gt_f)
        r_lo = jnp.where((op == int(SymOp.GT))[:, None], blo, r_lo)
        r_hi = jnp.where((op == int(SymOp.GT))[:, None], bhi, r_hi)

        eq_t = sing_a & sing_b & u256.eq(la, lb)
        eq_f = u256.lt(ha, lb) | u256.lt(hb, la)  # disjoint intervals
        blo, bhi = bool_iv(eq_t, eq_f)
        r_lo = jnp.where((op == int(SymOp.EQ))[:, None], blo, r_lo)
        r_hi = jnp.where((op == int(SymOp.EQ))[:, None], bhi, r_hi)

        isz_t = u256.is_zero(ha)          # whole interval is {0}
        isz_f = ~u256.is_zero(la)         # 0 not in interval
        blo, bhi = bool_iv(isz_t, isz_f)
        r_lo = jnp.where((op == int(SymOp.ISZERO))[:, None], blo, r_lo)
        r_hi = jnp.where((op == int(SymOp.ISZERO))[:, None], bhi, r_hi)

        # SLT/SGT undecided: [0, 1]
        blo, bhi = bool_iv(jnp.zeros_like(lt_t), jnp.zeros_like(lt_t))
        r_lo = jnp.where(((op == int(SymOp.SLT)) | (op == int(SymOp.SGT)))[:, None], blo, r_lo)
        r_hi = jnp.where(((op == int(SymOp.SLT)) | (op == int(SymOp.SGT)))[:, None], bhi, r_hi)

        # --- known-bits transfer (default: nothing known) ---
        all1 = _full_like(ha, True)
        rm = jnp.zeros_like(ha)
        rv = jnp.zeros_like(ha)
        rm = jnp.where(is_const[:, None], all1, rm)
        rv = jnp.where(is_const[:, None], imm, rv)
        # bounded leaves: the high bits are known zero
        free_km = jnp.zeros_like(ha)
        free_km = jnp.where(
            ((kind == int(FreeKind.CALLER)) | (kind == int(FreeKind.ORIGIN)))[:, None],
            u256.bit_not(addr_hi), free_km)
        free_km = jnp.where(
            ((kind == int(FreeKind.CALLDATASIZE)) | (kind == int(FreeKind.TIMESTAMP))
             | (kind == int(FreeKind.NUMBER)))[:, None],
            u256.bit_not(small_hi), free_km)
        rm = jnp.where(is_free[:, None], free_km, rm)

        # bitwise ops are exact on known bits
        and_m = (ka & kb) | (ka & ~va) | (kb & ~vb)  # a known-0 forces 0
        rm = jnp.where((op == int(SymOp.AND))[:, None], and_m, rm)
        rv = jnp.where((op == int(SymOp.AND))[:, None], va & vb & and_m, rv)
        or_m = (ka & kb) | (ka & va) | (kb & vb)     # a known-1 forces 1
        rm = jnp.where((op == int(SymOp.OR))[:, None], or_m, rm)
        rv = jnp.where((op == int(SymOp.OR))[:, None], (va | vb) & or_m, rv)
        rm = jnp.where((op == int(SymOp.XOR))[:, None], ka & kb, rm)
        rv = jnp.where((op == int(SymOp.XOR))[:, None], (va ^ vb) & ka & kb, rv)
        rm = jnp.where((op == int(SymOp.NOT))[:, None], ka, rm)
        rv = jnp.where((op == int(SymOp.NOT))[:, None], ~va & ka, rv)

        # shifts by a singleton amount: masks shift too; shifted-in bits
        # are known zero (tape operand order: a = shift, b = value)
        shift_conc = sing_a & u256.lt(la, jnp.zeros_like(la).at[:, 0].set(256))
        ones_shr = u256.shr(la, all1)   # low (256-k) bits set
        ones_shl = u256.shl(la, all1)   # high (256-k) bits set
        shr_m = u256.shr(la, kb) | u256.bit_not(ones_shr)
        shl_m = u256.shl(la, kb) | u256.bit_not(ones_shl)
        is_shr_c = (op == int(SymOp.SHR)) & shift_conc
        is_shl_c = (op == int(SymOp.SHL)) & shift_conc
        rm = jnp.where(is_shr_c[:, None], shr_m, rm)
        rv = jnp.where(is_shr_c[:, None], u256.shr(la, vb), rv)
        rm = jnp.where(is_shl_c[:, None], shl_m, rm)
        rv = jnp.where(is_shl_c[:, None], u256.shl(la, vb), rv)

        # boolean producers: bits 1..255 known zero; the verdict bit when
        # known-bits alone decide it
        is_bool = ((op == int(SymOp.LT)) | (op == int(SymOp.GT))
                   | (op == int(SymOp.SLT)) | (op == int(SymOp.SGT))
                   | (op == int(SymOp.EQ)) | (op == int(SymOp.ISZERO)))
        not_one = u256.bit_not(t_one)
        diff = (va ^ vb) & ka & kb
        kb_ne = ~u256.is_zero(diff)                       # EQ surely false
        a_full = jnp.all(ka == 0xFFFFFFFF, axis=-1)
        b_full = jnp.all(kb == 0xFFFFFFFF, axis=-1)
        kb_eq = a_full & b_full & u256.is_zero(va ^ vb)   # EQ surely true
        isz_nz = ~u256.is_zero(va & ka)                   # ISZERO surely 0
        isz_z = a_full & u256.is_zero(va)                 # ISZERO surely 1
        rm = jnp.where(is_bool[:, None], not_one, rm)
        rv = jnp.where(is_bool[:, None], 0, rv)
        eq_dec = (op == int(SymOp.EQ)) & (kb_ne | kb_eq)
        isz_dec = (op == int(SymOp.ISZERO)) & (isz_nz | isz_z)
        dec = eq_dec | isz_dec
        dec_one = ((op == int(SymOp.EQ)) & kb_eq) | ((op == int(SymOp.ISZERO)) & isz_z)
        rm = jnp.where(dec[:, None], all1, rm)
        rv = jnp.where(dec_one[:, None], t_one, rv)

        live = (idx >= 1) & (idx < sf.tape_len) & (op != int(SymOp.NULL))
        widx = jnp.where(live, jnp.clip(idx, 0, T - 1), T)  # T: nowhere
        return (ci._write_slot(lo, widx, r_lo), ci._write_slot(hi, widx, r_hi),
                ci._write_slot(km, widx, rm), ci._write_slot(kv, widx, rv))

    # per-lane resume: lane p walks nodes [prop_len[p], tape_len[p]);
    # trip count = the largest new-node count over lanes
    base_idx = jnp.maximum(sf.prop_len, 1).astype(jnp.int32)
    stop = jnp.max(sf.tape_len - base_idx).astype(jnp.int32)

    def wbody(state):
        j, carry = state
        return j + 1, body(base_idx + j, carry)

    _, (lo, hi, km, kv) = lax.while_loop(
        lambda s: s[0] < stop, wbody, (jnp.int32(0), (lo, hi, km, kv)))
    sf = sf.replace(
        iv_lo=lo, iv_hi=hi, kb_m=km, kb_v=kv,
        prop_len=jnp.maximum(sf.prop_len, sf.tape_len),
    )

    # constraint check (either domain may contradict)
    C = sf.con_node.shape[1]
    con_live = jnp.arange(C)[None, :] < sf.con_len[:, None]
    # the two verdicts of every node, one dense pass over the four
    # arrays; a constraint then reads one bool at its node
    node = jnp.clip(sf.con_node, 0, T - 1)
    cant_be_nonzero = jnp.take_along_axis(
        jnp.all(hi == 0, axis=-1) | (
            jnp.all(km == 0xFFFFFFFF, axis=-1) & jnp.all(kv == 0, axis=-1)),
        node, axis=1)
    cant_be_zero = jnp.take_along_axis(
        ~jnp.all(lo == 0, axis=-1) | jnp.any((kv & km) != 0, axis=-1),
        node, axis=1)
    contradicted = con_live & (sf.con_node != 0) & jnp.where(
        sf.con_sign, cant_be_nonzero, cant_be_zero
    )
    infeasible = jnp.any(contradicted, axis=1)
    return sf, infeasible


@jax.named_scope("kill_infeasible")
def kill_infeasible(sf: SymFrontier) -> SymFrontier:
    """Deactivate lanes whose path condition is provably unsatisfiable."""
    sf, inf = propagate_feasibility(sf)
    # errored lanes stay resident (not recycled) until the tx boundary so
    # their err_code survives for the per-tx trap tally; they are also not
    # "kills" — the trap already accounts for them
    inf = inf & sf.base.active & ~sf.base.error
    return sf.replace(
        base=sf.base.replace(active=sf.base.active & ~inf),
        # a killed lane's pending (deferred) fork request dies with it —
        # expand_forks also guards, but the invariant belongs here
        fork_req=sf.fork_req & ~inf,
        killed_infeasible=sf.killed_infeasible | inf,
        killed_total=sf.killed_total + jnp.sum(inf, dtype=jnp.int32),
    )
