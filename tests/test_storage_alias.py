"""Numeric storage-alias probe (SURVEY §7 hard part
#1 — the reference gets this from Z3 ``Array`` semantics ⚠unv).

A write through a symbolic key ``f(x)`` and a read through a
structurally different but numerically equal key must CONNECT when the
known-bits domain fully determines both values; keys it cannot determine
must keep the sound assumed-distinct behavior (fresh leaf, no false
connection).
"""

import numpy as np

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.ops import u256
from mythril_tpu.symbolic import SymSpec

from test_symbolic import srun


def _entry(sf, lane, key_int):
    """(value_int, val_sym, key_sym) of the storage-cache entry whose
    CONCRETE key equals key_int, or None."""
    b = sf.base
    used = np.asarray(b.st_used[lane])
    keys = np.asarray(b.st_keys[lane])
    vals = np.asarray(b.st_vals[lane])
    vsym = np.asarray(sf.st_val_sym[lane])
    ksym = np.asarray(sf.st_key_sym[lane])
    for k in range(used.shape[0]):
        if used[k] and ksym[k] == 0 and u256.to_int(keys[k]) == key_int:
            return u256.to_int(vals[k]), int(vsym[k]), int(ksym[k])
    return None


def test_provably_equal_keys_connect():
    # storage[x & 0] = 0xAA  (key is a SYMBOLIC node, provably 0)
    # then SLOAD(0) — structurally different, numerically equal — must
    # return 0xAA, proven by storing the loaded word at concrete slot 1
    code = assemble(
        0xAA, 0, "CALLDATALOAD", 0, "AND", "SSTORE",
        0, "SLOAD", 1, "SSTORE", "STOP",
    )
    sf = srun(code, propagate_every=1)
    ent = _entry(sf, 0, 1)
    assert ent is not None, "slot-1 entry missing"
    val, vsym, _ = ent
    assert vsym == 0, "load through aliased key must be the CONCRETE store"
    assert val == 0xAA
    # and the write itself was demoted to a concrete key-0 entry
    ent0 = _entry(sf, 0, 0)
    assert ent0 is not None and ent0[0] == 0xAA


def test_unproven_keys_stay_distinct():
    # storage[x & 1] = 0xAA: the domain knows 255 bits, not bit 0 — the
    # value is NOT provable, so SLOAD(0) must get a fresh leaf (sound
    # assumed-distinct), never the 0xAA
    code = assemble(
        0xAA, 0, "CALLDATALOAD", 1, "AND", "SSTORE",
        0, "SLOAD", 1, "SSTORE", "STOP",
    )
    sf = srun(code, propagate_every=1)
    ent = _entry(sf, 0, 1)
    assert ent is not None
    val, vsym, _ = ent
    assert vsym != 0, "unproven alias must load a fresh symbolic leaf"


def test_probe_gated_on_propagation():
    # with feasibility sweeps disabled the kb domain never materializes;
    # the stale-row guard (key_sym < prop_len) must keep the old
    # assumed-distinct behavior rather than demote on garbage bits
    code = assemble(
        0xAA, 0, "CALLDATALOAD", 0, "AND", "SSTORE",
        0, "SLOAD", 1, "SSTORE", "STOP",
    )
    sf = srun(code, propagate_every=0)
    ent = _entry(sf, 0, 1)
    assert ent is not None
    assert ent[1] != 0  # no sweep -> no proof -> fresh leaf


def test_demoted_miss_leaves_hash_cons_with_concrete():
    # no prior store: SLOAD(x & 0) then SLOAD(0) must hash-cons to the
    # SAME storage leaf (same account, same numeric key), observable as
    # identical val_sym node ids stored at slots 1 and 2
    code = assemble(
        0, "CALLDATALOAD", 0, "AND", "SLOAD", 1, "SSTORE",
        0, "SLOAD", 2, "SSTORE", "STOP",
    )
    sf = srun(code, propagate_every=1)
    e1, e2 = _entry(sf, 0, 1), _entry(sf, 0, 2)
    assert e1 is not None and e2 is not None
    assert e1[1] != 0 and e1[1] == e2[1], \
        "aliased loads must share one hash-consed STORAGE leaf"


def test_rewrite_through_late_proven_key_wins():
    """Ordering hazard (round-5 review): write through f(x) while
    unproven, interleave a concrete write of the aliasing value, then
    RE-write through f(x) — once the proof lands, reads must return the
    chronologically last write (st_seq order), not the highest slot."""
    from mythril_tpu.config import TEST_LIMITS
    from mythril_tpu.symbolic import sym_run

    from test_symbolic import build

    code = assemble(
        0xAA, 0, "CALLDATALOAD", 0, "AND", "SSTORE",  # [f(x)] = AA
        0xBB, 0, "SSTORE",                            # [0]    = BB
        0xCC, 0, "CALLDATALOAD", 0, "AND", "SSTORE",  # [f(x)] = CC (last)
        0, "SLOAD", 1, "SSTORE", "STOP",
    )
    sf, env, corpus = build(code)
    # phase 1: run through all three stores with NO sweeps — the alias
    # stays unproven, so the stores land in separate slots
    sf = sym_run(sf, env, corpus, SymSpec(), TEST_LIMITS,
                 max_steps=15, propagate_every=0)
    # phase 2: sweeps on — the proof lands before the SLOAD
    sf = sym_run(sf, env, corpus, SymSpec(), TEST_LIMITS,
                 max_steps=32, propagate_every=1)
    ent = _entry(sf, 0, 1)
    assert ent is not None
    val, vsym, _ = ent
    assert vsym == 0
    assert val == 0xCC, (
        f"read returned 0x{val:x}: a stale alias-group member shadowed "
        f"the chronologically last write")


def test_berlin_warm_entry_is_not_a_value_hit():
    """Berlin warm-tracking allocates (key, 0, unwritten, seq 0) entries
    on concrete SLOAD misses; a repeated SLOAD of the same unwritten
    slot must keep reading the SAME symbolic STORAGE leaf, never flip to
    concrete 0 via the warm entry (round-5 review finding)."""
    import dataclasses

    import numpy as np

    from mythril_tpu.config import TEST_LIMITS
    from mythril_tpu.core import Corpus, make_env
    from mythril_tpu.disassembler import ContractImage
    from mythril_tpu.symbolic import make_sym_frontier, sym_run

    L = dataclasses.replace(TEST_LIMITS, gas_schedule="berlin")
    code = assemble(5, "SLOAD", 1, "SSTORE",
                    5, "SLOAD", 2, "SSTORE", "STOP")
    img = ContractImage.from_bytecode(code, L.max_code)
    corpus = Corpus.from_images([img])
    active = np.zeros(4, dtype=bool)
    active[0] = True
    sf = make_sym_frontier(4, L, active=active)
    env = make_env(4)
    sf = sym_run(sf, env, corpus, SymSpec(), L, max_steps=64)
    e1, e2 = _entry(sf, 0, 1), _entry(sf, 0, 2)
    assert e1 is not None and e2 is not None
    assert e1[1] != 0, "first load must be a symbolic leaf"
    assert e1[1] == e2[1], (
        "second load of the same unwritten slot flipped away from the "
        "first load's leaf (berlin warm entry matched as a value hit)")


def test_alias_probe_off_compiles_out_to_syntactic_matching():
    """SymSpec(alias_probe=False) is the trace-time opt-out: the same
    program that CONNECTS under the probe must fall back to the sound
    assumed-distinct behavior (fresh leaf), pinning that the compiled-out
    branch stays trace-valid and semantically syntactic."""
    code = assemble(
        0xAA, 0, "CALLDATALOAD", 0, "AND", "SSTORE",
        0, "SLOAD", 1, "SSTORE", "STOP",
    )
    sf = srun(code, spec=SymSpec(alias_probe=False), propagate_every=1)
    ent = _entry(sf, 0, 1)
    assert ent is not None
    assert ent[1] != 0, "probe off: load must be a fresh symbolic leaf"
