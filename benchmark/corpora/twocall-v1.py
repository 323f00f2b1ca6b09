"""Corpus ``twocall-v1``: a seeded, endless stream of labelled contracts
whose flaw needs TWO calls, at the size such contracts had on mainnet.

The source is smartbugs-curated ``dataset/access_control`` (DASP 2):
``parity_wallet_bug_2.sol`` (the Parity ``WalletLibrary``: anyone may
call ``initWallet`` again and then ``kill``, SWC-106) and
``unprotected0.sol`` (``changeOwner`` without ``onlyOwner``, then the
owner's withdrawal, SWC-105), run as SmartBugs runs Mythril: from
sources, so the constructor runs first (Durieux et al.,
arXiv:1910.10601). ``deployed-v1`` has the same two flaws at the curated
files' own 2-6 functions. The ``WalletLibrary`` that was on mainnet has
about twenty external functions, and the sb-wild contracts that embed
the pattern 20-60: here every contract has 20-60, laid out as
``wild-v1`` lays a contract out (3.2-19.5 KB), and comes as creation
code plus runtime code, as ``deployed-v1``'s do. Nothing can be fetched
here, so the contracts are generated from ``deployed-v1``'s blocks.

Batches of 8 take the two sets of ``SLOTS`` in turn, each

- 4 flawed through two calls only: ``reinit_kill`` x 2 and
  ``owner_change_unprotected`` x 2, at four function counts spread over
  20-60 (one ~20, the ``WalletLibrary``'s own);
- 3 ``init_once_safe``: the repaired ``initWallet``, safe only because
  the constructor set ``initialized``, beside an owner-only ``kill``
  and withdrawal;
- 1 with ``wild-v1``'s one-call ``kill`` at ~30 functions: what the
  first call covers is not to be traded for the second.

Every contract is labelled, and a flawed one carries ``witness``: the
calldata of its attack from ``STRANGER``, in order. The seed orders the
batch, the functions, the selectors, the constants and the trailers.
``max_code`` under 3072 gives ``deployed-v1``'s lean contracts (the
flaw, its safe sibling and a filler): what the test limits hold.

The same ``seed`` gives the same stream; nothing here imports the
program under test or JAX.
"""

from __future__ import annotations

import hashlib
import importlib.util
import os
import random

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


dep = _load("twocall_v1_deployed_blocks",
            os.path.join(HERE, "deployed-v1.py"))

BATCH = dep.BATCH
CREATOR, STRANGER, M256 = dep.CREATOR, dep.STRANGER, dep.M256
OWNER_SLOT, INIT_SLOT, SUPPLY_SLOT, SUPPLY = (
    dep.OWNER_SLOT, dep.INIT_SLOT, dep.SUPPLY_SLOT, dep.SUPPLY)
CURATED, calldata = dep.CURATED, dep.calldata
#: the kinds whose flaw needs both calls, and the SWC id of each
TWO_CALL = {"reinit_kill": "106", "owner_change_unprotected": "105"}

#: the two sets of 8 contracts, (kind or wild-v1 flaws, selectors) each
SLOTS = (
    (("reinit_kill", 20), ("reinit_kill", 44),
     ("owner_change_unprotected", 28), ("owner_change_unprotected", 57),
     ("init_once_safe", 24), ("init_once_safe", 38),
     ("init_once_safe", 60), (("kill",), 30)),
    (("reinit_kill", 21), ("reinit_kill", 52),
     ("owner_change_unprotected", 33), ("owner_change_unprotected", 60),
     ("init_once_safe", 20), ("init_once_safe", 41),
     ("init_once_safe", 55), (("kill",), 31)))
assert all(len(s) == BATCH for s in SLOTS)


def _trailer(seed: int, idx: int) -> bytes:
    """Solidity's bzzr0 metadata, as ``wild-v1``'s."""
    h = hashlib.sha256(f"twocall-v1:{seed}:{idx}".encode()).digest()
    return b"\xa1\x65bzzr0\x58\x20" + h + b"\x00\x29"


def batch(seed: int, bi: int, max_code: int = 24576) -> list:
    """Batch ``bi`` of the stream for ``seed``: 8 dicts with ``name``,
    ``code`` (runtime bytes), ``creation`` (bytes), ``kind``,
    ``must_report``, ``must_not_report`` and ``witness`` (SWC id ->
    the attack's calldata, in order; empty for a safe contract)."""
    rng = random.Random(f"twocall-v1:{int(seed)}:{bi}")
    lean = max_code < 3072
    slots = list(SLOTS[bi % len(SLOTS)])
    rng.shuffle(slots)
    out = []
    for pos, (what, n_sel) in enumerate(slots):
        idx = bi * BATCH + pos
        code, kind, must, must_not, witness = dep.contract(
            rng, what, n_sel, lean)
        code += _trailer(seed, idx)
        init = dep.creation(code, lean)
        assert len(init) <= max_code, (kind, len(init), max_code)
        out.append({"name": f"t{idx:06d}_{kind}", "code": code,
                    "creation": init, "kind": kind, "must_report": must,
                    "must_not_report": must_not, "witness": witness})
    return out
