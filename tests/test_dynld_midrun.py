"""Mid-execution dynamic loading of runtime-computed call targets.

Reference: ``DynLoader.dynld`` resolves CALL targets the moment LASER
reaches them (⚠unv, SURVEY §3.4). The frontier analog loads at the
between-tx host seam: tx 1 records a concrete CALL to an address the
corpus doesn't hold (computed at runtime — no PUSH20 for the static
prefetch to find), the seam fetches its code over the (mocked) RPC
client, and tx 2's re-entry resolves into the REAL callee code, where a
finding is witnessed. This closes the "mid-execution dynld" half of
The static-reference half is the pre-pass in
``utils/loader.py:prefetch_callees``.
"""

import json

import numpy as np

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.analysis import SymExecWrapper, fire_lasers
from mythril_tpu.utils.loader import DynLoader, FileRpcClient

L = TEST_LIMITS
CALLEE_ADDR = 0xB0B

# the target: mutate (so paths survive the tx seam), then CALL an
# address computed by arithmetic — 0xB0A + 1 — which defeats any
# static PUSH-immediate scan, the exact case the pre-pass cannot cover
TARGET = assemble(
    1, 0, "SSTORE",
    0, 0, 0, 0, 0,            # outLen outOff inLen inOff value
    0xB0A, 1, "ADD",          # to = 0xB0B, at runtime
    "GAS", "CALL", "POP", "STOP",
)

# the on-chain callee: classic unprotected SELFDESTRUCT (SWC-106)
CALLEE = assemble("ORIGIN", "SELFDESTRUCT")


def make_loader(tmp_path):
    db = {f"0x{CALLEE_ADDR:040x}": {"code": "0x" + CALLEE.hex()}}
    p = tmp_path / "chain.json"
    p.write_text(json.dumps(db))
    return DynLoader(FileRpcClient(str(p)))


def run(loader):
    return SymExecWrapper(
        [TARGET], limits=L, lanes_per_contract=8, max_steps=96,
        transaction_count=2, dyn_loader=loader,
    )


def test_midrun_dynld_resolves_runtime_computed_callee(tmp_path):
    sym = run(make_loader(tmp_path))
    assert sym.dynld_loaded == [CALLEE_ADDR]
    assert len(sym.images) == 2        # callee joined the corpus
    report = fire_lasers(sym)
    hits = [i for i in report.issues if i.swc_id == "106"]
    assert hits, "SELFDESTRUCT inside the loaded callee must be found"
    assert any(i.contract == f"onchain_0x{CALLEE_ADDR:040x}" for i in hits), \
        [i.contract for i in hits]


def test_without_loader_callee_stays_havoc(tmp_path):
    sym = run(None)
    assert sym.dynld_loaded == []
    assert len(sym.images) == 1
    report = fire_lasers(sym)
    assert not [i for i in report.issues if i.swc_id == "106"]


class _GarbageClient:
    """A node answering eth_getCode with non-hex garbage."""

    def eth_getCode(self, address):
        return "0xnothexatall"

    def eth_getStorageAt(self, address, slot):
        return "alsonothex"


def test_malformed_rpc_response_degrades_not_crashes(tmp_path):
    """A garbage node response must degrade to the sound havoc path,
    never crash the in-flight analysis (review r5 finding). A single
    failure counts as TRANSIENT (retried at the next seam); only
    repeated failures enter the permanent miss cache."""
    sym = run(DynLoader(_GarbageClient()))
    assert sym.dynld_loaded == []
    assert sym._dynld_fails.get(CALLEE_ADDR) == 1   # one seam, one try
    assert CALLEE_ADDR not in sym._dynld_miss       # not yet permanent
    assert fire_lasers(sym).issues is not None      # analysis completed


class _CountingClient:
    """Records every eth_getCode address; never returns code."""

    def __init__(self):
        self.requests = []

    def eth_getCode(self, address):
        self.requests.append(address)
        return "0x"

    def eth_getStorageAt(self, address, slot):
        return "0x" + "00" * 32


# mutate, then CALL the identity precompile (address 0x4) — a concrete
# in-range target that must NEVER be fetched over RPC (ADVICE r5: junk
# and precompile addresses were burning the 4-slot dynld budget)
PRECOMPILE_CALLER = assemble(
    1, 0, "SSTORE",
    0, 0, 0, 0, 0,
    4, "GAS", "CALL", "POP", "STOP",
)


def test_precompile_addresses_never_harvested():
    client = _CountingClient()
    sym = SymExecWrapper(
        [PRECOMPILE_CALLER], limits=L, lanes_per_contract=8, max_steps=96,
        transaction_count=2, dyn_loader=DynLoader(client),
    )
    assert client.requests == [], \
        f"precompile fetch attempted: {client.requests}"
    assert sym.dynld_loaded == []
    # nor should 0x4 occupy a permanent-miss slot: it was filtered, not
    # tried-and-missed
    assert 4 not in sym._dynld_miss


def test_dynld_misses_are_cached(tmp_path):
    # empty chain DB: the fetch misses; the address must enter the miss
    # cache and not be refetched (FileRpcClient has no call counter, so
    # probe the cache directly)
    db_path = tmp_path / "empty.json"
    db_path.write_text("{}")
    sym = run(DynLoader(FileRpcClient(str(db_path))))
    assert sym.dynld_loaded == []
    assert CALLEE_ADDR in sym._dynld_miss
