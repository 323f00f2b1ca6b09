"""Chain-head follower (serve/follower.py, ``serve --follow URI``):
ingestion of newly deployed contracts as the standing lowest-priority
tenant, durable-cursor resume, bounded backoff on RPC failure, and the
shed-first contract under overload. The "node" is a threaded loopback
JSON-RPC server (the tests/test_rpc_client.py pattern — no egress
exists in this image), the engine is the stub campaign from
tests/test_serve.py's protocol.
"""

import json
import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

import mythril_tpu  # noqa: F401
from mythril_tpu.obs import metrics as obs_metrics
from mythril_tpu.serve import (FOLLOWER_PRIORITY, AnalysisDaemon,
                               ChainFollower, ServeOptions, ShedPolicy)

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools"))
import serve_client  # noqa: E402

ADDR_A = "0x" + "aa" * 20
ADDR_B = "0x" + "bb" * 20
ISSUE_HEX = "0x01aa"          # \x01-prefixed -> one stub issue


def counter(name):
    return obs_metrics.REGISTRY.counter(name).value


@pytest.fixture(autouse=True)
def _restore_registry_enabled():
    was = obs_metrics.REGISTRY.enabled
    yield
    obs_metrics.REGISTRY.enabled = was


class _ChainNode(BaseHTTPRequestHandler):
    """Canned JSON-RPC chain: class attrs model the head, per-block
    creation transactions, receipts and deployed code."""

    head = 5
    blocks = {}      # block number -> [ {"hash", "to"} ]
    receipts = {}    # tx hash -> {"contractAddress"}
    codes = {}       # address(lower) -> "0x..." runtime code
    fail_all = False

    def do_POST(self):  # noqa: N802 — BaseHTTPRequestHandler API
        cls = type(self)
        body = json.loads(
            self.rfile.read(int(self.headers["Content-Length"])))
        if cls.fail_all:
            self.send_error(500, "node down")
            return
        method, params = body["method"], body["params"]
        if method == "eth_blockNumber":
            result = hex(cls.head)
        elif method == "eth_getBlockByNumber":
            n = int(params[0], 16)
            result = ({"number": params[0],
                       "transactions": cls.blocks.get(n, [])}
                      if n <= cls.head else None)
        elif method == "eth_getTransactionReceipt":
            result = cls.receipts.get(params[0])
        elif method == "eth_getCode":
            result = cls.codes.get(params[0].lower(), "0x")
        else:
            self._reply({"jsonrpc": "2.0", "id": body["id"],
                         "error": {"code": -32601,
                                   "message": "method not found"}})
            return
        self._reply({"jsonrpc": "2.0", "id": body["id"],
                     "result": result})

    def _reply(self, obj):
        data = json.dumps(obj).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def log_message(self, *a):
        pass


def _deploy(block, addr, code_hex, txh=None):
    """Register one creation in the canned chain."""
    txh = txh or f"0xtx{block:04d}{addr[-4:]}"
    _ChainNode.blocks.setdefault(block, []).append(
        {"hash": txh, "to": None})
    _ChainNode.receipts[txh] = {"contractAddress": addr}
    _ChainNode.codes[addr.lower()] = code_hex


@pytest.fixture()
def node():
    _ChainNode.head = 5
    _ChainNode.blocks = {}
    _ChainNode.receipts = {}
    _ChainNode.codes = {}
    _ChainNode.fail_all = False
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _ChainNode)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        yield f"http://127.0.0.1:{srv.server_address[1]}"
    finally:
        srv.shutdown()
        srv.server_close()


class StubCampaign:
    def __init__(self, gate=None):
        self.gate = gate
        self.entered = threading.Event()
        self.calls = 0
        self.batches = []

    def shape_is_warm(self):
        return self.calls > 0

    def run_external_batch(self, items, bi=None):
        self.entered.set()
        if self.gate is not None:
            assert self.gate.wait(30.0), "test gate never released"
        self.calls += 1
        self.batches.append([n for n, _ in items])
        issues = [{"contract": n, "swc-id": "106", "title": "stub"}
                  for n, c in items if c.startswith(b"\x01")]
        return {"issues": issues, "paths": len(items), "dropped": 0,
                "iprof": {}, "quarantined": [], "retries": 0,
                "status": "ok", "batch": self.calls - 1,
                "wall_sec": 0.0}


def _daemon(tmp_path, node_url, stub, **kw):
    kw.setdefault("options", ServeOptions(batch_size=4))
    kw.setdefault("solver_store", None)
    dm = AnalysisDaemon(
        data_dir=str(tmp_path / "serve_data"), port=0,
        campaign_factory=(lambda cfg: stub),
        follow_uri=node_url, follow_poll=0.05, **kw)
    dm.start()
    return dm


def _wait(pred, timeout=15.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return False


def test_follower_ingests_new_contracts_and_persists_cursor(tmp_path,
                                                            node):
    stub = StubCampaign()
    dm = _daemon(tmp_path, node, stub)
    try:
        f = dm.follower
        assert f is not None and f.priority == FOLLOWER_PRIORITY
        # a fresh follower starts AT the head — no backfill
        assert _wait(lambda: f.cursor == 5)
        # one creation tx in block 6 (plus a plain transfer to skip)
        _deploy(6, ADDR_A, ISSUE_HEX)
        _ChainNode.blocks[6].append({"hash": "0xplain", "to": ADDR_B})
        _ChainNode.head = 6
        assert _wait(lambda: f.ingested == 1 and f.cursor == 6)
        # the contract went through the normal queue under the
        # follower tenant and was analyzed by the stub
        assert _wait(lambda: any(
            names and names[0].startswith(ADDR_A)
            for names in stub.batches))
        health = dm.health()
        assert health["follower"]["lag"] == 0
        assert health["follower"]["cursor"] == 6
        assert health["tenants"]["follower"]["admitted"] == 1
        # durable cursor on disk (written right after the attribute
        # moves: a reader that saw `cursor == 6` may be ahead of it)
        def durable():
            with open(os.path.join(dm.data_dir,
                                   "follower_cursor.json")) as fh:
                return json.load(fh)["block"]

        assert _wait(lambda: durable() == 6), durable()
        # the verdict is in the store: a user asking later gets a
        # dedupe hit — the precomputed-answer story
        assert _wait(lambda: dm.store.count() == 1)
    finally:
        dm.scheduler.abort()
        dm.shutdown("test teardown")


def test_follower_resumes_from_durable_cursor(tmp_path, node):
    # first daemon ingests block 6, then stops
    stub1 = StubCampaign()
    dm1 = _daemon(tmp_path, node, stub1)
    try:
        _deploy(6, ADDR_A, ISSUE_HEX)
        _ChainNode.head = 6
        assert _wait(lambda: dm1.follower.cursor == 6)
    finally:
        dm1.scheduler.abort()
        dm1.shutdown("restart")
    # block 7 deploys while the daemon is DOWN; the restarted follower
    # must resume from the durable cursor and walk only block 7
    _deploy(7, ADDR_B, "0x02bb")
    _ChainNode.head = 7
    stub2 = StubCampaign()
    dm2 = _daemon(tmp_path, node, stub2)
    try:
        assert dm2.follower.cursor == 6          # loaded, not head
        assert _wait(lambda: dm2.follower.cursor == 7)
        assert dm2.follower.ingested == 1        # block 7 only
        names = [n for b in stub2.batches for n in b]
        assert all(n.startswith(ADDR_B) for n in names)
    finally:
        dm2.scheduler.abort()
        dm2.shutdown("test teardown")


def test_follower_rpc_failure_bounded_backoff_then_recovery(tmp_path,
                                                            node):
    _ChainNode.fail_all = True
    stub = StubCampaign()
    dm = _daemon(tmp_path, node, stub)
    try:
        f = dm.follower
        assert _wait(lambda: f.rpc_errors >= 2)
        assert 0 < f.status()["backoff_sec"] <= f.max_backoff
        assert dm.health()["ok"] is True         # daemon unaffected
        _ChainNode.fail_all = False              # node comes back
        assert _wait(lambda: f.cursor == 5)
        assert f.status()["backoff_sec"] == 0.0 or _wait(
            lambda: f.status()["backoff_sec"] == 0.0)
    finally:
        dm.scheduler.abort()
        dm.shutdown("test teardown")


def test_follower_is_shed_first_under_overload(tmp_path, node):
    """The follower is the standing proof-load for the shed ladder:
    while the daemon is overloaded its lowest-priority submissions
    resolve as typed shed results (store-miss) — no queue growth, no
    drop — and the cursor still advances (the block was answered)."""
    gate = threading.Event()
    stub = StubCampaign(gate=gate)
    dm = _daemon(tmp_path, node, stub, max_queue=4,
                 shed=ShedPolicy(depth_hi=0.25, age_hi=999.0,
                                 priority_max=0),
                 options=ServeOptions(batch_size=1))
    try:
        url = f"http://127.0.0.1:{dm.port}"
        # overload: one batch held in flight + one queued -> shedding
        serve_client.submit(url, [("busy1", b"\x01b1"),
                                  ("busy2", b"\x01b2")],
                            tenant="fg", priority=5)
        assert _wait(lambda: dm.queue.shed_state == "shedding")
        # ...which it is from the moment both are queued: read the depth
        # once the first has left the queue for the gate
        assert stub.entered.wait(10.0)
        depth_before = dm.queue.depth()
        miss0 = obs_metrics.REGISTRY.counter(
            "serve_shed_total", labels={"reason": "store-miss"}).value
        _deploy(6, ADDR_A, ISSUE_HEX)
        _ChainNode.head = 6
        f = dm.follower
        assert _wait(lambda: f.cursor == 6)      # block answered...
        assert f.ingested == 1
        assert dm.queue.depth() == depth_before  # ...without queueing
        assert obs_metrics.REGISTRY.counter(
            "serve_shed_total",
            labels={"reason": "store-miss"}).value - miss0 >= 1
        assert dm.queue.stats()["tenants"]["follower"]["shed"] >= 1
        gate.set()                               # clear the overload
        assert _wait(lambda: dm.queue.shed_state == "ok")
    finally:
        gate.set()
        dm.scheduler.abort()
        dm.shutdown("test teardown")
