"""HTTP JSON-RPC client over a real loopback transport (reference: ``tests/rpc_test.py`` mocks its node the same way ⚠unv,
SURVEY.md §4 "RPC tests"). No egress exists in this image, so the "node"
is a threaded ``http.server`` on 127.0.0.1 serving canned JSON-RPC
responses — the full client path (request encoding, transport, retry,
error surfacing) runs for real.
"""

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from mythril_tpu.utils.loader import (DynLoader, DynLoaderError,
                                      HttpRpcClient, rpc_client_from_uri)

CODE = "0x6001600201"
SLOT0 = "0x" + "11" * 32


class _Node(BaseHTTPRequestHandler):
    """Canned JSON-RPC node. Class attrs configure behavior per test."""

    fail_first = 0      # 500-error this many requests before answering
    seen = None         # list collecting parsed request payloads
    codes = None        # optional {addr_lower: hexcode} per-address map

    def do_POST(self):  # noqa: N802 — BaseHTTPRequestHandler API
        cls = type(self)
        if self.path == "/nosuch":
            self.send_error(404, "not found")
            return
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        if cls.seen is not None:
            cls.seen.append(body)
        if cls.fail_first > 0:
            cls.fail_first -= 1
            self.send_error(500, "flaky node")
            return
        method, params = body["method"], body["params"]
        if method == "eth_getCode":
            result = (cls.codes.get(params[0].lower(), "0x")
                      if cls.codes is not None else CODE)
        elif method == "eth_getStorageAt":
            result = SLOT0 if int(params[1], 16) == 0 else "0x0"
        elif method == "eth_getBalance":
            result = "0xde0b6b3a7640000"  # 1 ether
        elif method == "eth_blockNumber":
            result = "0x10"
        elif method == "eth_getTransactionCount":
            result = "0x2"
        else:
            out = {"jsonrpc": "2.0", "id": body["id"],
                   "error": {"code": -32601, "message": "method not found"}}
            self._reply(out)
            return
        self._reply({"jsonrpc": "2.0", "id": body["id"], "result": result})

    def _reply(self, obj):
        data = json.dumps(obj).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def log_message(self, *a):  # silence per-request stderr noise
        pass


@pytest.fixture()
def node():
    _Node.fail_first = 0
    _Node.seen = []
    _Node.codes = None
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _Node)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        yield f"http://127.0.0.1:{srv.server_address[1]}"
    finally:
        srv.shutdown()
        srv.server_close()


def test_get_code_and_storage(node):
    c = HttpRpcClient(node)
    assert c.eth_getCode("0x" + "ab" * 20) == CODE
    assert c.eth_getStorageAt("0x" + "ab" * 20, "0x0") == SLOT0
    assert c.eth_getStorageAt("0x" + "ab" * 20, "0x5") == "0x0"
    # request encoding: jsonrpc 2.0, monotonically increasing ids
    assert all(r["jsonrpc"] == "2.0" for r in _Node.seen)
    ids = [r["id"] for r in _Node.seen]
    assert ids == sorted(ids)


def test_eth_json_rpc_surface(node):
    c = HttpRpcClient(node)
    assert int(c.eth_getBalance("0x" + "ab" * 20), 16) == 10**18
    assert int(c.eth_blockNumber(), 16) == 16
    assert int(c.eth_getTransactionCount("0x" + "ab" * 20), 16) == 2


def test_transport_retry_then_success(node):
    _Node.fail_first = 2
    c = HttpRpcClient(node, retries=2)
    assert c.eth_getCode("0x" + "ab" * 20) == CODE  # 2 failures absorbed


def test_transport_retries_exhausted(node):
    _Node.fail_first = 10
    c = HttpRpcClient(node, retries=1)
    # 5xx is retried; once exhausted the HTTP status surfaces (an
    # answered request is never reported as a transport fault)
    with pytest.raises(DynLoaderError, match="rpc http 500"):
        c.eth_getCode("0x" + "ab" * 20)


def test_http_4xx_not_retried(node):
    c = HttpRpcClient(node + "/nosuch", retries=3)
    with pytest.raises(DynLoaderError, match="rpc http 404"):
        c.eth_getCode("0x" + "ab" * 20)


def test_rpc_error_not_retried(node):
    c = HttpRpcClient(node, retries=3)
    with pytest.raises(DynLoaderError, match="method not found"):
        c._call("eth_bogus", [])
    # one request only: JSON-RPC errors are answers, not transport faults
    assert len(_Node.seen) == 1


def test_dead_endpoint_fails_clearly():
    c = HttpRpcClient("http://127.0.0.1:1", timeout=0.2, retries=0)
    with pytest.raises(DynLoaderError, match="transport"):
        c.eth_getCode("0x" + "ab" * 20)


def test_dynloader_over_http(node):
    dl = DynLoader(rpc_client_from_uri(node))
    addr = int("ab" * 20, 16)
    assert dl.dynld(addr) == bytes.fromhex(CODE[2:])
    assert dl.read_storage(addr, 0) == int(SLOT0, 16)
    assert dl.read_balance(addr) == 10**18


def test_read_storage_cli_end_to_end(node, capsys):
    # `read-storage --rpc http://...` drives the real client
    from mythril_tpu.interfaces.cli import main

    rc = main(["read-storage", "0x0", "0x" + "ab" * 20, "--rpc", node])
    out = capsys.readouterr().out.strip()
    assert rc == 0
    assert out == "0x" + "11" * 32


def test_analyze_address_over_http(node, capsys):
    from mythril_tpu.interfaces.cli import main

    rc = main(["analyze", "-a", "0x" + "ab" * 20, "--rpc", node,
               "-t", "1", "--max-steps", "16", "--lanes-per-contract", "4",
               "--limits-profile", "test", "-o", "json"])
    out = capsys.readouterr().out
    assert rc == 0
    assert json.loads(out)["success"] is True


def test_prefetch_callees_scans_push20():
    from mythril_tpu.disassembler.asm import assemble

    callee_addr = int("cd" * 20, 16)
    target = assemble(
        0, 0, 0, 0, 0, ("push20", callee_addr), ("push2", 50000),
        "CALL", "POP", "STOP",
    )
    callee = assemble(5, 9, "SSTORE", "STOP")

    class MockClient:
        def eth_getCode(self, address):
            if int(address, 16) == callee_addr:
                return "0x" + callee.hex()
            return "0x"

        def eth_getStorageAt(self, address, slot):
            return "0x0"

    dl = DynLoader(MockClient())
    got = dl.prefetch_callees(target)
    assert got == [(callee_addr, callee)]


def test_analyze_address_prefetches_callees(node, capsys, tmp_path):
    """analyze -a pulls the target AND its hardcoded callee; the callee
    joins the corpus under its REAL address, observable in the
    statespace dump's per-contract instruction coverage."""
    from mythril_tpu.disassembler.asm import assemble
    from mythril_tpu.interfaces.cli import main

    callee_addr = int("cd" * 20, 16)
    target = assemble(
        0, 0, 0, 0, 0, ("push20", callee_addr), ("push2", 50000),
        "CALL", "POP", "STOP",
    )
    callee = assemble(5, 9, "SSTORE", "STOP")
    _Node.codes = {"0x" + "ab" * 20: "0x" + target.hex(),
                   "0x" + "cd" * 20: "0x" + callee.hex()}
    ss = tmp_path / "ss.json"
    rc = main(["analyze", "-a", "0x" + "ab" * 20, "--rpc", node,
               "-t", "1", "--max-steps", "32", "--lanes-per-contract", "4",
               "--limits-profile", "test", "--statespace-json", str(ss),
               "-o", "json"])
    err = capsys.readouterr().err
    assert rc == 0
    assert "dynld: loaded callee 0x" + "cd" * 20 in err
    cov = json.loads(ss.read_text())["instruction_coverage_pct"]
    assert len(cov) == 2  # target + prefetched callee both in the corpus


def test_prefetch_excludes_target_and_bounds_attempts():
    from mythril_tpu.disassembler.asm import assemble

    self_addr = int("ab" * 20, 16)
    callee_addr = int("cd" * 20, 16)
    # self-reference + callee + a pile of garbage address constants
    toks = [("push20", self_addr), "POP", ("push20", callee_addr), "POP"]
    for k in range(40):
        toks += [("push20", 0x1000 + k), "POP"]
    target = assemble(*toks, "STOP")
    callee = assemble("STOP")
    probes = []

    class MockClient:
        def eth_getCode(self, address):
            probes.append(address)
            return "0x" + callee.hex() if int(address, 16) == callee_addr \
                else "0x"

        def eth_getStorageAt(self, address, slot):
            return "0x0"

    dl = DynLoader(MockClient())
    got = dl.prefetch_callees(target, limit=2, exclude=(self_addr,))
    assert got == [(callee_addr, callee)]       # self-ref never fetched
    assert all(int(a, 16) != self_addr for a in probes)
    assert len(probes) <= 8                      # 4×limit round-trip bound
