"""Remote 4byte.directory tier of the SignatureDB, loopback-tested like the RPC client: a threaded local HTTP server
plays 4byte.directory's /api/v1/signatures/ endpoint shape."""

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

import pytest

from mythril_tpu.utils.signatures import SignatureDB, selector_of

KNOWN = "lockAndLoad(uint256,bytes32)"  # NOT in the built-in table
KNOWN_SEL = selector_of(KNOWN)


class _FourByte(BaseHTTPRequestHandler):
    requests = None  # list of hex_signature params seen

    def do_GET(self):  # noqa: N802
        q = parse_qs(urlparse(self.path).query)
        sel = (q.get("hex_signature") or [""])[0]
        if type(self).requests is not None:
            type(self).requests.append(sel)
        results = ([{"id": 1, "text_signature": KNOWN}]
                   if sel == "0x" + KNOWN_SEL else [])
        data = json.dumps({"count": len(results),
                           "results": results}).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def log_message(self, *a):
        pass


@pytest.fixture()
def fourbyte():
    _FourByte.requests = []
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _FourByte)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        yield f"http://127.0.0.1:{srv.server_address[1]}/api/v1/signatures/"
    finally:
        srv.shutdown()
        srv.server_close()


def test_remote_hit_is_memoized(fourbyte):
    db = SignatureDB(remote_url=fourbyte)
    assert db.lookup(KNOWN_SEL) == [KNOWN]
    assert db.lookup(KNOWN_SEL) == [KNOWN]  # second hit from local table
    assert len(_FourByte.requests) == 1     # exactly one remote round-trip


def test_remote_miss_is_memoized(fourbyte):
    db = SignatureDB(remote_url=fourbyte)
    missing = "deadbeef"
    assert db.lookup(missing) == []
    assert db.lookup(missing) == []
    assert len(_FourByte.requests) == 1     # miss cached, no re-query


def test_local_hit_never_queries_remote(fourbyte):
    db = SignatureDB(remote_url=fourbyte)
    assert db.lookup(selector_of("transfer(address,uint256)")) == [
        "transfer(address,uint256)"]
    assert _FourByte.requests == []


def test_dead_endpoint_degrades_to_local_only():
    db = SignatureDB(remote_url="http://127.0.0.1:1/api", remote_timeout=0.2)
    assert db.lookup("cafebabe") == []       # silent miss, no exception
    assert db.lookup(selector_of("deposit()")) == ["deposit()"]


def test_env_var_opt_in(fourbyte, monkeypatch):
    monkeypatch.setenv("MYTHRIL_4BYTE_URL", fourbyte)
    db = SignatureDB()
    assert db.lookup(KNOWN_SEL) == [KNOWN]
