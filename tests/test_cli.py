"""CLI + orchestration layer.

Reference: ``tests/cmd_line_test.py`` / ``tests/test_cli_opts.py`` (⚠unv,
SURVEY.md §4 "CLI tests") — arg parsing, output formats, command flow.
Runs in-process via ``cli.main`` (a subprocess would re-pay jax startup).
"""

import json

import pytest

import mythril_tpu  # noqa: F401
from mythril_tpu.interfaces.cli import create_parser, main
from mythril_tpu.mythril import (MythrilAnalyzer, MythrilConfig,
                                 MythrilDisassembler)
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.symbolic import SymSpec

# unprotected SELFDESTRUCT — one-instruction finding, fast to analyze
KILLABLE = assemble(0, "SELFDESTRUCT").hex()


def run_cli(capsys, *argv):
    rc = main(list(argv))
    out = capsys.readouterr().out
    return rc, out


def test_version(capsys):
    rc, out = run_cli(capsys, "version")
    assert rc == 0 and out.startswith("mythril_tpu ")


def test_list_detectors(capsys):
    rc, out = run_cli(capsys, "list-detectors")
    assert rc == 0
    assert "AccidentallyKillable" in out and "SWC-106" in out
    assert len(out.strip().splitlines()) >= 15


def test_disassemble(capsys):
    rc, out = run_cli(capsys, "d", "-c", "600160020100")
    assert rc == 0
    assert "PUSH1 0x01" in out and "ADD" in out


def test_analyze_json(capsys):
    rc, out = run_cli(
        capsys, "analyze", "-c", KILLABLE, "-t", "1",
        "--max-steps", "32", "--lanes-per-contract", "4",
        "--limits-profile", "test",
        "-m", "AccidentallyKillable", "-o", "json",
    )
    assert rc == 0
    payload = json.loads(out)
    assert payload["success"] is True
    swcs = {i["swc-id"] for i in payload["issues"]}
    assert "106" in swcs


def test_analyze_text_from_file(tmp_path, capsys):
    f = tmp_path / "code.hex"
    f.write_text("0x" + KILLABLE)
    rc, out = run_cli(
        capsys, "a", "-f", str(f), "-t", "1", "--max-steps", "32",
        "--lanes-per-contract", "4", "--limits-profile", "test",
        "-m", "AccidentallyKillable",
    )
    assert rc == 0
    assert "Unprotected SELFDESTRUCT" in out


def test_missing_input_errors():
    with pytest.raises(SystemExit):
        main(["analyze"])


def test_parser_reference_flags():
    p = create_parser()
    args = p.parse_args([
        "analyze", "-c", "00", "-t", "3", "-m", "EtherThief,TxOrigin",
        "-o", "markdown", "--loop-bound", "2", "--execution-timeout", "10",
    ])
    assert args.transaction_count == 3
    assert args.loop_bound == 2
    assert args.execution_timeout == 10.0


def test_orchestration_creation_path():
    # MythrilAnalyzer threads creation bytecode into the creation tx
    ctor = assemble("CALLER", 0, "SSTORE", 0, 0, "RETURN")
    runtime = assemble(0, "SLOAD", 1, "SSTORE", "STOP")
    contract = MythrilDisassembler.load_from_bytecode(
        runtime.hex(), creation_code=ctor.hex(), name="Owned")
    cfg = MythrilConfig(limits=TEST_LIMITS, spec=SymSpec(storage=False),
                        transaction_count=1, max_steps=128,
                        lanes_per_contract=4)
    analyzer = MythrilAnalyzer([contract], cfg)
    report = analyzer.fire_lasers()
    assert analyzer.sym is not None
    assert len(analyzer.sym.tx_contexts) == 2  # creation + 1 message tx
    assert report.contract_name == "Owned"


def test_analyze_jsonv2(capsys):
    rc, out = run_cli(
        capsys, "analyze", "-c", KILLABLE, "-t", "1",
        "--max-steps", "64", "--lanes-per-contract", "4",
        "--limits-profile", "test",
        "-m", "AccidentallyKillable", "-o", "jsonv2",
    )
    assert rc == 0
    doc = json.loads(out)
    assert isinstance(doc, list) and doc[0]["sourceType"] == "raw-bytecode"
    issues = doc[0]["issues"]
    assert issues and issues[0]["swcID"] == "SWC-106"
    assert "head" in issues[0]["description"]
    assert issues[0]["locations"][0]["sourceMap"].count(":") == 2


# --- round-4 command completeness ---

def test_function_to_hash(capsys):
    rc, out = run_cli(capsys, "function-to-hash", "transfer(address,uint256)")
    assert rc == 0 and out.strip() == "0xa9059cbb"


def test_hash_to_address(capsys):
    rc, out = run_cli(
        capsys, "hash-to-address",
        "0x0000000000000000000000005aaeb6053f3e94c9b9a09f33669435e7ef1beaed")
    # EIP-55 reference vector
    assert rc == 0
    assert out.strip() == "0x5aAeb6053F3E94C9b9A09f33669435E7Ef1BeAed"


def _write_rpc_mock(tmp_path, addr: str, code_hex: str, storage=None):
    mock = {addr: {"code": "0x" + code_hex,
                   "storage": {hex(k): hex(v)
                               for k, v in (storage or {}).items()}}}
    p = tmp_path / "rpc.json"
    p.write_text(json.dumps(mock))
    return f"file:{p}"


def test_read_storage_via_mock_rpc(tmp_path, capsys):
    uri = _write_rpc_mock(tmp_path, "0x" + "ab" * 20, "6001", {1: 0x2A})
    rc, out = run_cli(capsys, "read-storage", "1", "0x" + "ab" * 20,
                      "--rpc", uri)
    assert rc == 0
    assert int(out.strip(), 16) == 0x2A


def test_analyze_address_via_mock_rpc(tmp_path, capsys):
    uri = _write_rpc_mock(tmp_path, "0x" + "cd" * 20, KILLABLE)
    rc, out = run_cli(capsys, "analyze", "-a", "0x" + "cd" * 20,
                      "--rpc", uri, "-o", "json", "-t", "1",
                      "--max-steps", "64", "--lanes-per-contract", "8",
                      "--limits-profile", "test", "-m",
                      "AccidentallyKillable")
    assert rc == 0
    issues = json.loads(out)["issues"]
    assert any(i["swc-id"] == "106" for i in issues)


def test_concolic_command(capsys):
    # branch on calldata word: seed takes the fallthrough; the flip must
    # produce calldata driving the taken side
    code = assemble(
        0, "CALLDATALOAD", ("ref", "set"), "JUMPI", "STOP",
        ("label", "set"), 1, 0, "SSTORE", "STOP",
    ).hex()
    rc, out = run_cli(capsys, "concolic", "-c", code,
                      "--calldata", "00" * 32,
                      "--max-steps", "64", "--limits-profile", "test")
    assert rc == 0
    flips = json.loads(out)
    assert len(flips) >= 1
    assert any(int(f["calldata"][2:66] or "0", 16) != 0 for f in flips)


def test_safe_functions(capsys):
    # two-function dispatcher: kill() SELFDESTRUCTs (flagged),
    # totalSupply() just stores (safe); both selectors are in the local
    # signature DB
    code = assemble(
        0, "CALLDATALOAD", ("push1", 224), "SHR",
        "DUP1", ("push4", 0x41C0E1B5), "EQ", ("ref", "kill"), "JUMPI",
        "DUP1", ("push4", 0x18160DDD), "EQ", ("ref", "total"), "JUMPI",
        "STOP",
        ("label", "kill"), 0, "SELFDESTRUCT",
        ("label", "total"), 1, 2, "SSTORE", "STOP",
    ).hex()
    rc, out = run_cli(capsys, "safe-functions", "-c", code,
                      "-t", "1", "--max-steps", "64",
                      "--lanes-per-contract", "8", "--limits-profile", "test")
    assert rc == 0
    assert "totalSupply()" in out, out
    assert "kill()" not in out, out


def test_analyze_sol_file_via_stub_solc(tmp_path, capsys, monkeypatch):
    """`analyze -f contract.sol` drives the solc subprocess seam
    (round 4; reference: `myth analyze contract.sol`, SURVEY §3.1)."""
    import sys as _sys

    sol = tmp_path / "k.sol"
    sol.write_text("contract K { }\n")
    stub = tmp_path / "solc"
    stub.write_text(
        f"#!{_sys.executable}\n"
        "import json, sys\n"
        "inp = json.load(sys.stdin)\n"
        "name = list(inp['sources'])[0]\n"
        "out = {'sources': {name: {'id': 0}}, 'contracts': {name: {'K': {\n"
        "  'evm': {'deployedBytecode': {'object': '%s',\n"
        "                               'sourceMap': '0:5:0:-'}}}}}}\n"
        "json.dump(out, sys.stdout)\n" % KILLABLE
    )
    stub.chmod(0o755)
    monkeypatch.setenv("MYTHRIL_SOLC", str(stub))
    rc, out = run_cli(
        capsys, "analyze", "-f", str(sol), "-t", "1",
        "--max-steps", "32", "--lanes-per-contract", "4",
        "--limits-profile", "test", "-m", "AccidentallyKillable",
        "-o", "json",
    )
    assert rc == 0
    doc = json.loads(out)
    assert any(i["swc-id"] == "106" for i in doc["issues"])


def test_analyze_sol_without_solc_fails_clearly(tmp_path, capsys, monkeypatch):
    monkeypatch.setenv("MYTHRIL_SOLC", str(tmp_path / "missing-solc"))
    sol = tmp_path / "k.sol"
    sol.write_text("contract K { }\n")
    with pytest.raises(SystemExit) as ei:
        main(["analyze", "-f", str(sol)])
    assert ei.value.code == 2


# --- round-5 reference flag parity ---

def test_parser_round5_parity_flags():
    p = create_parser()
    args = p.parse_args([
        "analyze", "-c", "00", "--max-depth", "64",
        "--call-depth-limit", "3", "--solver-timeout", "5000",
        "--create-timeout", "30", "--parallel-solving",
        "--unconstrained-storage", "--statespace-json", "ss.json",
    ])
    assert args.max_depth == 64
    assert args.call_depth_limit == 3
    assert args.solver_timeout == 5000
    assert args.create_timeout == 30.0
    assert args.parallel_solving is True
    assert args.unconstrained_storage is True
    assert args.statespace_json == "ss.json"


def test_parser_worker_isolation_flag():
    p = create_parser()
    args = p.parse_args(["analyze", "--corpus", "x"])
    assert args.worker_isolation == "auto"      # on under --fleet only
    args = p.parse_args(["analyze", "--corpus", "x",
                         "--worker-isolation", "on"])
    assert args.worker_isolation == "on"
    args = p.parse_args(["serve", "--worker-isolation", "off"])
    assert args.worker_isolation == "off"
    with pytest.raises(SystemExit):
        p.parse_args(["analyze", "--corpus", "x",
                      "--worker-isolation", "sometimes"])


def test_parser_serve_overload_flags():
    p = create_parser()
    args = p.parse_args(["serve"])
    assert args.tenant_rate is None and args.quota is None
    assert args.shed_depth_hi == 0.85 and args.shed_age_hi == 30.0
    assert args.shed_priority_max == 0 and args.no_shed is False
    assert args.follow is None and args.follow_poll == 2.0
    args = p.parse_args([
        "serve", "--tenant-rate", "2.5", "--tenant-burst", "16",
        "--tenant-max-inflight", "8", "--quota", "scanner=2:8:4",
        "--quota", "ops=::64", "--shed-depth-hi", "0.5",
        "--shed-age-hi", "10", "--shed-priority-max", "1",
        "--follow", "http://127.0.0.1:8545", "--follow-poll", "0.5"])
    assert args.tenant_rate == 2.5 and args.tenant_max_inflight == 8
    assert args.quota == ["scanner=2:8:4", "ops=::64"]
    assert args.shed_depth_hi == 0.5 and args.shed_priority_max == 1
    assert args.follow == "http://127.0.0.1:8545"
    assert p.parse_args(["serve", "--no-shed"]).no_shed is True


def test_flag_max_depth_overrides_max_steps(capsys):
    # --max-depth (reference name) wins over the default --max-steps
    rc, out = run_cli(
        capsys, "analyze", "-c", KILLABLE, "-t", "1",
        "--max-depth", "32", "--lanes-per-contract", "4",
        "--limits-profile", "test",
        "-m", "AccidentallyKillable", "-o", "json",
    )
    assert rc == 0
    assert any(i["swc-id"] == "106" for i in json.loads(out)["issues"])


def test_flag_solver_timeout_and_parallel(capsys):
    rc, out = run_cli(
        capsys, "analyze", "-c", KILLABLE, "-t", "1",
        "--max-steps", "32", "--lanes-per-contract", "4",
        "--limits-profile", "test", "--solver-timeout", "10000",
        "--parallel-solving",
        "-m", "AccidentallyKillable", "-o", "json",
    )
    assert rc == 0
    assert any(i["swc-id"] == "106" for i in json.loads(out)["issues"])


def test_flag_storage_conflict_errors(capsys):
    with pytest.raises(SystemExit) as ei:
        main(["analyze", "-c", KILLABLE, "--concrete-storage",
              "--unconstrained-storage"])
    assert ei.value.code == 2


def test_flag_unconstrained_storage(capsys):
    rc, out = run_cli(
        capsys, "analyze", "-c", KILLABLE, "-t", "1",
        "--max-steps", "32", "--lanes-per-contract", "4",
        "--limits-profile", "test", "--unconstrained-storage",
        "-m", "AccidentallyKillable", "-o", "json",
    )
    assert rc == 0
    assert any(i["swc-id"] == "106" for i in json.loads(out)["issues"])


def test_flag_call_depth_limit_reshapes_limits(capsys):
    # a different frame cap is a different compiled shape; keep it tiny
    rc, out = run_cli(
        capsys, "analyze", "-c", KILLABLE, "-t", "1",
        "--max-steps", "32", "--lanes-per-contract", "4",
        "--limits-profile", "test", "--call-depth-limit", "2",
        "-m", "AccidentallyKillable", "-o", "json",
    )
    assert rc == 0
    assert any(i["swc-id"] == "106" for i in json.loads(out)["issues"])


def test_flag_create_timeout_creation_still_completes():
    ctor = assemble("CALLER", 0, "SSTORE", 0, 0, "RETURN")
    runtime = assemble(0, "SLOAD", 1, "SSTORE", "STOP")
    contract = MythrilDisassembler.load_from_bytecode(
        runtime.hex(), creation_code=ctor.hex(), name="Owned")
    cfg = MythrilConfig(limits=TEST_LIMITS, spec=SymSpec(storage=False),
                        transaction_count=1, max_steps=128,
                        lanes_per_contract=4, create_timeout=300.0)
    analyzer = MythrilAnalyzer([contract], cfg)
    analyzer.fire_lasers()
    # a generous creation budget must not mark the run timed out
    assert analyzer.sym.timed_out is False
    assert len(analyzer.sym.tx_contexts) == 2


def test_statespace_json_dump(tmp_path, capsys):
    ss = tmp_path / "statespace.json"
    rc, _ = run_cli(
        capsys, "analyze", "-c", KILLABLE, "-t", "1",
        "--max-steps", "32", "--lanes-per-contract", "4",
        "--limits-profile", "test", "--statespace-json", str(ss),
        "-m", "AccidentallyKillable", "-o", "json",
    )
    assert rc == 0
    doc = json.loads(ss.read_text())
    assert doc["lanes"] == 4
    assert doc["transactions"] and doc["transactions"][0]["paths"]
    p0 = doc["transactions"][0]["paths"][0]
    assert {"contract", "pc", "depth", "halted", "branches"} <= set(p0)
    assert "instruction_coverage_pct" in doc


def test_concolic_trace_file_input(tmp_path, capsys):
    # reference trace-file mode (mythril/concolic/concrete_data.py ⚠unv):
    # code + seed come from the recorded trace's last step
    code = assemble(
        0, "CALLDATALOAD", ("ref", "set"), "JUMPI", "STOP",
        ("label", "set"), 1, 0, "SSTORE", "STOP",
    )
    trace = {
        "initialState": {
            "accounts": {
                "0x" + "ab" * 20: {"code": "0x" + code.hex(),
                                   "storage": {}, "balance": "0x0",
                                   "nonce": 0}
            }
        },
        "steps": [
            {"address": "0x" + "ab" * 20, "input": "0x" + "00" * 32,
             "value": "0x0", "origin": "0x" + "cd" * 20}
        ],
    }
    p = tmp_path / "trace.json"
    p.write_text(json.dumps(trace))
    rc, out = run_cli(capsys, "concolic", "--input", str(p),
                      "--max-steps", "64", "--limits-profile", "test")
    assert rc == 0
    flips = json.loads(out)
    assert len(flips) >= 1
    assert any(int(f["calldata"][2:66] or "0", 16) != 0 for f in flips)


def test_strategy_naive_random_accepted(capsys):
    rc, out = run_cli(
        capsys, "analyze", "-c", KILLABLE, "-t", "1",
        "--max-steps", "32", "--lanes-per-contract", "4",
        "--limits-profile", "test", "--strategy", "naive-random",
        "-m", "AccidentallyKillable", "-o", "json",
    )
    assert rc == 0
    assert any(i["swc-id"] == "106" for i in json.loads(out)["issues"])


def test_graph_html_output(tmp_path, capsys):
    # *.html -> self-contained interactive CFG page (no external
    # resources — verifiable offline); anything else stays DOT
    html_p = tmp_path / "cfg.html"
    dot_p = tmp_path / "cfg.dot"
    for p in (html_p, dot_p):
        rc, _ = run_cli(
            capsys, "analyze", "-c", KILLABLE, "-t", "1",
            "--max-steps", "32", "--lanes-per-contract", "4",
            "--limits-profile", "test", "--graph", str(p),
            "-m", "AccidentallyKillable", "-o", "json",
        )
        assert rc == 0
    html = html_p.read_text()
    assert html.startswith("<!DOCTYPE html>")
    assert '"nodes":' in html and "__DATA__" not in html
    assert "http" not in html.split("xmlns")[0]  # no external fetches
    assert dot_p.read_text().startswith("digraph")
