"""CFG, signature DB, solidity artifact ingestion, concolic engine.

CFG/graph output, SignatureDB
(Issue.function), source maps, concolic (BASELINE config 5).
"""

import json

import numpy as np
import pytest

import mythril_tpu  # noqa: F401
from mythril_tpu.config import TEST_LIMITS
from mythril_tpu.disassembler.asm import assemble
from mythril_tpu.disassembler.cfg import CFG, JumpType
from mythril_tpu.utils.signatures import SignatureDB, selector_of
from mythril_tpu.solidity import (get_contracts_from_standard_json,
                                  parse_srcmap)

L = TEST_LIMITS


# --- CFG -----------------------------------------------------------------

BRANCHY = assemble(
    0, "CALLDATALOAD", ("ref", "a"), "JUMPI",
    1, 0, "SSTORE", "STOP",
    ("label", "a"), 2, 0, "SSTORE", "STOP",
)


def test_cfg_blocks_and_edges():
    cfg = CFG(BRANCHY)
    assert len(cfg.nodes) >= 3  # entry, fallthrough, jump target
    kinds = {e.jump_type for e in cfg.edges}
    assert JumpType.CONDITIONAL in kinds, "static JUMPI target resolved"
    assert JumpType.FALLTHROUGH in kinds
    entry = cfg.nodes[0]
    dests = {e.dst for e in cfg.edges if e.src == entry.uid}
    assert len(dests) == 2, "JUMPI block has two successors"


def test_cfg_dot_output_and_reached_overlay():
    cfg = CFG(BRANCHY)
    visited = np.zeros(L.max_code, dtype=bool)
    visited[0] = True
    cfg.mark_reached(visited)
    dot = cfg.as_dot("demo")
    assert dot.startswith('digraph "demo"')
    assert "->" in dot and "#c8e6c9" in dot  # one reached block colored


# --- Signature DB --------------------------------------------------------

def test_selector_matches_public_value():
    # the canonical ERC-20 transfer selector is public knowledge — this
    # also cross-checks the host keccak
    assert selector_of("transfer(address,uint256)") == "a9059cbb"


def test_signature_db_lookup_and_add(tmp_path):
    db = SignatureDB()
    assert db.lookup("a9059cbb") == ["transfer(address,uint256)"]
    assert db.lookup(bytes.fromhex("a9059cbb")) == ["transfer(address,uint256)"]
    sel = db.add("mySpecialFn(uint256)")
    assert db.lookup(sel) == ["mySpecialFn(uint256)"]
    p = str(tmp_path / "sigs.json")
    db.path = p
    db.save()
    db2 = SignatureDB(path=p)
    assert db2.lookup(sel) == ["mySpecialFn(uint256)"]


# --- Solidity artifact ---------------------------------------------------

def _fake_artifact():
    # PUSH1 1 / PUSH1 2 / ADD — 3 instructions, 3 srcmap entries
    runtime = "6001600202"  # keep it trivially disassemblable
    source = "line one\nline two\nline three\n"
    output = {
        "sources": {"Demo.sol": {"id": 0}},
        "contracts": {"Demo.sol": {"Demo": {"evm": {
            "bytecode": {"object": "60006000f3"},
            "deployedBytecode": {
                "object": runtime,
                # entries: offsets on lines 1, 2, 3
                "sourceMap": "0:4:0;9:4:0;18:5:0",
            },
        }}}},
    }
    inp = {"sources": {"Demo.sol": {"content": source}}}
    return output, inp


def test_artifact_ingestion_and_source_map(tmp_path):
    output, inp = _fake_artifact()
    out_p, in_p = str(tmp_path / "out.json"), str(tmp_path / "in.json")
    json.dump(output, open(out_p, "w"))
    json.dump(inp, open(in_p, "w"))
    contracts = get_contracts_from_standard_json(out_p, in_p)
    assert len(contracts) == 1
    c = contracts[0]
    assert c.name == "Demo" and c.creation_code is not None
    # pc 4 = ADD (third instruction) -> srcmap entry 2 -> line 3
    loc = c.source_location(4)
    assert loc["filename"] == "Demo.sol" and loc["lineno"] == 3
    # srcmap field inheritance
    entries = parse_srcmap("1:2:0;;:3")
    assert entries[1].offset == 1 and entries[1].length == 2
    assert entries[2].length == 3 and entries[2].offset == 1


def test_issue_gets_source_line(tmp_path):
    # end-to-end: artifact -> analyzer -> issue carries file:line
    from mythril_tpu.mythril import MythrilAnalyzer, MythrilConfig
    from mythril_tpu.solidity.soliditycontract import SolidityContract

    code = assemble(0, "SELFDESTRUCT")  # 3 instructions: PUSH1 0 / SELFDESTRUCT
    src = "contract Kill {\n  function die() { selfdestruct(0); }\n}\n"
    c = SolidityContract(
        name="Kill", code=code,
        srcmap=parse_srcmap("0:10:0;16:38:0"),
        sources={0: ("Kill.sol", src)},
    )
    cfg = MythrilConfig(limits=L, transaction_count=1, max_steps=64,
                        lanes_per_contract=4)
    report = MythrilAnalyzer([c], cfg).fire_lasers(
        modules=["AccidentallyKillable"])
    issues = [i for i in report.issues if i.swc_id == "106"]
    assert issues and issues[0].filename == "Kill.sol"
    assert issues[0].lineno == 2
    assert "selfdestruct" in issues[0].code_snippet


# --- Concolic ------------------------------------------------------------

def test_concolic_flips_branch():
    from mythril_tpu.concolic import concolic_execution

    # if (calldataload(0) == 5) sstore(0,1) else sstore(0,2)
    code = assemble(
        0, "CALLDATALOAD", 5, "EQ", ("ref", "eq"), "JUMPI",
        2, 0, "SSTORE", "STOP",
        ("label", "eq"), 1, 0, "SSTORE", "STOP",
    )
    seed = (0).to_bytes(32, "big")  # takes the != branch
    flips = concolic_execution(code, seed, limits=L, n_lanes=8, max_steps=64)
    assert flips, "at least the EQ branch must flip"
    flipped_words = {int.from_bytes(f.calldata[:32].ljust(32, b"\0"), "big")
                     for f in flips}
    assert 5 in flipped_words, "flip must produce the ==5 input"


# --- Search strategies ---------------------------------------------------

def test_fork_policies_agree_when_capacity_sufficient():
    from mythril_tpu.core import Corpus, make_env
    from mythril_tpu.disassembler import ContractImage
    from mythril_tpu.symbolic import SymSpec, make_sym_frontier, sym_run

    img = ContractImage.from_bytecode(BRANCHY, L.max_code)
    corpus = Corpus.from_images([img])

    def run(policy):
        active = np.zeros(8, dtype=bool)
        active[0] = True
        sf = make_sym_frontier(8, L, active=active)
        env = make_env(8)
        return sym_run(sf, env, corpus, SymSpec(), L, max_steps=64,
                       fork_policy=policy)

    outs = {p: run(p) for p in ("fifo", "shallow", "deep")}
    base = np.asarray(outs["fifo"].base.active)
    for p in ("shallow", "deep"):
        assert np.array_equal(np.asarray(outs[p].base.active), base), (
            f"{p}: with free slots for every fork the policies must agree")
        assert int(np.asarray(outs[p].dropped_total)) == 0


def test_jsonv2_carries_real_srcmap(tmp_path):
    # jsonv2 sourceMap must be the solc
    # offset:length:fileIdx, not a synthesized pc:1:idx
    from mythril_tpu.mythril import MythrilAnalyzer, MythrilConfig
    from mythril_tpu.solidity.soliditycontract import SolidityContract

    code = assemble(0, "SELFDESTRUCT")
    src = "contract Kill {\n  function die() { selfdestruct(0); }\n}\n"
    c = SolidityContract(
        name="Kill", code=code,
        srcmap=parse_srcmap("0:10:0;16:38:0"),
        sources={0: ("Kill.sol", src)},
    )
    cfg = MythrilConfig(limits=L, transaction_count=1, max_steps=64,
                        lanes_per_contract=4)
    report = MythrilAnalyzer([c], cfg).fire_lasers(
        modules=["AccidentallyKillable"])
    body = json.loads(report.as_jsonv2())[0]
    entry = [i for i in body["issues"] if i["swcID"] == "SWC-106"][0]
    sm = entry["locations"][0]["sourceMap"]
    off, length, fidx = (int(x) for x in sm.split(":"))
    assert (off, length) == (16, 38), sm           # the srcmap span
    assert body["sourceList"][fidx] == "Kill.sol"


# --- solc subprocess front door (round 4) --------------------------------


def test_solc_subprocess_compile(tmp_path):
    """Drive compile_solidity through a STUB solc that speaks the
    standard-JSON protocol (no real compiler in this image — the
    subprocess seam is what's under test; artifact ingestion past the
    seam is covered above)."""
    import sys as _sys

    from mythril_tpu.mythril.orchestration import MythrilDisassembler

    code = assemble(1, 0, "SSTORE", "STOP")
    sol = tmp_path / "c.sol"
    sol.write_text("contract C { uint x; }\n")
    stub = tmp_path / "solc"
    stub.write_text(
        f"#!{_sys.executable}\n"
        "import json, sys\n"
        "inp = json.load(sys.stdin)\n"
        "assert inp['language'] == 'Solidity'\n"
        "assert '--standard-json' in sys.argv\n"
        "name = list(inp['sources'])[0]\n"
        "out = {'sources': {name: {'id': 0}}, 'contracts': {name: {'C': {\n"
        "  'evm': {'bytecode': {'object': '%s'},\n"
        "          'deployedBytecode': {'object': '%s',\n"
        "                               'sourceMap': '0:10:0:-'}}}}}}\n"
        "json.dump(out, sys.stdout)\n" % (code.hex(), code.hex())
    )
    stub.chmod(0o755)

    cs = MythrilDisassembler.load_from_solidity(str(sol), solc_path=str(stub))
    assert len(cs) == 1 and cs[0].name == "C"
    assert cs[0].code == code and cs[0].creation_code == code
    loc = cs[0].source_location(0)
    assert loc and loc["lineno"] == 1 and loc["filename"] == str(sol)


def test_solc_missing_raises_clear_error(tmp_path):
    from mythril_tpu.solidity.soliditycontract import SolcNotFound
    from mythril_tpu.mythril.orchestration import MythrilDisassembler

    sol = tmp_path / "c.sol"
    sol.write_text("contract C {}\n")
    with pytest.raises(SolcNotFound, match="standard-JSON"):
        MythrilDisassembler.load_from_solidity(
            str(sol), solc_path=str(tmp_path / "definitely-not-solc"))


def test_solc_compile_error_surfaces(tmp_path):
    import sys as _sys

    from mythril_tpu.solidity.soliditycontract import SolcError, compile_solidity

    sol = tmp_path / "bad.sol"
    sol.write_text("contract {\n")
    stub = tmp_path / "solc"
    stub.write_text(
        f"#!{_sys.executable}\n"
        "import json, sys\n"
        "json.load(sys.stdin)\n"
        "json.dump({'errors': [{'severity': 'error',\n"
        "  'formattedMessage': 'ParserError: expected identifier'}]},\n"
        "  sys.stdout)\n"
    )
    stub.chmod(0o755)
    with pytest.raises(SolcError, match="ParserError"):
        compile_solidity([str(sol)], solc_path=str(stub))


def test_annotation_space_propagation():
    """Annotation channel (reference: laser/smt annotations riding every
    operation): tags reach derived nodes and keccak chains, not
    independent subtrees; annotate invalidates the memo."""
    from mythril_tpu.smt.tape import AnnotationSpace, HostNode, HostTape
    from mythril_tpu.symbolic.ops import FreeKind, SymOp

    N = lambda op, a=0, b=0, imm=0: HostNode(int(op), a, b, imm)
    nodes = [
        N(SymOp.NULL),
        N(SymOp.FREE, int(FreeKind.CALLDATA_WORD), 0),  # 1
        N(SymOp.CONST, imm=7),                          # 2
        N(SymOp.ADD, 1, 2),                             # 3
        N(SymOp.AND, 3, 2),                             # 4: derived from 3
        N(SymOp.MUL, 2, 2),                             # 5: independent
        N(SymOp.KECCAK_SEED, imm=32),                   # 6
        N(SymOp.KECCAK_ABS, 6, 4),                      # 7: absorbs node 4
        N(SymOp.KECCAK, 7),                             # 8: digest
    ]
    t = HostTape(nodes=nodes, constraints=[])
    sp = AnnotationSpace(t)
    sp.annotate(3, "wrap")
    assert "wrap" in sp.annotations(3)
    assert "wrap" in sp.annotations(4)
    assert "wrap" in sp.annotations(8)      # through the keccak chain
    assert "wrap" not in sp.annotations(5)
    assert sp.any_sink([8], "wrap") and not sp.any_sink([5], "wrap")
    sp.annotate(5, "other")
    assert "other" in sp.annotations(5)
