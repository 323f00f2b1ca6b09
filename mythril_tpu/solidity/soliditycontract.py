"""Solidity frontend: solc subprocess + standard-JSON ingestion + srcmaps.

Reference: ``mythril/solidity/soliditycontract.py`` (⚠unv, SURVEY.md §2
row "Solidity frontend") shells out to solc. Two paths here:

- :func:`compile_solidity` runs ``solc --standard-json`` when a compiler
  is on PATH (gated — this image carries none; the subprocess protocol
  is stub-tested);
- :func:`get_contracts_from_standard_json` consumes solc's OUTPUT
  artifact (``evm.deployedBytecode.object`` + ``sourceMap``) — the same
  data, one process boundary earlier, for hermetic environments.

Issues then map to source lines, which the reference's golden reports
include.

Source-map format (solc docs, public spec): ``s:l:f:j:m`` entries
separated by ``;``, empty fields inheriting the previous entry; one entry
per INSTRUCTION of the deployed code.
"""

from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple, Union

from ..disassembler.disassembly import _to_bytes, disassemble


@dataclass(frozen=True)
class SourceMapEntry:
    offset: int      # byte offset into the source file
    length: int
    file_idx: int    # -1 = compiler-generated
    jump: str        # 'i' / 'o' / '-'


def parse_srcmap(srcmap: str) -> List[SourceMapEntry]:
    out: List[SourceMapEntry] = []
    prev = [0, 0, 0, "-"]
    if not srcmap:
        return out
    for entry in srcmap.split(";"):
        fields = entry.split(":")
        for i in range(4):
            if i < len(fields) and fields[i] != "":
                prev[i] = fields[i] if i == 3 else int(fields[i])
        out.append(SourceMapEntry(int(prev[0]), int(prev[1]),
                                  int(prev[2]), str(prev[3])))
    return out


@dataclass
class SolidityContract:
    """Quacks like ``EVMContract`` (code/creation_code/name) plus source
    mapping, so ``MythrilAnalyzer`` takes it directly."""

    name: str
    code: bytes
    creation_code: Optional[bytes] = None
    srcmap: List[SourceMapEntry] = field(default_factory=list)
    # file_idx -> (filename, content-or-None)
    sources: Dict[int, Tuple[str, Optional[str]]] = field(default_factory=dict)
    _pc_to_instr: Optional[Dict[int, int]] = field(default=None, repr=False)

    def __post_init__(self):
        self._pc_to_instr = {
            ins.address: i for i, ins in enumerate(disassemble(self.code))
        }

    def get_easm(self) -> str:
        from ..disassembler.disassembly import Disassembly

        return Disassembly(self.code).get_easm()

    def source_location(self, pc: int) -> Optional[Dict]:
        """{'filename', 'offset', 'length', 'lineno', 'snippet'} for a
        deployed-code pc, or None when unmapped."""
        idx = self._pc_to_instr.get(pc)
        if idx is None or idx >= len(self.srcmap):
            return None
        e = self.srcmap[idx]
        if e.file_idx < 0 or e.file_idx not in self.sources:
            return None
        filename, content = self.sources[e.file_idx]
        loc = {"filename": filename, "offset": e.offset, "length": e.length,
               "lineno": None, "snippet": None}
        if content is not None and e.offset <= len(content):
            loc["lineno"] = content.count("\n", 0, e.offset) + 1
            snippet = content[e.offset: e.offset + e.length]
            loc["snippet"] = re.sub(r"\s+", " ", snippet)[:120]
        return loc


def make_standard_json_input(sources: Dict[str, str]) -> dict:
    """Compiler INPUT document for ``{path: source_text}`` requesting the
    artifacts the frontend consumes (deployed/creation bytecode + srcmaps)."""
    return {
        "language": "Solidity",
        "sources": {name: {"content": text} for name, text in sources.items()},
        "settings": {
            "outputSelection": {
                "*": {"*": ["evm.bytecode.object",
                            "evm.deployedBytecode.object",
                            "evm.deployedBytecode.sourceMap"]}
            }
        },
    }


def compile_solidity(paths: List[str],
                     solc_path: Optional[str] = None,
                     timeout: float = 120.0) -> List[SolidityContract]:
    """Shell out to ``solc --standard-json`` and ingest the result.

    Reference: ``SolidityContract`` invoking solc as a subprocess
    (``mythril/solidity/soliditycontract.py`` + ``ethereum/util.py``
    ⚠unv, SURVEY.md §3.1 "PROCESS BOUNDARY"). This image carries no solc
    binary, so the path is GATED: a missing compiler raises a clear
    ``SolcNotFound`` naming the artifact-ingestion alternative, and tests
    drive the subprocess protocol with a stub solc (same standard-JSON
    contract either way)."""
    import shutil
    import subprocess

    solc = solc_path or os.environ.get("MYTHRIL_SOLC", "solc")
    if shutil.which(solc) is None:
        raise SolcNotFound(
            f"solc binary {solc!r} not found on PATH; compile offline and "
            "load the standard-JSON artifact instead "
            "(get_contracts_from_standard_json)")
    sources = {}
    for p in paths:
        with open(p) as fh:
            sources[p] = fh.read()
    inp = make_standard_json_input(sources)
    try:
        r = subprocess.run([solc, "--standard-json"],
                           input=json.dumps(inp), capture_output=True,
                           text=True, timeout=timeout)
    except subprocess.TimeoutExpired as e:
        raise SolcError(f"solc timed out after {timeout:.0f}s") from e
    if r.returncode != 0:
        raise SolcError(f"solc exited {r.returncode}: {r.stderr[:500]}")
    try:
        out = json.loads(r.stdout)
    except json.JSONDecodeError as e:
        raise SolcError(f"solc emitted invalid JSON: {e}") from e
    errors = [e for e in out.get("errors", [])
              if e.get("severity") == "error"]
    if errors:
        raise SolcError("; ".join(
            e.get("formattedMessage", e.get("message", "?"))[:200]
            for e in errors[:5]))
    return get_contracts_from_standard_json(out, inp)


class SolcNotFound(RuntimeError):
    """No solc on PATH (expected in hermetic images — use artifacts)."""


class SolcError(RuntimeError):
    """solc ran but failed (compile errors, bad output)."""


def get_contracts_from_standard_json(
    artifact: Union[str, dict],
    input_json: Union[str, dict, None] = None,
) -> List[SolidityContract]:
    """Load every contract with deployed bytecode from a solc standard-
    JSON OUTPUT (path or dict). ``input_json`` (the compiler INPUT, which
    holds the source text) enables line numbers; without it locations are
    byte offsets only. Also accepts combined files that carry both under
    ``{"input": ..., "output": ...}``."""
    def load(x):
        if isinstance(x, str):
            with open(x) as fh:
                return json.load(fh)
        return x

    doc = load(artifact)
    if "output" in doc and "contracts" in doc.get("output", {}):
        input_json = input_json or doc.get("input")
        doc = doc["output"]
    inp = load(input_json) if input_json else {}

    # file name -> source index (output "sources" carries ids)
    ids = {name: meta.get("id", i)
           for i, (name, meta) in enumerate(doc.get("sources", {}).items())}
    contents = {name: src.get("content")
                for name, src in inp.get("sources", {}).items()}
    sources = {idx: (name, contents.get(name)) for name, idx in ids.items()}

    out: List[SolidityContract] = []
    for file_name, contracts in doc.get("contracts", {}).items():
        for cname, cdata in contracts.items():
            evm = cdata.get("evm", {})
            deployed = evm.get("deployedBytecode", {}) or {}
            runtime_hex = deployed.get("object") or ""
            if not runtime_hex:
                continue
            creation_hex = (evm.get("bytecode", {}) or {}).get("object")
            out.append(SolidityContract(
                name=cname,
                code=_to_bytes(runtime_hex),
                creation_code=_to_bytes(creation_hex) if creation_hex else None,
                srcmap=parse_srcmap(deployed.get("sourceMap", "")),
                sources=sources,
            ))
    return out
