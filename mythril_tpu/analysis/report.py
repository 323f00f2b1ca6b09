"""Issue + Report: the user-visible output of an analysis run.

Mirrors the reference's ``mythril/analysis/report.py`` (⚠unv): an
``Issue`` carries SWC id, severity, locations, and a concrete
transaction witness; ``Report`` renders text / markdown / json with the
same top-level shape so downstream tooling can switch over.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Dict, List, Optional

SWC_TITLES = {
    "101": "Integer Overflow and Underflow",
    "104": "Unchecked Call Return Value",
    "105": "Unprotected Ether Withdrawal",
    "106": "Unprotected SELFDESTRUCT Instruction",
    "107": "Reentrancy",
    "110": "Assert Violation",
    "111": "Use of Deprecated Solidity Functions",
    "112": "Delegatecall to Untrusted Callee",
    "113": "DoS with Failed Call",
    "114": "Transaction Order Dependence",
    "115": "Authorization through tx.origin",
    "116": "Block values as a proxy for time",
    "120": "Weak Sources of Randomness from Chain Attributes",
    "124": "Write to Arbitrary Storage Location",
    "127": "Arbitrary Jump with Function Type Variable",
}


@dataclass
class Issue:
    swc_id: str
    title: str
    severity: str              # High / Medium / Low
    address: int               # bytecode offset (pc)
    description: str
    contract: str = ""
    function: str = ""
    lane: int = -1             # frontier lane that witnessed the issue
    transaction_sequence: Optional[List[Dict]] = None
    # source mapping (filled when a solidity artifact provided srcmaps)
    filename: str = ""
    lineno: Optional[int] = None
    code_snippet: str = ""
    src_offset: Optional[int] = None   # byte offset into the source file
    src_length: Optional[int] = None

    def as_dict(self) -> Dict:
        return {
            "swc-id": self.swc_id,
            "swcTitle": SWC_TITLES.get(self.swc_id, ""),
            "title": self.title,
            "severity": self.severity,
            "address": self.address,
            "contract": self.contract,
            "function": self.function,
            "description": self.description,
            "filename": self.filename,
            "lineno": self.lineno,
            "code": self.code_snippet,
            "tx_sequence": self.transaction_sequence,
        }


@dataclass
class Report:
    issues: List[Issue] = field(default_factory=list)
    contract_name: str = ""
    # lost-coverage accounting from analysis.symbolic.coverage_summary —
    # lanes errored per cap, dropped forks, saturated event logs. Rendered
    # as warnings so silent-loss parity gaps are auditable.
    coverage: Optional[Dict] = None

    def append(self, issue: Issue) -> None:
        self.issues.append(issue)

    def sorted(self) -> List[Issue]:
        return sorted(self.issues, key=lambda i: (i.address, i.swc_id))

    def coverage_warnings(self) -> List[str]:
        cov = self.coverage or {}
        warn = []
        if cov.get("lanes_lost_to_caps"):
            from ..core.frontier import CAP_TRAPS, TRAP_NAMES

            cap_names = {TRAP_NAMES[c] for c in CAP_TRAPS}
            caps = {k: v for k, v in cov.get("lanes_errored", {}).items()
                    if k in cap_names}
            warn.append(
                f"{cov['lanes_lost_to_caps']} lane(s) lost to engine capacity "
                f"caps ({caps}); findings on those paths are missed."
            )
        if cov.get("dropped_forks"):
            warn.append(
                f"{cov['dropped_forks']} fork(s) dropped: frontier had no free "
                "lanes; unexplored branches exist."
            )
        if cov.get("saturated_call_logs"):
            warn.append(
                f"{cov['saturated_call_logs']} lane(s) saturated the external-"
                "call event log; later calls were not recorded."
            )
        if cov.get("saturated_arith_logs"):
            warn.append(
                f"{cov['saturated_arith_logs']} lane(s) saturated the arithmetic "
                "event log; later overflow candidates were not recorded."
            )
        lb = (cov.get("lanes_errored") or {}).get("loop_bound")
        if lb:
            warn.append(
                f"{lb} path(s) retired at the loop bound; loop iterations "
                "beyond --loop-bound were not explored."
            )
        if cov.get("deadline_expired_running"):
            warn.append(
                f"execution timeout hit with {cov['deadline_expired_running']} "
                "path(s) still running; coverage is partial."
            )
        solver = (cov.get("solver") or {}).get("total") or {}
        if solver.get("unknown"):
            by_mod = {name: s["unknown"]
                      for name, s in (cov["solver"].get("by_module") or {}).items()
                      if s.get("unknown")}
            warn.append(
                f"{solver['unknown']}/{solver['attempts']} solver queries "
                f"returned unknown ({by_mod}); candidate findings on those "
                "paths were dropped."
            )
        return warn

    def as_text(self) -> str:
        if not self.issues:
            base = "The analysis was completed successfully. No issues were detected.\n"
            warns = self.coverage_warnings()
            if warns:
                base += "".join(f"WARNING: {w}\n" for w in warns)
            return base
        out = []
        for w in self.coverage_warnings():
            out.append(f"WARNING: {w}")
        for i in self.sorted():
            out.append(f"==== {i.title} ====")
            out.append(f"SWC ID: {i.swc_id}")
            out.append(f"Severity: {i.severity}")
            out.append(f"Contract: {i.contract or 'Unknown'}")
            if i.function:
                out.append(f"Function name: {i.function}")
            out.append(f"PC address: {i.address}")
            if i.filename:
                loc = f"In file: {i.filename}"
                if i.lineno is not None:
                    loc += f":{i.lineno}"
                out.append(loc)
                if i.code_snippet:
                    out.append(f"  {i.code_snippet}")
            out.append(i.description.strip())
            if i.transaction_sequence:
                out.append("Transaction Sequence:")
                for tx in i.transaction_sequence:
                    out.append("  " + json.dumps(tx, sort_keys=True))
            out.append("")
        return "\n".join(out)

    def as_markdown(self) -> str:
        warns = "".join(f"> **Warning:** {w}\n" for w in self.coverage_warnings())
        if not self.issues:
            return "# Analysis results\n\n" + warns + "\nNo issues found.\n"
        out = ["# Analysis results\n"]
        if warns:
            out.append(warns)
        for i in self.sorted():
            out.append(f"## {i.title}")
            out.append(f"- SWC ID: {i.swc_id}")
            out.append(f"- Severity: {i.severity}")
            out.append(f"- PC address: {i.address}\n")
            out.append(i.description.strip() + "\n")
        return "\n".join(out)

    def as_json(self) -> str:
        return json.dumps(
            {
                "success": True,
                "error": None,
                "issues": [i.as_dict() for i in self.sorted()],
                "coverage": self.coverage,
            },
            sort_keys=True,
        )

    def as_jsonv2(self) -> str:
        """MythX-style report shape (reference: ``get_output_jsonv2`` in
        ``mythril/analysis/report.py`` ⚠unv): one entry per analyzed
        source, issues with head/tail descriptions and srcmap-style
        locations."""
        sources = sorted({i.filename or i.contract or "bytecode"
                          for i in self.issues}) or ["bytecode"]
        src_idx = {s: k for k, s in enumerate(sources)}
        issues = []
        for i in self.sorted():
            issues.append({
                "swcID": f"SWC-{i.swc_id}",
                "swcTitle": SWC_TITLES.get(i.swc_id, ""),
                "description": {"head": i.title,
                                "tail": i.description.strip()},
                "severity": i.severity,
                # real solc srcmap (offset:length:fileIdx) when the
                # artifact provided one; bytecode-offset fallback keeps
                # length 0 so consumers can't mistake a pc for a source
                # span
                "locations": [{
                    "sourceMap": (
                        f"{i.src_offset}:{i.src_length}:"
                        f"{src_idx.get(i.filename, 0)}"
                        if i.src_offset is not None
                        else f"{i.address}:0:"
                        f"{src_idx.get(i.filename or i.contract or 'bytecode', 0)}"
                    ),
                }],
                "extra": {
                    "contract": i.contract,
                    "function": i.function,
                    "testCases": i.transaction_sequence,
                },
            })
        return json.dumps([{
            "issues": issues,
            "sourceType": "raw-bytecode",
            "sourceFormat": "evm-byzantium-bytecode",
            "sourceList": sources,
            "meta": {"coverage": self.coverage},
        }], sort_keys=True)
