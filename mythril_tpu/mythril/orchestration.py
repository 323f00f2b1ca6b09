"""Config + loading + analysis front door.

Reference: ``mythril/mythril/{mythril_config,mythril_disassembler,
mythril_analyzer}.py`` (⚠unv, SURVEY.md §2 rows "Orchestration" /
"EVMContract"). No RPC and no solc in this environment: contracts load
from hex strings / files (runtime and optional creation bytecode — the
pieces a solc standard-JSON artifact provides).
"""

from __future__ import annotations

import dataclasses
import re
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

from ..analysis import Report, SymExecWrapper, fire_lasers
from ..config import DEFAULT_LIMITS, LimitsConfig
from ..disassembler.disassembly import Disassembly, _to_bytes
from ..symbolic import SymSpec


@dataclass
class MythrilConfig:
    """Analysis-wide knobs (reference: ``MythrilConfig`` ini + the
    ``support_args`` flag singleton ⚠unv — collapsed into one explicit
    dataclass; no hidden globals)."""

    # factories, not bare instances: both defaults are frozen today, but a
    # shared class-level default would silently alias any future mutable
    # field across configs. `replace` makes a real
    # copy — a lambda returning the singleton would still alias.
    limits: LimitsConfig = field(
        default_factory=lambda: dataclasses.replace(DEFAULT_LIMITS))
    spec: SymSpec = field(default_factory=SymSpec)
    transaction_count: int = 2
    max_steps: int = 512
    lanes_per_contract: int = 64
    solver_iters: int = 400
    solver_timeout: Optional[float] = None  # seconds per solver query
    loop_bound: Optional[int] = None      # None = limits.loop_bound
    execution_timeout: Optional[float] = None  # seconds; None = unbounded
    create_timeout: Optional[float] = None  # seconds for the creation tx
    parallel_solving: bool = False        # detection modules in a thread pool
    strategy: str = "bfs"                 # bfs | dfs (fork-admission policy)
    enable_iprof: bool = False            # per-opcode instruction profiler
    plugins: tuple = ()                   # LaserPlugin instances (e.g. from
    # outer discovery, plugin/discovery.py)
    dyn_loader: object = None             # utils.loader.DynLoader: enables
    # MID-EXECUTION dynamic loading — tx N's concrete-but-unknown call
    # targets are fetched at the tx seam and resolve in tx N+1
    # (reference: DynLoader.dynld on CALL ⚠unv, SURVEY §3.4)
    dynld_limit: int = 4                  # per-run mid-execution loads

    def resolved_limits(self) -> LimitsConfig:
        if self.loop_bound is None:
            return self.limits
        return dataclasses.replace(self.limits, loop_bound=self.loop_bound)


@dataclass
class EVMContract:
    """Runtime (+ optional creation) bytecode for one contract
    (reference: ``mythril/ethereum/evmcontract.py`` ⚠unv)."""

    code: bytes
    creation_code: Optional[bytes] = None
    name: str = "MAIN"
    #: on-chain address (``analyze -a`` / dynld prefetch): when set, the
    #: frontier account table registers THIS address for the contract so
    #: hardcoded cross-contract calls resolve against the real chain
    #: layout instead of the synthetic contract_address(i) defaults
    address: Optional[int] = None
    _disassembly: Optional[Disassembly] = field(default=None, repr=False)

    @property
    def disassembly(self) -> Disassembly:
        if self._disassembly is None:
            self._disassembly = Disassembly(self.code)
        return self._disassembly

    def get_easm(self) -> str:
        return self.disassembly.get_easm()


class MythrilDisassembler:
    """Loading front door (reference: ``MythrilDisassembler`` ⚠unv).
    ``load_from_solidity`` shells out to solc when one is on PATH
    (``MYTHRIL_SOLC`` overrides the binary); hermetic images without a
    compiler load solc OUTPUT artifacts via standard-JSON ingestion or
    raw bytecode via :meth:`load_from_bytecode`."""

    @staticmethod
    def load_from_bytecode(code, creation_code=None,
                           name: str = "MAIN") -> EVMContract:
        return EVMContract(
            code=_to_bytes(code),
            creation_code=_to_bytes(creation_code) if creation_code else None,
            name=name,
        )

    @staticmethod
    def load_from_solidity(paths, solc_path=None):
        """Compile ``.sol`` files with solc --standard-json and return
        ``SolidityContract``s (source-mapped). Reference: SURVEY §3.1's
        process boundary; raises ``SolcNotFound`` without a compiler."""
        from ..solidity.soliditycontract import compile_solidity

        if isinstance(paths, str):
            paths = [paths]
        return compile_solidity(list(paths), solc_path=solc_path)

    @staticmethod
    def load_from_file(path: str, creation_path: Optional[str] = None,
                       name: Optional[str] = None) -> EVMContract:
        def read(p: str) -> bytes:
            with open(p) as fh:
                return _to_bytes(fh.read())

        return EVMContract(
            code=read(path),
            creation_code=read(creation_path) if creation_path else None,
            name=name or path.rsplit("/", 1)[-1],
        )


class MythrilAnalyzer:
    """Analysis driver (reference: ``MythrilAnalyzer.fire_lasers`` ⚠unv)."""

    def __init__(self, contracts: Sequence[EVMContract],
                 config: Optional[MythrilConfig] = None):
        self.contracts = list(contracts)
        self.config = config or MythrilConfig()
        self.sym: Optional[SymExecWrapper] = None

    def fire_lasers(self, modules: Optional[List[str]] = None) -> Report:
        cfg = self.config
        creation = [c.creation_code for c in self.contracts]
        with_creation = any(c is not None for c in creation)
        if with_creation:
            # contracts without creation code deploy via an empty-effect
            # constructor (immediate RETURN) so the batch stays uniform
            creation = [c if c is not None else b"\x00" for c in creation]
        # getattr, not attribute access: SolidityContract duck-types
        # code/creation_code/name only and carries no address field
        addrs = None
        if any(getattr(c, "address", None) is not None
               for c in self.contracts):
            from ..core.frontier import contract_address

            addrs = [getattr(c, "address", None)
                     if getattr(c, "address", None) is not None
                     else contract_address(i)
                     for i, c in enumerate(self.contracts)]
        self.sym = SymExecWrapper(
            [c.code for c in self.contracts],
            contract_names=[c.name for c in self.contracts],
            contract_addrs=addrs,
            limits=cfg.resolved_limits(),
            spec=cfg.spec,
            lanes_per_contract=cfg.lanes_per_contract,
            max_steps=cfg.max_steps,
            solver_iters=cfg.solver_iters,
            solver_timeout=cfg.solver_timeout,
            transaction_count=cfg.transaction_count,
            creation_bytecodes=creation if with_creation else None,
            execution_timeout=cfg.execution_timeout,
            create_timeout=cfg.create_timeout,
            strategy=cfg.strategy,
            enable_iprof=cfg.enable_iprof,
            plugins=cfg.plugins,
            dyn_loader=cfg.dyn_loader,
            dynld_limit=cfg.dynld_limit,
        )
        report = fire_lasers(self.sym, white_list=modules,
                             parallel=cfg.parallel_solving)
        if self.contracts:
            report.contract_name = self.contracts[0].name
        self._attach_source_locations(report)
        return report

    def _attach_source_locations(self, report: Report) -> None:
        """Map issue pcs to source lines for contracts that carry srcmaps
        (SolidityContract quacks like EVMContract plus source_location)."""
        by_name = {c.name: c for c in self.contracts}
        for issue in report.issues:
            name = issue.contract.removesuffix(" (constructor)")
            c = by_name.get(name)
            locate = getattr(c, "source_location", None)
            if locate is None or issue.contract.endswith(" (constructor)"):
                continue  # creation-code srcmaps not tracked (runtime only)
            loc = locate(issue.address)
            if loc:
                issue.filename = loc["filename"]
                issue.lineno = loc["lineno"]
                issue.code_snippet = loc.get("snippet") or ""
                issue.src_offset = loc["offset"]
                issue.src_length = loc["length"]
