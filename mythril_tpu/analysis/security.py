"""fire_lasers: run every registered detection module over a finished
exploration and collect the report.

Reference: ``mythril/analysis/security.py`` (⚠unv) — POST modules run
over the final statespace, CALLBACK modules are drained; per-module
exceptions are caught so one module can't kill the run (SURVEY.md §5.3).
"""

from __future__ import annotations

import logging
from typing import List, Optional

from .module.loader import ModuleLoader
from .report import Issue, Report
from .symbolic import coverage_summary

log = logging.getLogger(__name__)


def fire_lasers(target, white_list: Optional[List[str]] = None,
                parallel: bool = False,
                workers: Optional[int] = None) -> Report:
    """`target` is an AnalysisContext or a SymExecWrapper; a wrapper's
    per-transaction context snapshots are all scanned (module issue caches
    dedup repeat findings across txs). Witness-search statistics are
    tallied per module (reference: ``SolverStatistics`` ⚠unv, SURVEY §5.1)
    and attached to the report's coverage block — the ``unknown`` column
    is the silently-dropped-findings channel.

    ``parallel`` (reference: ``--parallel-solving`` ⚠unv) runs the
    detection modules of each tx context concurrently in a thread pool:
    the witness search is host Python whose hot loop sits in the native C
    tape evaluator, so module-level threads overlap the GIL-released
    evaluator calls. ``workers`` caps that pool (the campaign's
    ``--solver-workers`` flag; default: min(8, #modules)). Per-module
    solver accounting is serial-only (the process-wide counter can't
    attribute interleaved deltas)."""
    from ..smt.solver import SOLVER_STATS

    contexts = getattr(target, "tx_contexts", None) or [target]
    report = Report()
    try:
        # a SymExecWrapper's richer summary (instruction coverage %) wins
        cov = getattr(target, "coverage", None)
        report.coverage = cov if isinstance(cov, dict) else coverage_summary(contexts)
    except Exception:  # noqa: BLE001 — accounting must not kill the run
        log.exception("coverage accounting failed")
    loader = ModuleLoader()
    loader.reset_modules()
    modules = loader.get_detection_modules(white_list)
    run_start = SOLVER_STATS.snapshot()
    by_module = {}

    def run_module(module, ctx):
        # consume incrementally: issues yielded BEFORE a module crashes
        # must survive the exception (a bare list() would discard them)
        out = []
        try:
            for issue in module.execute(ctx):
                out.append(issue)
        except Exception:  # noqa: BLE001 — degrade like the reference
            log.exception("detection module %s failed", module.name)
        return out

    for ctx in contexts:
        if parallel and len(modules) > 1:
            from concurrent.futures import ThreadPoolExecutor

            # copy the tape's leaves to the host serially: module threads
            # then only read them (lazy per-lane extraction under the GIL
            # is benign — duplicate work at worst, never a wrong tape)
            lanes = ctx.lanes(include_errors=True, include_reverted=True)
            if len(lanes):
                ctx.tape(int(lanes[0]))
            with ThreadPoolExecutor(
                    max_workers=min(workers or 8, len(modules))) as pool:
                for issues in pool.map(lambda m: run_module(m, ctx), modules):
                    for issue in issues:
                        report.append(issue)
            continue
        for module in modules:
            before = SOLVER_STATS.snapshot()
            issues = run_module(module, ctx)
            for issue in issues:
                report.append(issue)
            d = SOLVER_STATS.delta(before)
            if d["attempts"]:
                agg = by_module.setdefault(
                    module.name,
                    {"attempts": 0, "sat": 0, "unknown": 0, "time_sec": 0.0})
                for k in agg:
                    agg[k] = round(agg[k] + d[k], 3)
    if report.coverage is not None:
        report.coverage["solver"] = {
            "total": SOLVER_STATS.delta(run_start),
            "by_module": by_module,
        }
    _label_functions(report)
    return report


def _label_functions(report: Report) -> None:
    """Fill ``Issue.function`` from the witness selector via the local
    signature DB (reference: SignatureDB wiring in the disassembler
    ⚠unv); unknown selectors keep their hex form."""
    from ..utils.signatures import SignatureDB

    db = None
    for issue in report.issues:
        seq = issue.transaction_sequence
        if issue.function or not seq:
            continue
        inp = seq[-1].get("input", "")
        if len(inp) < 10:
            continue
        if db is None:
            db = SignatureDB()
        sigs = db.lookup(inp)
        issue.function = sigs[0] if sigs else f"0x{inp[2:10]}"
