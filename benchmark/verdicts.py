"""The comparison that decides ``correct``.

The reference is the corpus generator's construction: each contract
carries the SWC ids that must be reported for it (the flaw is written
into the code) and those that must not (the guard is written into it).
A contract is *wrong* when a ``must_report`` id is missing, a
``must_not_report`` id is present, or it has no answer at all. Ids that
the configuration's detection modules cannot report (``swc_in_scope``)
are left out of both lists.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional

#: ``backend_events`` kinds that mean a run did not stay on the chip
BAD_EVENTS = {"cpu_fallback", "tier_fallback", "degrade", "breaker_open",
              "worker_death", "worker_breaker_pinned"}


def compare(contracts: Iterable[dict], reported: Dict[str, set],
            swc_in_scope: Optional[List[str]] = None) -> List[dict]:
    """One row per contract that is due: ``name``, ``kind``, ``answered``,
    ``missing`` (must_report ids not reported), ``extra``
    (must_not_report ids reported)."""
    scope = None if swc_in_scope is None else set(swc_in_scope)
    rows = []
    for c in contracts:
        got = reported.get(c["name"])
        must = [s for s in c["must_report"] if scope is None or s in scope]
        must_not = [s for s in c["must_not_report"]
                    if scope is None or s in scope]
        rows.append({
            "name": c["name"], "kind": c["kind"],
            "answered": got is not None,
            "missing": sorted(s for s in must if s not in (got or ())),
            "extra": sorted(s for s in must_not if s in (got or ())),
            "labels": len(must) + len(must_not),
        })
    return rows


def wrong(row: dict) -> bool:
    return (not row["answered"]) or bool(row["missing"] or row["extra"])


def summary_lines(rows: List[dict]) -> List[str]:
    """Per kind: each number compared beside its limit (every limit of
    this comparison is exact: 0 wrong answers)."""
    kinds: Dict[str, List[dict]] = {}
    for r in rows:
        kinds.setdefault(r["kind"], []).append(r)
    out = []
    for kind in sorted(kinds):
        rs = kinds[kind]
        out.append(
            f"check verdicts kind={kind} contracts={len(rs)} "
            f"labels={sum(r['labels'] for r in rs)} "
            f"unanswered={sum(not r['answered'] for r in rs)} (limit 0) "
            f"must_report_missing={sum(len(r['missing']) for r in rs)} "
            f"(limit 0) must_not_report_present="
            f"{sum(len(r['extra']) for r in rs)} (limit 0)")
    return out
