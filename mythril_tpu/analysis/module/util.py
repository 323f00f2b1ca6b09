"""Shared scan helpers for detection modules (reference:
``mythril/analysis/module/util.py`` ⚠unv holds the analogous
issue-plumbing helpers)."""

from __future__ import annotations

from typing import Iterator, List

from ...obs.device import fetch
from ...ops import u256


class CallEvent:
    __slots__ = ("idx", "op", "pc", "cid", "to_sym", "to", "value_sym", "value")

    def __init__(self, idx, op, pc, cid, to_sym, to, value_sym, value):
        self.idx, self.op, self.pc, self.cid = idx, op, pc, cid
        self.to_sym, self.to = to_sym, to
        self.value_sym, self.value = value_sym, value


class CallLog:
    """Host copy of the per-lane external-call records."""

    def __init__(self, sf):
        self.n = fetch(sf.n_calls, "n_calls")
        self.op = fetch(sf.call_op, "call_op")
        self.pc = fetch(sf.call_pc, "call_pc")
        self.cid = fetch(sf.call_cid, "call_cid")
        self.to_sym = fetch(sf.call_to_sym, "call_to_sym")
        self.to = fetch(sf.call_to, "call_to")
        self.value_sym = fetch(sf.call_value_sym, "call_value_sym")
        self.value = fetch(sf.call_value, "call_value")

    def lane(self, lane: int) -> Iterator[CallEvent]:
        for j in range(min(int(self.n[lane]), self.op.shape[1])):
            yield CallEvent(
                idx=j,
                op=int(self.op[lane, j]),
                pc=int(self.pc[lane, j]),
                cid=int(self.cid[lane, j]),
                to_sym=int(self.to_sym[lane, j]),
                to=u256.to_int(self.to[lane, j]),
                value_sym=int(self.value_sym[lane, j]),
                value=u256.to_int(self.value[lane, j]),
            )
