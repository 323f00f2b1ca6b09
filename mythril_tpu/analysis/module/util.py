"""Shared scan helpers for detection modules (reference:
``mythril/analysis/module/util.py`` ⚠unv holds the analogous
issue-plumbing helpers)."""

from __future__ import annotations

from typing import Iterator, List

from ...ops import u256


class CallEvent:
    __slots__ = ("idx", "op", "pc", "cid", "to_sym", "to", "value_sym", "value")

    def __init__(self, idx, op, pc, cid, to_sym, to, value_sym, value):
        self.idx, self.op, self.pc, self.cid = idx, op, pc, cid
        self.to_sym, self.to = to_sym, to
        self.value_sym, self.value = value_sym, value


class CallLog:
    """Host copy of the per-lane external-call records."""

    def __init__(self, ctx):
        self.n = ctx.host("n_calls")
        self.op = ctx.host("call_op")
        self.pc = ctx.host("call_pc")
        self.cid = ctx.host("call_cid")
        self.to_sym = ctx.host("call_to_sym")
        self.to = ctx.host("call_to")
        self.value_sym = ctx.host("call_value_sym")
        self.value = ctx.host("call_value")

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
