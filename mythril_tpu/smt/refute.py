"""Unsat proofs for host tapes.

The witness search (``smt/solver.py``) can only ever answer sat-or-
unknown; every `unknown` is a potential silent false negative. This
module proves the easy majority of genuinely-unsat queries — EVM path
conditions are dominated by dispatcher selector EQs and require()-style
comparisons over injective chains of one free leaf — by FORCED-VALUE
propagation:

- every constraint is reduced (through chains of injective ops: ADD,
  SUB, XOR, NOT, odd MUL, and the boolean EQ/ISZERO structure) to facts
  about a single free LEAF: ``leaf == v``, ``leaf != v``, or an interval
  bound when the leaf is compared bare;
- facts are merged per leaf; any contradiction (two different forced
  values, a forced value that is forbidden or out of bounds, an empty
  interval, or a closed constraint evaluating false) is an UNSAT proof.

Before that, the POLARITY check: one predicate asserted both true and
false. Predicates are compared by a normal form (:func:`_normal_form`),
not by node id, because a solc-shaped guard asserts an EQUIVALENT node
of the one a detection module asks about, not the same node:
``ISZERO(GT(x, s))`` on the path against ``LT(s, x)`` from the module.
Every rewrite is an identity over 256-bit words under ``smt/eval``'s
semantics (``tests/test_refute_normal_form.py`` checks each against
``evaluate``); ``docs/solver.md`` lists them.

This is the analog of the reference's unsat verdicts from Z3
(``laser/smt/solver`` ⚠unv, SURVEY §2.2) for the structural fragment;
anything it cannot decide stays with the randomized search.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Set, Tuple

from ..symbolic.ops import SymOp, FreeKind
from .eval import BY_NODE_KINDS, Assignment, M256, evaluate
from .tape import HostTape

_INJECTIVE = (int(SymOp.ADD), int(SymOp.SUB), int(SymOp.XOR),
              int(SymOp.NOT), int(SymOp.MUL))
_NULL, _CONST, _FREE, _ADD, _LT, _GT, _EQ, _ISZERO = (
    int(op) for op in (SymOp.NULL, SymOp.CONST, SymOp.FREE, SymOp.ADD,
                       SymOp.LT, SymOp.GT, SymOp.EQ, SymOp.ISZERO))

#: the normal form's rewrites, weakest first: a polarity conflict is
#: named after the strongest one either of its two constraints needed
#: (``polarity``: none, the two are one node or one structural term)
_REWRITES = ("polarity", "iszero", "gt_lt", "eq_commute", "add_carry")
_ISZERO_RW, _GT_LT_RW, _EQ_COMMUTE_RW, _ADD_CARRY_RW = 1, 2, 3, 4
#: every value of ``Refutation.rule`` (``solver_refute_total{rule}``)
RULES = _REWRITES + ("closed", "facts")


class Refutation(NamedTuple):
    """An unsat proof: the rule that found it and a readable reason."""

    rule: str
    reason: str


def _terms(tape: HostTape) -> Tuple[List[int], List[Tuple[int, int]]]:
    """Structural term of every node, and of every node's two operands.

    Two nodes share a term id exactly when their ``(op, a, b, imm)``
    cones are one term, whether or not they were interned to one node
    id. Leaves whose value is keyed by node id (``BY_NODE_KINDS``) are
    each their own variable and keep the id; every other leaf is its
    ``(kind, index)``, which is all ``_free_value`` reads. Id 0 and
    NULL slots are term 0 (they evaluate to zero); an operand outside
    SSA order gets a term nothing else has. Exact where ``smt/canon``'s
    digests are probabilistic, and one dict lookup a node where those
    cost two hashing passes: this runs on every query."""
    nodes = tape.nodes
    n = len(nodes)
    table: Dict[tuple, int] = {}
    term = [0] * n
    kids = [(0, 0)] * n
    for i in range(1, n):
        nd = nodes[i]
        op = nd.op
        if op == _NULL:
            continue
        if op == _CONST:
            key = (op, nd.imm & M256)
        elif op == _FREE:
            key = (op, nd.a, i if nd.a in BY_NODE_KINDS else nd.b)
        else:
            a, b = nd.a, nd.b
            kids[i] = (term[a] if 0 <= a < i else -i,
                       term[b] if 0 <= b < i else -i)
            key = (op, kids[i], nd.imm)
        term[i] = table.setdefault(key, len(table) + 1)
    return term, kids


def _normal_form(tape: HostTape, term, kids, node: int, sign: bool):
    """``(key, sign, rewrite)`` of the constraint ``bool(vals[node]) ==
    sign``: constraints with one key assert one predicate, so one key
    under both signs is unsatisfiable. The identities, for any words:

    - ``ISZERO(x)`` is truthy exactly when ``x`` is not (boolean or
      not), so ``(ISZERO(x), s)`` is ``(x, not s)``, while the root is
      ISZERO;
    - ``GT(a, b)`` is ``LT(b, a)``;
    - ``LT(ADD(a, b), a)`` and ``LT(ADD(a, b), b)`` are both the carry
      of ``a + b`` (the sum wrapped exactly when it is below either
      operand), in either operand order of the ADD;
    - ``EQ(a, b)`` is ``EQ(b, a)``: operands in term order;
    - anything else is its structural term."""
    nodes = tape.nodes
    rw = 0
    nd = nodes[node]
    while nd.op == _ISZERO and 0 < nd.a < node:
        node, sign, rw = nd.a, not sign, _ISZERO_RW
        nd = nodes[node]
    op = nd.op
    if op in (_LT, _GT):
        x, (tx, ty) = nd.a, kids[node]
        if op == _GT:
            x, tx, ty, rw = nd.b, ty, tx, _GT_LT_RW
        if 0 < x < node and nodes[x].op == _ADD and ty in kids[x]:
            return ("carry", *sorted(kids[x])), sign, _ADD_CARRY_RW
        return (_LT, tx, ty), sign, rw
    if op == _EQ:
        ta, tb = kids[node]
        if tb < ta:
            ta, tb, rw = tb, ta, _EQ_COMMUTE_RW
        return (op, ta, tb), sign, rw
    return term[node], sign, rw


def _free_reach(tape: HostTape):
    hf = [False] * len(tape.nodes)
    for i, nd in enumerate(tape.nodes):
        if i == 0 or nd.op == int(SymOp.NULL):
            continue
        if nd.op in (int(SymOp.FREE), int(SymOp.CD_SELECT)):
            # a select is free whatever its offset is, and opaque: no
            # rule reduces through it, so no fact is derived about it
            hf[i] = True
        elif nd.op != int(SymOp.CONST):
            hf[i] = (nd.a and nd.a < i and hf[nd.a]) or \
                    (nd.b and nd.b < i and hf[nd.b])
    return hf


def _reduce_to_leaf(tape, vals, hf, i: int, target: int
                    ) -> Optional[Tuple[int, int]]:
    """Solve f(leaf) == target where f is a chain of INJECTIVE ops with
    exactly one free side per node. Returns (leaf_node, forced_value) or
    None. Injectivity matters: the caller also uses the result negated
    (f(leaf) != target  <=>  leaf != forced_value)."""
    target &= M256
    while True:
        nd = tape.nodes[i]
        if nd.op == int(SymOp.FREE):
            return i, target
        a, b = nd.a, nd.b
        a_free = bool(a) and hf[a]
        b_free = bool(b) and hf[b]
        if a_free and b_free:
            return None
        av = vals[a] if a else 0
        bv = vals[b] if b else 0
        op = nd.op
        if op == int(SymOp.ADD):
            i, target = (a, target - bv) if a_free else (b, target - av)
        elif op == int(SymOp.SUB):
            i, target = (a, target + bv) if a_free else (b, av - target)
        elif op == int(SymOp.XOR):
            i, target = (a, target ^ bv) if a_free else (b, target ^ av)
        elif op == int(SymOp.NOT):
            i, target = a, target ^ M256
        elif op == int(SymOp.MUL):
            c, x = (bv, a) if a_free else (av, b)
            if not (c & 1):
                return None
            i, target = x, target * pow(c, -1, 1 << 256)
        else:
            return None
        target &= M256


class _Facts:
    """Per-leaf merged facts; raises _Conflict on contradiction."""

    def __init__(self):
        self.eq: Dict[int, int] = {}
        self.neq: Dict[int, Set[int]] = {}
        self.lo: Dict[int, int] = {}
        self.hi: Dict[int, int] = {}

    def force(self, leaf: int, v: int) -> bool:
        if leaf in self.eq and self.eq[leaf] != v:
            return False
        if v in self.neq.get(leaf, ()):
            return False
        if not (self.lo.get(leaf, 0) <= v <= self.hi.get(leaf, M256)):
            return False
        self.eq[leaf] = v
        return True

    def forbid(self, leaf: int, v: int) -> bool:
        if self.eq.get(leaf) == v:
            return False
        self.neq.setdefault(leaf, set()).add(v)
        return True

    def bound(self, leaf: int, lo: Optional[int] = None,
              hi: Optional[int] = None) -> bool:
        if lo is not None:
            self.lo[leaf] = max(self.lo.get(leaf, 0), lo)
        if hi is not None:
            self.hi[leaf] = min(self.hi.get(leaf, M256), hi)
        l, h = self.lo.get(leaf, 0), self.hi.get(leaf, M256)
        if l > h:
            return False
        if leaf in self.eq and not (l <= self.eq[leaf] <= h):
            return False
        # a pinched interval whose every value is forbidden is empty
        if h - l < 8 and all(v in self.neq.get(leaf, ())
                             for v in range(l, h + 1)):
            return False
        return True


def refute_tape(tape: HostTape) -> Optional[Refutation]:
    """Return the proof (rule and readable reason) if the tape's
    constraint set is PROVABLY unsatisfiable, else None (decide
    nothing)."""
    if not tape.constraints:
        return None
    # polarity conflict: one predicate, modulo its equivalent forms,
    # asserted both true and false
    n = len(tape.nodes)
    term, kids = _terms(tape)
    seen: Dict[object, Tuple[int, bool, int]] = {}
    for node, sign in tape.constraints:
        key, want, rw = ((("node", node), bool(sign), 0)
                         if not 0 < node < n else
                         _normal_form(tape, term, kids, node, bool(sign)))
        first, had, rw0 = seen.setdefault(key, (node, want, rw))
        if had != want:
            if first == node:
                return Refutation(
                    "polarity", f"node {node} asserted both true and false")
            rule = _REWRITES[max(rw, rw0)]
            return Refutation(
                rule, f"nodes {first} and {node} assert one predicate "
                      f"both true and false ({rule})")

    hf = _free_reach(tape)
    vals = evaluate(tape, Assignment())
    facts = _Facts()
    for node, sign in tape.constraints:
        if node <= 0 or node >= len(tape.nodes):
            continue
        if not hf[node]:
            # closed constraint: its value is assignment-independent
            if bool(vals[node]) != bool(sign):
                return Refutation(
                    "closed", f"closed constraint at node {node} is false")
            continue
        if not _apply(tape, vals, hf, facts, node, bool(sign)):
            return Refutation(
                "facts", f"conflicting facts at constraint node {node}")
    return None


def _apply(tape, vals, hf, facts: _Facts, i: int, want: bool) -> bool:
    """Derive leaf facts from `node i must evaluate truthy == want`.
    Returns False ONLY on a proven conflict (unknown structure -> True)."""
    nd = tape.nodes[i]
    op = nd.op
    # a bare free leaf used directly as a branch condition (its a / b
    # are a kind and an index, not operands: read no `hf` for them)
    if op == int(SymOp.FREE):
        return facts.forbid(i, 0) if want else facts.force(i, 0)
    a, b = nd.a, nd.b
    a_free = bool(a) and hf[a]
    b_free = bool(b) and hf[b]

    if op == int(SymOp.ISZERO):
        # ISZERO(a) truthy <=> a == 0
        red = _reduce_to_leaf(tape, vals, hf, a, 0)
        if red is None:
            return True
        leaf, v = red
        return facts.force(leaf, v) if want else facts.forbid(leaf, v)

    if op == int(SymOp.EQ):
        if a_free and b_free:
            return True
        free, const = (a, vals[b] if b else 0) if a_free else (b, vals[a] if a else 0)
        red = _reduce_to_leaf(tape, vals, hf, free, const)
        if red is None:
            return True
        leaf, v = red
        return facts.force(leaf, v) if want else facts.forbid(leaf, v)

    if op in (_LT, _GT):
        if a_free and b_free:
            return True
        # interval facts only for a BARE free leaf (arith chains wrap mod
        # 2^256, so monotone reasoning through them would be unsound)
        free, const = (a, vals[b] if b else 0) if a_free else (b, vals[a] if a else 0)
        if tape.nodes[free].op != int(SymOp.FREE):
            return True
        leaf_lt = (op == int(SymOp.LT)) == a_free  # "leaf < const" form?
        if leaf_lt and want:          # leaf < const
            if const == 0:
                return False
            return facts.bound(free, hi=const - 1)
        if leaf_lt and not want:      # leaf >= const
            return facts.bound(free, lo=const)
        if want:                      # leaf > const
            if const == M256:
                return False
            return facts.bound(free, lo=const + 1)
        return facts.bound(free, hi=const)  # leaf <= const

    # AND of two boolean-ish sides asserted true forces both sides
    if op == int(SymOp.AND) and want:
        ok = True
        if a_free:
            ok = ok and _apply(tape, vals, hf, facts, a, True)
        if b_free and ok:
            ok = ok and _apply(tape, vals, hf, facts, b, True)
        return ok

    return True
