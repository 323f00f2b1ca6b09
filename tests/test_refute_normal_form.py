"""The refuter's polarity check compares predicates by a normal form
(``smt/refute.py`` ``_normal_form``, docs/solver.md "What refute
proves").

Three contracts:

- every rewrite is an identity under ``smt.eval.evaluate``: all the
  forms the normal form sends to one key evaluate to one predicate, on
  boundary and seeded random operands, under both signs;
- near misses are NOT refuted and reach the search with the verdict the
  parent commit gave them;
- the tapes the search used to give up on (one per shape and corpus,
  dumped from the parent with ``MYTHRIL_DUMP_UNKNOWN``) are proven
  unsat by the refute stage, under the rule named, with no search, no
  write to the durable store, and the LRU key they always had.
"""

import glob
import importlib.util
import itertools
import json
import os
import random

import pytest

import mythril_tpu  # noqa: F401
from mythril_tpu.obs import metrics as obs_metrics
from mythril_tpu.smt import portfolio
from mythril_tpu.smt.canon import canonical_digest
from mythril_tpu.smt.eval import Assignment, evaluate
from mythril_tpu.smt.refute import _normal_form, _terms, refute_tape
from mythril_tpu.smt.solver import _SOLVE_CACHE, solve_tape_ex
from mythril_tpu.smt.tape import HostNode, HostTape
from mythril_tpu.smt.vstore import VerdictStore
from mythril_tpu.symbolic.ops import FreeKind, SymOp

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "refute_unknown")
M256 = (1 << 256) - 1
BOUNDARY = (0, 1, 2, (1 << 128), (1 << 255) - 1, 1 << 255, (1 << 255) + 1,
            M256 - 1, M256)


@pytest.fixture(autouse=True)
def _isolated_portfolio():
    _SOLVE_CACHE.clear()
    prev = portfolio.set_store(None)
    yield
    portfolio.set_store(prev)
    _SOLVE_CACHE.clear()


class Build:
    """A tape over three calldata words ``a``, ``b``, ``c`` that
    appends a node for every expression asked of it (no interning: two
    requests for one expression are two node ids, as a guard and a
    module's predicate are)."""

    def __init__(self):
        self.nodes = [HostNode(int(SymOp.NULL), 0, 0, 0)]
        self.a, self.b, self.c = (
            self.op(SymOp.FREE, int(FreeKind.CALLDATA_WORD), 32 * k)
            for k in range(3))

    def op(self, op, a=0, b=0, imm=0) -> int:
        self.nodes.append(HostNode(int(op), a, b, imm))
        return len(self.nodes) - 1

    def lt(self, x, y): return self.op(SymOp.LT, x, y)      # noqa: E704
    def gt(self, x, y): return self.op(SymOp.GT, x, y)      # noqa: E704
    def eq(self, x, y): return self.op(SymOp.EQ, x, y)      # noqa: E704
    def add(self, x, y): return self.op(SymOp.ADD, x, y)    # noqa: E704
    def sub(self, x, y): return self.op(SymOp.SUB, x, y)    # noqa: E704
    def isz(self, x): return self.op(SymOp.ISZERO, x)       # noqa: E704

    def tape(self, constraints=()) -> HostTape:
        return HostTape(nodes=self.nodes, constraints=list(constraints))


def _operands():
    rng = random.Random(35)
    pairs = list(itertools.product(BOUNDARY, repeat=2))
    for _ in range(200):
        bits = rng.choice((8, 64, 128, 255, 256))
        x = rng.getrandbits(bits)
        # near pairs hit the carry / borrow / equality edges
        y = rng.choice((rng.getrandbits(bits), x, (x + 1) & M256,
                        (M256 - x) & M256, (M256 - x + 1) & M256))
        pairs.append((x, y))
    return pairs


def _forms_iszero(t):
    x = t.add(t.a, t.b)           # any word, not a boolean
    return [x, t.isz(t.isz(x)), t.isz(t.isz(t.isz(t.isz(x))))], \
        [t.isz(x), t.isz(t.isz(t.isz(x)))]


def _forms_gt_lt(t):
    return [t.gt(t.a, t.b), t.lt(t.b, t.a), t.isz(t.isz(t.gt(t.a, t.b)))], \
        [t.isz(t.gt(t.a, t.b)), t.isz(t.lt(t.b, t.a))]


def _forms_eq_commute(t):
    d = t.op(SymOp.DIV, t.op(SymOp.MUL, t.a, t.b), t.b)
    return [t.eq(d, t.a), t.eq(t.a, d)], [t.isz(t.eq(t.a, d))]


def _forms_add_carry(t):
    s, r = t.add(t.a, t.b), t.add(t.b, t.a)
    return ([t.lt(s, t.a), t.lt(s, t.b), t.lt(r, t.a), t.lt(r, t.b),
             t.gt(t.a, s), t.gt(t.b, r)],
            [t.isz(t.lt(s, t.b)), t.isz(t.gt(t.a, r))])


def _forms_polarity(t):
    # one structural term at two node ids, no rewrite between them
    return [t.op(SymOp.SLT, t.a, t.b), t.op(SymOp.SLT, t.a, t.b)], []


@pytest.mark.parametrize("rule,forms", [
    ("polarity", _forms_polarity), ("iszero", _forms_iszero),
    ("gt_lt", _forms_gt_lt), ("eq_commute", _forms_eq_commute),
    ("add_carry", _forms_add_carry)])
def test_rule_is_an_identity_under_the_evaluator(rule, forms):
    """``same`` all assert the predicate, ``negated`` its negation: the
    normal form must send them to ONE key (so the rule is exercised),
    and on every operand pair and under both signs ``evaluate`` must
    agree that they are one predicate. Then the first form against
    each other one, with the sign that contradicts, is refuted under
    this rule."""
    t = Build()
    same, negated = forms(t)
    tape = t.tape()
    term, kids = _terms(tape)
    members = []                   # (node, sign, normal-form sign)
    keys = set()
    for node in same + negated:
        for sign in (True, False):
            key, want, _ = _normal_form(tape, term, kids, node, sign)
            keys.add(key)
            members.append((node, sign, want))
            # the form's own polarity is what the test says it is
            assert (want == sign) == (node in same), (node, sign)
    assert len(keys) == 1, keys
    for x, y in _operands():
        asn = Assignment()
        asn.write_calldata_word(0, x)
        asn.write_calldata_word(32, y)
        vals = evaluate(tape, asn)
        truth = {(bool(vals[n]) == s) == w for n, s, w in members}
        assert len(truth) == 1, (rule, hex(x), hex(y))
    first = same[0]
    for other in same[1:] + negated:
        clash = other in same      # same predicate: opposite signs clash
        proof = refute_tape(t.tape([(first, True), (other, not clash)]))
        assert proof is not None and proof.rule == rule, (other, proof)
        assert refute_tape(t.tape([(first, True), (other, clash)])) is None


def _near_third_leaf(t):
    # the carry of a + b says nothing about a + b < c
    s = t.add(t.a, t.b)
    return [(t.isz(t.lt(s, t.a)), True), (t.lt(s, t.c), True)]


def _near_sub_borrow(t):
    # a - b < a is not a - b < b (a=2, b=1): SUB has no carry rule
    d = t.sub(t.a, t.b)
    return [(t.lt(d, t.a), True), (t.lt(d, t.b), False)]


def _near_signed(t):
    # not (a >s b) and b < a: a = 2^255 + 1 is negative and large
    return [(t.isz(t.op(SymOp.SGT, t.a, t.b)), True), (t.lt(t.b, t.a), True)]


def _near_eq_other_cone(t):
    return [(t.eq(t.a, t.b), True), (t.eq(t.b, t.c), False)]


def _near_same_sign(t):
    # one key twice under ONE sign is no conflict
    return [(t.gt(t.a, t.b), True), (t.isz(t.lt(t.b, t.a)), False)]


def _near_by_node_leaves(t):
    # two havoc leaves with one payload are two variables, not one term
    h1, h2 = (t.op(SymOp.FREE, int(FreeKind.HAVOC), 7) for _ in range(2))
    return [(t.lt(h1, t.a), True), (t.lt(h2, t.a), False)]


@pytest.mark.parametrize("build", [
    _near_third_leaf, _near_sub_borrow, _near_signed, _near_eq_other_cone,
    _near_same_sign, _near_by_node_leaves])
def test_near_miss_is_left_to_the_search(build):
    """None is a polarity conflict; each is satisfiable, the all-zero
    probe does not satisfy it, and the parent commit's search finds a
    witness: the same stage must give the same verdict here."""
    t = Build()
    tape = t.tape(build(t))
    assert refute_tape(tape) is None
    p0 = portfolio.PORTFOLIO_STATS.snapshot()
    verdict, asn = solve_tape_ex(tape)
    d = portfolio.stats_delta(portfolio.PORTFOLIO_STATS.snapshot(), p0)
    assert verdict == "sat"        # the parent's verdict (fa23f8a)
    assert d["stages"]["refute"]["hits"] == 0
    assert d["stages"]["search"] == {**d["stages"]["search"],
                                     "attempts": 1, "hits": 1, "sat": 1}
    vals = evaluate(tape, asn)
    assert all(bool(vals[n]) == s for n, s in tape.constraints)


def test_random_tapes_refuted_by_a_rewrite_have_no_model_on_the_grid():
    """Soundness against the evaluator, on shapes nobody wrote by hand:
    seeded random DAGs over two calldata words, two havoc leaves with
    one payload (two variables) and a constant, biased towards
    comparisons of an ADD/SUB with its operands under ISZERO chains.
    Whenever the polarity check refutes one, no assignment of boundary
    words may satisfy it."""
    rng = random.Random(3535)
    grid = (0, 1, 1 << 255, M256 - 1, M256)
    ops = [SymOp.LT, SymOp.GT] * 2 + [SymOp.ISZERO, SymOp.ADD] * 2 + [
        SymOp.EQ, SymOp.SUB, SymOp.SLT, SymOp.SGT, SymOp.AND, SymOp.MUL]
    rewrites = {"polarity": 0, "iszero": 0, "gt_lt": 0, "eq_commute": 0,
                "add_carry": 0}
    for _ in range(300):
        t = Build()
        h1, h2 = (t.op(SymOp.FREE, int(FreeKind.HAVOC), 3) for _ in range(2))
        t.op(SymOp.CONST, imm=rng.choice(grid))
        first = len(t.nodes)
        for _ in range(rng.randint(4, 14)):
            op, n = rng.choice(ops), len(t.nodes)
            a, b = rng.randrange(1, n), rng.randrange(1, n)
            sums = [j for j in range(first, n) if t.nodes[j].op in (
                int(SymOp.ADD), int(SymOp.SUB))]
            if op in (SymOp.LT, SymOp.GT) and sums and rng.random() < 0.7:
                j = rng.choice(sums)
                k = rng.choice((t.nodes[j].a, t.nodes[j].b, b))
                a, b = rng.choice(((j, k), (k, j)))
            tests = [j for j in range(first, n) if t.nodes[j].op in (
                int(SymOp.LT), int(SymOp.GT), int(SymOp.EQ),
                int(SymOp.ISZERO))]
            if op == SymOp.ISZERO and tests:
                a = rng.choice(tests)
            if op == SymOp.EQ and tests and rng.random() < 0.5:
                j = rng.choice(tests)      # the other way round
                a, b = t.nodes[j].b or b, t.nodes[j].a
            t.op(op, a, 0 if op == SymOp.ISZERO else b)
        roots = tests or [len(t.nodes) - 1]
        tape = t.tape([(rng.choice(roots), rng.random() < 0.5)
                       for _ in range(rng.randint(2, 6))])
        proof = refute_tape(tape)
        if proof is None or proof.rule not in rewrites:
            continue
        rewrites[proof.rule] += 1
        for x, y, u, v in itertools.product(grid, repeat=4):
            asn = Assignment()
            asn.write_calldata_word(0, x)
            asn.write_calldata_word(32, y)
            asn.by_node[h1], asn.by_node[h2] = u, v
            vals = evaluate(tape, asn)
            assert not all(bool(vals[n]) == s for n, s in tape.constraints), (
                proof, tape, (x, y, u, v))
    assert all(rewrites.values()), rewrites     # every rule was met


def test_constraint_on_a_bare_leaf_reads_no_operand():
    """A leaf's ``a`` / ``b`` are a kind and an index, not node ids:
    the facts pass indexed its free-reach table with them and raised
    on a calldata word at an offset beyond the tape's length."""
    t = Build()
    far = t.op(SymOp.FREE, int(FreeKind.CALLDATA_WORD), 4096)
    assert refute_tape(t.tape([(far, True)])) is None
    five = t.eq(far, t.op(SymOp.CONST, imm=5))
    proof = refute_tape(t.tape([(far, False), (five, True)]))
    assert proof is not None and proof.rule == "facts"


def _fixture_tapes():
    return sorted(glob.glob(os.path.join(FIXTURES, "*.json")))


def test_one_fixture_per_shape_and_corpus():
    names = {os.path.basename(p)[:-5] for p in _fixture_tapes()}
    assert names == {f"{c}_{r}" for c in ("intarith", "fullsuite", "deployed")
                     for r in ("add_carry", "gt_lt", "eq_commute")}


@pytest.mark.parametrize("path", _fixture_tapes(),
                         ids=lambda p: os.path.basename(p)[:-5])
def test_dumped_unknown_is_proven_unsat(path, tmp_path):
    """A query the parent's search burned its 400 iterations on."""
    with open(path) as fh:
        doc = json.load(fh)
    rule = os.path.basename(path)[:-5].split("_", 1)[1]
    tape = HostTape(
        nodes=[HostNode(o, a, b, int(imm, 16)) for o, a, b, imm
               in doc["nodes"]],
        constraints=[(n, s) for n, s in doc["constraints"]])
    # the LRU and store key is the one the parent computed: a changed
    # digest would orphan every store a fleet holds
    assert canonical_digest(tape) == doc["digest"]
    store = VerdictStore(str(tmp_path / "vs"))
    portfolio.set_store(store)
    counter = obs_metrics.REGISTRY.counter("solver_refute_total",
                                           labels={"rule": rule})
    before = counter.value
    p0 = portfolio.PORTFOLIO_STATS.snapshot()
    verdict, asn = solve_tape_ex(tape, max_iters=400)
    d = portfolio.stats_delta(portfolio.PORTFOLIO_STATS.snapshot(), p0)
    assert (verdict, asn) == ("unsat", None)
    assert d["stages"]["refute"]["hits"] == 1
    assert d["stages"]["refute"]["unsat"] == 1
    assert d["stages"]["search"]["attempts"] == 0
    assert d["stages"]["probe"]["attempts"] == 0
    assert counter.value == before + 1
    # refuter verdicts re-derive in microseconds and are never stored
    assert store.count() == 0


def test_refute_event_names_its_rule():
    from mythril_tpu.obs import trace as obs_trace

    t = Build()
    tape = t.tape([(t.isz(t.gt(t.a, t.b)), True), (t.lt(t.b, t.a), True)])
    tracer = obs_trace.configure(buffer=True)
    try:
        assert solve_tape_ex(tape)[0] == "unsat"
        events = [e for e in tracer.drain_buffer()
                  if e.get("kind") == "solver_stage"]
    finally:
        obs_trace.close()
    assert len(events) == 1
    assert (events[0]["stage"], events[0]["verdict"],
            events[0]["rule"]) == ("refute", "unsat", "gt_lt")
    # tools/trace_report.py section 8 counts a row a rule from them
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(os.path.dirname(FIXTURES), "..", "..",
                                     "tools", "trace_report.py"))
    tr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tr)
    ladder = {"kind": "solver_portfolio", "queries": 1,
              "stages": {"refute": {"attempts": 1, "hits": 1, "unsat": 1}}}
    text = tr.report(*tr._from_jsonl(events + [ladder]))
    assert "refute by gt_lt" in text and "refute by add_carry" not in text
