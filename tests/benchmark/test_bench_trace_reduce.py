"""``benchmark/trace_reduce.py`` on a small recorded trace: 28 KB from
one TPU v5e chip, three rounds of two tiny programs with a 20 ms sleep
between the rounds (``data/tiny_tpu.xplane.pb``)."""

import os

import pytest

from bench_paths import DATA, load

tr = load("trace_reduce.py", "bench_trace_reduce")


@pytest.fixture(scope="module")
def trace():
    return tr.load(os.path.join(DATA, "tiny_tpu.xplane.pb"))


def test_union_and_gaps_arithmetic():
    iv = [(0, 10), (5, 12), (20, 30), (22, 25)]
    assert tr.union_length(iv) == 22
    assert tr.gaps(iv, 0, 40) == [(12, 20), (30, 40)]
    assert tr.gaps([], 3, 7) == [(3, 7)]


def test_self_times_nest():
    # a while of 100 ns with two children of 30 and 50 ns
    out = {n: (s, leaf) for n, _, _, s, leaf in tr.self_times(
        [(0, 100, "while"), (10, 30, "a"), (45, 50, "b")])}
    assert out == {"while": (20, False), "a": (30, True), "b": (50, True)}


def test_trace_loads_device_and_clock(trace):
    assert len(trace["devices"]) == 1
    dev = trace["devices"][0]
    assert len(dev["modules"]) == 6 and len(dev["ops"]) == 36
    assert trace["mono_offset_ns"] is not None
    assert [n for _, _, n in trace["annotations"]].count(
        "bench_span_sleep") == 3


def test_busy_idle_and_modules(trace):
    red = tr.reduce(trace)
    mods = red["modules"]
    assert mods["jit_step"][0] == 3 and mods["jit_other"][0] == 3
    # busy is the union of leaf operations: below the modules' own
    # spans (the while's waiting is not busy), above none of them
    module_s = mods["jit_step"][1] + mods["jit_other"][1]
    assert 0 < red["busy_s"] <= module_s
    assert red["busy_s"] == pytest.approx(33.4e-6, rel=0.02)
    assert red["window_s"] == pytest.approx(44.94e-3, rel=0.01)
    idle = 1 - red["busy_s"] / red["window_s"]
    assert 0.99 < idle < 1.0
    # self times: the fusion inside the while leads, the while itself
    # keeps only what its children do not cover
    ops = dict(red["device_ops"])
    assert red["device_ops"][0][0] == "fusion.8"
    assert ops["while"] < 1e-6


def test_gaps_are_attributed_to_program_spans(trace):
    off = trace["mono_offset_ns"]
    spans = [{"name": n, "mono": (s + off) / 1e9, "dur": d / 1e9}
             for s, d, n in trace["annotations"]]
    # an outer span over everything must not win over the inner ones
    lo = min(s["mono"] for s in spans)
    hi = max(s["mono"] + s["dur"] for s in spans)
    spans.append({"name": "outer", "mono": lo - 1, "dur": hi - lo + 2})
    red = tr.reduce(trace, spans=spans)
    gaps = dict(red["idle_gaps"])
    # two sleeps lie between the first and the last device event
    assert gaps["bench_span_sleep"] == pytest.approx(0.0414, rel=0.1)
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
    # without the clock, nothing can be laid over the gaps
    blind = dict(trace, mono_offset_ns=None)
    assert [k for k, _ in tr.reduce(blind, spans=spans)["idle_gaps"]] \
        == ["unattributed"]


def test_module_calls_and_the_edge_rule_of_sym_run_ms(trace):
    red = tr.reduce(trace)
    assert len(red["module_calls"]["jit_step"]) == 3
    assert sum(red["module_calls"]["jit_step"]) == pytest.approx(
        red["modules"]["jit_step"][1])
    reader = load("layer_metrics/sym_run_ms.py", "bench_sym_run_ms")
    cut = {"profile": {"module_calls": {
        "jit__sym_run_impl": [0.4, 1.5, 1.7, 0.2], "jit_squeeze": [1e-6]}}}
    assert reader.read(cut) == pytest.approx(1600.0)
    two = {"profile": {"module_calls": {"jit__sym_run_impl": [0.4, 1.5]}}}
    assert reader.read(two) == pytest.approx(950.0)
    assert reader.read({"profile": None}) is None
    assert reader.read({"profile": {"module_calls": {}}}) is None
