"""``BENCHMARK.json`` against the contract's limits on names and units,
and against the files each entry is found by."""

import json
import os
import re
import subprocess
import sys

import pytest

from bench_paths import BENCH, ROOT, load

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert bench["paths"] == ["benchmark", "tests/benchmark"]


def test_every_name_and_unit_uses_the_allowed_characters(bench):
    names = []
    for c in bench["configs"]:
        names += [c["name"], *c["reduced"]]
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bench["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in bench["end_to_end"] + bench["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for n in names:
        assert NAME.match(n), n
    for group in ("configs", "workloads"):
        got = [x["name"] for x in bench[group]]
        assert len(got) == len(set(got))
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for entry in bench["configs"] + bench["workloads"]:
        for key in ("why", "source"):
            if key in entry:
                assert 1 <= len(entry[key]) <= 200
                assert "\n" not in entry[key] and "\t" not in entry[key]


def test_metrics_are_wired(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e
        moved = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", moved)) <= moved, m["name"]
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".py")), m["name"]
    run = load("run.py", "bench_run_manifest")
    for cell in cells:
        reported = {m["name"] for m in run.metrics_of_cell(
            bench, cell, "end_to_end")}
        assert "setup_s" in reported and len(reported) >= 2
        assert run.metrics_of_cell(bench, cell, "per_layer")


def test_every_cell_finds_its_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in bench["workloads"]:
        path = os.path.join(ROOT, configs[w["config"]]["file"])
        assert path.startswith(BENCH + os.sep)
        with open(path) as fh:
            cfg = json.load(fh)
        assert cfg["source"] == configs[w["config"]]["source"]
        assert set(configs[w["config"]]["reduced"]) == set(cfg["reduced"])
        assert os.path.isfile(os.path.join(
            BENCH, "corpora", cfg["corpus"] + ".py"))
        with open(os.path.join(BENCH, "traffic",
                               w["traffic"] + ".json")) as fh:
            traffic = json.load(fh)
        assert traffic["name"] == w["traffic"]
        assert os.path.isfile(os.path.join(
            BENCH, "drivers", traffic["driver"] + ".py"))
    with open(os.path.join(BENCH, "peaks.json")) as fh:
        peaks = json.load(fh)
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_run_refuses_to_measure_without_a_tpu(bench):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         bench["workloads"][0]["name"], "--seed", "2147483659",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "not measuring" in out.stderr
    assert not [ln for ln in out.stdout.splitlines()
                if ln.startswith("{") and '"correct"' in ln]
