#!/usr/bin/env python3
"""One run of one benchmark cell, on the machine it is started on.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by the names in
``BENCHMARK.json``: the configuration's file, the traffic mix
(``benchmark/traffic/<traffic>.json``, which names its driver under
``benchmark/drivers/`` and the configuration its corpus under
``benchmark/corpora/``), and for a traced run one reader per per-layer
metric under ``benchmark/layer_metrics/``. Adding a cell, a corpus, a
traffic mix or a per-layer metric takes new files and new entries only.

The last line of standard output is the result object and nothing else;
what was compared, the device, generator lateness and per-batch rows go
on earlier lines. Without a TPU (or with fewer chips than the cell asks
for) the run exits non-zero and prints no result: there is no fallback.
"""

from __future__ import annotations

import time

T0 = time.monotonic()       # set-up is measured from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(ROOT, ".bench_work")    # listed in .gitignore


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str):
    with open(path) as fh:
        return json.load(fh)


def load_cell(root: str, workload: str) -> SimpleNamespace:
    """The cell's entries in ``BENCHMARK.json`` and the files they
    name."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(root, entry["file"]))
    traffic = load_json(os.path.join(root, "benchmark", "traffic",
                                     cell["traffic"] + ".json"))
    return SimpleNamespace(bench=bench, cell=cell, config=config,
                           traffic=traffic)


def metrics_of_cell(bench: dict, cell: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports. A
    metric without a ``workloads`` key is reported wherever the
    end-to-end metric it moves is (an end-to-end one: everywhere)."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def require_devices(chips: int, require_tpu: bool = True) -> dict:
    """What JAX finds, or exit non-zero: the harness's look for a
    chip."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if require_tpu and (dev["platform"] != "tpu" or len(devs) < chips):
        print(f"benchmark/run.py: needs {chips} TPU chip(s), JAX finds "
              f"{dev}: not measuring", file=sys.stderr)
        raise SystemExit(3)
    if require_tpu:
        peaks_of(dev["kind"])
    return dev


def peaks_of(kind: str) -> dict:
    """The table of peaks, keyed by ``device_kind``; a device that is
    not in it is an error, not a default."""
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in peaks:
        print(f"benchmark/run.py: no peaks for device kind {kind!r} in "
              f"benchmark/peaks.json: not measuring", file=sys.stderr)
        raise SystemExit(3)
    return peaks[kind]


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def read_layer_metrics(bench: dict, cell: str, obs: dict,
                       log=print) -> dict:
    """One reader per metric, found by the metric's name; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics_of_cell(bench, cell, "per_layer"):
        path = os.path.join(HERE, "layer_metrics", m["name"] + ".py")
        reader = load_module(path, "layer_metric_" + m["name"]
                             .replace(".", "_").replace("-", "_"))
        value = reader.read(obs)
        if value is None:
            log(f"layer metric {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, require_tpu: bool = True, loaded=None,
             log=print) -> dict:
    """Drive one run and return the result object. ``require_tpu=False``
    and ``loaded`` (a :func:`load_cell` result whose configuration a
    test has cut to the test limits) are for the tests under
    ``tests/benchmark``: the command line has no such switch."""
    loaded = loaded or load_cell(root, workload)
    cell, config, traffic = loaded.cell, loaded.config, loaded.traffic
    corpus = load_module(
        os.path.join(HERE, "corpora", config["corpus"] + ".py"),
        "corpus_" + config["corpus"].replace("-", "_"))
    driver = load_module(
        os.path.join(HERE, "drivers", traffic["driver"] + ".py"),
        "driver_" + traffic["driver"])
    work = os.path.join(WORK, workload)
    os.makedirs(work, exist_ok=True)
    ctx = SimpleNamespace(
        root=root, here=HERE, work=work, t0=T0, cell=cell, config=config,
        traffic=traffic, corpus=corpus, seed=int(seed),
        seconds=float(seconds), trace=bool(trace), chips=cell["chips"],
        require_tpu=require_tpu, log=log,
        require_devices=require_devices,
        memory_peak_bytes=memory_peak_bytes)
    res = driver.run(ctx)
    for line in res["checks"]:
        log(line)
    if trace:
        metrics = read_layer_metrics(loaded.bench, workload, res["obs"],
                                     log)
    else:
        metrics = {}
        for m in metrics_of_cell(loaded.bench, workload, "end_to_end"):
            metrics[m["name"]] = {"value": res["e2e"][m["name"]],
                                  "unit": m["unit"]}
    out = {"correct": bool(res["correct"]),
           "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": metrics,
           "device": res["device"]}
    if trace and res.get("breakdown"):
        out["breakdown"] = res["breakdown"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    out = run_cell(ROOT, args.workload, args.seed, args.seconds,
                   bool(args.trace))
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
