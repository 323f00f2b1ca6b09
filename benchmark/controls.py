#!/usr/bin/env python3
"""The control of ``correct``, on the chip at a cell's own size.

    python3 benchmark/controls.py <workload> <seconds> <seed>...

One process, one load of the compiled programs: for every seed the cell
as it is (has to come out correct) and the cell with a control of its
configuration's file applied, ``module_withheld`` unless ``CONTROL``
names another (has to come out not correct). A control with an ``env``
part has to be given the environment from outside, before the engine
is loaded: ``MYTHRIL_HOST_CALLBACKS=0 CONTROL=host_callbacks_off``.
The benchmark's own runs never run this. Prints one JSON line per run
and exits 0 only if every sound run was correct and every control was
not.
"""

import copy
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
import run as bench  # noqa: E402


def main(argv) -> int:
    workload, seconds, seeds = argv[0], float(argv[1]), argv[2:]
    name = os.environ.get("CONTROL", "module_withheld")
    base = bench.load_cell(bench.ROOT, workload)
    control = copy.deepcopy(base)
    spec = control.config["controls"][name]
    for key in ("analyze_args", "serve_args"):
        control.config[key] = control.config[key] + spec.get("args", [])
    for k, v in spec.get("env", {}).items():
        if os.environ.get(k) != v:
            raise SystemExit(f"control {name} needs {k}={v} in the "
                             f"environment")
    ok = True
    for seed in seeds:
        variants = [("control:" + name, control, False)]
        if not spec.get("env"):
            variants.insert(0, ("sound", base, True))
        for label, loaded, want in variants:
            lines = []
            out = bench.run_cell(bench.ROOT, workload, int(seed), seconds,
                                 False, loaded=copy.deepcopy(loaded),
                                 log=lines.append)
            wrong = [ln for ln in lines if ln.startswith("wrong verdict")]
            print(json.dumps({
                "workload": workload, "seed": int(seed), "run": label,
                "correct": out["correct"], "wanted": want,
                "attempted": out["attempted"], "failed": out["failed"],
                "wrong": wrong[:4], "device": out["device"]["kind"],
                "metrics": {k: round(v["value"], 3)
                            for k, v in out["metrics"].items()}}),
                flush=True)
            ok = ok and out["correct"] is want
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
