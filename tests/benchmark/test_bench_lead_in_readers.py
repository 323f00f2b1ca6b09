"""The four readers of the device phase's lead-in over a hand-made
``obs`` (a window's first phase with no host phase beside it, one with
the host phase wholly inside the lead-in, one with the host phase half
inside a ``sym_run`` call, another thread's spans, a program from
before the ``batch_build`` spans), and over the spans of one traced
chip run, where they give what that run printed."""

import json
import os

import pytest

from bench_paths import DATA, load

NAMES = ("lead_in_s", "lead_in_cpu_share", "host_phase_hidden_share",
         "device_unfed_share")


def reader(name: str):
    return load(f"layer_metrics/{name}.py",
                "bench_" + name.replace(".", "_"))


def span(name, mono, dur, tid=1, **attrs):
    return {"kind": "span", "name": name, "mono": mono, "dur": dur,
            "tid": tid, **attrs}


def build(mono, durs, cpus, tid=1):
    """The four stages of one lead-in, one after the other."""
    out = []
    for stage, dur, cpu in zip(("images", "corpus", "frontier", "start"),
                               durs, cpus):
        out.append(span("batch_build", mono, dur, tid, stage=stage,
                        device_fetches=0, device_wait_s=0.0, cpu_s=cpu,
                        proc_cpu_s=cpu))
        mono += dur
    return out


PHASES = [
    # the window's first phase: nobody beside its lead-in of 1.0 s
    span("device_phase", 0.0, 10.0, bi=0),
    span("superstep", 1.0, 4.0, tx=0), span("superstep", 5.5, 4.0, tx=1),
    # the host phase of batch 0 wholly inside the lead-in (2.0 s)
    span("device_phase", 10.0, 12.0, bi=1),
    span("host_phase", 10.0, 1.5, tid=2, bi=0),
    span("superstep", 12.0, 5.0, tx=0), span("superstep", 17.5, 4.0, tx=1),
    # the host phase of batch 1 half inside the first call
    span("device_phase", 22.0, 10.0, bi=2),
    span("host_phase", 22.0, 2.0, tid=2, bi=1),
    span("superstep", 23.0, 8.0, tx=0),
    # the window's last host phase, nothing beside it
    span("host_phase", 32.0, 1.0, tid=2, bi=2),
    # another thread's call and stage are nobody's lead-in
    span("superstep", 10.5, 1.0, tid=9, tx=0),
    span("batch_build", 10.5, 0.5, tid=9, stage="images", cpu_s=0.5),
]
BUILDS = (build(0.0, (0.1, 0.2, 0.6, 0.1), (0.08, 0.15, 0.3, 0.05))
          + build(10.0, (0.2, 0.6, 1.0, 0.2), (0.1, 0.2, 0.3, 0.05))
          + build(22.0, (0.1, 0.2, 0.6, 0.1), (0.05, 0.1, 0.3, 0.05)))

OBS = {"kind": "campaign", "window": (0.0, 40.0), "window_s": 40.0,
       "spans": PHASES + BUILDS}
# the same run of a program from before the ``batch_build`` spans
OLD = {**OBS, "spans": [s for s in PHASES if s["name"] != "batch_build"]}

WANT = {
    "lead_in_s": (1.0 + 2.0 + 1.0) / 3,
    "lead_in_cpu_share": 100.0 * (0.58 + 0.65 + 0.5) / 4.0,
    "host_phase_hidden_share": 100.0 * 1.0 / 4.5,
    "device_unfed_share": 100.0 * (1.0 - 25.0 / 40.0),
}


@pytest.mark.parametrize("name", NAMES)
def test_reader_on_the_hand_made_run(name):
    assert reader(name).read(OBS) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NAMES)
def test_reader_on_a_program_without_batch_build(name):
    want = None if name == "lead_in_cpu_share" else WANT[name]
    assert reader(name).read(OLD) == (
        None if want is None else pytest.approx(want))


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("obs", [
    {**OBS, "kind": "serve"},
    {**OBS, "spans": []},
    # a serial run has host work but no device phase beside it
    {**OBS, "spans": [s for s in PHASES if s["name"] != "device_phase"]},
], ids=["another_driver", "untraced", "no_device_phase"])
def test_reader_finds_nothing_to_read(name, obs):
    assert reader(name).read(obs) is None


def test_overlap_counts_a_second_once():
    lead_in = load("layer_metrics/_lead_in.py", "bench__lead_in")
    assert lead_in.overlap(0.0, 10.0, [(1.0, 3.0), (2.0, 4.0),
                                       (9.0, 12.0)]) == pytest.approx(4.0)
    assert lead_in.overlap(5.0, 6.0, [(1.0, 3.0)]) == 0.0


def chip_runs():
    path = os.path.join(DATA, "lead_in_chip_runs.json")
    with open(path) as fh:
        return json.load(fh)


@pytest.mark.parametrize("name", NAMES)
def test_reader_on_a_traced_chip_run_gives_what_the_run_printed(name):
    """``lead_in_chip_runs.json``: the ``device_phase``, ``host_phase``,
    ``superstep`` and ``batch_build`` spans of traced runs on a TPU v5
    lite, with the window and the result line's ``metrics``."""
    for run in chip_runs():
        obs = {"kind": "campaign", "spans": run["spans"],
               "window": tuple(run["window"]),
               "window_s": run["window"][1] - run["window"][0]}
        assert reader(name).read(obs) == pytest.approx(
            run["metrics"][name]["value"], rel=1e-9)
        if name == "host_phase_hidden_share":
            # the program's own gauge says the same
            assert abs(100.0 * run["pipeline_occupancy"]
                       - run["metrics"][name]["value"]) <= 2.0
