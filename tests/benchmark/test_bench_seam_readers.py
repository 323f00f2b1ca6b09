"""The seven readers of the host/device seam over a hand-made ``obs``:
the value each forms, and ``None`` where the program gives nothing to
read (a program from before the spans' new attributes, as the parent of
the PR that added them is)."""

import pytest

from bench_paths import load


def reader(name: str):
    return load(f"layer_metrics/{name}.py",
                "bench_" + name.replace(".", "_"))


def span(name, mono, dur, tid=1, **attrs):
    return {"kind": "span", "name": name, "mono": mono, "dur": dur,
            "tid": tid, **attrs}


def superstep(mono, dur, steps_run, wait, tid=1, **attrs):
    return span("superstep", mono, dur, tid, steps=64,
                steps_run=steps_run, enqueue_s=0.01, device_wait_s=wait,
                **attrs)


def phase(name, mono, dur, fetches, wait, cpu, tid=1):
    return span(name, mono, dur, tid, device_fetches=fetches,
                device_wait_s=wait, cpu_s=cpu)


OBS = {
    "kind": "campaign", "window_s": 70.0, "batches": 2,
    "engine_setup": {"device": {"platform": "tpu", "kind": "TPU v5 lite",
                                "count": 1}},
    "registry_before": {"counters": {"engine_supersteps_total": 400.0,
                                     "engine_supersteps_budget_total":
                                     1024.0},
                        "gauges": {}},
    "registry_after": {"counters": {"engine_supersteps_total": 1000.0,
                                    "engine_supersteps_budget_total":
                                    3072.0},
                       "gauges": {"frontier_bytes": 163.8e6}},
    "spans": [
        # batch 0: a cold call (the compile) that no rate may count,
        # two warm calls, and 0.5 s of harvest syncs
        phase("device_phase", 0.0, 30.0, 12, 3.8 + 0.5, 1.0),
        superstep(1.0, 9.0, 64, 8.9, cold=True),
        superstep(11.0, 1.6, 64, 1.5, cold=False),
        superstep(13.0, 0.4, 16, 0.3, cold=False, drain=True),
        # batch 1, with the host phase of batch 0 beside it on thread 2
        phase("device_phase", 30.0, 10.0, 5, 2.5, 0.5),
        superstep(31.0, 2.0, 20, 1.9, cold=False),
        # another thread's call is not inside this device phase
        superstep(32.0, 1.0, 0, 0.9, tid=9, cold=False),
        phase("host_phase", 30.0, 30.0, 300, 21.0, 6.0, tid=2),
        phase("host_phase", 60.0, 10.0, 100, 1.0, 6.0, tid=2),
        span("pipeline_stall", 40.0, 6.0, wait="device-waits-host"),
    ],
}

# the same run on a program from before the attributes
OLD = {
    **OBS,
    "registry_before": {"counters": {"engine_supersteps_total": 512.0},
                        "gauges": {}},
    "registry_after": {"counters": {"engine_supersteps_total": 1536.0},
                       "gauges": {}},
    "spans": [span("device_phase", 0.0, 30.0, bi=0),
              span("superstep", 1.0, 0.01, steps=64, cold=False),
              span("host_phase", 30.0, 30.0, tid=2, bi=0)],
}

WARM_S = (1.6 + 0.4 + 2.0 + 1.0) / (64 + 16 + 20 + 0)

WANT = {
    "superstep_ms": 1e3 * WARM_S,
    "supersteps_per_batch": 300.0,
    "frontier_hbm_share": 100.0 * (2 * 163.8e6 / WARM_S) / 819e9,
    "host_device_wait_share": 100.0 * 22.0 / 40.0,
    "host_cpu_share": 100.0 * 12.0 / 40.0,
    "host_fetches_per_batch": 200.0,
    # 4.3 - (8.9 + 1.5 + 0.3) is below zero: a phase cannot owe syncs;
    # 2.5 - 1.9 (thread 9's call is not this phase's)
    "device_phase_sync_share": 100.0 * (0.0 + 0.6) / 40.0,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_forms_its_value_and_finds_nothing_on_an_older_program(
        name):
    read = reader(name).read
    assert read(OBS) == pytest.approx(WANT[name])
    assert read(OLD) is None
    assert read({"kind": "campaign", "spans": [], "batches": 0,
                 "window_s": 1.0, "engine_setup": {},
                 "registry_before": {}, "registry_after": {}}) is None
    assert read({"kind": "serve", "spans": OBS["spans"]}) is None


def test_cold_calls_and_uncounted_spans_stay_out_of_the_rate():
    read = reader("superstep_ms").read
    only_cold = {**OBS, "spans": [superstep(1.0, 9.0, 64, 8.9, cold=True)]}
    assert read(only_cold) is None
    # a device kind without peaks is nothing to read, not a default
    other = {**OBS, "engine_setup": {"device": {"kind": "cpu"}}}
    assert reader("frontier_hbm_share").read(other) is None
    assert read(other) == pytest.approx(WANT["superstep_ms"])
