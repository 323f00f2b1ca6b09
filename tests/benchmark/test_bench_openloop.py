"""The open-loop generator: its schedule and its due-time accounting."""

import time

import pytest

from bench_paths import load

ol = load("openloop.py", "bench_openloop")

TRAFFIC = {"name": "t", "rate_per_s": 8.0,
           "repeat_share": 0.6, "zipf_s": 1.0, "recent": 64}


def gaps(plan):
    due = [0.0] + [p["due"] for p in plan]
    return sorted(round(b - a, 9) for a, b in zip(due, due[1:]))


def test_schedule_is_drawn_from_the_seed():
    assert ol.schedule(TRAFFIC, 45, 1) == ol.schedule(TRAFFIC, 45, 1)
    other = ol.schedule(TRAFFIC, 45, 2 ** 31 + 2)
    assert other != ol.schedule(TRAFFIC, 45, 1) and gaps(other) != gaps(
        ol.schedule(TRAFFIC, 45, 1))
    assert len(other) == len(ol.schedule(TRAFFIC, 45, 1))


@pytest.mark.parametrize("seconds,n", [(45, 360), (51, 408), (10, 80)])
def test_schedule_fills_the_window_at_the_rate(seconds, n):
    plan = ol.schedule(TRAFFIC, seconds, 3)
    assert len(plan) == n
    assert plan[-1]["due"] == pytest.approx(seconds)
    assert sum(p["fresh"] is None for p in plan) == round(0.6 * n)
    mean_gap = sum(gaps(plan)) / n
    assert mean_gap == pytest.approx(1 / 8.0)
    # fresh bytecodes are numbered in order of first use; a repeat
    # names one of them that came before, among the 64 most recent
    seen = 0
    for p in plan:
        if p["fresh"] is not None:
            assert p["fresh"] == seen
            seen += 1
        else:
            assert max(0, seen - 64) <= p["repeat_of"] < seen


def test_repeat_share_zero_is_all_fresh():
    plan = ol.schedule(dict(TRAFFIC, repeat_share=0.0), 10, 3)
    assert [p["fresh"] for p in plan] == list(range(len(plan)))


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert ol.percentile(xs, 0.5) == 50
    assert ol.percentile(xs, 0.95) == 95
    assert ol.percentile([3.0], 0.95) == 3.0


def test_latency_counts_from_when_the_request_was_due():
    plan = [{"due": 0.05 * (k + 1), "fresh": k, "repeat_of": None}
            for k in range(6)]

    def send(rec):
        time.sleep(0.12 if rec["fresh"] == 2 else 0.01)
        if rec["fresh"] == 4:
            raise OSError("refused")
        return {"status": "ok", "k": rec["fresh"]}

    recs = ol.drive(plan, send, give_up_after=2.0, threads=4)
    assert [r["answer"].get("k") for r in recs] == [0, 1, 2, 3, None, 5]
    assert recs[4]["answer"]["status"] == "error"
    for r in recs:
        assert 0 <= r["lateness"] < 0.05
        assert r["latency"] == pytest.approx(r["done"] - r["due"])
        assert r["latency"] >= r["done"] - r["sent"]
    assert recs[2]["latency"] > 0.11 > recs[3]["latency"]


def test_an_unanswered_request_is_charged_until_the_give_up():
    plan = [{"due": 0.01, "fresh": 0, "repeat_of": None}]
    t0 = time.monotonic()
    recs = ol.drive(plan, lambda rec: time.sleep(1.5), give_up_after=0.2,
                    threads=1)
    assert time.monotonic() - t0 < 1.4
    assert recs[0]["done"] is None and recs[0]["answer"] is None
    assert recs[0]["latency"] == pytest.approx(0.2, abs=0.05)
