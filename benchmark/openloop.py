"""The open-loop generator: a schedule drawn from a traffic file, and
the accounting of when each request was due.

The schedule is drawn from the run's seed: the gaps between arrivals
are an exponential sample (mean 1/rate, scaled to fill the window), and
which requests repeat an earlier bytecode, and which one, is drawn with
them. A repeat names an earlier *distinct* bytecode, Zipf-distributed
(exponent ``zipf_s``) over the ``recent`` most recent ones. Every seed
gives the same number of requests and of repeats, at other times. Where
a burst falls against the batch boundaries moves the tail (by 30%
between seeds 701 and 702 with ~9 s batches and six to a window, TPU
v5e, PR 24): a cell's two sets of runs use the same seeds, and its
bound is set from the spread the seeds give.

Each request is timed from when it was **due**, not from when it was
sent, so a stall of the generator or the server is charged to the
requests it delayed. How late the generator sent is reported apart.
"""

from __future__ import annotations

import math
import random
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Dict, List


def schedule(traffic: dict, seconds: float, seed: int) -> List[dict]:
    """``[{"due": s, "fresh": k | None, "repeat_of": j | None}]``:
    ``fresh`` counts the distinct bytecodes in order of first use,
    ``repeat_of`` names one of them."""
    n = max(1, int(round(float(traffic["rate_per_s"]) * seconds)))
    rng = random.Random(f"openloop:{int(seed)}:{n}")
    gaps = [rng.expovariate(1.0) for _ in range(n)]
    scale = seconds / sum(gaps)
    n_rep = int(round(float(traffic["repeat_share"]) * n))
    # the first request is always fresh: there is nothing to repeat
    rep_at = set(rng.sample(range(1, n), min(n_rep, n - 1))) if n > 1 \
        else set()
    s, recent = float(traffic["zipf_s"]), int(traffic["recent"])
    out, t, distinct = [], 0.0, 0
    for i in range(n):
        t += gaps[i] * scale
        if i in rep_at:
            pool = min(recent, distinct)
            weights = [1.0 / (r + 1) ** s for r in range(pool)]
            rank = rng.choices(range(pool), weights)[0]
            out.append({"due": t, "fresh": None,
                        "repeat_of": distinct - 1 - rank})
        else:
            out.append({"due": t, "fresh": distinct, "repeat_of": None})
            distinct += 1
    return out


def percentile(values: List[float], q: float) -> float:
    """The value at rank ceil(q * n) of the sorted values (nearest
    rank): with 360 requests the 95th percentile has 18 beyond it."""
    xs = sorted(values)
    return xs[min(len(xs) - 1, max(0, math.ceil(q * len(xs)) - 1))]


def drive(plan: List[dict], send: Callable[[dict], Dict],
          give_up_after: float, threads: int = 256) -> List[dict]:
    """Send every request of ``plan`` at its due time (seconds from
    now) through ``send`` and wait for all answers, at most
    ``give_up_after`` seconds past the last due time. Returns one record
    per request: ``due``, ``sent``, ``done`` (None if unanswered),
    ``lateness`` (sent - due), ``latency`` (done - due, or give-up time
    - due) and the ``answer`` ``send`` returned."""
    t0 = time.monotonic()
    recs = [dict(p, sent=None, done=None, answer=None) for p in plan]
    lock = threading.Lock()

    def one(rec: dict) -> None:
        try:
            ans = send(rec)
        except Exception as e:  # noqa: BLE001 — a failed request is a result
            ans = {"status": "error", "error": f"{type(e).__name__}: {e}"}
        with lock:
            rec["answer"] = ans
            rec["done"] = time.monotonic() - t0

    # not a ``with`` block: leaving one would wait for the requests that
    # were given up on
    pool = ThreadPoolExecutor(max_workers=threads)
    futs = []
    for rec in recs:
        delay = rec["due"] - (time.monotonic() - t0)
        if delay > 0:
            time.sleep(delay)
        rec["sent"] = time.monotonic() - t0
        futs.append(pool.submit(one, rec))
    deadline = t0 + plan[-1]["due"] + give_up_after
    for f in futs:
        try:
            f.result(timeout=max(0.0, deadline - time.monotonic()))
        except Exception:  # noqa: BLE001 — timeout: left unanswered
            pass
    gave_up = time.monotonic() - t0
    pool.shutdown(wait=False, cancel_futures=True)
    out = []
    with lock:
        for rec in recs:
            done = rec["done"]
            out.append(dict(
                rec, lateness=rec["sent"] - rec["due"],
                latency=(done if done is not None else gave_up)
                - rec["due"]))
    return out
