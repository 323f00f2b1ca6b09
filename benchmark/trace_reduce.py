"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) of one chip to the
numbers the per-layer metrics and ``breakdown`` read.

A TPU trace holds one ``/device:TPU:<n>`` plane per chip with the lines
``XLA Modules`` (one event per executed program) and ``XLA Ops`` (one
event per HLO operation, nested: a ``while`` spans its body's
operations), and a ``/host:CPU`` plane whose ``python`` line carries the
``jax.profiler.TraceAnnotation`` events. Times are nanoseconds from the
start of the profile.

- *busy* is the union of the intervals of leaf operations (those that
  span no other operation): a ``while`` that waits for the host inside
  its body is not busy while it waits.
- an operation's *self time* is its duration less its children's.
- the host's monotonic clock is tied to the trace's by the
  ``bench_clock_sync`` annotation, which carries ``mono_ns``; the
  program's spans (``time.monotonic``) are then laid over the idle gaps.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

SYNC = "bench_clock_sync"


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def union_length(intervals: Sequence[Tuple[float, float]]) -> float:
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps(intervals: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The parts of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if e <= cur:
            continue
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def self_times(events: Sequence[Tuple[float, float, str]]):
    """(name, start, end, self, leaf) for properly nested events of one
    line."""
    out = []
    stack: list = []   # [name, start, end, child_total, has_child]

    def close():
        name, s, e, child, has = stack.pop()
        out.append((name, s, e, max(0.0, (e - s) - child), not has))
        if stack:
            stack[-1][3] += e - s
            stack[-1][4] = True

    for s, d, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and s >= stack[-1][2]:
            close()
        stack.append([name, s, s + d, 0.0, False])
    while stack:
        close()
    return out


def op_label(hlo_text: str) -> str:
    """``%fusion.8 = f32[...] fusion(...)`` -> ``fusion.8``."""
    m = re.match(r"\s*%?([\w.\-]+)", hlo_text)
    return m.group(1) if m else hlo_text[:40]


def _stats(ev) -> Dict:
    try:
        return dict(ev.stats)
    except Exception:  # noqa: BLE001 — a stat that does not decode
        return {}


def load(path: str) -> Dict:
    """Planes of interest as plain lists: ``devices`` (one dict per
    device plane with ``modules`` and ``ops`` as (start_ns, dur_ns,
    name)), ``annotations`` ((start_ns, dur_ns, name) of the host's
    python line) and ``mono_offset_ns`` (add to a trace time to get the
    host's monotonic clock), None when the sync annotation is absent."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices, annotations, offset = [], [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"name": plane.name, "modules": [], "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] = [(e.start_ns, e.duration_ns, e.name)
                                      for e in line.events]
                elif line.name == "XLA Ops":
                    dev["ops"] = [(e.start_ns, e.duration_ns, e.name)
                                  for e in line.events]
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == SYNC:
                        mono = _stats(e).get("mono_ns")
                        if mono is not None:
                            offset = float(mono) - e.start_ns
                    elif e.name.startswith("bench_"):
                        annotations.append(
                            (e.start_ns, e.duration_ns, e.name))
    return {"devices": devices, "annotations": annotations,
            "mono_offset_ns": offset}


def reduce(trace: Dict, window_ns: Optional[Tuple[float, float]] = None,
           spans: Sequence[Dict] = (), top: int = 10) -> Dict:
    """``busy_s`` and ``window_s`` (averaged over the device planes),
    ``modules`` (name -> [count, seconds]), ``module_calls`` (name ->
    seconds of each event of the first device, in time order; a trace
    that starts or ends inside a call cuts it short), ``device_ops`` (top self
    times) and ``idle_gaps`` (the longest gaps, each named after the
    program span that covers most of it). ``window_ns`` is in trace
    time; without it the window runs from the first to the last device
    event. ``spans`` are the program's records (``name``, ``mono``,
    ``dur`` in seconds)."""
    devs = [d for d in trace["devices"] if d["ops"] or d["modules"]]
    if not devs:
        return {"busy_s": 0.0, "window_s": 0.0, "modules": {},
                "module_calls": {}, "device_ops": [], "idle_gaps": []}
    busy_total, win_total = 0.0, 0.0
    op_self: Dict[str, float] = {}
    modules: Dict[str, List[float]] = {}
    calls: Dict[str, List[float]] = {}
    all_gaps: List[Tuple[float, float]] = []
    for d in devs:
        evs = d["ops"] or d["modules"]
        lo = window_ns[0] if window_ns else min(s for s, _, _ in evs)
        hi = window_ns[1] if window_ns else max(s + du for s, du, _ in evs)
        nested = self_times(evs)
        leaves = [(max(s, lo), min(e, hi)) for _, s, e, _, leaf in nested
                  if leaf and e > lo and s < hi]
        busy_total += union_length(leaves)
        win_total += hi - lo
        for name, s, e, self_ns, _ in nested:
            if e > lo and s < hi:
                key = op_label(name)
                op_self[key] = op_self.get(key, 0.0) + self_ns
        for s, du, name in sorted(d["modules"]):
            if s + du > lo and s < hi:
                key = re.sub(r"\(\d+\)$", "", name)
                rec = modules.setdefault(key, [0, 0.0])
                rec[0] += 1
                rec[1] += du / 1e9
                if d is devs[0]:
                    calls.setdefault(key, []).append(du / 1e9)
        if d is devs[0]:
            all_gaps = gaps(leaves, lo, hi)
    n = len(devs)
    off = trace.get("mono_offset_ns")
    named: Dict[str, float] = {}
    for gs, ge in all_gaps:
        # the innermost program span over the gap: of the spans that
        # cover half of it or more, the shortest; else the widest cover
        label, best, covering = "unattributed", 0.0, None
        if off is not None:
            for sp in spans:
                s0 = sp["mono"] * 1e9 - off
                ov = min(ge, s0 + sp["dur"] * 1e9) - max(gs, s0)
                if ov >= 0.5 * (ge - gs):
                    if covering is None or sp["dur"] < covering["dur"]:
                        covering = sp
                elif ov > best and covering is None:
                    best, label = ov, sp["name"]
        if covering is not None:
            label = covering["name"]
        named[label] = named.get(label, 0.0) + (ge - gs) / 1e9
    return {
        "busy_s": busy_total / n / 1e9,
        "window_s": win_total / n / 1e9,
        "modules": modules,
        "module_calls": calls,
        "device_ops": sorted(([k, v / 1e9] for k, v in op_self.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in named.items()),
                            key=lambda kv: -kv[1])[:top],
    }
