"""Reduction of a profiler trace to device busy time, idle gaps and ops.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain dict, which is also the form of the recorded fixture the tests
use::

    {"devices": {"/device:TPU:0": [[name, start_ns, dur_ns], ...], ...},
     "host":    [[name, start_ns, dur_ns], ...]}

``devices`` holds the events of each device's ``XLA Ops`` line: the
operations that ran on it.  ``host`` holds the harness's own
annotations (``bench.`` events), to which ``run.py`` adds the program's
telemetry spans as ``span.`` events on the trace's clock.  Busy time is
the union of a device's op intervals inside the window, so ops that
overlap count once; idle is the rest of the window.
"""
from __future__ import annotations

import glob
import os

OPS_LINE = "XLA Ops"


def latest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [ev.name, ev.start_ns, ev.duration_ns]
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append([ev.name, ev.start_ns, ev.duration_ns])
    return {"devices": devices, "host": host}


def union(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged [start, end) intervals of ``events`` clipped to [lo, hi)."""
    spans = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events
                   if s < hi and s + d > lo)
    out: list[list[float]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(events, lo, hi))


def gaps(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """Idle [start, end) intervals of one device inside [lo, hi)."""
    out, t = [], lo
    for s, e in union(events, lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if t < hi:
        out.append((t, hi))
    return out


def top_ops(events, lo: float, hi: float, k: int = 10
            ) -> list[tuple[str, float]]:
    """The ``k`` op names with the most device time in [lo, hi), seconds."""
    tot: dict[str, float] = {}
    for name, s, d in events:
        e = min(s + d, hi)
        s = max(s, lo)
        if e > s:
            tot[name] = tot.get(name, 0.0) + (e - s)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [(name, ns / 1e9) for name, ns in best]


def label(host, s: float, e: float) -> str:
    """What the host was doing over [s, e): the shortest ``span.`` event
    that covers the interval's midpoint, else the ``bench.`` one."""
    mid = 0.5 * (s + e)
    cover = [(d, name) for name, hs, d in host if hs <= mid < hs + d]
    spans = sorted(c for c in cover if c[1].startswith("span."))
    if spans:
        return spans[0][1][len("span."):]
    rest = sorted(cover)
    return rest[0][1] if rest else "outside any annotation"


def reduce(trace: dict, lo: float, hi: float, k: int = 10) -> dict:
    """Busy seconds per device, the window, and the breakdown."""
    devs = trace["devices"]
    busy = {name: busy_ns(ev, lo, hi) / 1e9 for name, ev in devs.items()}
    all_ops = [ev for evs in devs.values() for ev in evs]
    idle = [(e - s, s, e) for evs in devs.values()
            for s, e in gaps(evs, lo, hi)]
    idle.sort(key=lambda g: -g[0])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy,
        "device_ops": [[n, v] for n, v in top_ops(all_ops, lo, hi, k)],
        "idle_gaps": [[label(trace["host"], s, e), d / 1e9]
                      for d, s, e in idle[:k]],
    }
