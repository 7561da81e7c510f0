"""Device time by engine phase, and the program's annotations, from the
profiler trace of a traced run.

``load(path)`` reads an ``.xplane.pb`` into a plain dict, which is also
the form the tests build by hand::

    {"devices": {"/device:TPU:0": [[name, start_ns, dur_ns], ...]},
     "phases":  {"/device:TPU:0": [phase, ...]},     # one per op
     "annotations": [[name, start_ns, dur_ns, {stat: value}], ...],
     "first_call": start_ns | None,
     "phase_source": "tf_op" | None}

``devices`` holds each device's ``XLA Ops`` line, as ``trace.load``
reads it.  An op's phase is the innermost name of ``PHASES`` in its
``op_name`` (the ``jax.named_scope`` of the engine's phase functions),
else ``other``.  The TPU's trace carries the ``op_name`` as the
``tf_op`` stat of the op's event metadata, which ``ProfileData`` does
not expose, so a small protobuf wire decoder reads it from the file's
bytes; ``phase_source`` is ``tf_op`` where that named some op.
``annotations`` holds the program's ``e2c.<span>`` events
(``core/telemetry.py``) with their attributes, and ``first_call`` the
start of the harness's ``bench.call.0`` annotation.

``from_ctx(ctx)`` finds the trace a ``--trace 1`` run of ``run.py``
just wrote and adds its window on the trace's clock, ``window_ns``:
from the start of ``bench.call.0`` for the ``window_s`` of the run's
trace summary, as ``run.py`` sets it.

Times are exclusive: an op's time in [lo, hi) less that of the ops
nested in it on the same line, so a ``while`` does not count its body
again, and a device's phases plus ``other`` add up to its busy time.
"""
from __future__ import annotations

import glob
import os
import re

from bench import harness as H
from bench import trace as TR

PHASES = ("next_event", "completions", "availability", "release",
          "arrivals", "deadline_drops", "drain", "start_tasks",
          "retire", "refill", "compact")
OTHER = "other"
PREFIX = "e2c."
NORMALIZE = ("e2c.normalize", "e2c.chunk_normalize")
FIRST_CALL = "bench.call.0"
_TRANSFORMED = re.compile(r"[\w.\-]+\((.*)\)")


def phase_of(op_name: str | None) -> str:
    """The innermost engine phase named in ``op_name``, else ``other``;
    a transformed scope (``vmap(drain)``) counts as its inner name."""
    for part in reversed((op_name or "").split("/")):
        m = _TRANSFORMED.fullmatch(part)
        while m:
            part = m.group(1)
            m = _TRANSFORMED.fullmatch(part)
        if part in PHASES:
            return part
    return OTHER


# -- minimal protobuf wire decoding: XSpace -> tf_op of each device op ---
def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message; a length-delimited value is
    a memoryview, a varint an int, fixed-width values are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _first(buf, field: int, default=None):
    return next((v for f, v in _fields(buf) if f == field), default)


def op_names(raw: bytes) -> dict[str, dict[str, str]]:
    """From a serialized XSpace, each device plane's event metadata:
    {plane: {event name: its ``tf_op`` stat}}."""
    out: dict[str, dict[str, str]] = {}
    for f, plane in _fields(memoryview(raw)):
        if f != 1:                                # XSpace.planes
            continue
        name = bytes(_first(plane, 2, b"")).decode()
        if not name.startswith("/device:"):
            continue
        stat_names, metas = {}, []
        for g, v in _fields(plane):
            if g == 5:                            # stat_metadata map
                sm = _first(v, 2, b"")
                stat_names[_first(sm, 1, 0)] = bytes(_first(sm, 2, b""))
            elif g == 4:                          # event_metadata map
                metas.append(_first(v, 2, b""))
        ops = out[name] = {}
        for em in metas:
            for h, stat in _fields(em):
                if h == 5 and stat_names.get(_first(stat, 1, 0)) == b"tf_op":
                    ev = bytes(_first(em, 2, b"")).decode()
                    ops.setdefault(ev, bytes(_first(stat, 5, b"")).decode())
    return out


def load(path: str) -> dict:
    """The devices' ops with their phases, and the host's annotations,
    of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    with open(path, "rb") as fh:
        raw = fh.read()
    names = op_names(raw)
    devices, phases, annotations = {}, {}, []
    source = first_call = None
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name.startswith("/device:"):
            tf_op = names.get(plane.name, {})
            source = source or ("tf_op" if tf_op else None)
            for line in plane.lines:
                if line.name == TR.OPS_LINE:
                    ops = devices[plane.name] = []
                    for ev in line.events:
                        ops.append([ev.name, ev.start_ns, ev.duration_ns])
                    phases[plane.name] = [phase_of(tf_op.get(n))
                                          for n, _, _ in ops]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        annotations.append([ev.name, ev.start_ns,
                                            ev.duration_ns, dict(ev.stats)])
                    elif ev.name == FIRST_CALL:
                        first_call = ev.start_ns
    return {"devices": devices, "phases": phases,
            "annotations": annotations, "first_call": first_call,
            "phase_source": source}


_LOADED: dict = {}


def from_ctx(ctx: dict) -> dict | None:
    """The trace of the run whose readers' ``ctx`` this is, with its
    window; None where there is no trace or no first call in it.  The
    run wrote its trace under ``results/bench/trace/<cell>`` after
    clearing that directory, so it is the newest there."""
    summary = ctx.get("trace")
    found = glob.glob(os.path.join(H.OUT_DIR, "trace", "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    if not summary or not found:
        return None
    path = max(found, key=os.path.getmtime)
    key = (path, os.path.getmtime(path))
    if key not in _LOADED:
        _LOADED.clear()
        _LOADED[key] = load(path)
    tr = _LOADED[key]
    lo = tr["first_call"]
    if lo is None:
        return None
    return dict(tr, window_ns=(lo, lo + summary["window_s"] * 1e9))


def exclusive(ops, phases, lo: float, hi: float) -> dict[str, float]:
    """Exclusive ns per phase of one line's ``[name, start, dur]`` ops in
    [lo, hi): each op's clipped time less its nested ops' clipped time."""
    out: dict[str, float] = {}
    stack: list[list] = []        # [end, phase, clipped ns left]

    def close(top):
        out[top[1]] = out.get(top[1], 0.0) + top[2]

    for (_, s, d), phase in sorted(zip(ops, phases),
                                   key=lambda e: (e[0][1], -e[0][2])):
        while stack and s >= stack[-1][0]:
            close(stack.pop())
        own = max(0.0, min(s + d, hi) - max(s, lo))
        if stack:
            stack[-1][2] -= own
        stack.append([s + d, phase, own])
    while stack:
        close(stack.pop())
    return out


def split(tr: dict, lo: float, hi: float) -> dict[str, float]:
    """Exclusive seconds per phase in [lo, hi), summed over devices."""
    out: dict[str, float] = {}
    for dev, ops in tr["devices"].items():
        for phase, ns in exclusive(ops, tr["phases"][dev], lo, hi).items():
            out[phase] = out.get(phase, 0.0) + ns / 1e9
    return out


def scoped(tr: dict) -> bool:
    """Whether any device op carries an engine phase (a program without
    the phase scopes leaves every op in ``other``)."""
    return any(p != OTHER for ps in tr["phases"].values() for p in ps)


def exposed(tr: dict, lo: float, hi: float,
            names=NORMALIZE) -> tuple[float, int] | None:
    """Idle ns of the idlest device inside the union of the ``names``
    annotations that meet [lo, hi), and the replicas they normalized
    (their ``n_replicas``); None without such annotations or devices."""
    spans = [a for a in tr["annotations"]
             if a[0] in names and a[1] < hi and a[1] + a[2] > lo]
    if not spans or not tr["devices"]:
        return None
    busy = [TR.union(ops, lo, hi) for ops in tr["devices"].values()]
    idlest = min(busy, key=lambda ivs: sum(e - s for s, e in ivs))
    norm = TR.union([a[:3] for a in spans], lo, hi)
    idle = sum(e - s for s, e in norm) - _overlap_ns(norm, idlest)
    return idle, sum(int(a[3].get("n_replicas", 0)) for a in spans)


def _overlap_ns(a, b) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        out += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out
