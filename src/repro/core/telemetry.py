"""Host-side pipeline telemetry: span-structured JSONL event logs.

The in-jit layer (``core/metrics.py``) measures the *simulated* system;
this module measures the *pipeline that runs it* — per-spec normalize /
lower / compile / execute wall times, executable-cache hit/miss/retrace
counters, replica counts and device/mesh info for every
``launch/experiment.py`` run.  ROADMAP item 3 (pod-scale Monte-Carlo)
is untunable without knowing where the wall-clock goes.

Records are newline-delimited JSON under ``results/telemetry/`` so any
log pipeline can ingest them.  Two record kinds share the envelope
``{"ts": <unix seconds>, "run": <run id>, "kind": ...}``:

* ``span``: ``{"name", "dur_s", "depth", "span", "parent"}`` plus
  arbitrary user attributes — one record per completed ``span()``
  context, written at exit (children therefore precede parents; the
  ``span``/``parent`` ids reconstruct the tree).
* ``event``: ``{"name"}`` plus attributes — point-in-time counters such
  as cache statistics.

Every ``span()`` is also a ``jax.profiler.TraceAnnotation`` named
``e2c.<name>`` with the span's opening attributes, log or no log, so a
``jax.profiler`` trace shows the program's stages beside the device ops
(a no-op when no profiler session runs).

The process keeps three compile-stage counters, the seconds jax
spends tracing (``trace_s``), lowering to MLIR (``lower_s``) and in the
backend compiler or the persistent cache (``backend_s``); nested traces
count once.  A span records each counter's increase over its duration
as an attribute, where non-zero.  A log writes the counters as a
``compile_clock`` event twice: when it opens, the seconds the process
spent before it (``window="before"``, a program's set-up), and when
:func:`disable` closes it, those spent while it was open
(``window="log"``).

The global log is opt-in and null by default: ``span()`` / ``event()``
on a disabled module write nothing, so instrumented library code never
pays for telemetry nobody asked for.  Enable programmatically
(``telemetry.enable(...)``) or by exporting ``REPRO_TELEMETRY=1`` (or
``=/some/dir``).  See docs/observability.md.
"""
from __future__ import annotations

import contextlib
import json
import os
import time
import uuid
from typing import Any, Iterator

import jax

DEFAULT_DIR = os.path.join("results", "telemetry")
_ENV = "REPRO_TELEMETRY"
#: jax.monitoring duration events -> compile-stage counter
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}


def _jsonable(v: Any) -> Any:
    """Best-effort plain-JSON coercion (numpy scalars, paths, tuples)."""
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    item = getattr(v, "item", None)
    if callable(item):
        try:
            return item()
        except Exception:
            pass
    return str(v)


class CompileClock:
    """The compile-stage counters of ``COMPILE_EVENTS``, in seconds."""

    def __init__(self):
        self.totals = {k: 0.0 for k in COMPILE_EVENTS.values()}
        # per counter, the merged [start, end) intervals whose end may
        # still be overlapped by a later (enclosing) event
        self._intervals: dict[str, list[list[float]]] = {
            k: [] for k in self.totals}

    def on_event(self, counter: str, dur: float) -> None:
        """Add one finished compile-stage event to ``counter``: its
        interval ends now, and an event that encloses earlier ones (a
        nested trace) adds only the time they do not cover."""
        end = time.perf_counter()
        start = end - dur
        ivs = self._intervals[counter]
        while ivs and ivs[-1][1] >= start:
            s, e = ivs.pop()
            self.totals[counter] -= e - s
            start = min(start, s)
        ivs.append([start, end])
        self.totals[counter] += end - start

    def since(self, before: dict[str, float]) -> dict[str, float]:
        return {k: v - before[k] for k, v in self.totals.items()}


class TelemetryLog:
    """One JSONL file of spans/events for one logical run.

    Append-only and flushed per record, so a crashed run keeps every
    span that completed.  Not thread-safe by design — the experiment
    pipeline is single-threaded host code.
    """

    def __init__(self, out_dir: str = DEFAULT_DIR,
                 run_id: str | None = None):
        self.run_id = run_id or time.strftime("%Y%m%d-%H%M%S") \
            + "-" + uuid.uuid4().hex[:6]
        self.out_dir = out_dir
        self.path = os.path.join(out_dir, f"telemetry-{self.run_id}.jsonl")
        self._fh = None
        self._stack: list[str] = []     # open span ids, for parenting
        self.n_records = 0
        self._opened = dict(CLOCK.totals)

    @property
    def compile(self) -> dict[str, float]:
        """Compile-stage seconds since the log opened."""
        return CLOCK.since(self._opened)

    def _write(self, rec: dict) -> None:
        if self._fh is None:
            os.makedirs(self.out_dir, exist_ok=True)
            self._fh = open(self.path, "a", encoding="utf-8")
        self._fh.write(json.dumps(rec, separators=(",", ":")) + "\n")
        self._fh.flush()
        self.n_records += 1

    def event(self, name: str, **attrs: Any) -> None:
        """Point-in-time record (counters, cache stats, config)."""
        self._write({"ts": round(time.time(), 6), "run": self.run_id,
                     "kind": "event", "name": name,
                     **{k: _jsonable(v) for k, v in attrs.items()}})

    @contextlib.contextmanager
    def span(self, name: str, **attrs: Any) -> Iterator[dict]:
        """Timed block; yields a dict for attributes added mid-span.
        The record lands at exit with ``dur_s`` wall time; exceptions
        propagate but still produce a record with ``error`` set."""
        sid = uuid.uuid4().hex[:8]
        parent = self._stack[-1] if self._stack else None
        self._stack.append(sid)
        extra: dict = {}
        before = dict(CLOCK.totals)
        t0 = time.perf_counter()
        try:
            yield extra
        except BaseException as e:
            extra["error"] = f"{type(e).__name__}: {e}"
            raise
        finally:
            dur = time.perf_counter() - t0
            self._stack.pop()
            for k, v in CLOCK.since(before).items():
                if v > 0:
                    extra.setdefault(k, round(v, 6))
            self._write({
                "ts": round(time.time(), 6), "run": self.run_id,
                "kind": "span", "name": name, "dur_s": round(dur, 6),
                "depth": len(self._stack), "span": sid, "parent": parent,
                **{k: _jsonable(v) for k, v in {**attrs, **extra}.items()},
            })

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None


# ---------------------------------------------------------------------------
# Module-level current log (null by default)
# ---------------------------------------------------------------------------
#: the process's compile-stage counters, fed from import on
CLOCK = CompileClock()


def _on_duration(event: str, duration: float, **_: Any) -> None:
    counter = COMPILE_EVENTS.get(event)
    if counter is not None:
        CLOCK.on_event(counter, duration)


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def _compile_clock(log: TelemetryLog, window: str,
                   totals: dict[str, float]) -> None:
    log.event("compile_clock", window=window,
              **{k: round(v, 6) for k, v in totals.items()})


_CURRENT: TelemetryLog | None = None
if os.environ.get(_ENV):
    _v = os.environ[_ENV]
    _CURRENT = TelemetryLog(_v if os.sep in _v or _v.startswith(".")
                            else DEFAULT_DIR)
    _compile_clock(_CURRENT, "before", CLOCK.totals)


def enable(out_dir: str = DEFAULT_DIR,
           run_id: str | None = None) -> TelemetryLog:
    """Install (and return) a fresh module-level log, which opens with
    the process's compile-stage seconds so far (``compile_clock``)."""
    global _CURRENT
    disable()
    _CURRENT = TelemetryLog(out_dir, run_id)
    _compile_clock(_CURRENT, "before", CLOCK.totals)
    return _CURRENT


def disable() -> None:
    """Write the log's ``compile_clock`` totals and close it."""
    global _CURRENT
    if _CURRENT is not None:
        _compile_clock(_CURRENT, "log", _CURRENT.compile)
        _CURRENT.close()
    _CURRENT = None


def current() -> TelemetryLog | None:
    return _CURRENT


@contextlib.contextmanager
def span(name: str, **attrs: Any) -> Iterator[dict]:
    """``current().span(...)`` inside an ``e2c.<name>`` profiler
    annotation; only the annotation when telemetry is off."""
    with jax.profiler.TraceAnnotation(f"e2c.{name}", **attrs):
        if _CURRENT is None:
            yield {}
        else:
            with _CURRENT.span(name, **attrs) as extra:
                yield extra


def event(name: str, **attrs: Any) -> None:
    """``current().event(...)`` or a free no-op when telemetry is off."""
    if _CURRENT is not None:
        _CURRENT.event(name, **attrs)


def read_jsonl(path: str) -> list[dict]:
    """Parse one telemetry file back into records (for tests/analysis)."""
    with open(path, encoding="utf-8") as fh:
        return [json.loads(line) for line in fh if line.strip()]
