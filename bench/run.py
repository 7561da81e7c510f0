"""Run one benchmark cell once on the accelerator and print its result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted in ``setup_s``, from process start): device check,
persistent compilation cache, one warm-up call at the window's shapes.
The window then runs whole ``run_experiment`` calls back to back, call
``i`` with seed ``--seed + i``, until ``--seconds`` have passed; the
metrics are taken over every finished call, from the window's start to
the end of its last call.  With ``--trace 1`` the window runs under the
profiler and the program's telemetry, and the cell's per-layer metrics
are printed instead of its end-to-end ones.  After the window the
answers are checked against the plain reference (``check.py``).

The last line of stdout is one JSON object; the compared numbers and
their limits are also the last lines of stderr.  Without a TPU, or with
fewer chips than the cell asks for, it exits 2 and prints no result.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import check  # noqa: E402
from bench import harness as H  # noqa: E402
from bench import trace as TR  # noqa: E402

#: the warm-up call's seed lies outside any window's seeds
WARM_SEED_OFFSET = 1 << 40
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Sums and counts jax's backend compile events (a persistent-cache
    load is one too); tracing is not counted."""

    def __init__(self):
        import jax
        self.total = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.total += duration
            self.count += 1


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_check(chips: int):
    """-> the devices the cell uses; None (after saying why) without a TPU
    or with too few chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"bench: no TPU found (platform {devs[0].platform}); "
            "refusing to measure")
        return None
    if len(devs) < chips:
        log(f"bench: cell asks for {chips} chips, {len(devs)} found")
        return None
    return devs


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def retired(rows) -> int:
    return int(sum(float(sum(rows[k])) for k in check.TERMINAL))


def unfinished_replicas(rows, n_tasks: int) -> int:
    done = sum(rows[k].astype("int64") for k in check.TERMINAL)
    return int((done < n_tasks).sum())


def spans_on_trace_clock(spans, offset_ns: float):
    """Telemetry spans as ``span.<name>`` host events on the trace clock
    (a span's record is written at its end, ``ts`` in unix seconds)."""
    out = []
    for s in spans:
        if s.get("kind") == "span":
            end = s["ts"] * 1e9 + offset_ns
            out.append([f"span.{s['name']}", end - s["dur_s"] * 1e9,
                        s["dur_s"] * 1e9])
    return out


def run_window(path, cfg, traffic, seed, seconds, annotate):
    """Whole calls back to back until ``seconds`` have passed; ->
    (calls, window start, window end), each call (seed, rows, agg,
    start, end) on ``time.time()``."""
    import contextlib

    import jax
    calls = []
    t0 = time.time()
    while True:
        s = seed + len(calls)
        ann = (jax.profiler.TraceAnnotation(f"bench.call.{len(calls)}")
               if annotate else contextlib.nullcontext())
        c0 = time.time()
        with ann:
            rows, agg = path.call(H.make_spec(cfg, traffic, s), traffic)
        c1 = time.time()
        calls.append((s, rows, agg, c0, c1))
        if c1 - t0 >= seconds:
            return calls, t0, c1


def per_layer(res, calls, traced, compile_s):
    """Reduce the trace and the spans; -> (metrics ctx, trace summary)."""
    from repro.core import telemetry as TL
    spans = TL.read_jsonl(traced["telemetry"]) if os.path.exists(
        traced["telemetry"]) else []
    trace = TR.load(TR.latest_xplane(traced["dir"]))
    marks = {n: s for n, s, _ in trace["host"] if n.startswith("bench.call.")}
    # the trace's clock against time.time(): the first call's annotation
    offset = marks["bench.call.0"] - calls[0][3] * 1e9 if marks else 0.0
    trace["host"] += spans_on_trace_clock(spans, offset)
    lo = calls[0][3] * 1e9 + offset
    hi = calls[-1][4] * 1e9 + offset
    summary = TR.reduce(trace, lo, hi)
    ctx = {"trace": summary, "spans": spans, "compile_s": compile_s,
           "traffic": res["traffic"], "config": res["config"],
           "tasks_traced": sum(retired(c[1]) for c in calls)}
    return ctx, summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    res = H.resolve(args.workload)
    cfg, traffic, path = res["config"], res["traffic"], res["path"]
    devs = device_check(res["cell"]["chips"])
    if devs is None:
        return 2

    import jax

    from repro.core import telemetry as TL
    from repro.launch import experiment as X
    log(f"bench: cache {X.enable_compilation_cache()}")
    clock = CompileClock()
    path.warm(H.make_spec(cfg, traffic, args.seed + WARM_SEED_OFFSET),
              traffic)
    compile_s, setup_compiles = clock.total, clock.count
    setup_traces = X.cache_stats()["retraces"]

    traced = None
    if args.trace:
        traced = {"dir": os.path.join(H.OUT_DIR, "trace", args.workload),
                  "telemetry": os.path.join(H.OUT_DIR, "telemetry",
                                            f"telemetry-{args.workload}"
                                            ".jsonl")}
        shutil.rmtree(traced["dir"], ignore_errors=True)
        if os.path.exists(traced["telemetry"]):
            os.remove(traced["telemetry"])
        TL.enable(os.path.dirname(traced["telemetry"]), args.workload)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # host annotations only: a Python
        opts.host_tracer_level = 1      # tracer would swamp the window
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(traced["dir"], profiler_options=opts)
    setup_s = time.time() - T_START
    try:
        calls, t0, t1 = run_window(path, cfg, traffic, args.seed,
                                   args.seconds, annotate=bool(args.trace))
    finally:
        if args.trace:
            jax.profiler.stop_trace()
            TL.disable()
    window_s = t1 - t0
    log(f"bench: setup_s={setup_s} compile_s={compile_s} "
        f"setup_compiles={setup_compiles}")
    log(f"bench: compiles_in_window={clock.count - setup_compiles} "
        f"retraces_in_window={X.cache_stats()['retraces'] - setup_traces} "
        f"calls={len(calls)} window_s={window_s} per_call_s="
        f"{[c[4] - c[3] for c in calls]}")
    peak = memory_peak(devs)
    n_rep, n_tasks = traffic["replicas"], cfg["n_tasks"]
    tasks = sum(retired(c[1]) for c in calls)
    failed = sum(unfinished_replicas(c[1], n_tasks) for c in calls)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"attempted": n_rep * len(calls), "failed": failed}
    if args.trace:
        ctx, summary = per_layer(res, calls, traced, compile_s)
        device["busy_s"] = sum(summary["busy_s"].values()) / max(
            len(summary["busy_s"]), 1)
        device["window_s"] = summary["window_s"]
        metrics = {}
        for m in res["per_layer"]:
            v = res["readers"][m["name"]].read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    else:
        values = {"tasks_per_s": tasks / window_s, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in res["end_to_end"]}

    t_check = time.time()
    numbers = check.compare([c[:3] for c in calls], cfg, traffic, args.seed)
    correct = check.verdict(numbers) and failed == 0
    log(f"bench: check_s={time.time() - t_check} failed={failed}")
    for k, v in numbers.items():
        log(f"{k} {v} limit {check.LIMITS[k]}")
    result = {"correct": correct, **out, "metrics": metrics,
              "device": device}
    result["checks"] = {k: {"value": v, "limit": check.LIMITS[k]}
                        for k, v in numbers.items()}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
