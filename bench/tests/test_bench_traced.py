"""A traced run of ``bench/run.py`` on the CPU, at tiny size, reports
the per-layer metrics whose sources the CPU has: the set-up's trace and
lower seconds from the program's telemetry log; the device-trace
readers find no device plane and the line leaves them out."""
from bench import harness as H
from bench.tests.test_bench_run import _result, steered, tiny_bench  # noqa: F401
from repro.core import telemetry as TL


def test_traced_run_reports_trace_lower_s(steered, capsys):  # noqa: F811
    res, _ = _result(capsys, "braun512x16.stream", trace=1)
    assert res["correct"] is True
    assert res["metrics"]["trace_lower_s"]["value"] > 0
    assert res["metrics"]["trace_lower_s"]["unit"] == "s"
    assert "drain_us_per_task" not in res["metrics"]
    assert "exposed_normalize_ms_per_replica" not in res["metrics"]
    recs = TL.read_jsonl(f"{H.OUT_DIR}/telemetry/"
                         "telemetry-braun512x16.stream.jsonl")
    assert (recs[0]["name"], recs[0]["window"]) == ("compile_clock",
                                                    "before")
    assert (recs[-1]["name"], recs[-1]["window"]) == ("compile_clock", "log")
    assert {"draw", "stack"} <= {r["name"] for r in recs}
