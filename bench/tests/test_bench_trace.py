"""The trace reduction (bench/trace.py) on a small recorded trace."""
import json
import os

import pytest

from bench import trace as TR

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_small.json")


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE, encoding="utf-8") as fh:
        return json.load(fh)


def _window(tr):
    evs = [e for evs in tr["devices"].values() for e in evs]
    return min(s for _, s, _ in evs), max(s + d for _, s, d in evs)


def test_union_merges_overlap_and_clips():
    ev = [["a", 0, 10], ["b", 5, 10], ["c", 30, 5], ["d", 32, 1]]
    assert TR.union(ev, 0, 100) == [(0, 15), (30, 35)]
    assert TR.union(ev, 7, 31) == [(7, 15), (30, 31)]
    assert TR.busy_ns(ev, 0, 100) == 20


def test_gaps_cover_the_rest_of_the_window():
    ev = [["a", 10, 10], ["b", 15, 10], ["c", 40, 5]]
    assert TR.gaps(ev, 0, 50) == [(0, 10), (25, 40), (45, 50)]
    assert TR.gaps([], 0, 5) == [(0, 5)]


def test_top_ops_sum_by_name_within_window():
    ev = [["x", 0, 4], ["y", 4, 1], ["x", 10, 4], ["z", 20, 100]]
    assert TR.top_ops(ev, 0, 30, k=2) == [("z", 10e-9), ("x", 8e-9)]


def test_label_prefers_the_innermost_program_span():
    host = [["bench.call.0", 0, 100], ["span.experiment", 0, 90],
            ["span.chunk_normalize", 10, 20]]
    assert TR.label(host, 12, 18) == "chunk_normalize"
    assert TR.label(host, 92, 96) == "bench.call.0"
    assert TR.label(host, 200, 210) == "outside any annotation"


def test_recorded_busy_is_the_union_per_device(recorded):
    lo, hi = _window(recorded)
    out = TR.reduce(recorded, lo, hi)
    assert out["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert set(out["busy_s"]) == set(recorded["devices"])
    for dev, evs in recorded["devices"].items():
        naive = sum(min(s + d, hi) - max(s, lo) for _, s, d in evs) / 1e9
        assert 0 < out["busy_s"][dev] <= naive + 1e-12
        assert out["busy_s"][dev] <= out["window_s"]


def test_recorded_breakdown(recorded):
    lo, hi = _window(recorded)
    out = TR.reduce(recorded, lo, hi, k=3)
    assert len(out["device_ops"]) <= 3
    secs = [v for _, v in out["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    gaps = [v for _, v in out["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    total_idle = sum(
        sum(e - s for s, e in TR.gaps(evs, lo, hi))
        for evs in recorded["devices"].values()) / 1e9
    assert sum(gaps) <= total_idle + 1e-12
