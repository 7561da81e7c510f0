"""``bench/run.py`` end to end on the CPU, at tiny sizes: it refuses to
measure without a TPU; with the device check steered it drives every
path and prints a result line that holds to the contract; and with the
timed path broken underneath, ``correct`` comes out false."""
import json

import jax
import pytest

from bench import check
from bench import harness as H
from bench import run
from bench.tests import tiny
from repro.core import engine as E
from repro.core import state as S
from repro.core import streaming as ST
from repro.launch import chunked as CH
from repro.launch import experiment as X

CELLS = sorted({w["name"] for w in H.load_benchmark()["workloads"]}
               | {w["name"] for w in tiny.PROSPECTIVE})
SEED = 2 ** 31 + 40503


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    return tiny.make(str(tmp_path_factory.mktemp("tiny")))


@pytest.fixture
def steered(tiny_bench, monkeypatch, tmp_path):
    """run.py against the tiny copy, with the CPU let through."""
    monkeypatch.setattr(H, "BENCH_DIR", tiny_bench)
    monkeypatch.setattr(H, "OUT_DIR", str(tmp_path / "out"))
    monkeypatch.setattr(check, "MAX_WORKERS", 1)
    monkeypatch.setattr(run, "device_check",
                        lambda chips: jax.devices()[:1])
    # the persistent cache is process-wide: keep it off for later tests
    monkeypatch.setattr(X, "enable_compilation_cache", lambda: "off")
    X.clear_cache()
    yield
    X.clear_cache()


def _result(capsys, cell, trace=0, seconds=0.5):
    rc = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                   str(seconds), "--trace", str(trace)])
    out, err = capsys.readouterr()
    assert rc == 0, err
    return json.loads(out.strip().splitlines()[-1]), err


def test_refuses_without_a_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert run.main(["--workload", CELLS[0], "--seed", "1", "--seconds",
                     "1", "--trace", "0"]) == 2
    out, err = capsys.readouterr()
    assert out == "" and "no TPU" in err


def test_refuses_with_too_few_chips(monkeypatch, capsys):
    class FakeTpu:
        platform = "tpu"
        device_kind = "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTpu()])
    assert run.device_check(4) is None
    assert run.device_check(1) is not None
    assert "4 chips" in capsys.readouterr().err


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(steered, capsys, cell):
    res, err = _result(capsys, cell)
    assert list(res)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(res)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] % H.resolve(cell)["traffic"]["replicas"] == 0
    assert set(res["metrics"]) == {"tasks_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert "compiles_in_window=0 retraces_in_window=0" in err, err
    tail = err.strip().splitlines()[-len(res["checks"]):]
    assert [ln.split()[0] for ln in tail] == list(res["checks"])


def test_traced_run_reports_per_layer_metrics(steered, capsys):
    res, _ = _result(capsys, "braun512x16.grid", trace=1)
    assert res["correct"] is True
    assert {"normalize_ms_per_replica", "compile_s"} <= set(res["metrics"])
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _state_unchanged(monkeypatch):
    run_stream = ST.run_stream

    def frozen_stream(stream, mtype, eet, power, pid, params, *a, **k):
        return run_stream(stream, mtype, eet, power, pid,
                          params._replace(max_events=1), *a, **k)
    monkeypatch.setattr(E, "run_sim", lambda tasks, mtype, tables, pid,
                        params, dyn=None, pp=None, par=None:
                        S.init_state(tasks, mtype, dyn, par))
    monkeypatch.setattr(ST, "run_stream", frozen_stream)


def _half_batch(monkeypatch):
    normalize, normalize_chunk = X.normalize, X.normalize_chunk

    def half(reps):
        n = reps.n_replicas // 2
        return jax.tree.map(lambda x: x[:n], reps)
    monkeypatch.setattr(X, "normalize", lambda spec: half(normalize(spec)))
    monkeypatch.setattr(X, "normalize_chunk",
                        lambda spec, lo, hi: half(normalize_chunk(spec, lo,
                                                                  hi)))


def _exchange_left_out(monkeypatch):
    """The fold sees one device's quarter of each chunk, as it would if
    the partial aggregates were never combined across chips."""
    fold = CH._fold

    def one_device(cols, metrics, pol_idx, aspec):
        q = pol_idx.shape[0] // 4
        return fold(cols, {k: v[:q] for k, v in metrics.items()},
                    pol_idx[:q], aspec)
    monkeypatch.setattr(CH, "_fold", one_device)


def _answer_altered(monkeypatch):
    dense, stream = X.summarize_replica, ST.summarize_stream_replica

    def bump(row):
        return dict(row, completed=row["completed"] + 1)
    monkeypatch.setattr(X, "summarize_replica",
                        lambda *a, **k: bump(dense(*a, **k)))
    monkeypatch.setattr(ST, "summarize_stream_replica",
                        lambda *a, **k: bump(stream(*a, **k)))


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "exchange_left_out": _exchange_left_out,
          "answer_altered": _answer_altered}
CASES = [(c, f) for c in CELLS for f in FAULTS
         if f != "exchange_left_out" or c.endswith("grid4")]


@pytest.mark.parametrize("cell,fault", CASES)
def test_broken_timed_path_is_not_correct(steered, monkeypatch, capsys,
                                          cell, fault):
    FAULTS[fault](monkeypatch)
    rc = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                   "0.2", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc == 0, err
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_control_is_not_correct(tiny_bench, cell):
    res = H.resolve(cell, bench_dir=tiny_bench)
    calls = [(SEED + i, {}, None) for i in range(2)]
    numbers = check.compare(calls, res["config"], res["traffic"], SEED,
                            control="bfloat16", workers=1)
    assert not check.verdict(numbers), numbers
