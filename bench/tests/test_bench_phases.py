"""Device time by engine phase and the program's annotations
(bench/phases.py), and the three readers built on them, on hand-made
traces: as plain dicts, as an xplane written from a text proto, and as
the annotations a CPU trace records."""
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from bench import harness as H
from bench import phases as PH
from bench import trace as TR

READERS = {name: H.load_module("metrics", name) for name in (
    "trace_lower_s", "drain_us_per_task", "exposed_normalize_ms_per_replica")}


def _device(offset=0):
    """A ``while`` over [0, 100) holding scoped leaf ops and one that no
    phase names, then a fold op after the loop."""
    ev = [["while.1", 0, 100, "other"],
          ["fusion.1", 10, 20, "drain"],
          ["fusion.2", 40, 10, "start_tasks"],
          ["fusion.3", 60, 10, "other"],
          ["fusion.4", 120, 30, "other"]]
    return ([[n, s + offset, d] for n, s, d, _ in ev], [p for *_, p in ev])


@pytest.fixture
def handmade():
    (ops0, ph0), (ops1, ph1) = _device(), _device(offset=5)
    return {"devices": {"/device:TPU:0": ops0, "/device:TPU:1": ops1},
            "phases": {"/device:TPU:0": ph0, "/device:TPU:1": ph1},
            "annotations": [
                ["e2c.chunk_normalize", 100, 40, {"n_replicas": "4"}],
                ["e2c.draw", 100, 30, {}],
                ["e2c.normalize", 150, 20, {"n_replicas": 2}],
                ["e2c.execute", 0, 100, {}]],
            "first_call": 0,
            "phase_source": "tf_op"}


@pytest.mark.parametrize("op_name,phase", [
    ("jit(sweep)/vmap(one)/while/body/drain/while/body/lt", "drain"),
    ("jit(f)/vmap(jit(run_stream))/while/body/refill/compact/gather",
     "compact"),
    ("jit(f)/while/body/vmap(start_tasks)/add", "start_tasks"),
    ("jit(f)/while/body/select_n", "other"),
    ("jit(f)/drainage/add", "other"),
    (None, "other"),
])
def test_phase_is_the_innermost_scope(op_name, phase):
    assert PH.phase_of(op_name) == phase


def test_exclusive_time_does_not_count_a_loop_body_twice(handmade):
    dev = "/device:TPU:0"
    out = PH.exclusive(handmade["devices"][dev], handmade["phases"][dev],
                       0, 200)
    assert out == {"other": 60 + 10 + 30, "drain": 20, "start_tasks": 10}
    assert sum(out.values()) == 130      # the busy time: loop + fold


def test_exclusive_time_clips_to_the_window(handmade):
    dev = "/device:TPU:0"
    # [20, 130): the loop's 80 less its clipped children 10 + 10 + 10,
    # plus the fold's first 10
    assert PH.exclusive(handmade["devices"][dev], handmade["phases"][dev],
                        20, 130) == {"other": 50 + 10 + 10, "drain": 10,
                                     "start_tasks": 10}


def test_split_sums_devices_and_equals_busy(handmade):
    out = PH.split(handmade, 0, 200)
    busy = sum(TR.busy_ns(ops, 0, 200)
               for ops in handmade["devices"].values()) / 1e9
    assert sum(out.values()) == pytest.approx(busy)
    assert out["drain"] == pytest.approx(40e-9)


def test_exposed_normalize_is_idle_inside_normalize_spans(handmade):
    # both devices are busy 130 ns of [0, 200), so the first counts:
    # the normalize spans cover [100, 140) and [150, 170), of which it
    # is busy in [120, 140) only — 40 ns idle, over 4 + 2 replicas
    idle, reps = PH.exposed(handmade, 0, 200)
    assert (idle, reps) == (40, 6)
    assert PH.exposed(handmade, 0, 90) is None       # no normalize there
    assert PH.exposed(dict(handmade, devices={}), 0, 200) is None


def _served(monkeypatch, tr):
    """Readers' ``PH.from_ctx`` serving ``tr`` over [0, 200)."""
    monkeypatch.setattr(PH, "from_ctx",
                        lambda ctx: dict(tr, window_ns=(0, 200)))


def test_readers(handmade, monkeypatch):
    _served(monkeypatch, handmade)
    ctx = {"tasks_traced": 8,
           "spans": [{"kind": "span", "name": "warm"},
                     {"kind": "event", "name": "compile_clock",
                      "window": "before", "trace_s": 1.5, "lower_s": 2.0,
                      "backend_s": 0.5},
                     {"kind": "event", "name": "compile_clock",
                      "window": "log", "trace_s": 9.0, "lower_s": 9.0,
                      "backend_s": 9.0}]}
    assert READERS["trace_lower_s"].read(ctx) == 3.5
    assert READERS["drain_us_per_task"].read(ctx) == pytest.approx(
        1e6 * 40e-9 / 8)
    assert READERS["exposed_normalize_ms_per_replica"].read(
        ctx) == pytest.approx(40 / 1e6 / 6)


def test_readers_find_nothing_in_a_program_without_the_sources(
        handmade, monkeypatch):
    """An older program: no phase scopes, no ``e2c.`` annotations, no
    compile counters — every reader returns None and none raises."""
    older = dict(handmade, annotations=[], phase_source=None,
                 phases={d: ["other"] * len(ops)
                         for d, ops in handmade["devices"].items()})
    _served(monkeypatch, older)
    ctx = {"tasks_traced": 8,
           "spans": [{"kind": "span", "name": "normalize"}]}
    for name, reader in READERS.items():
        assert reader.read(ctx) is None, name
    monkeypatch.setattr(PH, "from_ctx", lambda ctx: None)    # no trace
    assert all(r.read({"traffic": {}}) is None for r in READERS.values())


def _xspace(ops, host):
    """A serialized XSpace: one TPU ``XLA Ops`` line of ``ops`` (HLO
    text, start ns, ns, op_name or None) and one host line of ``host``
    (name, start ns, ns, {int stat: value})."""
    from jax.profiler import ProfileData
    names = {n: i + 1 for i, n in enumerate(dict.fromkeys(o[0] for o in ops))}
    tf_op = {o[0]: o[3] for o in ops}
    dev = "".join(
        f'events {{ metadata_id: {names[n]} offset_ps: {s * 1000} '
        f'duration_ps: {d * 1000} }}\n' for n, s, d, _ in ops)
    dev_meta = "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" '
        + (f'stats {{ metadata_id: 7 str_value: "{tf_op[n]}" }} '
           if tf_op[n] else "") + "} }\n" for n, i in names.items())
    hnames = {h[0]: i + 1 for i, h in enumerate(host)}
    stats = sorted({k for h in host for k in h[3]})
    sid = {k: i + 1 for i, k in enumerate(stats)}
    hev = "".join(
        f'events {{ metadata_id: {hnames[n]} offset_ps: {s * 1000} '
        f'duration_ps: {d * 1000} '
        + "".join(f'stats {{ metadata_id: {sid[k]} int64_value: {v} }} '
                  for k, v in st.items()) + "}\n"
        for n, s, d, st in host)
    hmeta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }}\n' for n, i in hnames.items())
    smeta = "".join(f'stat_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{k}" }} }}\n' for k, i in sid.items())
    return ProfileData.text_proto_to_serialized_xspace(f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{dev}  }}
{dev_meta}  stat_metadata {{ key: 7 value {{ id: 7 name: "tf_op" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
{hev}  }}
{hmeta}{smeta}}}
""")


def test_a_tpu_trace_read_from_its_file(monkeypatch, tmp_path):
    """The readers' own path on an xplane as the TPU writes it: ops
    named by their HLO text, their op_name as the metadata's ``tf_op``,
    the window from ``bench.call.0`` for the summary's ``window_s``."""
    ops = [["%while.1 = f32[8] while(...)", 1000, 100, "jit(f)/while"],
           ["%fusion.1 = f32[8] fusion(...)", 1010, 20,
            "jit(f)/vmap(jit(run))/while/body/drain/while/body/lt"],
           ["%fusion.2 = f32[8] fusion(...)", 1040, 10,
            "jit(f)/while/body/start_tasks/add"],
           ["%copy.3 = f32[8] copy(...)", 1060, 10, None],
           ["%fusion.1 = f32[8] fusion(...)", 1120, 30,
            "jit(f)/vmap(jit(run))/while/body/drain/while/body/lt"]]
    host = [["bench.call.0", 1000, 150, {}],
            ["e2c.chunk_normalize", 1100, 20, {"n_replicas": 4, "chunk": 1}],
            ["e2c.draw", 1100, 15, {}],
            ["e2c.normalize", 1500, 10, {"n_replicas": 9}]]
    out = tmp_path / "out"
    stale = out / "trace" / "other.cell" / "plugins" / "profile" / "a"
    fresh = out / "trace" / "a.cell" / "plugins" / "profile" / "b"
    for d, raw in ((stale, _xspace(ops[:1], host[:1])),
                   (fresh, _xspace(ops, host))):
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(raw)
    os.utime(stale / "host.xplane.pb", (1, 1))
    monkeypatch.setattr(H, "OUT_DIR", str(out))
    ctx = {"trace": {"window_s": 150e-9}, "tasks_traced": 10, "spans": []}
    tr = PH.from_ctx(ctx)
    assert tr["window_ns"] == (1000, 1150)
    assert tr["phase_source"] == "tf_op"
    assert tr["phases"]["/device:TPU:0"] == [
        "other", "drain", "start_tasks", "other", "drain"]
    # the loop's 100 less its children's 40; the fold's 30 is drain too
    assert PH.split(tr, *tr["window_ns"]) == pytest.approx(
        {"other": 70e-9, "drain": 50e-9, "start_tasks": 10e-9})
    assert READERS["drain_us_per_task"].read(ctx) == pytest.approx(
        1e6 * 50e-9 / 10)
    # [1100, 1120) is idle inside the chunk's normalize; the monolithic
    # normalize lies outside the window
    assert READERS["exposed_normalize_ms_per_replica"].read(
        ctx) == pytest.approx(20 / 1e6 / 4)
    assert PH.from_ctx({"trace": None}) is None


def test_load_reads_the_programs_annotations(tmp_path):
    """A trace the CPU records: the program's annotations with their
    attributes, and no device plane."""
    from repro.core import telemetry as TL
    jax.profiler.start_trace(str(tmp_path))
    with TL.span("normalize", n_replicas=5):
        jnp.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    tr = PH.load(path)
    assert [a[0] for a in tr["annotations"]] == ["e2c.normalize"]
    assert tr["annotations"][0][3]["n_replicas"] == 5
    assert tr["phase_source"] is None and not PH.scoped(tr)
