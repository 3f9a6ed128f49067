"""The program-span reduction (``bench/spans.py``) on a hand-built trace
whose answers are known: a TPU plane with ``XLA Ops`` and ``XLA Modules``
lines and a host plane with the benchmark's request spans and the
server's ``cf.*`` spans, written as an ``.xplane.pb`` file and read back
as a run reads its own."""
from __future__ import annotations

from pathlib import Path

import pytest

from benchtools import REPO  # noqa: F401  (puts the checkout on sys.path)

# (name, start_ns, end_ns).  The window is [0, 1000).
OPS = [("%fusion.1", 0, 50), ("%fusion.2", 120, 300), ("%copy.3", 600, 700),
       ("%fusion.4", 850, 880)]
MODULES = [("jit_onboard_twinsearch(11)", 0, 50),
           ("jit_onboard_twinsearch(11)", 120, 300),
           ("jit_add_rating(7)", 600, 700), ("jit_add_rating(7)", 850, 880),
           ("jit_add_rating(7)", 1100, 1200)]          # after the window
SPANS = [("window", 0, 1000), ("onboard_user", 0, 500),
         ("recommend_batch", 500, 560), ("add_rating", 560, 800),
         ("add_rating", 800, 1000)]
PROGRAM = [("cf.onboard.rotate", 40, 320), ("cf.wal.append", 60, 110),
           ("cf.onboard.run", 320, 420), ("cf.health_check", 420, 480),
           ("cf.read.validate", 500, 510), ("cf.read.dedup", 520, 530),
           ("cf.read.fanout", 540, 556), ("cf.wal.append", 560, 590),
           ("cf.add_rating.apply", 590, 610), ("cf.wal.append", 800, 810)]


def _plane(pid: int, name: str, lines: dict) -> str:
    names = sorted({n for evs in lines.values() for n, _, _ in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    text = f'planes {{ id: {pid} name: "{name}"\n'
    for k, (line, evs) in enumerate(lines.items()):
        text += f'lines {{ id: {k + 1} name: "{line}" timestamp_ns: 0\n'
        text += "".join(f"events {{ metadata_id: {ids[n]} offset_ps: "
                        f"{s * 1000} duration_ps: {(e - s) * 1000} }}\n"
                        for n, s, e in evs)
        text += "}\n"
    text += "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }}\n' for n, i in ids.items())
    return text + "}\n"


def _write(tmp_path_factory, host_events) -> Path:
    from jax.profiler import ProfileData
    text = (_plane(1, "/device:TPU:0", {"XLA Modules": MODULES,
                                        "XLA Ops": OPS})
            + _plane(2, "/host:CPU", {"python3": host_events}))
    d = tmp_path_factory.mktemp("spans") / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return d.parents[2]


@pytest.fixture(scope="module")
def traces(tmp_path_factory):
    from bench import spans, trace
    with_spans = _write(tmp_path_factory, SPANS + PROGRAM)
    without = _write(tmp_path_factory, SPANS)
    return {"spans": spans.reduce_dir(with_spans),
            "plain": trace.reduce_dir(with_spans),
            "none": spans.reduce_dir(without)}


def test_what_trace_reads_is_unchanged(traces):
    t, plain = traces["spans"], traces["plain"]
    assert (t.ops, t.spans, t.window, t.n_devices) \
        == (plain.ops, plain.spans, plain.window, plain.n_devices)
    assert t.busy_s == plain.busy_s == pytest.approx(360e-9)
    assert t.breakdown()["device_ops"] == plain.breakdown()["device_ops"]
    assert [n for n, _ in plain.idle_gaps()] \
        == ["onboard_user", "add_rating", "add_rating", "onboard_user"]


def test_program_spans_and_modules_are_kept(traces):
    t = traces["spans"]
    assert [s[0] for s in t.program_spans] == [s[0] for s in PROGRAM]
    assert {n for n, _, _ in t.modules} == {"jit_onboard_twinsearch",
                                            "jit_add_rating"}
    assert t.module_seconds("jit_add_rating") == pytest.approx(130e-9)
    assert traces["none"].program_spans == []


def test_gaps_named_by_the_innermost_stage(traces):
    gaps = traces["spans"].idle_gaps()
    # [300, 600): its middle, 450, is in cf.health_check; [700, 850) and
    # [880, 1000) are in add_rating requests, in no stage; [50, 120) lies
    # in cf.wal.append inside cf.onboard.rotate
    assert [n for n, _ in gaps] == [
        "onboard_user/cf.health_check", "add_rating", "add_rating",
        "onboard_user/cf.wal.append"]
    assert [t for _, t in gaps] == pytest.approx(
        [300e-9, 150e-9, 120e-9, 70e-9])
    assert [n for n, _ in traces["none"].idle_gaps()] \
        == [n for n, _ in traces["plain"].idle_gaps()]


def test_readings(traces):
    from bench import spans
    t = traces["spans"]
    r = {k: f(t) for k, f in spans.READINGS.items()}
    assert r["wal_append_ms"] == pytest.approx((50 + 30 + 10) / 3 * 1e-6)
    assert r["health_check_ms"] == pytest.approx(60e-6)     # one onboard
    assert r["read_validate_ms"] == pytest.approx(10e-6)    # one read
    assert r["read_dedup_ms"] == pytest.approx(10e-6)
    assert r["read_fanout_ms"] == pytest.approx(16e-6)
    assert r["add_rating_device_ms"] == pytest.approx(130e-6 / 2)
    none = {k: f(traces["none"]) for k, f in spans.READINGS.items()}
    assert none.pop("add_rating_device_ms") == pytest.approx(130e-6 / 2)
    assert set(none.values()) == {None}
    assert {f(traces["plain"]) for f in spans.READINGS.values()} == {None}


def test_request_split(traces):
    from bench import spans
    split = spans.request_split(traces["spans"])
    rec = split["recommend_batch"]
    assert rec["n"] == 1 and rec["mean_ms"] == pytest.approx(60e-6)
    assert rec["stage_ms"]["cf.read.fanout"] == pytest.approx(16e-6)
    assert rec["no_stage_ms"] == pytest.approx(24e-6)
    assert rec["min_cover"] == pytest.approx(36 / 60)
    onboard = split["onboard_user"]
    # the nested WAL append counts under its own name, once in the union
    assert onboard["stage_ms"]["cf.wal.append"] == pytest.approx(50e-6)
    assert onboard["stage_ms"]["(any stage)"] == pytest.approx(440e-6)
    assert split["add_rating"]["n"] == 2
