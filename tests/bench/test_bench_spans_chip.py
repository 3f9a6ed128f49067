"""The program-span reduction (``bench/spans.py``) on traces recorded on
one TPU v5e, 0.5 s traced windows of ``ml1m.read_zipf`` (seed 3000000019):
one of a server without the ``cf.*`` spans, whose readings of
``bench/trace.py`` are pinned in ``test_bench_trace_chip.py`` and must
read the same through the extended reduction, and one of a server with
them."""
from __future__ import annotations

import gzip

import pytest

from benchtools import REPO

DATA = REPO / "tests/bench/data"
OLD = DATA / "ml1m_read_zipf_0.5s.xplane.pb.gz"
NEW = DATA / "ml1m_read_zipf_0.5s_spans.xplane.pb.gz"
READ_STAGES = ("cf.read.validate", "cf.read.probe", "cf.read.dedup",
               "cf.read.score", "cf.read.fanout")


def _reduce(tmp_path_factory, gz):
    from bench import spans
    d = tmp_path_factory.mktemp("chip") / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "t.xplane.pb").write_bytes(gzip.decompress(gz.read_bytes()))
    return spans.reduce_dir(d.parents[2])


@pytest.fixture(scope="module")
def old(tmp_path_factory):
    return _reduce(tmp_path_factory, OLD)


@pytest.fixture(scope="module")
def new(tmp_path_factory):
    return _reduce(tmp_path_factory, NEW)


def _run(t):
    from bench.run import RunRecord
    return RunRecord(outcomes=[], stats0={}, stats1={}, rotation_ms=[],
                     compile={}, window_s=t.window_s, setup_s=0.0,
                     config={"n_items": 3706}, trace=t,
                     peaks={"hbm_bytes_per_s": 819e9})


def test_old_trace_reads_as_before(old):
    from bench import spans, spec
    assert old.window_s == pytest.approx(0.499263804, rel=1e-6)
    assert old.busy_s == pytest.approx(0.068315553, rel=1e-6)
    assert spec.reader(REPO, "knn_score_roofline")(_run(old)) \
        == pytest.approx(9.868623219818321, rel=1e-9)
    assert spec.reader(REPO, "read_device_ms")(_run(old)) \
        == pytest.approx(1.85518585, rel=1e-6)
    assert old.program_spans == []
    readings = {k: f(old) for k, f in spans.READINGS.items()}
    # 43.6 ms of jit_add_rating over the window's 20 add_rating requests
    assert readings.pop("add_rating_device_ms") \
        == pytest.approx(2.1812398, rel=1e-6)
    assert set(readings.values()) == {None}
    assert "jit__lambda" in {n for n, _, _ in old.modules}


def test_new_trace_readings(new):
    from bench import spans
    r = {k: f(new) for k, f in spans.READINGS.items()}
    assert r.pop("health_check_ms") is None      # a read cell onboards none
    assert all(v is not None and v > 0 for v in r.values()), r


def test_read_stages_cover_every_read(new):
    for name in ("recommend_batch", "predict_batch"):
        reqs = new.named(name)
        assert reqs
        for _, s, e in reqs:
            within = new.stages_within(s, e)
            assert set(READ_STAGES) <= set(within)
            covered = sum(within[k] for k in READ_STAGES)
            assert covered >= 0.9 * (e - s) * 1e-9, (name, within)


def test_every_program_has_a_name(new):
    names = {n for n, _, _ in new.modules}
    assert "jit__lambda" not in names
    assert {"jit_probe_recommend", "jit_score_recommend",
            "jit_add_rating"} <= names


def test_read_gaps_named_by_stage(new):
    inside = [(n, t) for n, t in new.idle_gaps()
              if n.split("/")[0] in ("recommend_batch", "predict_batch")]
    assert inside
    staged = sum(t for n, t in inside if "/cf.read." in n)
    assert staged >= 0.9 * sum(t for _, t in inside)
