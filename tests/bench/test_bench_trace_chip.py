"""The trace reduction and the device-trace readers on a trace recorded
on one TPU v5e: a 0.5 s traced window of ``ml1m.read_zipf`` (seed
3000000019), whose run printed busy 0.068315553 s of a 0.499263804 s
window, ``read_device_ms`` 1.85518585 and ``knn_score_roofline``
9.868623219818321."""
from __future__ import annotations

import gzip

import pytest

from benchtools import REPO

TRACE = REPO / "tests/bench/data/ml1m_read_zipf_0.5s.xplane.pb.gz"


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    from bench import trace
    d = tmp_path_factory.mktemp("chip") / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "t.xplane.pb").write_bytes(gzip.decompress(TRACE.read_bytes()))
    return trace.reduce_dir(d.parents[2])


def test_busy_and_window_as_the_run_read_them(chip_trace):
    assert chip_trace.n_devices == 1
    assert chip_trace.window_s == pytest.approx(0.499263804, rel=1e-6)
    assert chip_trace.busy_s == pytest.approx(0.068315553, rel=1e-6)
    counts = {n: len(chip_trace.named(n)) for n in
              ("window", "recommend_batch", "predict_batch", "add_rating")}
    assert counts == {"window": 1, "recommend_batch": 10,
                      "predict_batch": 10, "add_rating": 20}
    gaps = chip_trace.idle_gaps()
    assert gaps and all(t > 0 for _, t in gaps)
    assert sum(t for _, t in gaps) == pytest.approx(
        chip_trace.window_s - chip_trace.busy_s, rel=1e-6)


def test_readers_on_the_chip_trace(chip_trace):
    from bench import spec
    from bench.run import RunRecord
    run = RunRecord(outcomes=[], stats0={}, stats1={}, rotation_ms=[],
                    compile={}, window_s=chip_trace.window_s, setup_s=0.0,
                    config={"n_items": 3706}, trace=chip_trace,
                    peaks={"hbm_bytes_per_s": 819e9})
    assert spec.reader(REPO, "knn_score_roofline")(run) \
        == pytest.approx(9.868623219818321, rel=1e-9)
    assert spec.reader(REPO, "read_device_ms")(run) \
        == pytest.approx(1.85518585, rel=1e-6)
    assert any(name.startswith("%knn_scores") for name, _, _ in chip_trace.ops)
