"""The trace reduction on a hand-built trace whose answers are known: a
TPU plane with an ``XLA Ops`` line and a host plane with the benchmark's
spans, written as an ``.xplane.pb`` file and read back as a run reads
its own."""
from __future__ import annotations

import pytest

from benchtools import REPO

# (name, start_ns, end_ns).  The window is [0, 1000).
OPS = [("fusion.1", 100, 300), ("copy.2", 250, 400),
       ("_score_kernel.3", 600, 700), ("fusion.4", 1200, 1300)]
SPANS = [("window", 0, 1000), ("wait", 0, 90), ("recommend_batch", 90, 450),
         ("wait", 450, 550), ("predict_batch", 550, 800),
         ("add_rating", 800, 900), ("unrelated", 0, 1000)]


def _plane(pid: int, name: str, line: str, events) -> str:
    names = sorted({n for n, _, _ in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    evs = "".join(f"events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} "
                  f"duration_ps: {(e - s) * 1000} }}\n" for n, s, e in events)
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for n, i in ids.items())
    return (f'planes {{ id: {pid} name: "{name}"\n'
            f'lines {{ id: 1 name: "{line}" timestamp_ns: 0\n{evs}}}\n'
            f'{meta}}}\n')


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    from jax.profiler import ProfileData
    from bench import trace
    text = (_plane(1, "/device:TPU:0", "XLA Ops", OPS)
            + _plane(2, "/host:CPU", "python3", SPANS))
    d = tmp_path_factory.mktemp("synthetic") / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return trace.reduce_dir(d.parents[2])


def test_busy_is_the_union_inside_the_window(reduced):
    assert reduced.window == (0, 1000)
    assert reduced.busy_s == pytest.approx(400e-9)
    assert reduced.window_s == pytest.approx(1000e-9)
    assert reduced.busy_between(90, 450) == pytest.approx(300e-9)
    assert reduced.op_seconds(lambda n: "_score_kernel" in n) \
        == pytest.approx(100e-9)


def test_idle_gaps_named_after_the_enclosing_span(reduced):
    gaps = reduced.idle_gaps()
    assert [n for n, _ in gaps] == ["add_rating", "wait", "wait"]
    assert [t for _, t in gaps] == pytest.approx([300e-9, 200e-9, 100e-9])
    bd = reduced.breakdown()
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(200e-9)]
    assert {n for n, _ in bd["device_ops"]} == {o[0] for o in OPS}
    assert bd["idle_gaps"][0] == ["add_rating", pytest.approx(300e-9)]


KNN = ("%knn_scores.1 = f32[256,4096]{1,0:T(8,128)S(1)} custom-call("
       "s32[256,20]{1,0:T(8,128)S(1)} %broadcast_clamp_fusion, "
       "f32[256,20]{1,0} %w), custom_call_target=\"tpu_custom_call\"")


def _run(ops, spans, outcomes=(), n_items=3706):
    """A run record as a metric's reader gets it, around a trace built
    from (name, start_ns, end_ns) lists."""
    from bench import trace
    from bench.run import RunRecord
    return RunRecord(outcomes=list(outcomes), stats0={}, stats1={},
                     rotation_ms=[], compile={}, window_s=1.0, setup_s=1.0,
                     config={"n_items": n_items},
                     trace=trace.Trace(ops=ops, spans=spans),
                     peaks={"hbm_bytes_per_s": 819e9})


def test_knn_score_roofline_counts_the_bytes_a_call_needs():
    from bench import spec
    read = spec.reader(REPO, "knn_score_roofline")
    need = 4 * 3706 * 256 * 22 / 819e9            # seconds at the peak
    run = _run([(KNN, 0, 1_000_000), (KNN, 2_000_000, 3_000_000),
                ("%fusion.3 = f32[8]", 0, 5_000_000)],
               [("window", 0, 4_000_000)])
    assert read(run) == pytest.approx(100 * 2 * need / 2e-3)
    assert read(_run([("%fusion.3 = f32[8]", 0, 10)],
                     [("window", 0, 20)])) is None


def test_device_ms_per_request_from_its_spans():
    from bench import spec
    from bench.run import Outcome
    from bench.traffic import Request
    ops = [("%fusion.1", 10, 30), ("%fusion.2", 50, 90), ("%copy.3", 120, 125)]
    spans = [("window", 0, 200), ("onboard_user", 0, 40),
             ("onboard_user", 40, 100), ("onboard_user", 110, 130)]
    outs = [Outcome(Request(0.0, "onboard"), info={"rotated": r})
            for r in (False, True, False)]
    run = _run(ops, spans, outs)
    assert spec.reader(REPO, "onboard_device_ms")(run) \
        == pytest.approx((20 + 5) / 2 * 1e-6)
    reads = [("window", 0, 200), ("recommend_batch", 0, 60),
             ("predict_batch", 100, 200)]
    assert spec.reader(REPO, "read_device_ms")(_run(ops, reads)) \
        == pytest.approx((30 + 5) / 2 * 1e-6)
    assert spec.reader(REPO, "read_device_ms")(_run(ops, spans)) is None
