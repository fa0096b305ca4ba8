"""The reduction from a profiler trace to the per-layer metrics, on a short
window of ``detr-interactive`` recorded on a TPU v5 lite (the
``.xplane.pb`` of a ``run.py --seconds 2 --trace 1`` run, gzipped)."""
from pathlib import Path

import pytest

from benchmarks.chip import model, work, xplane
from benchmarks.chip.harness import HERE, Run, metric_reader

TRACE = Path(__file__).resolve().parent / "data" / \
    "detr-interactive.xplane.pb.gz"


@pytest.fixture(scope="module")
def summary():
    return xplane.summarize(xplane.load(TRACE))


def test_window_and_busy_time(summary):
    assert 1.9 < summary.window_s < 2.2
    assert list(summary.ops) == [0]
    assert 0 < summary.busy_s() <= summary.window_s
    assert all(summary.start <= s <= e <= summary.end
               for s, e, _ in summary.ops[0])


def test_each_forward_runs_twelve_msda_kernels(summary):
    calls = summary.msda_calls()
    assert calls and all(len(c) == 12 for c in calls)
    # the encoder's kernels take far longer than the decoder's 300 queries
    assert min(calls[0][:6]) > 10 * max(calls[0][6:])


def test_breakdown_names_ops_and_host_spans(summary):
    b = summary.breakdown()
    assert 0 < len(b["device_ops"]) <= xplane.TOP
    assert b["device_ops"][0][0].startswith("msgs_fused_packed")
    assert all(" " not in name for name, _ in b["device_ops"])
    assert all(name.startswith("bench.") or name == "host.other"
               for name, _ in b["idle_gaps"])
    idle = sum(s for _, s in b["idle_gaps"])
    assert idle == pytest.approx(summary.window_s - summary.busy_s(),
                                 abs=1e-6)


def test_per_layer_readers_on_the_trace(summary):
    m = model.load(HERE / "configs" / "deformable-detr.json")[0]
    cell = type("Cell", (), {"model": m, "traffic": {"max_batch": 1}})
    run = Run(cell=cell, seconds=summary.window_s, setup_s=0.0, t0=0.0,
              t1=summary.window_s, recs=[], memory_peak_bytes=0,
              platform="tpu", device_kind="TPU v5 lite", device_count=1,
              trace=summary)
    idle = metric_reader("device_idle_share")(run)
    assert 0 < idle < 100
    share = metric_reader("msda_roofline_share")(run)
    assert 0 < share < 100


def test_op_name_and_union():
    assert xplane.op_name("%fusion.12 = f32[4]{0} fusion(%a), kind=kLoop") \
        == "fusion.12"
    assert xplane.op_name("jit_fwd(123)") == "jit_fwd(123)"
    s = xplane.Summary(0.0, 100.0, {0: [(0.0, 10.0, "a"), (5.0, 20.0, "b"),
                                        (50.0, 60.0, "c")]},
                       [(30.0, 40.0, "bench.wait")], {0: []})
    assert s.busy_s() == pytest.approx(30e-9)
    assert s.idle_gaps() == [(pytest.approx(30e-9), "bench.wait"),
                             (pytest.approx(40e-9), "host.other")]


def test_unknown_peak_makes_the_share_an_error(summary):
    m = model.load(HERE / "configs" / "deformable-detr.json")[0]
    cell = type("Cell", (), {"model": m, "traffic": {"max_batch": 1}})
    run = Run(cell=cell, seconds=1.0, setup_s=0.0, t0=0.0, t1=1.0, recs=[],
              memory_peak_bytes=0, platform="tpu", device_kind="TPU v9",
              device_count=1, trace=summary)
    with pytest.raises(KeyError):
        metric_reader("msda_roofline_share")(run)
    assert work.peak_for("TPU v5 lite")
