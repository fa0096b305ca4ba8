"""The layers' metrics on a short window of ``detr-interactive`` recorded on
a TPU v5 lite by a program that names its layers (``jax.named_scope``)
and puts its engine spans on the profiler's trace: the ``.xplane.pb`` of
a 2-second traced run, gzipped, with the request stamps and the scope map
of the operations it ran beside it (``detr-interactive-scoped.json``).
The reduction that ``test_bench_xplane.py`` checks on the older trace
holds on this one too."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks.chip import layers, xplane
from benchmarks.chip.harness import Rec, Run, metric_reader
from tests.bench_chip import test_bench_xplane

DATA = Path(__file__).resolve().parent / "data"
TRACE = DATA / "detr-interactive-scoped.xplane.pb.gz"
STAMPS = json.loads((DATA / "detr-interactive-scoped.json").read_text())


@pytest.fixture(scope="module")
def summary():
    return xplane.summarize(xplane.load(TRACE))


@pytest.fixture(scope="module")
def run(summary):
    recs = [Rec(index=r["index"], image=None, due=r["due"],
                t_dispatch=r["t_dispatch"], batch=r["batch"],
                req=SimpleNamespace(done=r["done"], t_done=r["t_done"],
                                    step=r["step"], bucket=r["bucket"],
                                    t_dispatched=r["t_dispatched"]))
            for r in STAMPS["recs"]]
    return Run(cell=None, seconds=STAMPS["seconds"], setup_s=0.0,
               t0=STAMPS["t0"], t1=STAMPS["t1"], recs=recs,
               memory_peak_bytes=0, platform="tpu",
               device_kind="TPU v5 lite", device_count=1, trace=summary)


@pytest.mark.parametrize("check", [
    test_bench_xplane.test_window_and_busy_time,
    test_bench_xplane.test_each_forward_runs_twelve_msda_kernels,
    test_bench_xplane.test_breakdown_names_ops_and_host_spans,
    test_bench_xplane.test_per_layer_readers_on_the_trace,
    test_bench_xplane.test_unknown_peak_makes_the_share_an_error,
], ids=lambda f: f.__name__)
def test_the_older_reduction_holds(check, summary):
    check(summary)


def test_scopes_add_up_to_busy_time(summary):
    scopes = STAMPS["op_scopes"]
    ds = layers.device_scopes(summary, scopes)
    assert sum(ds.values()) == pytest.approx(summary.busy_s(), abs=1e-6)
    assert ds.get("unscoped", 0.0) < 0.05 * summary.busy_s()
    assert {f"encoder/block_{i}" for i in range(6)} \
        | {f"decoder/layer_{j}" for j in range(6)} <= ds.keys()


def test_layer_readers_on_the_trace(run, monkeypatch):
    monkeypatch.setattr(layers, "op_scopes", lambda _: STAMPS["op_scopes"])
    enc = metric_reader("encoder_ms_per_image")(run)
    dec = metric_reader("decoder_ms_per_image")(run)
    xla = metric_reader("msda_xla_ms_per_image")(run)
    busy = metric_reader("device_ms_per_image")(run)
    assert 0 < dec < xla < enc < busy


def test_every_dispatch_of_the_window_has_its_run(run, summary):
    rows = layers.request_split(run)
    runs = summary.modules[0]
    assert rows and len({r.req.step for r, _ in rows}) == len(runs)
    assert metric_reader("device_queue_ms_p50")(run) >= 0
    assert metric_reader("readback_ms_p90")(run) >= 0
    # the stages cut each latency into parts; here the device was idle, so
    # each run began before its dispatch returned
    for r, parts in rows:
        assert sum(parts) == pytest.approx(r.t_done - r.due, abs=1e-6)
        assert min(parts) >= 0 and parts[2] == 0.0
    assert "runs began before their dispatch returned" in \
        layers.split_line(rows)


def test_stamps_lie_on_the_trace_clock(run):
    """The end of each ``serve.dispatch`` host event, with its ``step``,
    is its requests' ``t_dispatched`` moved onto the trace's clock by the
    window (within a millisecond)."""
    ends = {}
    for plane in xplane.load(TRACE).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "serve.dispatch":
                    stats = dict(ev.stats)
                    assert {"step", "n", "bucket", "inflight"} <= stats.keys()
                    ends[stats["step"]] = ev.start_ns + ev.duration_ns
    stamped = [r for r in run.recs if run.t0 <= r.t_dispatch]
    assert stamped and all(r.req.step in ends for r in stamped)
    for r in stamped:
        on_trace = run.trace.start + (r.req.t_dispatched - run.t0) * 1e9
        assert abs(on_trace - ends[r.req.step]) < 1e6
