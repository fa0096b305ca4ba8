"""What the metrics of the program's layers read (``layers.py``), on
synthetic traces and on the tiny engine's compiled forward, without a
chip."""
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.chip import layers, xplane
from benchmarks.chip.harness import Rec, Run, metric_reader


def _run(ops, modules, recs=(), t0=0.0, seconds=1.0):
    summary = xplane.Summary(0.0, seconds * 1e9, {0: sorted(ops)}, [],
                             {0: sorted(modules)})
    return Run(cell=None, seconds=seconds, setup_s=0.0, t0=t0,
               t1=t0 + seconds, recs=list(recs), memory_peak_bytes=0,
               platform="tpu", device_kind="TPU v5 lite", device_count=1,
               trace=summary)


def _rec(i, due, t_dispatch, step, t_dispatched, t_done, bucket=512):
    req = SimpleNamespace(done=True, t_done=t_done, step=step,
                          t_dispatched=t_dispatched, bucket=bucket)
    return Rec(index=i, image=None, due=due, t_dispatch=t_dispatch, req=req)


def test_overlapping_ops_count_once_and_scopes_add_up_to_busy():
    ops = [(0.0, 10.0, "a"), (5.0, 20.0, "b"), (30.0, 40.0, "msgs_x.1"),
           (50.0, 60.0, "copy.1")]
    run = _run(ops, [])
    sec = layers.op_seconds(run.trace)
    assert sec == pytest.approx({"a": 10e-9, "b": 10e-9, "msgs_x.1": 10e-9,
                                 "copy.1": 10e-9})
    scopes = {"a": "encoder/block_0/msda/points", "b": "encoder/block_0/ffn",
              "msgs_x.1": "encoder/block_0/msda/sample/msgs_x", "copy.1": ""}
    ds = layers.device_scopes(run.trace, scopes)
    assert ds == pytest.approx({"encoder/block_0": 30e-9, "unscoped": 10e-9})
    assert sum(ds.values()) == pytest.approx(run.trace.busy_s())
    # the MSDA XLA work is every msda op but the kernel
    picked = [n for n in sec if layers.msda_xla(n, scopes[n])]
    assert picked == ["a"]


def test_request_split_pairs_dispatches_with_runs_in_order():
    # two dispatches (steps 7 and 8, the second of two requests) and the
    # two runs of the forward; a third dispatch whose run ends after the
    # window is left out
    runs = [(2e8, 5e8, "jit_fwd(1)"), (5e8, 8e8, "jit_fwd(1)")]
    recs = [_rec(0, 0.0, 0.05, 7, 0.1, 0.52),
            _rec(1, 0.1, 0.15, 8, 0.2, 0.81), _rec(2, 0.1, 0.15, 8, 0.2, 0.81),
            _rec(3, 0.8, 0.85, 9, 0.9, 1.3)]
    rows = layers.request_split(_run([(2e8, 8e8, "f")], runs, recs))
    assert [r.index for r, _ in rows] == [0, 1, 2]
    host_q, dispatch, dev_q, dev_run, back = rows[0][1]
    assert (host_q, dispatch, dev_q, dev_run, back) == pytest.approx(
        (0.05, 0.05, 0.1, 0.3, 0.02))
    assert rows[1][1][2] == pytest.approx(0.3)   # behind the first run
    for r, parts in rows:
        assert sum(parts) == pytest.approx(r.t_done - r.due)
    assert "within 1 ms of the latency for 3 of 3" in layers.split_line(rows)


def test_a_run_that_starts_before_its_dispatch_returns_ends_the_dispatch():
    """An idle device starts the run while the launching call returns:
    the dispatch stage stops at the run's start, the device queue is 0."""
    runs = [(2e8, 5e8, "jit_fwd(1)")]
    recs = [_rec(0, 0.0, 0.05, 7, 0.25, 0.52)]
    (r, parts), = layers.request_split(_run([(2e8, 5e8, "f")], runs, recs))
    assert parts == pytest.approx((0.05, 0.15, 0.0, 0.3, 0.02))
    assert "1 runs began before their dispatch returned" in \
        layers.split_line([(r, parts)])
    assert metric_reader("device_queue_ms_p50")(
        _run([(2e8, 5e8, "f")], runs, recs)) == 0.0


def test_request_split_refuses_pairings_that_cannot_be():
    runs = [(2e8, 5e8, "jit_fwd(1)"), (5e8, 8e8, "jit_fwd(1)")]
    one = [_rec(0, 0.0, 0.05, 7, 0.1, 0.52)]
    with pytest.raises(ValueError, match="2 runs"):
        layers.request_split(_run([(2e8, 8e8, "f")], runs, one))
    late = [_rec(0, 0.0, 0.3, 7, 0.35, 0.52), _rec(1, 0.0, 0.4, 8, 0.45, 0.9)]
    with pytest.raises(ValueError, match="before its dispatch"):
        layers.request_split(_run([(2e8, 8e8, "f")], runs, late))


def test_readers_read_nothing_from_a_program_without_stamps():
    """An older program's requests carry no ``step``: the serving-engine
    readers return None rather than raise."""
    rec = Rec(index=0, image=None, due=0.0, t_dispatch=0.1,
              req=SimpleNamespace(done=True, t_done=0.5, bucket=512))
    run = _run([(2e8, 4e8, "f")], [(2e8, 4e8, "jit_fwd(1)")], [rec])
    assert metric_reader("device_queue_ms_p50")(run) is None
    assert metric_reader("readback_ms_p90")(run) is None


def test_op_scopes_finds_the_forward_among_live_executables():
    """The window's operations are matched to the compiled bucket that
    holds them, here the tiny engine's 32-px forward."""
    from tests.test_obs import _tiny_engine
    engine = _tiny_engine()
    want, other = engine.op_scopes(32), engine.op_scopes(64)
    names = sorted(n for n in want if n not in other)[:20]
    assert names
    ops = [(float(i), i + 0.5, n) for i, n in enumerate(names)]
    run = _run(ops, [(0.0, 30.0, "jit_fwd(5)")])
    done = Rec(index=0, image=None, req=SimpleNamespace(done=True,
                                                         t_done=0.5))
    run.recs.append(done)
    assert layers.op_scopes(run) == want
    ms = layers.ms_per_image(run, lambda n, p: True)
    assert ms == pytest.approx(len(ops) * 0.5e-9 * 1e3)
    assert np.isfinite(metric_reader("encoder_ms_per_image")(run))
    engine.close()
