"""Serve-engine tests: bucket routing (exact fit vs pad-up), admission
rejection, mixed-load bit-parity with the single-shape synchronous
engine, the AOT compile-count spy (zero recompiles after warmup),
starvation reporting, post-processing (top-k decode, callbacks, worker
exception propagation), and streaming session churn accounting."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import msda
from repro.core.msdeform_attn import MSDeformAttnConfig
from repro.serve.buckets import BucketRouter, derive_buckets
from repro.serve.engine import DetrRequest, DetrServeEngine
from repro.serve.postproc import (PostprocWorker, StarvationError,
                                  softmax_np, topk_detections)


def _tiny_cfg():
    from repro.core.detector import DetectorConfig
    from repro.core.encoder import EncoderConfig
    attn = MSDeformAttnConfig(d_model=32, n_heads=2, n_levels=4, n_points=2,
                              fwp_mode="compact", fwp_k=1.0,
                              fwp_capacity=0.6,
                              range_narrow=(8.0, 6.0, 4.0, 3.0))
    return DetectorConfig(
        encoder=EncoderConfig(attn=attn, n_blocks=1, d_ffn=64),
        img_size=32, n_classes=3, backbone_width=8,
        decoder=msda.MSDADecoderConfig(n_layers=2, n_queries=8, d_ffn=32))


def _params(cfg):
    from repro.core.detector import init_detector
    return init_detector(jax.random.PRNGKey(1), cfg)


def _images(n, size, key=2):
    from repro.data.detection import synth_detection_batch
    shapes = tuple((size // k, size // k) for k in (4, 8, 16, 32))
    img, _, _, _ = synth_detection_batch(jax.random.PRNGKey(key), n, size,
                                         shapes)
    return np.asarray(img)


# --------------------------------------------------------------------------
# bucket derivation + routing
# --------------------------------------------------------------------------

def test_bucket_routing_exact_fit_and_pad_up():
    cfg = _tiny_cfg()
    router = BucketRouter(derive_buckets(cfg, (64, 32)))
    assert [b.resolution for b in router.buckets] == [32, 64]
    assert router.route(32, 32).resolution == 32       # exact fit
    assert router.route(20, 30).resolution == 32       # pad up, same bucket
    assert router.route(33, 8).resolution == 64        # one dim overflows
    assert router.route(64, 64).resolution == 64
    assert router.route(65, 10) is None                # oversized
    # per-bucket plans carry the bucket's pyramid
    b32, b64 = router.buckets
    assert b32.level_shapes == ((8, 8), (4, 4), (2, 2), (1, 1))
    assert b64.level_shapes == ((16, 16), (8, 8), (4, 4), (2, 2))
    assert b64.n_in == 4 * b32.n_in
    # derivation is memoized per shape: same plan object on re-derive
    again = derive_buckets(cfg, (32, 64))
    assert again[0].plan is b32.plan and again[1].plan is b64.plan
    # resolutions must divide the pyramid strides
    with pytest.raises(ValueError, match="stride"):
        derive_buckets(cfg, (48,))
    # admission validation
    _, reason = router.admit(np.zeros((1, 8, 8), np.float32))
    assert "(3, H, W)" in reason
    _, reason = router.admit(np.zeros((3, 0, 8), np.float32))
    assert "degenerate" in reason
    _, reason = router.admit(np.zeros((3, 65, 8), np.float32))
    assert "exceeds the largest bucket" in reason
    table = router.table()
    assert [row["resolution"] for row in table] == [32, 64]
    assert all(row["table_kb"] > 0 for row in table)


def test_oversized_request_rejected_not_served():
    cfg = _tiny_cfg()
    engine = DetrServeEngine(cfg, _params(cfg), max_batch=2,
                             resolutions=(32,), pipeline_postproc=False)
    ok_req = DetrRequest(rid=0, image=_images(1, 32)[0])
    big_req = DetrRequest(rid=1, image=np.zeros((3, 48, 48), np.float32))
    assert engine.submit(ok_req) is True
    assert engine.submit(big_req) is False
    assert big_req.error is not None and "48x48" in big_req.error
    done = engine.run_until_drained()
    assert [r.rid for r in done] == [0]
    assert engine.rejected == [big_req] and not big_req.done


# --------------------------------------------------------------------------
# mixed load: bit-parity, compile spy, starvation
# --------------------------------------------------------------------------

def test_same_shape_workload_bit_identical_to_single_bucket_sync():
    """On a same-shape workload the bucketed, pipelined engine must be
    BIT-identical to the single-shape synchronous engine: routing and the
    postproc thread change scheduling, never results."""
    cfg = _tiny_cfg()
    params = _params(cfg)
    imgs = _images(5, 32)
    sync = DetrServeEngine(cfg, params, max_batch=2, resolutions=(32,),
                           pipeline_postproc=False)
    piped = DetrServeEngine(cfg, params, max_batch=2, resolutions=(32, 64),
                            pipeline_postproc=True)
    for eng in (sync, piped):
        for i in range(len(imgs)):
            assert eng.submit(DetrRequest(rid=i, image=imgs[i]))
        eng.run_until_drained()
    by_rid = lambda eng: {r.rid: r for r in eng.finished}
    a, b = by_rid(sync), by_rid(piped)
    assert set(a) == set(b) == set(range(len(imgs)))
    for rid in a:
        assert b[rid].bucket == 32                  # routed, not padded up
        np.testing.assert_array_equal(a[rid].cls_probs, b[rid].cls_probs)
        np.testing.assert_array_equal(a[rid].boxes, b[rid].boxes)
        np.testing.assert_array_equal(a[rid].detections["scores"],
                                      b[rid].detections["scores"])
    piped.close()


def test_aot_buckets_zero_recompiles_under_mixed_load():
    """All compilation happens at engine construction; a mixed-resolution
    load (exact fits, pad-ups, short batches) must never retrace."""
    cfg = _tiny_cfg()
    engine = DetrServeEngine(cfg, _params(cfg), max_batch=2,
                             resolutions=(32, 64))
    assert engine.compile_count == len(engine.buckets) == 2
    # compile_count is a view over the registry counter: one labelled
    # series per bucket, each bumped exactly once at trace time
    m = engine.obs.metrics.get("msda_compiles_total")
    assert m.total() == 2
    assert m.value(bucket="32") == 1 and m.value(bucket="64") == 1
    imgs32, imgs64 = _images(3, 32), _images(2, 64)
    rid = 0
    for im in list(imgs32) + list(imgs64):
        assert engine.submit(DetrRequest(rid=rid, image=im))
        rid += 1
    # pad-up: odd sizes land in the 32/64 buckets
    for h, w in ((20, 28), (40, 64)):
        assert engine.submit(DetrRequest(
            rid=rid, image=imgs64[0][:, :h, :w].copy()))
        rid += 1
    done = engine.run_until_drained()
    assert len(done) == rid
    assert engine.compile_count == 2, "mixed load recompiled"
    assert engine.obs.metrics.get("msda_compiles_total").total() == 2
    assert sorted(r.rid for r in done) == list(range(rid))
    for r in done:
        assert r.cls_probs.shape == (8, cfg.n_classes + 1)
        assert np.all(np.isfinite(r.cls_probs))
    engine.close()


def test_run_until_drained_raises_starvation_report():
    cfg = _tiny_cfg()
    engine = DetrServeEngine(cfg, _params(cfg), max_batch=2,
                             resolutions=(32,), pipeline_postproc=False)
    for i in range(5):
        engine.submit(DetrRequest(rid=i, image=_images(1, 32, key=i)[0]))
    with pytest.raises(StarvationError) as ei:
        engine.run_until_drained(max_steps=1)
    rep = ei.value.report
    assert rep["queued"] == {32: 3} and rep["finished"] == 2
    # nothing was dropped: a follow-up drain completes the backlog
    done = engine.run_until_drained()
    assert sorted(r.rid for r in done) == list(range(5))


# --------------------------------------------------------------------------
# post-processing stage
# --------------------------------------------------------------------------

def test_topk_detections_and_callbacks():
    probs = softmax_np(np.asarray([[9.0, 0.0, -9.0],     # class 0
                                   [0.0, 9.0, -9.0],     # class 1
                                   [-9.0, -9.0, 9.0]]))  # background
    boxes = np.tile(np.asarray([[0.5, 0.5, 0.2, 0.2]]), (3, 1))
    det = topk_detections(probs, boxes, k=2)
    assert list(det["labels"]) == [0, 1]                 # background excluded
    assert det["scores"][0] >= det["scores"][1]
    assert det["boxes"].shape == (2, 4)
    cfg = _tiny_cfg()
    engine = DetrServeEngine(cfg, _params(cfg), max_batch=2,
                             resolutions=(32,), topk=3)
    fired = []
    for i in range(2):
        engine.submit(DetrRequest(rid=i, image=_images(2, 32)[i],
                                  callback=lambda r: fired.append(r.rid)))
    done = engine.run_until_drained()
    assert sorted(fired) == [0, 1]
    for r in done:
        assert len(r.detections["scores"]) == 3
        assert r.t_done >= r.t_submit > 0
    engine.close()


def test_postproc_worker_propagates_exceptions():
    def boom(item):
        raise ValueError("decode failed")
    w = PostprocWorker(boom, pipelined=True)
    w.submit(("x",))
    with pytest.raises(ValueError, match="decode failed"):
        w.drain()
    w.close()


# --------------------------------------------------------------------------
# streaming session churn: no frame dropped, none served twice
# --------------------------------------------------------------------------

def test_streaming_session_churn_accounting():
    from repro.serve.engine import StreamingDetrEngine
    from repro.stream import StreamConfig, drifting_scene
    levels = ((8, 10), (4, 5), (2, 3))
    attn = MSDeformAttnConfig(d_model=32, n_heads=4, n_levels=len(levels),
                              fwp_mode="compact", fwp_k=1.0, fwp_capacity=0.6,
                              range_narrow=(4.0, 3.0, 2.0))
    dec = msda.MSDADecoderConfig(n_layers=2, n_queries=8, d_ffn=32)
    key = jax.random.PRNGKey(3)
    d = attn.d_model
    params = {
        "decoder": msda.init_decoder(key, dec, attn),
        "cls_head": {"w": jax.random.normal(jax.random.fold_in(key, 1),
                                            (d, 3)) * 0.1,
                     "b": jnp.zeros((3,))},
        "box_head": {"w": jax.random.normal(jax.random.fold_in(key, 2),
                                            (d, 4)) * 0.1,
                     "b": jnp.zeros((4,))},
    }
    engine = StreamingDetrEngine(
        attn, dec, params, levels, max_sessions=2,
        stream_cfg=StreamConfig(tile_rows=1, delta_threshold=1e-4,
                                update_frac=0.9),
        update_fwp=False)
    scene = drifting_scene(3, levels, d, 6, batch=2)
    submitted = {}
    s0 = engine.open_session()
    s1 = engine.open_session()
    for t in range(2):
        engine.submit_frame(s0, scene[t][0])
        engine.submit_frame(s1, scene[t][1])
    submitted[s0], submitted[s1] = 2, 2
    engine.run_until_drained()
    closed = engine.close_session(s1)          # churn: leave mid-load...
    s2 = engine.open_session()                 # ...and a new session joins
    for t in range(2, 4):
        engine.submit_frame(s0, scene[t][0])
        engine.submit_frame(s2, scene[t][1])
    submitted[s0] += 2
    submitted[s2] = 2
    engine.run_until_drained()
    done = {s.sid: s.frames_done for s in engine.sessions.values()}
    done[closed.sid] = closed.frames_done
    assert done == submitted                   # no frame dropped/duplicated
    assert sum(len(s.queue) for s in engine.sessions.values()) == 0
    for sess in list(engine.sessions.values()) + [closed]:
        frames = [r["frame"] for r in sess.results]
        assert frames == list(range(len(frames)))   # each served once
    # a starved drain reports instead of silently returning
    engine.submit_frame(s0, scene[4][0])
    with pytest.raises(StarvationError) as ei:
        engine.run_until_drained(max_steps=0)
    assert ei.value.report["queued"] == {s0: 1}
    engine.run_until_drained()


def test_starvation_error_is_runtime_error_with_report():
    from repro.serve.lm import ServeEngine  # noqa: F401 — import side check
    err = StarvationError({"queued": 3})
    assert isinstance(err, RuntimeError)
    assert err.report["queued"] == 3 and "queued=3" in str(err)
    # the report is stamped (wall clock for logs, perf_counter to line up
    # with span data) unless the caller already supplied the keys
    assert err.report["wall_time"] > 0
    assert err.report["t_monotonic"] > 0


def test_starvation_error_reports_most_starved_age():
    err = StarvationError({"queued": {32: 3, 64: 1},
                           "oldest_age_s": {32: 1.25, 64: 0.5}})
    assert "most-starved request (queue 32) has waited 1.250s" in str(err)


# --------------------------------------------------------------------------
# worker / engine lifecycle (close joins the thread, submit-after-close)
# --------------------------------------------------------------------------

def test_postproc_worker_close_joins_and_rejects_submit():
    seen = []
    w = PostprocWorker(seen.append, pipelined=True)
    for i in range(3):
        w.submit(i)
    w.close()
    # FIFO queue + trailing stop sentinel: close() drained the backlog
    assert seen == [0, 1, 2]
    assert w._thread is None
    with pytest.raises(RuntimeError, match="closed"):
        w.submit(3)
    assert seen == [0, 1, 2]
    w.close()                                     # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        w.submit(4)


def test_postproc_worker_sync_mode_close_rejects_submit():
    seen = []
    w = PostprocWorker(seen.append, pipelined=False)
    w.submit(0)
    w.close()
    with pytest.raises(RuntimeError, match="closed"):
        w.submit(1)
    assert seen == [0]


def test_postproc_worker_context_manager():
    seen = []
    with PostprocWorker(seen.append, pipelined=True) as w:
        w.submit("a")
    assert seen == ["a"] and w._thread is None
    with pytest.raises(RuntimeError, match="closed"):
        w.submit("b")


def test_postproc_worker_submit_after_crash_raises():
    def boom(item):
        raise ValueError("decode failed")
    w = PostprocWorker(boom, pipelined=True)
    w.submit(("x",))
    with pytest.raises(ValueError, match="decode failed"):
        w.drain()
    # the crash surfaces on submit too — never enqueue after a dead loop
    with pytest.raises(ValueError, match="decode failed"):
        w.submit(("y",))
    w.close()


def test_engine_close_joins_worker_thread():
    cfg = _tiny_cfg()
    engine = DetrServeEngine(cfg, _params(cfg), max_batch=2,
                             resolutions=(32,))
    thread = engine._post._thread
    assert thread is not None and thread.is_alive()
    engine.submit(DetrRequest(rid=0, image=_images(1, 32)[0]))
    engine.step()
    engine.close()
    assert not thread.is_alive()                 # daemon joined, not leaked
    assert engine._post._thread is None
    assert [r.rid for r in engine.finished] == [0]   # close drained postproc
    with pytest.raises(RuntimeError, match="closed"):
        engine._post.submit(("dead",))
    engine.close()                               # idempotent


def test_engine_context_manager_closes_worker():
    cfg = _tiny_cfg()
    with DetrServeEngine(cfg, _params(cfg), max_batch=2,
                         resolutions=(32,)) as engine:
        engine.submit(DetrRequest(rid=0, image=_images(1, 32)[0]))
        done = engine.run_until_drained()
        assert [r.rid for r in done] == [0]
    assert engine._post._thread is None


# --------------------------------------------------------------------------
# tuned-budget provenance on the serving surfaces
# --------------------------------------------------------------------------

def test_bucket_table_reports_budget_provenance():
    cfg = _tiny_cfg()
    router = BucketRouter(derive_buckets(cfg, (32,)))
    (row,) = router.table()
    assert row["budget_kb"] > 0
    assert row["budget_source"] in ("static", "measured")


def test_streaming_capacity_estimate_reports_budget_source():
    from repro.msda import plan as plan_lib
    from repro.serve.engine import StreamingDetrEngine
    levels = ((8, 10), (4, 5), (2, 3))
    attn = MSDeformAttnConfig(d_model=32, n_heads=4, n_levels=len(levels),
                              fwp_mode="compact", fwp_k=1.0, fwp_capacity=0.6,
                              range_narrow=(4.0, 3.0, 2.0))
    dec = msda.MSDADecoderConfig(n_layers=2, n_queries=8, d_ffn=32)
    key = jax.random.PRNGKey(3)
    d = attn.d_model
    params = {
        "decoder": msda.init_decoder(key, dec, attn),
        "cls_head": {"w": jnp.zeros((d, 3)), "b": jnp.zeros((3,))},
        "box_head": {"w": jnp.zeros((d, 4)), "b": jnp.zeros((4,))},
    }
    engine = StreamingDetrEngine(attn, dec, params, levels, max_sessions=1,
                                 update_fwp=False)
    est = engine.capacity_estimate()
    # the engine's ensure_applied() loaded the committed table, so the
    # default budget is the measured one (static only without a table)
    assert est["budget_source"] == ("measured" if plan_lib.tuned_entry()
                                    else "static")
    assert est["budget_bytes"] == plan_lib.window_staging_budget()
    assert engine.capacity_estimate(budget_bytes=1 << 20)["budget_source"] \
        == "caller"
