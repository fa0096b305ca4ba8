"""Observability subsystem tests: the metrics registry (counters /
gauges / fixed-bucket histograms + Prometheus round-trip), the span
tracer (ring buffer, JSONL sink, cross-thread end, negative-duration
guard), the engine instrumentation contracts (compile counter == bucket
count, latency histogram == completed requests, <1% overhead), the
named scopes of the compiled forward and the engine's spans in a
profiler trace, the streaming counters vs ``mgr.report()``,
``MSDAPlan.snapshot()`` consistency, the JSONL/Prometheus validator, and
the dashboard renderer on synthetic events."""
import glob
import json
import re
import threading
import time

import jax
import numpy as np
import pytest

from repro.obs import (MetricsRegistry, NullRegistry, NullTracer,
                       Observability, Tracer, hlo_scopes, json_snapshot,
                       parse_prometheus_text, prometheus_text)
from repro.obs.metrics import DEFAULT_BYTES_BUCKETS, default_registry


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------

def test_counter_labels_total_and_negative_guard():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "test counter")
    c.inc(bucket="32")
    c.inc(2.0, bucket="64", outcome="completed")
    assert c.value(bucket="32") == 1.0
    # label order is irrelevant (sorted key)
    assert c.value(outcome="completed", bucket="64") == 2.0
    assert c.value(bucket="none") == 0.0
    assert c.total() == 3.0
    with pytest.raises(ValueError):
        c.inc(-1.0)
    # get-or-create returns the same object; kind mismatch raises
    assert reg.counter("requests_total") is c
    with pytest.raises(TypeError):
        reg.gauge("requests_total")


def test_gauge_set_inc_dec():
    g = MetricsRegistry().gauge("queue_depth")
    g.set(5, bucket="32")
    g.inc(bucket="32")
    g.dec(2, bucket="32")
    assert g.value(bucket="32") == 4.0


def test_histogram_buckets_quantile_and_counts():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.005, 0.05, 0.5, 5.0):
        h.observe(v, span="device")
    assert h.count(span="device") == 5
    assert h.total_count() == 5
    assert h.sum_value(span="device") == pytest.approx(5.56)
    # bucket-resolution quantiles: upper bound of the holding bucket
    assert h.quantile(0.5, span="device") == 0.1
    assert h.quantile(0.99, span="device") == float("inf")
    assert h.quantile(0.5, span="nope") is None
    (series,) = h.collect()
    assert series["buckets"] == [[0.01, 2], [0.1, 3], [1.0, 4]]  # cumulative
    assert series["count"] == 5
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=(1.0, 0.1))


def test_registry_snapshot_shape():
    reg = MetricsRegistry()
    reg.counter("c_total").inc(k="v")
    reg.gauge("g").set(2.0)
    reg.histogram("h_seconds", buckets=DEFAULT_BYTES_BUCKETS).observe(2048.0)
    snap = reg.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert snap["counters"]["c_total"]["values"] == [
        {"labels": {"k": "v"}, "value": 1.0}]
    assert snap["histograms"]["h_seconds"]["bucket_bounds"] == \
        list(DEFAULT_BYTES_BUCKETS)
    # snapshots are JSON-serializable as-is
    json.dumps(snap)


def test_null_registry_and_tracer_are_inert():
    obs = Observability.disabled()
    assert not obs.enabled
    obs.metrics.counter("x_total").inc(a="b")
    obs.metrics.gauge("g").set(1.0)
    obs.metrics.histogram("h").observe(1.0)
    assert obs.metrics.snapshot() == {"counters": {}, "gauges": {},
                                      "histograms": {}}
    sid = obs.tracer.start("queue")
    obs.tracer.end(sid)                     # no-op, never raises
    with obs.tracer.span("device"):
        pass
    assert obs.tracer.span_stats() == {} and obs.tracer.snapshot() == []


def test_default_registry_is_a_process_singleton():
    assert default_registry() is default_registry()
    assert isinstance(default_registry(), MetricsRegistry)


# --------------------------------------------------------------------------
# prometheus export round-trip
# --------------------------------------------------------------------------

def test_prometheus_text_round_trips_through_strict_parser():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests").inc(3, bucket="32")
    reg.counter("req_total").inc(bucket="64", outcome="ok")
    reg.gauge("depth", "queue depth").set(7, bucket="32")
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = prometheus_text(reg)
    parsed = parse_prometheus_text(text)
    assert (frozenset({"bucket": "32"}.items()), 3.0) in [
        (frozenset(l.items()), v) for l, v in parsed["req_total"]]
    assert parsed["depth"] == [({"bucket": "32"}, 7.0)]
    # histogram renders cumulative _bucket{le=} + _sum/_count series
    le = {l["le"] if l["le"] == "+Inf" else float(l["le"]): v
          for l, v in parsed["lat_seconds_bucket"]}
    assert le == {0.1: 1.0, 1.0: 2.0, "+Inf": 2.0}
    assert parsed["lat_seconds_count"] == [({}, 2.0)]
    assert parsed["lat_seconds_sum"][0][1] == pytest.approx(0.55)


@pytest.mark.parametrize("bad", [
    "not a metric line at all {",
    'x_total{unterminated="1 3.0',
    "x_total not-a-number",
    "# MALFORMED comment kind",
])
def test_prometheus_parser_rejects_malformed(bad):
    with pytest.raises(ValueError):
        parse_prometheus_text(bad + "\n")


# --------------------------------------------------------------------------
# tracer
# --------------------------------------------------------------------------

def test_tracer_spans_ring_buffer_and_stats():
    tr = Tracer(capacity=4)
    for i in range(6):
        sid = tr.start("step", rid=i)
        tr.end(sid, items=i)
    assert len(tr.spans) == 4                       # bounded ring
    assert [s.rid for s in tr.spans] == [2, 3, 4, 5]
    st = tr.span_stats()["step"]
    assert st["count"] == 4 and st["p50_ms"] >= 0.0
    assert tr.open_count() == 0
    snap = tr.snapshot(last=2)
    assert len(snap) == 2 and snap[-1]["rid"] == 5


def test_tracer_unknown_end_and_negative_duration_raise():
    tr = Tracer()
    with pytest.raises(KeyError):
        tr.end("t0-999")
    sid = tr.start("queue", t=100.0)
    with pytest.raises(ValueError):
        tr.end(sid, t=99.0)                         # clock went backwards
    # the span survives the refused end and can close properly
    assert tr.open_count() == 1
    sp = tr.end(sid, t=101.5)
    assert sp.duration_s == pytest.approx(1.5)


def test_tracer_cross_thread_end():
    tr = Tracer()
    sid = tr.start("device", rid=7)
    t = threading.Thread(target=lambda: tr.end(sid))
    t.start()
    t.join()
    assert tr.open_count() == 0 and tr.spans[-1].rid == 7


def test_tracer_jsonl_sink_and_validator(tmp_path):
    from repro.obs.validate import validate_jsonl
    path = str(tmp_path / "events.jsonl")
    obs = Observability.create(jsonl_path=path)
    with obs.tracer.span("frame_in", rid="s0", n=2):
        pass
    obs.metrics.counter("frames_total").inc()
    obs.flush_metrics()
    obs.tracer.event("plan", engine="test", plan={"backend": "jnp_gather"})
    obs.close()
    r = validate_jsonl(path)
    assert r["spans"] == 1 and r["names"] == ["frame_in"]
    types = [json.loads(l)["type"] for l in open(path)]
    assert types == ["span_start", "span_end", "metrics", "plan"]


def test_validator_rejects_broken_logs(tmp_path):
    from repro.obs.validate import main, validate_jsonl

    def _check(lines, match):
        p = tmp_path / "bad.jsonl"
        p.write_text("\n".join(json.dumps(e) for e in lines) + "\n")
        with pytest.raises(ValueError, match=match):
            validate_jsonl(str(p))
        assert main(["--jsonl", str(p)]) == 1       # CLI exits nonzero

    start = {"type": "span_start", "span": "a", "name": "q", "t": 1.0}
    _check([start], "never ended")
    _check([{"type": "span_end", "span": "a", "name": "q", "t": 2.0,
             "dur_s": 1.0}], "without matching")
    _check([start, {"type": "span_end", "span": "a", "name": "q", "t": 2.0,
                    "dur_s": -0.5}], "negative/missing duration")
    _check([start, {"type": "span_end", "span": "a", "name": "other",
                    "t": 2.0, "dur_s": 1.0}], "name mismatch")
    _check([start, start], "duplicate span_start")


# --------------------------------------------------------------------------
# instrumented engines
# --------------------------------------------------------------------------

def _tiny_engine():
    from tests.test_serve import _params, _tiny_cfg
    from repro.serve.engine import DetrServeEngine
    cfg = _tiny_cfg()
    return DetrServeEngine(cfg, _params(cfg), max_batch=2,
                           resolutions=(32, 64))


def test_engine_metrics_compile_counter_and_latency_histogram():
    """(i) compile counter == bucket count via the registry, (ii) latency
    histogram total == completed requests under mixed-resolution churn,
    (iii) per-request instrumentation cost < 1% of the measured request
    latency."""
    from tests.test_serve import _images
    from repro.serve.engine import DetrRequest
    engine = _tiny_engine()
    m = engine.obs.metrics
    compiles = m.get("msda_compiles_total")
    assert compiles.total() == len(engine.buckets) == 2
    assert compiles.value(bucket="32") == 1.0
    assert compiles.value(bucket="64") == 1.0

    imgs = list(_images(3, 32)) + list(_images(2, 64)) \
        + [np.asarray(_images(1, 64)[0][:, :40, :48])]      # pad-up route
    rid = 0
    for im in imgs:
        assert engine.submit(DetrRequest(rid=rid, image=im))
        rid += 1
    done = engine.run_until_drained()
    assert len(done) == rid

    # zero retraces under churn, asserted against the registry
    assert compiles.total() == 2
    assert engine.compile_count == 2                        # back-compat view
    lat = m.get("serve_request_latency_seconds")
    assert lat.total_count() == rid
    assert lat.count(bucket="32") == 3 and lat.count(bucket="64") == 3
    req = m.get("serve_requests_total")
    assert req.value(bucket="32", outcome="admitted") == 3
    assert req.value(outcome="completed", bucket="32") == 3
    # every request produced a queue + serve.fetch + postproc span
    stats = engine.obs.tracer.span_stats()
    assert stats["queue"]["count"] == rid
    assert stats["serve.fetch"]["count"] >= 1
    assert stats["postproc"]["count"] >= 1

    # (iii) overhead: deterministic per-request instrumentation cost
    # (what the serve path adds per request) vs measured request latency
    mean_req_s = lat.sum_value(bucket="64") / lat.count(bucket="64")
    probe = Observability.create()
    c = probe.metrics.counter("x_total")
    h = probe.metrics.histogram("x_seconds")
    n = 1000
    t0 = time.perf_counter()
    for i in range(n):
        c.inc(bucket="32", outcome="completed")
        probe.tracer.end(probe.tracer.start("queue", rid=i))
        for name in ("serve.submit", "serve.dispatch", "serve.fetch",
                     "postproc", "callback"):
            with probe.tracer.span(name, rid=i, step=i, n=1):
                pass
        h.observe(1e-3, bucket="32")
    per_req_s = (time.perf_counter() - t0) / n
    probe.close()
    assert per_req_s < 0.01 * mean_req_s, \
        f"instrumentation {per_req_s*1e6:.1f}us vs request {mean_req_s*1e6:.0f}us"
    engine.close()


def test_engine_rejected_requests_counted():
    from repro.serve.engine import DetrRequest
    engine = _tiny_engine()
    assert not engine.submit(DetrRequest(
        rid=0, image=np.zeros((3, 100, 100), np.float32)))   # oversized
    assert engine.obs.metrics.value("serve_requests_total",
                                    bucket="none", outcome="rejected") == 1.0
    engine.close()


def test_disabled_engine_serves_identically_with_empty_registry():
    from tests.test_serve import _images
    from repro.serve.engine import DetrRequest
    from repro.serve.engine import DetrServeEngine
    from tests.test_serve import _params, _tiny_cfg
    cfg = _tiny_cfg()
    engine = DetrServeEngine(cfg, _params(cfg), max_batch=2,
                             resolutions=(32,), obs=Observability.disabled())
    for i, im in enumerate(_images(2, 32)):
        assert engine.submit(DetrRequest(rid=i, image=im))
    done = engine.run_until_drained()
    assert len(done) == 2 and all(np.isfinite(r.cls_probs).all()
                                  for r in done)
    assert engine.obs.metrics.snapshot()["counters"] == {}
    assert engine.compile_count == 0        # null counter: the view reads 0
    engine.close()


# the layers the forward names (jax.named_scope), and what lies under them
FIXED_TREE = re.compile(r"(backbone|input_proj|heads|encoder/block_\d+"
                        r"|decoder(/cache_build|/layer_\d+)?)(/.+)?")
INSTR = re.compile(r"^\s*(?:ROOT\s+)?%(\S+) = .*?\s([a-z][\w-]*)\(")


def test_hlo_scopes_name_the_layers_of_the_compiled_forward():
    """Every dot of the compiled forward, and all but a few of its entry
    instructions, fall under the fixed tree of scopes; each MSDA call
    shows its five stages."""
    engine = _tiny_engine()
    text = engine._compiled[32].as_text()
    scopes = engine.op_scopes(32)
    assert scopes == hlo_scopes(text)
    ops = [m.groups() for m in map(INSTR.match, text.splitlines()) if m]
    dots = [n for n, op in ops if op == "dot"]
    assert dots and all(FIXED_TREE.fullmatch(scopes[n]) for n in dots)
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    counted = [n for n, op in (m.groups() for m in
                               map(INSTR.match, entry.splitlines()) if m)
               if op not in ("bitcast", "parameter", "tuple")]
    mapped = [n for n in counted if FIXED_TREE.fullmatch(scopes[n])]
    assert len(mapped) >= 0.95 * len(counted)
    layers = {"/".join(p.split("/")[:2]) for p in scopes.values()}
    assert {"backbone", "input_proj", "encoder/block_0",
            "decoder/cache_build", "decoder/layer_0", "decoder/layer_1",
            "heads"} <= layers
    stages = {p.split("/msda/")[1].split("/")[0]
              for p in scopes.values() if "/msda/" in p}
    assert {"value", "points", "sample", "out", "fwp"} <= stages
    engine.close()


def _trace_events(path):
    """{name: [stats]} of the host events in a recorded profiler trace
    (stats read for the engine's spans only)."""
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(
        glob.glob(str(path / "**" / "*.xplane.pb"), recursive=True)[0])
    out = {}
    for plane in prof.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.setdefault(ev.name, []).append(
                        dict(ev.stats) if ev.name.startswith("serve.")
                        else None)
    return out


def _serve_traced(engine, path, n=3):
    from tests.test_serve import _images
    from repro.serve.engine import DetrRequest
    jax.profiler.start_trace(str(path))
    try:
        for i, im in enumerate(_images(n, 32)):
            assert engine.submit(DetrRequest(rid=i, image=im))
        engine.run_until_drained()
    finally:
        jax.profiler.stop_trace()
    engine.close()
    return _trace_events(path)


def test_engine_spans_reach_the_profiler_trace(tmp_path):
    """serve.submit, serve.dispatch (with its step, batch size, bucket and
    batches in flight) and serve.fetch are host events of the trace."""
    events = _serve_traced(_tiny_engine(), tmp_path)
    assert len(events["serve.submit"]) == 3
    assert {"serve.fetch", "postproc"} <= events.keys()
    dispatch = events["serve.dispatch"]           # 3 requests, max_batch 2
    assert [d["step"] for d in dispatch] == [0, 1]
    assert [d["n"] for d in dispatch] == [2, 1]
    assert all(d["bucket"] == 32 and d["inflight"] >= 0 for d in dispatch)
    assert sorted(d["step"] for d in events["serve.fetch"]) == [0, 1]


def test_disabled_engine_puts_nothing_in_the_trace(tmp_path):
    from repro.serve.engine import DetrServeEngine
    from tests.test_serve import _params, _tiny_cfg
    cfg = _tiny_cfg()
    engine = DetrServeEngine(cfg, _params(cfg), max_batch=2,
                             resolutions=(32,), obs=Observability.disabled())
    events = _serve_traced(engine, tmp_path)
    assert not [n for n in events if n.startswith("serve.")
                or n in ("postproc", "callback")]


def test_streaming_manager_counters_match_report():
    from tests.test_stream import N_IN, _cfg, _mgr, D
    from repro.stream import StreamConfig
    mgr, plan = _mgr(_cfg(), StreamConfig(tile_rows=2, delta_threshold=1e-6,
                                          update_frac=0.5))
    key = jax.random.PRNGKey(3)
    x0 = jax.random.normal(key, (2, N_IN, D))
    mgr.step(x0)                                     # rebuild (cold)
    mgr.step(x0.at[:, 0:3].add(0.5))                 # incremental
    r = mgr.report()
    m = mgr.obs.metrics
    frames = m.get("stream_frames_total")
    assert frames.total() == r["frames"] == 2
    assert frames.value(mode="rebuild") == r["rebuild_frames"] == 1
    assert frames.value(mode="incremental") == r["incremental_frames"] == 1
    assert m.get("staged_bytes_total").total() == r["staged_bytes_total"]
    assert m.get("stream_rebuilds_total").value(reason="first-frame") == 1
    # trace_counts (the old dict) is now a live view over the registry
    assert mgr.trace_counts == {
        k: int(m.get("msda_traces_total").value(fn=k))
        for k in ("build", "frame", "restage")}
    # scatter/rebuild/diff spans recorded with durations
    stats = mgr.obs.tracer.span_stats()
    assert "diff" in stats and "rebuild" in stats
    assert all(st["total_s"] >= 0 for st in stats.values())


def _six_by_six_cfg(pap_mode):
    """_tiny_cfg at 6 encoder blocks and 6 decoder layers, dense as the
    served configuration is, under ``pap_mode``."""
    import dataclasses
    from tests.test_serve import _tiny_cfg
    cfg = _tiny_cfg()
    attn = dataclasses.replace(cfg.encoder.attn, pap_mode=pap_mode,
                               pap_keep=4, fwp_mode="off", range_narrow=None)
    return dataclasses.replace(
        cfg, encoder=dataclasses.replace(cfg.encoder, attn=attn, n_blocks=6),
        decoder=dataclasses.replace(cfg.decoder, n_layers=6))


def _point_select_traces():
    c = default_registry().counter("msda_point_select_traces_total")
    return {p: c.value(path=p) for p in ("identity", "gather")}


def test_point_select_counter_reads_the_path_of_every_msda_call():
    """Compiling the dense served forward takes the identity path in each
    of its 12 MSDA calls (6 encoder blocks + 6 decoder layers) and the
    gather path in none; tracing a "topk" forward takes only the gather."""
    from tests.test_serve import _params
    from repro.core.detector import detector_apply
    from repro.serve.engine import DetrServeEngine
    cfg = _six_by_six_cfg("off")
    before = _point_select_traces()
    engine = DetrServeEngine(cfg, _params(cfg), max_batch=1,
                             resolutions=(32,), obs=Observability.disabled())
    engine.close()
    after = _point_select_traces()
    assert after["identity"] - before["identity"] == 12
    assert after["gather"] == before["gather"]

    cfg = _six_by_six_cfg("topk")
    params = _params(cfg)
    images = jax.ShapeDtypeStruct((1, 3, 32, 32), np.float32)
    jax.eval_shape(lambda p, x: detector_apply(p, cfg, x)[:2], params, images)
    final = _point_select_traces()
    assert final["gather"] - after["gather"] == 12
    assert final["identity"] == after["identity"]


# --------------------------------------------------------------------------
# plan snapshot
# --------------------------------------------------------------------------

def test_plan_snapshot_is_structured_twin_of_describe():
    from repro import msda
    from repro.core.msdeform_attn import MSDeformAttnConfig
    cfg = MSDeformAttnConfig(d_model=32, n_heads=4, fwp_mode="compact",
                             fwp_k=1.0, fwp_capacity=0.6,
                             range_narrow=(4.0, 3.0, 2.0))
    plan = msda.make_plan(cfg, ((8, 10), (4, 5), (2, 3)),
                          backend="jnp_gather", n_queries=16, n_consumers=2)
    snap = plan.snapshot()
    assert snap["backend"] == plan.backend
    assert snap["value_table_bytes"] == plan.value_table_bytes
    assert snap["budget_source"] == plan.budget_source
    assert snap["decode"]["n_consumers"] == 2
    json.dumps(snap)                                 # exporter-safe
    # describe() is a pure formatter over the snapshot: the numbers in
    # the string are the numbers in the dict
    d = plan.describe()
    assert plan.backend in d
    assert f"table={snap['value_table_bytes'] / 1024:.0f}KB" in d


def test_engine_plan_events_logged_per_bucket(tmp_path, monkeypatch):
    from repro.obs.obs import OBS_JSONL_ENV
    path = str(tmp_path / "serve.jsonl")
    monkeypatch.setenv(OBS_JSONL_ENV, path)
    engine = _tiny_engine()                          # obs=None -> env sink
    engine.close()
    plans = [json.loads(l) for l in open(path)
             if json.loads(l)["type"] == "plan"]
    assert sorted(p["bucket"] for p in plans) == [32, 64]
    assert all(p["plan"]["backend"] == plans[0]["plan"]["backend"]
               for p in plans)


# --------------------------------------------------------------------------
# json snapshot + dashboard
# --------------------------------------------------------------------------

def test_json_snapshot_schema(tmp_path):
    from repro.obs import write_json_snapshot
    obs = Observability.create()
    obs.metrics.counter("c_total").inc()
    with obs.tracer.span("device"):
        pass
    snap = json_snapshot(obs.metrics, obs.tracer, extra={"run": "t1"})
    assert snap["schema"] == "repro.obs/v1"
    assert snap["metrics"]["counters"]["c_total"]["values"][0]["value"] == 1.0
    assert snap["spans"]["device"]["count"] == 1
    assert snap["run"] == "t1"
    p = tmp_path / "snap.json"
    write_json_snapshot(str(p), obs.metrics, obs.tracer)
    assert json.loads(p.read_text())["schema"] == "repro.obs/v1"
    obs.close()


def _synthetic_events():
    snap = {"counters": {
        "serve_requests_total": {"help": "", "values": [
            {"labels": {"bucket": "32", "outcome": "completed"},
             "value": 9.0}]},
        "staged_bytes_total": {"help": "", "values": [
            {"labels": {"mode": "incremental"}, "value": 4096.0},
            {"labels": {"mode": "rebuild"}, "value": 65536.0}]},
        "stream_frames_total": {"help": "", "values": [
            {"labels": {"mode": "incremental"}, "value": 8.0},
            {"labels": {"mode": "rebuild"}, "value": 1.0}]},
        "stream_rebuilds_total": {"help": "", "values": [
            {"labels": {"reason": "cold"}, "value": 1.0}]},
    }, "gauges": {
        "serve_queue_depth": {"help": "", "values": [
            {"labels": {"bucket": "32"}, "value": 3.0}]},
    }, "histograms": {}}
    return [
        {"type": "span_start", "span": "a", "name": "device", "t": 1.0},
        {"type": "span_end", "span": "a", "name": "device", "t": 1.02,
         "dur_s": 0.02},
        {"type": "plan", "t": 1.1, "bucket": "32",
         "plan": {"backend": "jnp_gather", "budget_source": "measured",
                  "table_dtype": "float32", "value_table_bytes": 43520}},
        {"type": "metrics", "t": 2.0, "data": snap},
    ]


def test_dashboard_renders_synthetic_events():
    from repro.obs.dashboard import feed_event, new_model, render_dashboard
    model = new_model()
    for ev in _synthetic_events():
        feed_event(model, ev)
    out = render_dashboard(model, width=80)
    assert "requests completed: 9" in out
    assert "bucket    32: ███" in out
    assert "device" in out and "20.00" in out        # 0.02 s span as ms
    assert "incremental:rebuild frames = 8:1" in out
    assert "rebuild reason cold" in out
    assert "backend=jnp_gather" in out and "budget=measured" in out
    # every line fits the box
    assert all(len(l) == 80 for l in out.splitlines())


def test_dashboard_feed_lines_tolerates_torn_tail():
    from repro.obs.dashboard import feed_lines, new_model
    model = new_model()
    lines = [json.dumps(e) for e in _synthetic_events()]
    lines.append('{"type": "span_start", "span": "b", "na')   # torn write
    feed_lines(model, lines)
    assert model["events"] == 4                      # torn line skipped
