"""Sustained-serving benchmark: bucketed continuous batching + pipelined
post-processing vs the step-synchronous single-bucket baseline.

Workload: an open-loop, many-session synthetic load with MIXED
resolutions (3:1 small:large). The baseline serves it the only way a
single-shape engine can — every image padded up to the largest
resolution, post-processing synchronous with the device loop. The
sustained engine routes each request to the smallest AOT bucket it fits
(the small majority runs the ~4x-cheaper small forward) and decodes
outputs on a worker thread while the device runs the next micro-batch.

Two measurements:
  * **closed loop** (the CI-gated ``msda_serve_*`` micro rows): drain a
    fixed mixed workload flat-out, report us/request (median of 3).
  * **open loop** (the latency story): arrivals paced at 0.9x the
    measured closed-loop throughput; requests/sec/chip and P50/P99
    request latency (submit -> postproc done) over the run.

CPU numbers (jnp_gather backend) — structural, like every micro row:
the tracked quantity is the sustained/baseline ratio, not wall time."""
from __future__ import annotations

import time

import jax
import numpy as np

RESOLUTIONS = (32, 64)        # the serve buckets, smallest to largest
MIX = (3, 1)                  # requests per cycle at (small, large)
N_REQUESTS = 16
MAX_BATCH = 4


def _setup():
    from repro import msda
    from repro.core.detector import DetectorConfig, init_detector
    from repro.core.encoder import EncoderConfig
    from repro.core.msdeform_attn import MSDeformAttnConfig
    attn = MSDeformAttnConfig(d_model=32, n_heads=4, n_levels=4, n_points=2,
                              fwp_mode="compact", fwp_k=1.0,
                              fwp_capacity=0.6,
                              range_narrow=(8.0, 6.0, 4.0, 3.0))
    cfg = DetectorConfig(
        encoder=EncoderConfig(attn=attn, n_blocks=1, d_ffn=64),
        img_size=max(RESOLUTIONS), n_classes=4, backbone_width=8,
        decoder=msda.MSDADecoderConfig(n_layers=2, n_queries=16, d_ffn=64))
    return cfg, init_detector(jax.random.PRNGKey(0), cfg)


def _engines(cfg, params):
    from repro.serve.engine import DetrServeEngine
    sustained = DetrServeEngine(cfg, params, max_batch=MAX_BATCH,
                                backend="jnp_gather",
                                resolutions=RESOLUTIONS,
                                pipeline_postproc=True)
    baseline = DetrServeEngine(cfg, params, max_batch=MAX_BATCH,
                               backend="jnp_gather",
                               resolutions=(max(RESOLUTIONS),),
                               pipeline_postproc=False)
    return sustained, baseline


def _workload(n):
    rng = np.random.default_rng(11)
    cycle = [RESOLUTIONS[0]] * MIX[0] + [RESOLUTIONS[1]] * MIX[1]
    return [rng.standard_normal((3, r, r)).astype(np.float32)
            for r in (cycle[i % len(cycle)] for i in range(n))]


def _drain(engine, images) -> float:
    """Closed loop: submit everything, drain flat-out; seconds elapsed."""
    from repro.serve.engine import DetrRequest
    engine.finished.clear()
    t0 = time.perf_counter()
    for i, im in enumerate(images):
        assert engine.submit(DetrRequest(rid=i, image=im))
    engine.run_until_drained()
    return time.perf_counter() - t0


def _closed_loop_us(engine, images, iters: int = 3) -> float:
    _drain(engine, images)                       # warm (AOT already compiled)
    ts = [_drain(engine, images) for _ in range(iters)]
    return float(np.median(ts)) / len(images) * 1e6


def _open_loop(engine, images, rps: float) -> dict:
    """Arrivals paced at ``rps``; P50/P99 latency = submit -> postproc."""
    from repro.serve.engine import DetrRequest
    engine.finished.clear()
    reqs = [DetrRequest(rid=i, image=im) for i, im in enumerate(images)]
    interval = 1.0 / rps
    start = time.perf_counter()
    nxt = 0
    while nxt < len(reqs) or engine.pending():
        now = time.perf_counter()
        while nxt < len(reqs) and start + nxt * interval <= now:
            engine.submit(reqs[nxt])
            nxt += 1
        if engine.pending():
            engine.step()
        elif nxt < len(reqs):
            time.sleep(max(0.0, min(1e-3, start + nxt * interval - now)))
    engine.drain()
    elapsed = time.perf_counter() - start
    lat_ms = np.asarray(sorted((r.t_done - r.t_submit) * 1e3
                               for r in engine.finished))
    chips = max(1, jax.device_count())
    return {
        "offered_rps": round(rps, 2),
        "completed": len(engine.finished),
        "rps": round(len(engine.finished) / elapsed, 2),
        "rps_per_chip": round(len(engine.finished) / elapsed / chips, 2),
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 2),
    }


def _obs_overhead(engine_us: float, n: int = 2000) -> dict:
    """Deterministic per-request instrumentation cost.

    A wall-clock A/B of two short engine runs is dominated by device and
    scheduler noise, so the tracked figure is the measured cost of the
    per-request instrumentation calls themselves (the counter bumps,
    gauge sets, histogram observes, and span start/ends a request incurs
    on the serve path), expressed as a fraction of the measured
    us/request."""
    from repro.obs import Observability
    obs = Observability.create()
    c = obs.metrics.counter("bench_requests_total", "bench")
    g = obs.metrics.gauge("bench_queue_depth", "bench")
    h = obs.metrics.histogram("bench_latency_seconds", "bench")
    t0 = time.perf_counter()
    for i in range(n):
        c.inc(bucket="32", outcome="completed")
        g.set(1.0, bucket="32")
        obs.tracer.end(obs.tracer.start("queue", rid=i))
        for name in ("serve.submit", "serve.dispatch", "serve.fetch",
                     "postproc", "callback"):
            with obs.tracer.span(name, rid=i, step=i, n=1):
                pass
        h.observe(1e-3, bucket="32")
    per_req_us = (time.perf_counter() - t0) / n * 1e6
    obs.close()
    return {
        "instrumentation_us_per_request": round(per_req_us, 2),
        "fraction_of_request": round(per_req_us / engine_us, 4),
    }


def _write_prom(engine, path) -> None:
    """Dump the engine's registry in Prometheus text format (CI smoke)."""
    if path:
        from repro.obs import prometheus_text
        with open(path, "w") as f:
            f.write(prometheus_text(engine.obs.metrics))


def report(dry: bool = False, log=print,
           prom_path: str | None = None) -> dict:
    cfg, params = _setup()
    sustained, baseline = _engines(cfg, params)
    n = 2 * sum(MIX) if dry else N_REQUESTS
    images = _workload(n)
    out = {
        "workload": {"n_requests": n, "resolutions": list(RESOLUTIONS),
                     "mix": f"{MIX[0]}:{MIX[1]} small:large",
                     "max_batch": MAX_BATCH},
        "buckets": sustained.bucket_table(),
        "compiles": {"sustained": sustained.compile_count,
                     "baseline": baseline.compile_count},
    }
    if dry:
        for name, eng in (("sustained", sustained), ("baseline", baseline)):
            _drain(eng, images)
            assert len(eng.finished) == n
        out["dry_run"] = True
        # the zero-recompile contract still holds on the dry pass
        assert sustained.compile_count == len(sustained.buckets)
        log(f"[serve] dry run ok: {n} mixed requests through "
            f"{len(sustained.buckets)} buckets, "
            f"{sustained.compile_count} compiles")
        _write_prom(sustained, prom_path)
        sustained.close()
        baseline.close()
        return out
    sus_us = _closed_loop_us(sustained, images)
    base_us = _closed_loop_us(baseline, images)
    assert sustained.compile_count == len(sustained.buckets), \
        "sustained load recompiled after warmup"
    rps_closed = 1e6 / sus_us
    out["closed_loop"] = {
        "sustained_us_per_request": round(sus_us, 1),
        "single_bucket_sync_us_per_request": round(base_us, 1),
        "speedup": round(base_us / sus_us, 2),
    }
    # open loop in two passes: a probe offered at the closed-loop rate
    # finds the OPEN-loop capacity (paced arrivals mean shorter batches,
    # so it sits below the closed-loop rate), then the reported run backs
    # off to 0.9x that capacity — P50/P99 of a sustainable load, not of
    # an overload queue
    probe = _open_loop(sustained, images, 0.9 * rps_closed)
    out["open_loop"] = _open_loop(sustained, images, 0.9 * probe["rps"])
    out["open_loop"]["capacity_rps"] = probe["rps"]
    # per-span latency breakdown over everything the sustained engine
    # served (closed-loop reps + both open-loop passes)
    out["spans"] = sustained.obs.tracer.span_stats()
    # instrumented-vs-uninstrumented: wall delta of a closed-loop drain
    # on an engine with the Null obs stack, plus the deterministic
    # per-request instrumentation call cost (the gated <1% figure)
    from repro.obs import Observability
    from repro.serve.engine import DetrServeEngine
    dark = DetrServeEngine(cfg, params, max_batch=MAX_BATCH,
                           backend="jnp_gather", resolutions=RESOLUTIONS,
                           pipeline_postproc=True,
                           obs=Observability.disabled())
    dark_us = _closed_loop_us(dark, images)
    dark.close()
    out["observability"] = dict(_obs_overhead(sus_us),
                                uninstrumented_us_per_request=round(dark_us, 1),
                                wall_delta_pct=round(
                                    (sus_us - dark_us) / dark_us * 100, 2))
    span_line = ", ".join(
        f"{name} P50 {st['p50_ms']}ms/P99 {st['p99_ms']}ms"
        for name, st in sorted(out["spans"].items())
        if name in ("queue", "serve.dispatch", "serve.fetch", "postproc",
                    "callback"))
    log(f"[serve] spans: {span_line}")
    log(f"[serve] obs overhead: "
        f"{out['observability']['instrumentation_us_per_request']} us/req "
        f"({100 * out['observability']['fraction_of_request']:.2f}% of "
        f"request)")
    log(f"[serve] sustained {sus_us:.0f} us/req vs single-bucket sync "
        f"{base_us:.0f} us/req ({base_us / sus_us:.2f}x); open loop "
        f"{out['open_loop']['rps_per_chip']} req/s/chip, "
        f"P50 {out['open_loop']['p50_ms']} ms / "
        f"P99 {out['open_loop']['p99_ms']} ms")
    _write_prom(sustained, prom_path)
    sustained.close()
    baseline.close()
    return out


def micro_rows(log=print) -> list:
    """The CI-gated rows: us/request through each serving mode."""
    cfg, params = _setup()
    sustained, baseline = _engines(cfg, params)
    images = _workload(N_REQUESTS)
    rows = [
        ("msda_serve_sustained", _closed_loop_us(sustained, images),
         f"{len(RESOLUTIONS)} AOT buckets + pipelined postproc, "
         f"{MIX[0]}:{MIX[1]} mixed load, us/request"),
        ("msda_serve_single_bucket_sync", _closed_loop_us(baseline, images),
         f"everything padded to {max(RESOLUTIONS)}px, synchronous "
         "postproc, us/request"),
    ]
    sustained.close()
    baseline.close()
    for name, t, d in rows:
        log(f"[serve] {name}: {t:.1f} us ({d})")
    return rows


if __name__ == "__main__":
    import json
    print(json.dumps(report(), indent=2))
