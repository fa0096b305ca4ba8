"""End-to-end driver: batched DETR serving with DEFA (the paper's
deployment scenario — MSDeformAttn inference acceleration).

Streams batches of synthetic images through the conv backbone + deformable
encoder (+ optional DETR-style decoder) with the DEFA stack enabled, and
reports throughput and the realized pruning ratios per batch.

  PYTHONPATH=src python examples/detr_serve.py --batches 4 --batch 8
  PYTHONPATH=src python examples/detr_serve.py --decoder   # N_q learned
      queries cross-attend a ONE-build shared ValueCache through the
      DetrServeEngine micro-batcher (build-once, sample-everywhere)
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import time

import jax
import numpy as np

from benchmarks.detr_toy import (toy_config, train_toy_decoder_detector,
                                 train_toy_detector, with_attn)
from repro.core.detector import detector_apply
from repro.data.detection import eval_detection_ap, synth_detection_batch
from repro.msda import available_backends, make_plan
from repro.serve.engine import DetrRequest, DetrServeEngine
from repro.utils.compile_cache import enable_compile_cache

DEFA_KW = dict(pap_mode="topk", pap_keep=6,
               fwp_mode="compact", fwp_k=1.0, fwp_capacity=0.6,
               range_narrow=(8.0, 6.0, 4.0, 3.0),
               act_bits=12, weight_bits=12)


def _device() -> str:
    """The device the timings above ran on, as JAX reports it."""
    d = jax.devices()[0]
    return f"{d.platform} {d.device_kind} x{len(jax.devices())}"


def serve_encoder_head(args) -> None:
    cfg, params = train_toy_detector()
    serve_cfg = with_attn(cfg, **DEFA_KW)

    plan = make_plan(serve_cfg.encoder.attn, serve_cfg.level_shapes,
                     backend=args.backend)
    print(f"[serve] {plan.describe()}")

    fwd = jax.jit(lambda p, img: detector_apply(p, serve_cfg, img,
                                                collect_stats=True,
                                                backend=args.backend))
    key = jax.random.PRNGKey(42)
    img, _, _, gt = synth_detection_batch(key, args.batch, cfg.img_size,
                                          cfg.level_shapes)
    jax.block_until_ready(fwd(params, img))          # warm compile

    total = 0
    t0 = time.perf_counter()
    aps = []
    for i in range(args.batches):
        img, _, _, gt = synth_detection_batch(
            jax.random.fold_in(key, i), args.batch, cfg.img_size,
            cfg.level_shapes)
        cls, box, aux = fwd(params, img)
        jax.block_until_ready(cls)
        total += args.batch
        aps.append(eval_detection_ap(cls, box, gt))
        keep = [float(b["pap_keep_frac"]) for b in aux["blocks"]]
        fwp = [float(b["fwp_keep_frac"]) for b in aux["blocks"][:-1]]
        print(f"batch {i}: PAP kept {np.mean(keep):.1%} of sampling points, "
              f"FWP kept {np.mean(fwp):.1%} of pixels, AP={aps[-1]:.3f}")
    dt = time.perf_counter() - t0
    print(f"\n[serve] {total} images in {dt:.2f}s = {total/dt:.2f} img/s "
          f"({_device()}), "
          f"mean AP {np.mean(aps):.3f}")


def serve_decoder_head(args) -> None:
    """Decoder-head serving through the DetrServeEngine micro-batcher:
    the value table is projected + FWP-compacted ONCE per forward and all
    decoder layers sample the shared cache."""
    cfg, params = train_toy_decoder_detector()
    serve_cfg = with_attn(cfg, **DEFA_KW)

    engine = DetrServeEngine(serve_cfg, params, max_batch=args.batch,
                             backend=args.backend)
    print(f"[serve/decoder] {engine.describe()}")

    key = jax.random.PRNGKey(42)
    rid = 0
    gts = []
    for i in range(args.batches):
        img, _, _, gt = synth_detection_batch(
            jax.random.fold_in(key, i), args.batch, cfg.img_size,
            cfg.level_shapes)
        gts.append(gt)
        for b in range(args.batch):
            engine.submit(DetrRequest(rid=rid, image=np.asarray(img[b])))
            rid += 1
    engine.step()                                    # warm compile
    t0 = time.perf_counter()
    done = engine.run_until_drained()
    dt = time.perf_counter() - t0

    # per-batch AP from the completed requests (submit order == rid order;
    # eval_detection_ap softmaxes its logits input, so feed log(probs))
    by_rid = {r.rid: r for r in done}
    aps = []
    for i, gt in enumerate(gts):
        reqs = [by_rid[i * args.batch + b] for b in range(args.batch)]
        logp = np.log(np.clip(np.stack([r.cls_probs for r in reqs]),
                              1e-9, None))
        aps.append(eval_detection_ap(logp,
                                     np.stack([r.boxes for r in reqs]), gt))
    timed = len(done) - args.batch
    print(f"[serve/decoder] {len(done)} requests ({timed} timed) in "
          f"{dt:.2f}s = {timed/max(dt, 1e-9):.2f} img/s ({_device()}), "
          f"mean AP {np.mean(aps):.3f}")


def serve_sustained(args) -> None:
    """Sustained mixed-resolution load through the bucketed engine:
    AOT shape buckets + continuous batching + pipelined post-processing
    vs the single-bucket synchronous baseline (benchmarks/serve_sustained).
    ``--dry-run`` routes a few mixed requests through every bucket and
    checks the zero-recompile contract without timing anything."""
    import json

    from benchmarks.serve_sustained import report
    r = report(dry=args.dry_run, prom_path=args.obs_prom)
    print("[serve/sustained] buckets: "
          + ", ".join(f"{b['resolution']}px ({b['table_kb']}KB table)"
                      for b in r["buckets"]))
    if args.dry_run:
        print("[serve/sustained] dry run ok "
              f"({r['compiles']['sustained']} AOT compiles, 0 retraces)")
        return
    cl, ol = r["closed_loop"], r["open_loop"]
    print(f"[serve/sustained] closed loop: "
          f"{cl['sustained_us_per_request']:.0f} us/req vs "
          f"{cl['single_bucket_sync_us_per_request']:.0f} us/req "
          f"single-bucket sync = {cl['speedup']:.2f}x")
    print(f"[serve/sustained] open loop @0.9x capacity: "
          f"{ol['rps_per_chip']} req/s/chip, "
          f"P50 {ol['p50_ms']} ms / P99 {ol['p99_ms']} ms")
    print(json.dumps(r, indent=2, default=str))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--backend", default=None,
                    choices=available_backends() + ["auto"],
                    help="MSDA backend override (default: plan from config)")
    ap.add_argument("--decoder", action="store_true",
                    help="serve the decoder-head detector (shared "
                         "ValueCache, build-once sample-everywhere)")
    ap.add_argument("--sustained", action="store_true",
                    help="sustained mixed-resolution load: AOT buckets + "
                         "continuous batching + pipelined postproc vs the "
                         "single-bucket synchronous baseline")
    ap.add_argument("--dry-run", action="store_true",
                    help="with --sustained: route a small mixed load, "
                         "check zero recompiles, skip timing (CI smoke)")
    ap.add_argument("--obs-prom", default=None, metavar="PATH",
                    help="with --sustained: write the engine's metrics "
                         "registry in Prometheus text format to PATH "
                         "(JSONL trace export is driven by the "
                         "REPRO_OBS_JSONL env var)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.sustained:
        serve_sustained(args)
    elif args.decoder:
        serve_decoder_head(args)
    else:
        serve_encoder_head(args)


if __name__ == "__main__":
    main()
