"""Bring-up smoke run on one TPU: the detection server at full width, and
each Pallas MSDA kernel compiled natively against the XLA gather path.

    python chip_smoke.py                               # one TPU chip
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse  # tiny CPU rehearsal

Everything runs in this one process; it starts no other. Phases:

1. ``serve`` — ``DetrServeEngine`` at the ``deformable-detr`` widths of
   ``configs/detr_family.py`` (d_model 256, 8 heads, 4 levels x 4 points,
   6 encoder blocks, d_ffn 1024, bf16) with a 6-layer x 300-query decoder
   head, seeded random weights, one 512-px bucket (N_in 21,760) and
   ``max_batch`` 1: at batch 2 the ``jnp_gather`` path does not fit the
   v5e's 16 GB of HBM (its gather materialises f32[B, 8, 32, 21760, 64]).
   Seeded synthetic images of mixed sizes <= 512 px are served until
   drained; every result must be finite, of shape (300, C) and (300, 4),
   with no compile after warm-up.
2. ``reference`` — the first request's logits and boxes against a
   float32 ``jnp_gather`` forward of the same weights at
   ``default_matmul_precision("highest")`` on the same device.
3. ``pallas_fused`` / ``pallas_windowed`` / ``pallas_decode`` — each
   kernel at the paper's widths (the 800x1333 four-level pyramid, d_model
   256, 8 heads; the decoder at 300 queries x 6 layers) in float32, its
   compiled HLO checked for ``tpu_custom_call``, against ``jnp_gather``:
   the encoder kernels on the same table and sampling points, the decoder
   on the same memory.

Numbers printed here are bring-up readings, not benchmark metrics. The
last line of standard output is ``{"ok": true, "device": {...}}`` only on
a TPU and only when every phase passed; off a TPU the script exits
non-zero without it. ``--rehearse`` runs the same phases at a tiny size
on the CPU (kernels in interpret mode) and still exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro import msda
from repro.configs.detr_family import CONFIGS, LEVEL_SHAPES
from repro.core import nn
from repro.core.detector import DetectorConfig, detector_apply, init_detector
from repro.core.msdeform_attn import init_msdeform_attn
from repro.msda.plan import plan_for
from repro.msda.sampling import generate_points
from repro.serve.engine import DetrRequest, DetrServeEngine
from repro.utils.compile_cache import enable_compile_cache

SEED = 0
N_CLASSES = 91                      # COCO category ids, + background

#: Bound on max|served - reference| / max(1, max|reference|) for the
#: bf16 server against the float32 reference: bf16 keeps 8 mantissa bits
#: (relative rounding 2^-9) through 12 blocks of residual updates.
SERVE_TOL = 0.05
#: The same bound for a float32 kernel against float32 jnp_gather.
KERNEL_TOL = 1e-4


@dataclasses.dataclass(frozen=True)
class Size:
    resolution: int                 # serving bucket (px)
    image_sizes: tuple              # (h, w) of the served requests
    d_model: int
    n_blocks: int                   # encoder blocks = decoder layers
    n_queries: int
    d_ffn: int
    kernel_levels: tuple            # level shapes of the kernel phases


FULL = Size(resolution=512,
            image_sizes=((512, 512), (480, 400), (384, 512), (300, 256)),
            d_model=256, n_blocks=6, n_queries=300, d_ffn=1024,
            kernel_levels=LEVEL_SHAPES)
TINY = Size(resolution=64, image_sizes=((64, 64), (60, 48), (32, 64),
                                        (40, 40)),
            d_model=64, n_blocks=2, n_queries=20, d_ffn=64,
            kernel_levels=((16, 20), (8, 10), (4, 5), (2, 3)))


def log(msg: str) -> None:
    print(msg, flush=True)


def attn_config(name: str, size: Size, dtype):
    attn = CONFIGS[name].encoder.attn
    return dataclasses.replace(attn, d_model=size.d_model, dtype=dtype)


def detector_config(size: Size, dtype) -> DetectorConfig:
    enc = CONFIGS["deformable-detr"].encoder
    enc = dataclasses.replace(enc, attn=attn_config("deformable-detr", size,
                                                    dtype),
                              n_blocks=size.n_blocks, d_ffn=size.d_ffn,
                              dtype=dtype)
    dec = msda.MSDADecoderConfig(n_layers=size.n_blocks,
                                 n_queries=size.n_queries,
                                 d_ffn=size.d_ffn, dtype=dtype)
    return DetectorConfig(encoder=enc, img_size=size.resolution,
                          n_classes=N_CLASSES, dtype=dtype, decoder=dec)


def to_f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32)
                        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(1.0, np.max(np.abs(want))))


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def compile_native(fn, *args):
    """AOT-compile ``fn`` for ``args``; return (executable, holds a Pallas
    TPU kernel)."""
    exe = jax.jit(fn).lower(*args).compile()
    return exe, "tpu_custom_call" in exe.as_text()


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def phase_serve(size: Size, on_tpu: bool):
    cfg = detector_config(size, jnp.bfloat16)
    params = init_detector(jax.random.PRNGKey(SEED), cfg)
    enc_plan = plan_for(cfg.encoder.attn, cfg.level_shapes, "auto")
    engine = DetrServeEngine(cfg, params, max_batch=1, backend="auto",
                             resolutions=(size.resolution,),
                             pipeline_postproc=False)
    log(f"[serve] encoder plan: {enc_plan.describe()}")
    log(f"[serve] decoder plan: {engine.describe()}")
    for res, sec in engine.compile_seconds.items():
        log(f"[serve] bucket {res}px compile_seconds={sec!r}")
    native = "tpu_custom_call" in engine._compiled[size.resolution].as_text()
    log(f"[serve] tpu_custom_call in bucket executable: {native}")
    uses_pallas = any(p.backend.startswith("pallas_")
                      for p in (enc_plan, engine.buckets[0].plan))
    if on_tpu and uses_pallas:
        check(native, "a Pallas plan compiled without a TPU kernel")
    warm = engine.compile_count
    check(warm == len(engine.buckets),
          f"compiles after warm-up {warm} != buckets {len(engine.buckets)}")

    rng = np.random.default_rng(SEED)
    images = [rng.standard_normal((3, h, w)).astype(np.float32)
              for h, w in size.image_sizes]
    for rid, img in enumerate(images):
        check(engine.submit(DetrRequest(rid=rid, image=img)),
              f"request {rid} rejected")
    done = engine.run_until_drained()
    engine.close()
    check(len(done) == len(images), f"{len(done)} of {len(images)} served")
    check(engine.compile_count == warm,
          f"recompiled while serving: {engine.compile_count} != {warm}")
    for req in sorted(done, key=lambda r: r.rid):
        check(req.cls_logits.shape == (size.n_queries, N_CLASSES + 1),
              f"logits shape {req.cls_logits.shape}")
        check(req.boxes.shape == (size.n_queries, 4),
              f"boxes shape {req.boxes.shape}")
        check(bool(np.isfinite(req.cls_logits).all()
                   and np.isfinite(req.boxes).all()),
              f"request {req.rid} has non-finite outputs")
        log(f"[serve] request {req.rid} image={req.image.shape[1:]} "
            f"latency_seconds={req.t_done - req.t_submit!r}")
    log(f"[serve] msda_compiles_total={engine.compile_count} "
        f"(buckets={len(engine.buckets)})")
    return cfg, params, min(done, key=lambda r: r.rid)


def phase_reference(size: Size, cfg, params, req) -> None:
    cfg32 = detector_config(size, jnp.float32)
    img = np.zeros((1, 3, size.resolution, size.resolution), np.float32)
    img[0, :, :req.image.shape[1], :req.image.shape[2]] = req.image
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, x: detector_apply(p, cfg32, x,
                                                  backend="jnp_gather")[:2])
        logits, boxes = fwd(to_f32(params), jnp.asarray(img))
    e_logit = rel_err(req.cls_logits, logits[0])
    e_box = rel_err(req.boxes, boxes[0])
    log(f"[reference] request {req.rid}: logits rel_err={e_logit!r}, "
        f"boxes rel_err={e_box!r} (bound {SERVE_TOL})")
    check(e_logit <= SERVE_TOL and e_box <= SERVE_TOL,
          "served outputs disagree with the float32 reference")


def _encoder_inputs(attn, levels, key):
    n_in = sum(h * w for h, w in levels)
    q = jax.random.normal(jax.random.fold_in(key, 1), (1, n_in, attn.d_model))
    x = jax.random.normal(jax.random.fold_in(key, 2), (1, n_in, attn.d_model))
    refs = nn.reference_points_for_levels(levels)[None]
    return q, refs, x


def phase_encoder_kernel(backend: str, size: Size, on_tpu: bool) -> None:
    """One encoder block's sampling on ``backend`` vs ``jnp_gather``, fed
    the same value table and sampling points. The windowed kernel runs
    the DEFA configuration (range narrowing, PAP, the FWP-compact table of
    a first block), the fused one the plain one. The points are made
    once: PAP's top-k and the 12-bit offsets are discrete, and two
    programs may round them differently."""
    name = "deformable-detr-defa" if backend == "pallas_windowed" \
        else "deformable-detr"
    attn = attn_config(name, size, jnp.float32)
    levels = size.kernel_levels
    key = jax.random.PRNGKey(SEED + 1)
    params = init_msdeform_attn(key, attn)
    q, refs, x = _encoder_inputs(attn, levels, key)
    ref_plan = msda.make_plan(attn, levels, backend="jnp_gather")
    plan = msda.make_plan(attn, levels, backend=backend)
    with jax.default_matmul_precision("highest"):
        _, state = msda.msda_attention(params, ref_plan, q, refs, x)
        cache = msda.build_value_cache(params, ref_plan, x, state)
        sel, pts = generate_points(params, attn, q, refs, levels,
                                   pix2slot=cache.pix2slot,
                                   keep_idx=cache.keep_idx)
    run = lambda p: (lambda v, pts_, probs: msda.get_backend(p.backend)(
        p, v, pts_, probs, cache=cache))
    want = jax.jit(run(ref_plan))(cache.v, pts, sel.probs)
    exe, native = compile_native(run(plan), cache.v, pts, sel.probs)
    t0 = time.perf_counter()
    got = jax.block_until_ready(exe(cache.v, pts, sel.probs))
    sec = time.perf_counter() - t0
    err = rel_err(got, want)
    log(f"[{backend}] {plan.describe()}")
    log(f"[{backend}] tpu_custom_call={native} rel_err={err!r} "
        f"(bound {KERNEL_TOL}) call_seconds={sec!r}")
    if on_tpu:
        check(native, f"{backend} compiled without a TPU kernel")
    check(err <= KERNEL_TOL, f"{backend} disagrees with jnp_gather")


def phase_decode_kernel(size: Size, on_tpu: bool) -> None:
    """The 6-layer x 300-query decoder on ``pallas_decode`` vs
    ``jnp_gather`` over one random encoder memory."""
    attn = attn_config("deformable-detr", size, jnp.float32)
    levels = size.kernel_levels
    n_in = sum(h * w for h, w in levels)
    dec = msda.MSDADecoderConfig(n_layers=size.n_blocks,
                                 n_queries=size.n_queries, d_ffn=size.d_ffn)
    key = jax.random.PRNGKey(SEED + 2)
    params = msda.init_decoder(key, dec, attn)
    memory = jax.random.normal(jax.random.fold_in(key, 1),
                               (1, n_in, attn.d_model))
    plans = {b: msda.make_plan(attn, levels, backend=b,
                               n_queries=dec.n_queries,
                               n_consumers=dec.n_layers)
             for b in ("jnp_gather", "pallas_decode")}
    run = lambda p: (lambda m: msda.decoder_apply(params, dec, p, m)[0])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(run(plans["jnp_gather"]))(memory)
        exe, native = compile_native(run(plans["pallas_decode"]), memory)
        t0 = time.perf_counter()
        got = jax.block_until_ready(exe(memory))
        sec = time.perf_counter() - t0
    err = rel_err(got, want)
    log(f"[pallas_decode] {plans['pallas_decode'].describe()}")
    log(f"[pallas_decode] tpu_custom_call={native} rel_err={err!r} "
        f"(bound {KERNEL_TOL}) call_seconds={sec!r}")
    if on_tpu:
        check(native, "pallas_decode compiled without a TPU kernel")
    check(err <= KERNEL_TOL, "pallas_decode disagrees with jnp_gather")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; never prints the ok line")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} jax={jax.__version__}")
    log(f"[device] compile cache: {enable_compile_cache()}")
    size = FULL if on_tpu else TINY

    t0 = time.perf_counter()
    cfg, params, req = phase_serve(size, on_tpu)
    phase_reference(size, cfg, params, req)
    for backend in ("pallas_fused", "pallas_windowed"):
        phase_encoder_kernel(backend, size, on_tpu)
    phase_decode_kernel(size, on_tpu)
    stats = dev.memory_stats() or {}
    log(f"[device] peak_bytes_in_use={stats.get('peak_bytes_in_use')!r} "
        f"wall_seconds={time.perf_counter() - t0!r}")

    if not on_tpu:
        print("chip_smoke: rehearsal passed off the TPU; no ok line",
              file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
