"""Toy end-to-end detector around the deformable encoder.

COCO is not available offline, so the paper's accuracy experiments (Fig. 6a)
are reproduced on a synthetic rectangle-detection task (see
repro/data/detection.py): a conv backbone builds a 4-level pyramid, the
DEFA encoder refines it, and a head predicts class + box. Two heads exist:

  * the seed's dense per-pixel head (one prediction per encoder query) —
    the default, used by the dense-assignment accuracy experiments;
  * a deformable-DETR-style DECODER head (``DetectorConfig.decoder``):
    N_q learned queries cross-attend against the encoder memory through
    ONE shared :class:`repro.msda.MSDAValueCache` — the paper's
    feature-map-reusing decoder workload (build-once, sample-everywhere;
    see repro/msda/decoder.py).

The pruning/quant AP deltas are measured on this task (EXPERIMENTS.md
compares *relative* AP drops against the paper's COCO numbers)."""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import nn

try:                                       # optional dep (scipy)
    from scipy.optimize import linear_sum_assignment as _linear_sum_assignment
except ImportError:                        # pragma: no cover - env-dependent
    _linear_sum_assignment = None
from repro.core.encoder import EncoderConfig, init_encoder, encoder_apply, encoder_logical_axes
from repro.msda.decoder import (MSDADecoderConfig, decoder_apply,
                                decoder_logical_axes, init_decoder)


@dataclasses.dataclass(frozen=True)
class DetectorConfig:
    encoder: EncoderConfig = dataclasses.field(default_factory=EncoderConfig)
    img_size: int = 64
    n_classes: int = 4                     # + background
    backbone_width: int = 32
    dtype: Any = jnp.float32
    # None => the seed's dense per-pixel head; set => DETR-style decoder
    # head over a shared value cache (build-once, sample-everywhere)
    decoder: Optional[MSDADecoderConfig] = None

    @property
    def level_shapes(self) -> Tuple[Tuple[int, int], ...]:
        s = self.img_size
        return tuple((s // k, s // k) for k in (4, 8, 16, 32))

    @property
    def d_model(self) -> int:
        return self.encoder.d_model


def init_detector(key: jax.Array, cfg: DetectorConfig) -> dict:
    keys = jax.random.split(key, 10)
    w, d = cfg.backbone_width, cfg.d_model
    params = {
        "stem": nn.conv_init(keys[0], 3, 3, w, cfg.dtype),         # stride 2
        "c1": nn.conv_init(keys[1], 3, w, w, cfg.dtype),           # stride 2 -> /4
        "c2": nn.conv_init(keys[2], 3, w, w, cfg.dtype),           # stride 2 -> /8
        "c3": nn.conv_init(keys[3], 3, w, w, cfg.dtype),           # stride 2 -> /16
        "c4": nn.conv_init(keys[4], 3, w, w, cfg.dtype),           # stride 2 -> /32
        "proj": [nn.linear_init(keys[5 + i], w, d, cfg.dtype) for i in range(4)],
        "encoder": init_encoder(keys[9], cfg.encoder),
        "cls_head": nn.linear_init(jax.random.fold_in(key, 101),
                                   d, cfg.n_classes + 1, cfg.dtype),
        "box_head": nn.linear_init(jax.random.fold_in(key, 102), d, 4, cfg.dtype),
    }
    if cfg.decoder is not None:
        params["decoder"] = init_decoder(jax.random.fold_in(key, 103),
                                         cfg.decoder, cfg.encoder.attn)
    return params


def detector_logical_axes(cfg: DetectorConfig) -> dict:
    conv_ax = {"w": (None, None, None, None), "b": (None,)}
    lin_ax = {"w": ("embed", None), "b": (None,)}
    axes = {
        "stem": conv_ax, "c1": conv_ax, "c2": conv_ax, "c3": conv_ax, "c4": conv_ax,
        "proj": [{"w": (None, "embed"), "b": (None,)} for _ in range(4)],
        "encoder": encoder_logical_axes(cfg.encoder),
        "cls_head": lin_ax, "box_head": lin_ax,
    }
    if cfg.decoder is not None:
        axes["decoder"] = decoder_logical_axes(cfg.decoder)
    return axes


def decoder_plan(cfg: DetectorConfig, backend: Optional[str] = None):
    """The decode-shaped MSDAPlan for this detector's decoder head.

    Single source of the raster-only-backend fallback: raster-only
    kernels (the windowed kernel) have no decode-shaped launch, so an
    explicit (or config-level) request for one degrades to ``auto`` for
    the decoder (which may then pick the persistent decode kernel)."""
    from repro.msda import backend_info
    from repro.msda.plan import plan_for
    assert cfg.decoder is not None, "decoder head required"
    dec_backend = backend or getattr(cfg.encoder.attn, "backend", None)
    if dec_backend is not None and dec_backend != "auto" \
            and backend_info(dec_backend).raster_only:
        dec_backend = "auto"
    # memoized (plan_for): the serve engine resolves one plan per shape
    # bucket and every forward of that bucket shares it
    return plan_for(cfg.encoder.attn, cfg.level_shapes, dec_backend,
                    cfg.decoder.n_queries, cfg.decoder.n_layers)


def encoder_backend(backend: Optional[str]) -> Optional[str]:
    """The mirror fallback for the raster ENCODER: decode-only backends
    (``pallas_decode``) have no raster launch, so such a request degrades
    to ``auto`` for the encoder while staying in force for the decoder
    (``examples/detr_serve.py --backend pallas_decode``)."""
    from repro.msda import backend_info
    if backend is not None and backend != "auto" \
            and backend_info(backend).decode_only:
        return "auto"
    return backend


def _pyramid(params, cfg: DetectorConfig, images: jnp.ndarray):
    """images (B,3,S,S) -> list of 4 fmaps (B, w, H_l, W_l), computed in
    the detector's dtype."""
    x = jax.nn.relu(nn.conv2d(params["stem"], images.astype(cfg.dtype),
                              stride=2))
    feats = []
    for name in ("c1", "c2", "c3", "c4"):
        x = jax.nn.relu(nn.conv2d(params[name], x, stride=2))
        feats.append(x)
    return feats


def detector_apply(params: dict, cfg: DetectorConfig, images: jnp.ndarray,
                   *, collect_stats: bool = False,
                   backend: str | None = None):
    """Returns (cls_logits (B,Nq,C+1), boxes (B,Nq,4 cxcywh), aux).

    Nq is N_in (per-pixel head) or ``cfg.decoder.n_queries`` (decoder
    head). ``backend`` overrides the MSDA backend ("auto" lets the plan
    pick by VMEM fit; see repro/msda/plan.py). With the decoder head,
    ``aux["decoder_blocks"]`` carries the per-layer decoder stats and
    the decoder samples ONE shared value cache built from the encoder
    memory under the encoder chain's final FWP compaction."""
    with jax.named_scope("backbone"):
        feats = _pyramid(params, cfg, images)
    level_shapes = cfg.level_shapes
    with jax.named_scope("input_proj"):
        flat = []
        for f, proj in zip(feats, params["proj"]):
            b, c, h, w = f.shape
            flat.append(nn.linear(
                proj, f.transpose(0, 2, 3, 1).reshape(b, h * w, c)))
        x_flat = jnp.concatenate(flat, axis=1)                      # (B, N_in, D)
        pos = jnp.concatenate(
            [nn.sine_pos_embed_2d(h, w, cfg.d_model) for h, w in level_shapes],
            axis=0)
        refs = nn.reference_points_for_levels(level_shapes)
    enc, aux, state = encoder_apply(
        params["encoder"], cfg.encoder, x_flat, pos, refs, level_shapes,
        collect_stats=collect_stats, backend=encoder_backend(backend),
        return_state=True)

    if cfg.decoder is None:
        with jax.named_scope("heads"):
            cls_logits = nn.linear(params["cls_head"], enc)
            boxes = jax.nn.sigmoid(nn.linear(params["box_head"], enc))
        return cls_logits, boxes, aux

    # ---- decoder head: build-once shared cache, N_q learned queries ------
    plan = decoder_plan(cfg, backend)
    hs, dec_refs, dstate = decoder_apply(params["decoder"], cfg.decoder,
                                         plan, enc, state,
                                         collect_stats=collect_stats)
    with jax.named_scope("heads"):
        cls_logits = nn.linear(params["cls_head"], hs)
        raw = nn.linear(params["box_head"], hs)
        # centers refine the decoder's reference points (deformable-DETR)
        cxy = jax.nn.sigmoid(raw[..., :2] + nn.inverse_sigmoid(dec_refs))
        wh = jax.nn.sigmoid(raw[..., 2:])
        boxes = jnp.concatenate([cxy, wh], axis=-1)
    aux = dict(aux)
    aux["decoder_blocks"] = list(dstate.block_stats)
    return cls_logits, boxes, aux


def detection_loss(params: dict, cfg: DetectorConfig, images: jnp.ndarray,
                   tgt_cls: jnp.ndarray, tgt_box: jnp.ndarray):
    """Dense per-query assignment loss (per-pixel head).

    tgt_cls: (B, N_in) int — class index, n_classes == background.
    tgt_box: (B, N_in, 4) — cxcywh of owning box (zeros for background)."""
    cls_logits, boxes, _ = detector_apply(params, cfg, images)
    logp = jax.nn.log_softmax(cls_logits, axis=-1)
    ce = -jnp.take_along_axis(logp, tgt_cls[..., None], axis=-1)[..., 0]
    pos = (tgt_cls < cfg.n_classes).astype(jnp.float32)
    # class-balanced: background dominates, weight positives up
    w = jnp.where(pos > 0, 5.0, 1.0)
    cls_loss = jnp.sum(ce * w) / jnp.sum(w)
    l1 = jnp.sum(jnp.abs(boxes - tgt_box), axis=-1)
    box_loss = jnp.sum(l1 * pos) / jnp.maximum(jnp.sum(pos), 1.0)
    return cls_loss + box_loss, {"cls_loss": cls_loss, "box_loss": box_loss}


_INACTIVE_COST = 1e6


def _hungarian_owners_host(cost: np.ndarray) -> np.ndarray:
    """Host-side optimal assignment per batch element: owner[b, m] is the
    query column assigned to gt row m (rows than columns or fewer)."""
    owner = np.zeros(cost.shape[:2], np.int32)
    for b in range(cost.shape[0]):
        row, col = _linear_sum_assignment(cost[b])
        owner[b, row] = col.astype(np.int32)
    return owner


def match_queries(cost: jnp.ndarray, gt_active: jnp.ndarray,
                  matcher: Optional[str] = None) -> jnp.ndarray:
    """gt -> query assignment for the set-prediction loss.

    ``cost`` (B, M, Nq) is consumed under ``stop_gradient`` (the
    assignment is a discrete decision; gradients flow through the matched
    boxes, not the matching). Matchers:

      * ``"hungarian"`` — ``scipy.optimize.linear_sum_assignment`` via
        ``jax.pure_callback`` (jit-safe): globally optimal, every active
        gt gets a DISTINCT query. Inactive gt rows are flattened to a
        constant cost so they take leftover queries without disturbing
        the active rows' optimum (they are masked out of the loss anyway).
      * ``"greedy"`` — the seed matcher: per-gt argmin, collisions
        allowed. The fallback when scipy is absent (optional dep) or the
        gt count exceeds the query count.

    ``matcher=None`` auto-selects hungarian when scipy is available."""
    if matcher is None:
        matcher = "hungarian" if _linear_sum_assignment is not None \
            else "greedy"
    if matcher not in ("hungarian", "greedy"):
        raise ValueError(f"unknown matcher {matcher!r}")
    cost = jax.lax.stop_gradient(cost)
    b, m, nq = cost.shape
    if matcher == "greedy" or _linear_sum_assignment is None or m > nq:
        return jnp.argmin(cost, axis=-1).astype(jnp.int32)
    cost = jnp.where(gt_active[:, :, None], cost, _INACTIVE_COST)
    # a diverged step (NaN/inf boxes) must degrade to a garbage-but-valid
    # assignment and a detectable NaN loss, like the greedy argmin does —
    # linear_sum_assignment raises on non-finite entries
    cost = jnp.nan_to_num(cost, nan=_INACTIVE_COST, posinf=_INACTIVE_COST,
                          neginf=-_INACTIVE_COST)
    return jax.pure_callback(
        _hungarian_owners_host,
        jax.ShapeDtypeStruct((b, m), jnp.int32), cost)


def decoder_detection_loss(params: dict, cfg: DetectorConfig,
                           images: jnp.ndarray, gt_cls: jnp.ndarray,
                           gt_box: jnp.ndarray, gt_active: jnp.ndarray,
                           matcher: Optional[str] = None):
    """Set-prediction loss for the decoder head (Hungarian matching).

    Each ACTIVE ground-truth box is assigned the query whose predicted
    box is closest in L1 — optimally via :func:`match_queries`
    (``linear_sum_assignment``; greedy per-gt argmin fallback when scipy
    is missing or ``matcher="greedy"``). The assignment happens under
    ``stop_gradient``; matched queries learn class + box, the rest learn
    background. The class targets are derived query-side (no
    duplicate-index scatter), so an inactive GT slot can never claim a
    query; under the greedy fallback a collision between two active GTs
    resolves deterministically to the lowest GT index (Hungarian
    assignments are collision-free by construction).

    gt_cls (B, M) int, gt_box (B, M, 4) cxcywh, gt_active (B, M) bool."""
    assert cfg.decoder is not None, "decoder head required"
    cls_logits, boxes, _ = detector_apply(params, cfg, images)
    b, nq, _ = cls_logits.shape

    cost = jnp.sum(jnp.abs(boxes[:, None] - gt_box[:, :, None]), -1)  # (B,M,Nq)
    owner = match_queries(cost, gt_active, matcher)                   # (B,M)

    # query-side targets: query q is positive iff some ACTIVE gt owns it
    claimed = (owner[:, :, None] == jnp.arange(nq)[None, None]) \
        & gt_active[:, :, None]                                       # (B,M,Nq)
    matched = jnp.any(claimed, axis=1)                                # (B,Nq)
    first_m = jnp.argmax(claimed, axis=1)                             # (B,Nq)
    cls_of = jnp.take_along_axis(gt_cls.astype(jnp.int32), first_m, axis=1)
    tgt_cls = jnp.where(matched, cls_of, cfg.n_classes)

    logp = jax.nn.log_softmax(cls_logits, axis=-1)
    ce = -jnp.take_along_axis(logp, tgt_cls[..., None], axis=-1)[..., 0]
    pos = (tgt_cls < cfg.n_classes).astype(jnp.float32)
    w = jnp.where(pos > 0, 5.0, 1.0)
    cls_loss = jnp.sum(ce * w) / jnp.sum(w)

    matched_box = jnp.take_along_axis(boxes, owner[..., None], axis=1)  # (B,M,4)
    l1 = jnp.sum(jnp.abs(matched_box - gt_box), axis=-1)
    act = gt_active.astype(jnp.float32)
    box_loss = jnp.sum(l1 * act) / jnp.maximum(jnp.sum(act), 1.0)
    return cls_loss + box_loss, {"cls_loss": cls_loss, "box_loss": box_loss}
