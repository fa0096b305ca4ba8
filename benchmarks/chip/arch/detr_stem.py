"""Deformable-DETR's transformer behind the program's 5-conv stem.

What ``repro.core.detector`` serves: five 3x3 stride-2 convs of width
``backbone_width`` (``stem``, ``c1``..``c4``), whose last four outputs are
the pyramid at strides 4-32 of one square ``input_size`` bucket; a linear
projection of each level to d_model; the sine embedding of pixel
indices, with no level embedding; the deformable encoder and decoder of
``reference.py``, whose reference points never move (no box refinement,
one stage); a softmax class head over ``num_classes`` + 1 (background)
and a one-layer box head.

A configuration file states each of Deformable-DETR's choices that this
module serves (``FIXED``); a file that states another value, leaves one
out, or holds a key this module does not know is refused.

The architecture interface, which the harness, ``check.py``, ``work.py``
and the readers call through ``Model.arch``: ``parse``, ``engine``,
``make_params``, ``to_f32``, ``reference``, ``canvas``,
``output_shapes``, ``flops_per_image``, ``msda_calls``."""
from __future__ import annotations

import dataclasses
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import model, reference as ref, weights, work
from benchmarks.chip.model import Refused
from benchmarks.chip.weights import Normal

# the one value of each choice of Deformable-DETR's that this module serves
FIXED = {"backbone": "stem5x32", "with_box_refine": False,
         "two_stage": False, "class_head": "softmax", "level_embed": False,
         "bbox_embed_layers": 1, "feature_strides": [4, 8, 16, 32]}
READ = ("name", "arch", "hidden_dim", "nheads", "num_feature_levels",
        "enc_n_points", "dec_n_points", "enc_layers", "dec_layers",
        "dim_feedforward", "num_queries", "num_classes", "backbone_width",
        "input_size", "dtype", "defa")
# what a file says of itself, read by no code but the manifest's tests
NOTES = ("source", "source_config", "what_this_is", "reduced", "reduced_why",
         "departures", "assumed", "limits", "limits_why")
STEM_STRIDES = (2, 4, 8, 16, 32)        # stem, c1..c4


@dataclasses.dataclass(frozen=True)
class StemModel(model.Model):
    backbone_width: int
    input_size: int


def parse(raw: dict) -> StemModel:
    name = raw.get("name")
    unknown = sorted(set(raw) - set(READ) - set(FIXED) - set(NOTES))
    if unknown:
        raise Refused(f"{name}: keys {unknown} are not known to arch "
                      f"detr_stem")
    for key, served in FIXED.items():
        if key not in raw:
            raise Refused(f"{name}: states no {key} (arch detr_stem serves "
                          f"{key}={served!r})")
        got = raw[key]
        if type(got) is not type(served) or got != served:
            raise Refused(f"{name}: {key}={got!r}, but arch detr_stem "
                          f"serves only {key}={served!r}")
    strides = FIXED["feature_strides"]
    if int(raw["num_feature_levels"]) != len(strides):
        raise Refused(f"{name}: num_feature_levels="
                      f"{raw['num_feature_levels']!r}, but the stem gives "
                      f"{len(strides)} levels")
    s = int(raw["input_size"])
    return StemModel(**model.transformer(raw),
                     level_shapes=tuple((s // k, s // k) for k in strides),
                     backbone_width=int(raw["backbone_width"]),
                     input_size=s)


# ---- the program ----------------------------------------------------------

def program(m: StemModel):
    """The detector config that the program serves for ``m``."""
    from repro.core.detector import DetectorConfig
    from repro.core.encoder import EncoderConfig
    from repro.core.msdeform_attn import MSDeformAttnConfig
    from repro.msda.decoder import MSDADecoderConfig
    dt = jnp.dtype(m.dtype)
    d = m.defa
    attn = MSDeformAttnConfig(
        d_model=m.d_model, n_heads=m.n_heads, n_levels=m.n_levels,
        n_points=m.n_points, pap_mode="topk" if d else "off",
        pap_keep=d.pap_keep if d else m.n_lp,
        fwp_mode="compact" if d else "off",
        fwp_k=d.fwp_k if d else 1.0,
        fwp_capacity=d.fwp_capacity if d else 1.0,
        range_narrow=d.range_narrow if d else None,
        act_bits=d.act_bits if d else None,
        weight_bits=d.weight_bits if d else None, dtype=dt)
    cfg = DetectorConfig(
        encoder=EncoderConfig(attn=attn, n_blocks=m.enc_layers,
                              d_ffn=m.d_ffn, dtype=dt),
        img_size=m.input_size, n_classes=m.n_classes,
        backbone_width=m.backbone_width, dtype=dt,
        decoder=MSDADecoderConfig(n_layers=m.dec_layers,
                                  n_queries=m.n_queries, d_ffn=m.d_ffn,
                                  dtype=dt))
    if tuple(cfg.level_shapes) != m.level_shapes:
        raise Refused(f"the program's pyramid {cfg.level_shapes} is not "
                      f"the configuration's {m.level_shapes}")
    return cfg


def check_layout(params, cfg) -> None:
    """The benchmark's weights have the program's tree, shapes and dtypes."""
    from repro.core.detector import init_detector
    want = jax.eval_shape(lambda: init_detector(jax.random.PRNGKey(0), cfg))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got != want:
        raise Refused("the benchmark's weight tree differs from the "
                      "program's init_detector tree")


def engine(m: StemModel, params, max_batch: int):
    """The program's server of ``m`` with ``params``, its one square
    bucket compiled (or loaded from the cache)."""
    from repro.msda.plan import plan_for
    from repro.serve.engine import DetrServeEngine
    cfg = program(m)
    check_layout(params, cfg)
    print(f"[setup] encoder plan: "
          f"{plan_for(cfg.encoder.attn, cfg.level_shapes, 'auto').describe()}",
          file=sys.stderr, flush=True)
    return DetrServeEngine(cfg, params, max_batch=max_batch, backend="auto",
                           resolutions=(m.input_size,))


# ---- weights --------------------------------------------------------------

def spec(m: StemModel) -> dict:
    """The tree of the served weights, each leaf a Normal or a constant."""
    w, d = m.backbone_width, m.d_model
    tree = {"stem": weights.conv(3, w)}
    for name in ("c1", "c2", "c3", "c4"):
        tree[name] = weights.conv(w, w)
    tree["proj"] = [weights.linear(w, d) for _ in range(m.n_levels)]
    tree["encoder"] = {"blocks": [
        {"attn": {**weights.sampling(m), **weights.value(m)},
         "ln1": weights.ln(d), "ln2": weights.ln(d),
         "ffn1": weights.linear(d, m.d_ffn),
         "ffn2": weights.linear(m.d_ffn, d)}
        for _ in range(m.enc_layers)]}
    tree["cls_head"] = weights.linear(d, m.n_classes + 1)
    tree["box_head"] = weights.linear(d, 4)
    tree["decoder"] = {
        "query_pos": Normal((m.n_queries, d), 1.0),
        "tgt_embed": Normal((m.n_queries, d), 1.0),
        "ref_head": weights.linear(d, 2),
        "value": weights.value(m),
        "layers": [{
            "self_q": weights.linear(d, d), "self_k": weights.linear(d, d),
            "self_v": weights.linear(d, d), "self_o": weights.linear(d, d),
            "ln_sa": weights.ln(d), "cross": weights.sampling(m),
            "ln1": weights.ln(d),
            "ffn1": weights.linear(d, m.d_ffn),
            "ffn2": weights.linear(m.d_ffn, d), "ln2": weights.ln(d),
            # with_box_refine is off: the reference points never move
            "ref_delta": {"w": np.zeros((d, 2), np.float32),
                          "b": np.zeros((2,), np.float32)}}
            for _ in range(m.dec_layers)],
    }
    return tree


def make_params(seed: int, m: StemModel) -> dict:
    """The served weights of ``m`` for ``seed``, in ``m.dtype``, on the
    default device."""
    return weights.make_params(seed, m, spec)


to_f32 = weights.to_f32


# ---- the plain reference --------------------------------------------------

def _conv_s2(p, x, lo: ref.Round):
    """3x3 conv, stride 2, SAME padding; x (C, H, W)."""
    y = jax.lax.conv_general_dilated(
        lo(x)[None], lo(p["w"]), (2, 2), "SAME",
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=ref.HI)[0]
    return y + p["b"][:, None, None]


def forward(params: dict, m: StemModel, image, lo: ref.Round = ref.ident):
    """params: the served tree in float32; image (3, S, S) float32, the
    request on its canvas. Returns (class logits (Nq, C+1), boxes (Nq, 4)
    as cx, cy, w, h in [0, 1])."""
    geo = ref.Geometry(m)
    x = jax.nn.relu(_conv_s2(params["stem"], image, lo))
    levels = []
    for name in ("c1", "c2", "c3", "c4"):
        x = jax.nn.relu(_conv_s2(params[name], x, lo))
        levels.append(x)
    mem = jnp.concatenate([
        ref.linear(pr, f.reshape(f.shape[0], -1).T, lo)
        for f, pr in zip(levels, params["proj"])], 0)       # (N_in, D)
    pos = jnp.concatenate([ref.sine_embed(h, w, m.d_model)
                           for h, w in geo.shapes], 0)
    refs = jnp.asarray(np.concatenate(
        [ref.pixel_centres(h, w) for h, w in geo.shapes], 0))
    mem, keep = ref.encoder(params["encoder"]["blocks"], m, geo, mem, pos,
                            refs, lo)

    dec = params["decoder"]
    table = ref.value_table(dec["value"], m, mem, keep, lo)
    qpos = dec["query_pos"]
    h = dec["tgt_embed"]
    point = jax.nn.sigmoid(ref.linear(dec["ref_head"], qpos, lo))
    for layer in dec["layers"]:
        h = ref.decoder_layer(layer, m, geo, h, qpos, point, table, lo)
        point = jax.nn.sigmoid(ref.inverse_sigmoid(point)
                               + ref.linear(layer["ref_delta"], h, lo))
    logits = ref.linear(params["cls_head"], h, lo)
    raw = ref.linear(params["box_head"], h, lo)
    cxy = jax.nn.sigmoid(raw[:, :2] + ref.inverse_sigmoid(point))
    boxes = jnp.concatenate([cxy, jax.nn.sigmoid(raw[:, 2:])], -1)
    return logits, boxes


@functools.lru_cache(maxsize=4)
def reference(m: StemModel, control: bool = False):
    """The jitted reference (``control``: operands rounded through
    float8_e4m3fn) of ``m``: (params, canvas) -> (logits, boxes)."""
    lo = ref.round_fp8 if control else ref.ident
    return jax.jit(lambda p, img: forward(p, m, img, lo))


def canvas(image: np.ndarray, m: StemModel) -> np.ndarray:
    """The request padded with zeros to the square bucket, as the engine
    pads it."""
    out = np.zeros((3, m.input_size, m.input_size), np.float32)
    out[:, :image.shape[1], :image.shape[2]] = image
    return out


def output_shapes(m: StemModel) -> tuple:
    """(class logits, boxes) of one served request."""
    return (m.n_queries, m.n_classes + 1), (m.n_queries, 4)


# ---- work -----------------------------------------------------------------

def backbone_flops(m: StemModel) -> float:
    s, w = m.input_size, m.backbone_width
    total, c_in = 0.0, 3
    for stride in STEM_STRIDES:
        total += work.mm_flops((s // stride) ** 2, c_in * 9, w)
        c_in = w
    return total


def decoder_flops(m: StemModel) -> float:
    q, d, h = m.n_queries, m.d_model, m.n_heads
    mm = work.mm_flops
    per_layer = (4 * mm(q, d, d)                     # self-attn q, k, v, o
                 + 2 * mm(q, d, q)                   # scores and values
                 + mm(q, d, h * m.n_lp)
                 + mm(q, d, h * m.points_kept * 2)
                 + work.decoder_call(m).flops
                 + mm(q, d, d)
                 + mm(q, d, m.d_ffn) + mm(q, m.d_ffn, d)
                 + mm(q, d, 2))                      # reference refinement
    table = mm(work.table_rows(m, m.enc_layers), d, d)   # one shared table
    return table + mm(q, d, 2) + m.dec_layers * per_layer


def flops_per_image(m: StemModel) -> float:
    """Model FLOPs of one image's forward, the stem counted as it runs."""
    d, mm = m.d_model, work.mm_flops
    proj = sum(mm(h * w, m.backbone_width, d) for h, w in m.level_shapes)
    heads = mm(m.n_queries, d, m.n_classes + 1) + mm(m.n_queries, d, 4)
    return (backbone_flops(m) + proj
            + sum(work.encoder_block_flops(m, b)
                  for b in range(m.enc_layers))
            + decoder_flops(m) + heads)


def msda_calls(m: StemModel) -> list:
    """The MSDA sampling calls of one image's forward, in program order:
    the encoder blocks, then the decoder layers."""
    return ([work.encoder_call(m, b) for b in range(m.enc_layers)]
            + [work.decoder_call(m)] * m.dec_layers)
