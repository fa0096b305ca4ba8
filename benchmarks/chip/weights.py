"""Weights from the seed, made on the device in one jitted call.

The tree has the layout the detector takes (``stem``, ``c1``..``c4``,
``proj``, ``encoder.blocks``, ``decoder``, heads); the harness checks it
leaf by leaf against the program's own ``init_detector`` shapes before
it serves. The distributions are the configuration's ``assumed.weights``.
The plain reference reads the same tree, cast to float32."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.model import Model

BIAS_STD = 0.02


def key_from_seed(seed: int) -> jax.Array:
    """A threefry key from any non-negative whole number, also one wider
    than 32 bits."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def offset_ring_bias(m: Model) -> np.ndarray:
    """Deformable-DETR's offset bias: head h's points start on a ring at
    angle 2*pi*h/H, scaled by the point index (shape (H, L*P*2))."""
    thetas = np.arange(m.n_heads) * (2.0 * np.pi / m.n_heads)
    grid = np.stack([np.cos(thetas), np.sin(thetas)], -1)
    grid = grid / np.abs(grid).max(-1, keepdims=True)
    grid = np.tile(grid[:, None, None, :], (1, m.n_levels, m.n_points, 1))
    grid = grid * (np.arange(m.n_points) + 1.0)[None, None, :, None]
    return grid.reshape(m.n_heads, m.n_lp * 2).astype(np.float32)


class _Normal:
    """A leaf drawn N(0, std^2)."""

    def __init__(self, shape, std):
        self.shape, self.std = tuple(shape), float(std)


def _linear(d_in, d_out):
    return {"w": _Normal((d_in, d_out), 1.0 / np.sqrt(d_in)),
            "b": _Normal((d_out,), BIAS_STD)}


def _conv(c_in, c_out):
    return {"w": _Normal((c_out, c_in, 3, 3), 1.0 / np.sqrt(c_in * 9)),
            "b": _Normal((c_out,), BIAS_STD)}


def _ln(d):
    return {"scale": np.ones((d,), np.float32),
            "bias": np.zeros((d,), np.float32)}


def _sampling(m: Model) -> dict:
    d, h, lp, dh = m.d_model, m.n_heads, m.n_lp, m.head_dim
    return {
        "attn_w": _Normal((d, h, lp), 2.0 / np.sqrt(d)),
        "attn_b": _Normal((h, lp), BIAS_STD),
        "offs_w": _Normal((d, h, lp * 2), 1.0 / np.sqrt(d)),
        "offs_b": offset_ring_bias(m),
        "out_w": _Normal((h, dh, d), 1.0 / np.sqrt(d)),
        "out_b": _Normal((d,), BIAS_STD),
    }


def _value(m: Model) -> dict:
    d, h, dh = m.d_model, m.n_heads, m.head_dim
    return {"value_w": _Normal((d, h, dh), 1.0 / np.sqrt(d)),
            "value_b": _Normal((h, dh), BIAS_STD)}


def _spec(m: Model) -> dict:
    """The tree of the served weights, each leaf a _Normal or a constant."""
    w, d = m.backbone_width, m.d_model
    spec = {"stem": _conv(3, w)}
    for name in ("c1", "c2", "c3", "c4"):
        spec[name] = _conv(w, w)
    spec["proj"] = [_linear(w, d) for _ in range(m.n_levels)]
    spec["encoder"] = {"blocks": [
        {"attn": {**_sampling(m), **_value(m)}, "ln1": _ln(d), "ln2": _ln(d),
         "ffn1": _linear(d, m.d_ffn), "ffn2": _linear(m.d_ffn, d)}
        for _ in range(m.enc_layers)]}
    spec["cls_head"] = _linear(d, m.n_classes + 1)
    spec["box_head"] = _linear(d, 4)
    spec["decoder"] = {
        "query_pos": _Normal((m.n_queries, d), 1.0),
        "tgt_embed": _Normal((m.n_queries, d), 1.0),
        "ref_head": _linear(d, 2),
        "value": _value(m),
        "layers": [{
            "self_q": _linear(d, d), "self_k": _linear(d, d),
            "self_v": _linear(d, d), "self_o": _linear(d, d),
            "ln_sa": _ln(d), "cross": _sampling(m), "ln1": _ln(d),
            "ffn1": _linear(d, m.d_ffn), "ffn2": _linear(m.d_ffn, d),
            "ln2": _ln(d),
            # with_box_refine is off: the reference points never move
            "ref_delta": {"w": np.zeros((d, 2), np.float32),
                          "b": np.zeros((2,), np.float32)}}
            for _ in range(m.dec_layers)],
    }
    return spec


def _init(key, m: Model) -> dict:
    """One draw of N(0, 1) for every random leaf, cut and scaled."""
    spec = _spec(m)
    leaves, tree = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, _Normal))
    total = sum(int(np.prod(x.shape)) for x in leaves
                if isinstance(x, _Normal))
    draw = jax.random.normal(key, (total,), jnp.float32)
    dtype = jnp.dtype(m.dtype)
    out, at = [], 0
    for x in leaves:
        if isinstance(x, _Normal):
            n = int(np.prod(x.shape))
            out.append((draw[at:at + n].reshape(x.shape) * x.std)
                       .astype(dtype))
            at += n
        else:
            out.append(jnp.asarray(x, dtype))
    return jax.tree.unflatten(tree, out)


@functools.partial(jax.jit, static_argnums=1)
def _make(key, m: Model) -> dict:
    return _init(key, m)


def make_params(seed: int, m: Model) -> dict:
    """The served weights of ``m`` for ``seed``, in ``m.dtype``, on the
    default device."""
    return _make(key_from_seed(seed), m)


def to_f32(params: dict) -> dict:
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
