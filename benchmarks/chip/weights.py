"""Weights from the seed, made on the device in one jitted call.

An architecture module gives the tree of its served weights (``spec``),
each leaf a ``Normal`` or a constant, in the layout of the program's own
parameters; the harness checks it leaf by leaf against the program
before it serves. One draw of N(0, 1) for the whole tree is cut into the
leaves in the tree's order. The distributions are the configuration's
``assumed.weights``. The plain reference reads the same tree, cast to
float32."""
from __future__ import annotations

import functools
from typing import Callable

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


class Normal:
    """A leaf drawn N(0, std^2)."""

    def __init__(self, shape, std):
        self.shape, self.std = tuple(shape), float(std)


def linear(d_in, d_out):
    return {"w": Normal((d_in, d_out), 1.0 / np.sqrt(d_in)),
            "b": Normal((d_out,), BIAS_STD)}


def conv(c_in, c_out):
    return {"w": Normal((c_out, c_in, 3, 3), 1.0 / np.sqrt(c_in * 9)),
            "b": Normal((c_out,), BIAS_STD)}


def ln(d):
    return {"scale": np.ones((d,), np.float32),
            "bias": np.zeros((d,), np.float32)}


def sampling(m: Model) -> dict:
    d, h, lp, dh = m.d_model, m.n_heads, m.n_lp, m.head_dim
    return {
        "attn_w": Normal((d, h, lp), 2.0 / np.sqrt(d)),
        "attn_b": Normal((h, lp), BIAS_STD),
        "offs_w": Normal((d, h, lp * 2), 1.0 / np.sqrt(d)),
        "offs_b": offset_ring_bias(m),
        "out_w": Normal((h, dh, d), 1.0 / np.sqrt(d)),
        "out_b": Normal((d,), BIAS_STD),
    }


def value(m: Model) -> dict:
    d, h, dh = m.d_model, m.n_heads, m.head_dim
    return {"value_w": Normal((d, h, dh), 1.0 / np.sqrt(d)),
            "value_b": Normal((h, dh), BIAS_STD)}


def _init(key, spec: dict, dtype: str) -> dict:
    """One draw of N(0, 1) for every random leaf, cut and scaled."""
    leaves, tree = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, Normal))
    total = sum(int(np.prod(x.shape)) for x in leaves
                if isinstance(x, Normal))
    draw = jax.random.normal(key, (total,), jnp.float32)
    dtype = jnp.dtype(dtype)
    out, at = [], 0
    for x in leaves:
        if isinstance(x, Normal):
            n = int(np.prod(x.shape))
            out.append((draw[at:at + n].reshape(x.shape) * x.std)
                       .astype(dtype))
            at += n
        else:
            out.append(jnp.asarray(x, dtype))
    return jax.tree.unflatten(tree, out)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, spec: Callable[[Model], dict], m: Model) -> dict:
    return _init(key, spec(m), m.dtype)


def make_params(seed: int, m: Model, spec: Callable[[Model], dict]) -> dict:
    """The weights of the tree ``spec(m)`` for ``seed``, in ``m.dtype``,
    on the default device."""
    return _make(key_from_seed(seed), spec, m)


def to_f32(params: dict) -> dict:
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
