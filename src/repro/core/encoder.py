"""Deformable-DETR-style encoder: a stack of MSDeformAttn blocks with the
DEFA block-to-block FWP mask chain (paper §3.1/§4.1 dataflow).

Block k counts sampled-pixel frequency during its MSGS and hands the
resulting fmap mask to block k+1, which prunes its value projection with it
(the first block always runs unpruned — there is no mask yet). The chain is
carried by an explicit :class:`repro.msda.MSDAPipelineState`, and every
block executes through one :class:`repro.msda.MSDAPlan` resolved ahead of
the loop (backend, tiling, and lane layout are shape-static)."""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import nn
from repro.core.msdeform_attn import (
    MSDeformAttnConfig, init_msdeform_attn, logical_axes,
)
from repro.msda import MSDAPipelineState, make_plan, msda_attention


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    attn: MSDeformAttnConfig = dataclasses.field(default_factory=MSDeformAttnConfig)
    n_blocks: int = 6
    d_ffn: int = 1024
    dtype: Any = jnp.float32

    @property
    def d_model(self) -> int:
        return self.attn.d_model


def init_encoder(key: jax.Array, cfg: EncoderConfig) -> dict:
    blocks = []
    for i in range(cfg.n_blocks):
        key, k1, k2, k3 = jax.random.split(key, 4)
        blocks.append({
            "attn": init_msdeform_attn(k1, cfg.attn),
            "ln1": nn.layer_norm_init(cfg.d_model, cfg.dtype),
            "ln2": nn.layer_norm_init(cfg.d_model, cfg.dtype),
            "ffn1": nn.linear_init(k2, cfg.d_model, cfg.d_ffn, cfg.dtype),
            "ffn2": nn.linear_init(k3, cfg.d_ffn, cfg.d_model, cfg.dtype),
        })
    return {"blocks": blocks}


def encoder_logical_axes(cfg: EncoderConfig) -> dict:
    blk = {
        "attn": logical_axes(cfg.attn),
        "ln1": {"scale": (None,), "bias": (None,)},
        "ln2": {"scale": (None,), "bias": (None,)},
        "ffn1": {"w": ("embed", "mlp"), "b": ("mlp",)},
        "ffn2": {"w": ("mlp", "embed"), "b": (None,)},
    }
    return {"blocks": [blk for _ in range(cfg.n_blocks)]}


@jax.named_scope("encoder")
def encoder_apply(
    params: dict,
    cfg: EncoderConfig,
    x_flat: jnp.ndarray,                   # (B, N_in, D) flattened pyramid
    pos_embed: jnp.ndarray,                # (N_in, D)
    ref_points: jnp.ndarray,               # (N_in, 2) or (B, N_in, 2)
    level_shapes: Sequence[Tuple[int, int]],
    *,
    collect_stats: bool = False,
    backend: Optional[str] = None,         # msda backend override (or "auto")
    return_state: bool = False,
):
    """Returns (features (B,N_in,D), aux with per-block DEFA stats).

    ``aux["blocks"]`` has one aligned entry per block (``None`` when that
    block didn't collect). With ``return_state=True`` the final
    :class:`MSDAPipelineState` is returned as a third value — the decoder
    consumes it so its shared value cache inherits the LAST encoder
    block's FWP compaction."""
    b = x_flat.shape[0]
    if ref_points.ndim == 2:
        ref_points = jnp.broadcast_to(ref_points[None], (b,) + ref_points.shape)
    plan = make_plan(cfg.attn, tuple((int(lh), int(lw))
                                     for lh, lw in level_shapes),
                     backend=backend)
    h = x_flat
    state = MSDAPipelineState.initial()
    for i, blk in enumerate(params["blocks"]):
        with jax.named_scope(f"block_{i}"):
            q = h + pos_embed[None]
            attn_out, state = msda_attention(
                blk["attn"], plan, q, ref_points, h,
                state=state, collect_stats=collect_stats)
            with jax.named_scope("norm"):
                h = nn.layer_norm(blk["ln1"], h + attn_out)
            with jax.named_scope("ffn"):
                ff = nn.linear(blk["ffn2"],
                               jax.nn.relu(nn.linear(blk["ffn1"], h)))
            with jax.named_scope("norm"):
                h = nn.layer_norm(blk["ln2"], h + ff)
    aux = {"blocks": list(state.block_stats)}
    if return_state:
        return h, aux, state
    return h, aux
