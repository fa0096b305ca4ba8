"""Public jit'd wrappers for the Pallas kernels.

On the CPU platform the kernels run in Pallas interpret mode (the kernel
body runs as traced Python); on any other platform they compile
natively. The platform alone decides: there is no switch that makes an
accelerator run interpret."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import msgs_decode as msgs_decode_kernel
from repro.kernels.msgs_fused import msgs_fused_pallas, msgs_fused_packed_pallas
from repro.kernels.msgs_windowed import msgs_windowed_msp_pallas
from repro.kernels.matmul import matmul_pallas


def _interpret() -> bool:
    """Interpret mode exactly on the CPU platform."""
    return jax.default_backend() == "cpu"


def msgs_fused(v, x_px, y_px, start, wl, hl, probs,
               remap: Optional[jnp.ndarray] = None,
               scale: Optional[jnp.ndarray] = None, *,
               block_q: int = 128):
    """Fused grid-sample + aggregation. See kernels/msgs_fused.py.
    ``scale`` is the int8 table's (B, 1, H, Dh) dequant scale."""
    return msgs_fused_pallas(v, x_px, y_px, start.astype(jnp.int32),
                             wl.astype(jnp.int32), hl.astype(jnp.int32),
                             probs, remap, scale,
                             block_q=block_q, interpret=_interpret())


def msgs_fused_packed(v, x_px, y_px, start, wl, hl, probs,
                      remap: Optional[jnp.ndarray] = None,
                      scale: Optional[jnp.ndarray] = None, *,
                      head_pack: int = 4, block_q: int = 128):
    """Head-packed fused grid-sample + aggregation: ``head_pack`` heads
    share one 128-lane group (see kernels/msgs_fused.py)."""
    return msgs_fused_packed_pallas(v, x_px, y_px, start.astype(jnp.int32),
                                    wl.astype(jnp.int32), hl.astype(jnp.int32),
                                    probs, remap, scale, head_pack=head_pack,
                                    block_q=block_q, interpret=_interpret())


def msgs_windowed_msp(v, x_px, y_px, lvl_of_pt, probs,
                      remap: Optional[jnp.ndarray] = None,
                      keep_idx: Optional[jnp.ndarray] = None,
                      scale: Optional[jnp.ndarray] = None, *,
                      level_shapes, ranges, tile_q: int = 128,
                      head_pack: int = 1, caps=None):
    """Single-launch multi-scale-parallel windowed MSGS + fused in-kernel
    level aggregation; FWP-compact-native. ``scale`` is the int8 table's
    per-group (B, n_groups, G, Dh) dequant scale.
    See kernels/msgs_windowed.py."""
    return msgs_windowed_msp_pallas(
        v, x_px, y_px, lvl_of_pt.astype(jnp.int32), probs,
        remap, keep_idx, scale,
        level_shapes=tuple(tuple(int(x) for x in s) for s in level_shapes),
        ranges=tuple(float(r) for r in ranges), tile_q=tile_q,
        head_pack=head_pack,
        caps=None if caps is None else tuple(int(c) for c in caps),
        interpret=_interpret())


def stage_decode_table(v, remap=None, *, head_pack: int = 1, scale=None):
    """Stage the value table ONCE in the decode launch layout (see
    kernels/msgs_decode.py); int8 tables stage codes + the per-group
    scale row together. Routed through the module attribute so the
    staging-spy tests can count stagings per memory."""
    return msgs_decode_kernel.stage_decode_table(v, remap,
                                                 head_pack=head_pack,
                                                 scale=scale)


def msgs_decode(staged, x_px, y_px, start, wl, hl, probs, *,
                block_q: int = 128):
    """Per-layer persistent decode sampling against a pre-staged table.
    Differentiable (custom_vjp backward = exact jnp reference)."""
    return msgs_decode_kernel.msgs_decode_pallas(
        staged, x_px, y_px, start, wl, hl, probs,
        block_q=block_q, interpret=_interpret())


def msgs_decode_layers(staged, x_px, y_px, start, wl, hl, probs, *,
                       block_q: int = 128):
    """Stacked multi-layer persistent decode: one launch, all layers'
    points, table staged once per (batch, head-group)."""
    return msgs_decode_kernel.msgs_decode_layers_pallas(
        staged, x_px, y_px, start, wl, hl, probs,
        block_q=block_q, interpret=_interpret())


def matmul(x, w, w_scale=None, *, bm: int = 128, bn: int = 128, bk: int = 128):
    """Tiled MXU matmul; int8-weight variant dequantizes in-kernel."""
    return matmul_pallas(x, w, w_scale, bm=bm, bn=bn, bk=bk,
                         interpret=_interpret())


def flash_decode(q, k, v, valid, *, chunk: int = 512):
    """Fused one-token GQA decode attention over a (masked) KV cache."""
    from repro.kernels.flash_decode import flash_decode_pallas
    return flash_decode_pallas(q, k, v, valid, chunk=chunk,
                               interpret=_interpret())
