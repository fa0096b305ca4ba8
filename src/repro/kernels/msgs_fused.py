"""Pallas TPU kernel: fused MSGS (bilinear grid-sampling) + aggregation.

This is DEFA contribution C6 mapped to the TPU: one kernel gathers the
four neighbour rows of every sampling point from the value table resident
in VMEM and applies the bilinear x probability weights as it goes — the
sampled values never round-trip through HBM (on the ASIC: never leave the
PE array).

**How the gather lowers.** The TPU's vector unit has no row gather from a
VMEM table by a vector of indices, so the corner addressing is split
between XLA and the kernel:

  * outside the kernel (:func:`corner_operands`) XLA turns each point into
    its four clipped corner rows — through the FWP-compact ``pix2slot``
    remap when the table is compacted — and four effective weights,
    ``bilinear x validity x probability``, in f32;
  * inside, one grid step reads those as scalars from SMEM and loads each
    corner row with a dynamic-offset ``pl.ds`` row load, multiplying it by
    its scalar weight into a per-head accumulator.

Eq. 4's three-multiplier factorization is the ASIC PE's multiplier count
(``benchmarks/energy_model.py``); on the TPU the four-weight form is one
scalar-vector multiply-add per corner and is what the kernel executes.

**Table layout.** Heads are laid out on a leading axis: the table is
staged as (B, H/G, N_rows, G·Dh) — ``G = head_pack`` heads side by side in
one row, the same layout as the persistent decode staging — so a block's
last two dims are whole axes. Dynamic row loads need 32-bit rows, so the
staged rows are bit-packed into uint32 words (:func:`table_words`): a
bf16 row carries two channels per word, an int8 row four; the kernel
unpacks the word planes with shifts, so VMEM holds the table at its
storage width. Each plane accumulates separately and the output is
written as (…, planes, TQ, words) f32 blocks that XLA re-interleaves.

C5 (inter-level parallelism) maps to the *layout*: the K point axis is
level-major, so the corner loads of one query spread across the disjoint
per-level segments of the flat value buffer — the VMEM analogue of "4
points from 4 levels hit 4 disjoint bank groups" (benchmarks/bank_sim.py
models the ASIC side). For fmaps beyond VMEM use the windowed variant
(msgs_windowed.py), which exploits C3 range-narrowing + C7 reuse.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.msda.sampling import corner_data

#: Corner entries (index + weight) one grid step reads from SMEM. Two
#: int32/f32 arrays double-buffered: 32768 x 4 B x 2 x 2 = 512 KiB of
#: SMEM, which the v5e compiler accepts (tests/test_tpu_compile.py).
SMEM_CORNERS = 32768

#: Corner loads unrolled per loop iteration (one sampling point's four).
CORNER_UNROLL = 4

_U32 = jnp.uint32
_LANES = 128


def word_planes(dtype) -> int:
    """Channels per uint32 word for a table stored in ``dtype``."""
    d = jnp.dtype(dtype)
    return 1 if d == jnp.float16 else 4 // d.itemsize


def table_words(t: jnp.ndarray) -> jnp.ndarray:
    """(..., C) table -> (..., C / p) uint32 words, p = word_planes.

    Channel c lives in word c // p at bit offset (32 / p)·(c % p). float16
    has no shift-only widening, so it is staged as f32 words."""
    if t.dtype == jnp.float16:
        t = t.astype(jnp.float32)
    p = word_planes(t.dtype)
    if p == 1:
        return jax.lax.bitcast_convert_type(t, _U32)
    return jax.lax.bitcast_convert_type(
        t.reshape(t.shape[:-1] + (t.shape[-1] // p, p)), _U32)


def _unpack(words: jnp.ndarray, dtype) -> Tuple[jnp.ndarray, ...]:
    """uint32 words -> one f32 array per channel plane (exact widening)."""
    d = jnp.dtype(dtype)
    f32 = jnp.float32
    if word_planes(d) == 1:
        return (jax.lax.bitcast_convert_type(words, f32),)
    if d == jnp.bfloat16:
        return (jax.lax.bitcast_convert_type(words << 16, f32),
                jax.lax.bitcast_convert_type(words & _U32(0xFFFF0000), f32))
    if d == jnp.int8:
        i = jax.lax.bitcast_convert_type(words, jnp.int32)
        return tuple(((i << (24 - 8 * j)) >> 24).astype(f32)
                     for j in range(4))
    raise TypeError(f"unsupported MSDA table dtype {d}")


def stage_words(vg: jnp.ndarray) -> Tuple[jnp.ndarray, int]:
    """(B, NG, N_rows, G, Dh) grouped table -> ((B, NG, N_rows, W) uint32
    words, Dh padded to a whole number of words per head)."""
    p = word_planes(vg.dtype)
    dh = vg.shape[-1]
    dh_p = dh + (-dh) % p
    if dh_p != dh:
        vg = jnp.pad(vg, [(0, 0)] * 4 + [(0, dh_p - dh)])
    b, ng, n, g, _ = vg.shape
    return table_words(vg.reshape(b, ng, n, g * dh_p)), dh_p


def corner_operands(x_px, y_px, start, wl, hl, probs,
                    remap: Optional[jnp.ndarray] = None):
    """Per-point corner rows and effective weights, (..., K·4) each.

    Point arrays are (B, ..., K); ``remap`` (B, N_pix) routes pixels to
    FWP-compact slots (pruned pixels -> the zero sentinel row). Invalid
    (out-of-level) corners keep a clipped in-range row and weight 0."""
    idx, wgt, valid = corner_data(x_px, y_px, wl, hl, start)
    b = idx.shape[0]
    idx = idx.reshape(idx.shape[:-2] + (-1,))
    if remap is not None:
        bidx = jnp.arange(b).reshape((b,) + (1,) * (idx.ndim - 1))
        idx = remap[bidx, idx]
    w = (wgt.astype(jnp.float32) * valid.astype(jnp.float32)
         * probs.astype(jnp.float32)[..., None])
    return idx.astype(jnp.int32), w.reshape(w.shape[:-2] + (-1,))


def vmem_limit(*block_bytes: int) -> int:
    """Scoped-VMEM limit for a launch whose pipelined blocks take
    ``block_bytes`` each (double-buffered), with headroom; never below
    the compiler's 16 MiB default nor above 100 MiB."""
    need = 2 * sum(block_bytes) + (4 << 20)
    return int(min(max(need, 16 << 20), 100 << 20))


def query_tile(block_q: int, head_pack: int, n_corners: int) -> int:
    """Query tile: ``block_q`` clipped so one step's corner scalars fit
    SMEM, a multiple of 8 (the output block's sublane tiling)."""
    cap = max(8, SMEM_CORNERS // (head_pack * n_corners) // 8 * 8)
    return max(8, min(-(-block_q // 8) * 8, cap))


def fold_rows(words: jnp.ndarray) -> jnp.ndarray:
    """(..., N_rows, W) words -> (..., N_rows / R, R·W) with R = 128 // W
    table rows side by side in one 128-lane row when W divides 128: VMEM
    pads a block's last dim to 128 lanes, so narrow rows are folded
    instead of padded (the staged block then holds exactly the table's
    bytes). R = 1 when W >= 128 or W does not divide 128."""
    n_w = words.shape[-1]
    fold = _LANES // n_w if n_w < _LANES and _LANES % n_w == 0 else 1
    if fold == 1:
        return words
    n = words.shape[-2]
    words = _pad_axis(words, words.ndim - 2, (-n) % fold)
    return words.reshape(words.shape[:-2] + (-1, fold * n_w))


def accumulate_tile(idx_ref, w_ref, tab, o_ref, *, tq: int, head_pack: int,
                    n_corners: int, row_words: int, dtype) -> None:
    """The shared kernel body: for each of ``tq`` queries and each of the
    ``head_pack`` heads, sum ``w * table[row]`` over the head's corners.

    idx_ref / w_ref: SMEM (tq·G·M,) corner rows and weights, ordered
    (query, head, corner); tab: VMEM (..., rows / R, R·row_words) uint32
    ref of :func:`fold_rows`-folded words; o_ref: VMEM
    (..., planes, tq, R·row_words) f32 ref whose first ``row_words``
    lanes receive the result. Leading block dims are size 1 and indexed
    at 0. Head g owns words [g·wph, (g+1)·wph) of a table row,
    wph = row_words / G. A corner of folded sub-row s lands in lanes
    [s·row_words, (s+1)·row_words) of its accumulator; the sub-rows are
    summed with lane rotations once per query."""
    lanes = tab.shape[-1]
    fold = lanes // row_words
    shift = fold.bit_length() - 1
    t_lead = (0,) * (len(tab.shape) - 2)
    o_lead = (0,) * (len(o_ref.shape) - 3)
    g_n, m_n = head_pack, n_corners
    n_planes = word_planes(dtype)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    lane_sub = lane // row_words
    lane_head = (lane % row_words) // (row_words // g_n)
    step = CORNER_UNROLL if m_n % CORNER_UNROLL == 0 else 1
    zeros = lambda: tuple(jnp.zeros((1, lanes), jnp.float32)
                          for _ in range(n_planes))

    def load(e):
        r = idx_ref[e]
        x = tab[t_lead + (pl.ds(r >> shift, 1), slice(None))]
        wgt = w_ref[e]
        if fold > 1:
            wgt = jnp.where(lane_sub == (r & (fold - 1)), wgt, 0.0)
        return wgt, _unpack(x, dtype)

    def per_query(q, carry):
        base = q * (g_n * m_n)
        out = zeros()
        for g in range(g_n):                              # static unroll
            def corners(i, acc, _e0=base + g * m_n):
                for u in range(step):
                    wgt, planes = load(_e0 + i * step + u)
                    acc = tuple(a + wgt * v for a, v in zip(acc, planes))
                return acc
            acc = jax.lax.fori_loop(0, m_n // step, corners, zeros())
            if g_n == 1:
                out = acc
            else:
                own = lane_head == g
                out = tuple(o + jnp.where(own, a, 0.0)
                            for o, a in zip(out, acc))
        for j in range(n_planes):
            o = out[j]
            half = lanes // 2
            while half >= row_words:        # sum the folded sub-rows
                o = o + pltpu.roll(o, half, 1)
                half //= 2
            o_ref[o_lead + (j, pl.ds(q, 1), slice(None))] = o
        return carry

    jax.lax.fori_loop(0, tq, per_query, 0)


def _pad_axis(a, axis: int, pad: int, value=0):
    if not pad:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


def unplane(out: jnp.ndarray, head_pack: int, dh_p: int,
            dh: int) -> jnp.ndarray:
    """(..., NG, planes, Nq, W) f32 kernel output -> (..., Nq, NG·G, Dh)."""
    *lead, ng, p, nq, n_w = out.shape
    nl = len(lead)
    perm = tuple(range(nl)) + (nl + 2, nl, nl + 3, nl + 1)
    o = out.transpose(perm).reshape(tuple(lead) + (nq, ng, head_pack, dh_p))
    return o[..., :dh].reshape(tuple(lead) + (nq, ng * head_pack, dh))


def group_heads(a: jnp.ndarray, head_pack: int, nq_p: int,
                fill=0) -> jnp.ndarray:
    """(B, ..., Nq, H, K) point operand -> (B, H/G, ..., Nq_p, G, K): the
    query axis padded to ``nq_p`` with ``fill`` and the heads split into
    groups, BEFORE the corner expansion — flattening the expanded
    (..., G, 4K) corners is then a plain reshape, where transposing them
    costs the TPU compiler tens of seconds at 20k queries."""
    a = _pad_axis(a, a.ndim - 3, nq_p - a.shape[-3], fill)
    *lead, nq, h, k = a.shape
    a = a.reshape(tuple(lead) + (nq, h // head_pack, head_pack, k))
    n = len(lead)
    return a.transpose((0, n + 1) + tuple(range(1, n)) + (n, n + 2, n + 3))


@functools.partial(jax.jit, static_argnames=(
    "dtype", "head_pack", "dh", "block_q", "name", "interpret"))
def sample_staged(words: jnp.ndarray, x_px, y_px, start, wl, hl, probs,
                  remap: Optional[jnp.ndarray], *, dtype, head_pack: int,
                  dh: int, block_q: int, name: str,
                  interpret: bool) -> jnp.ndarray:
    """One launch over grid (B, H/G, query tile, layer), layer innermost.

    words: (B, NG, N_rows, W) uint32 table staged from a ``dtype`` table
    (:func:`stage_words`); point operands (B, L, Nq, H, K), ``remap``
    (B, N_pix) or None (:func:`corner_operands`). The table block is
    indexed by (batch, head-group) only, so it stays resident in VMEM
    across the whole (query-tile x layer) sweep of one (batch,
    head-group). Returns (B, L, Nq, H, Dh) f32."""
    b, ng, _, n_w = words.shape
    _, n_l, nq, h, k = x_px.shape
    g = head_pack
    m = 4 * k
    p = word_planes(dtype)
    dh_p = n_w * p // g
    words = fold_rows(words)
    n_rows, lanes = words.shape[2:]
    tq = query_tile(block_q, g, m)
    nq_p = nq + (-nq) % tq
    n_t = nq_p // tq

    # padded queries: zero probability, in-range one-pixel level
    grp = lambda a, fill=0: group_heads(a, g, nq_p, fill)
    idx, w = corner_operands(grp(x_px), grp(y_px), grp(start), grp(wl, 1),
                             grp(hl, 1), grp(probs), remap)
    blk = tq * g * m

    def scalar_block(bi, gi, ti, li):
        return (((bi * ng + gi) * n_l + li) * n_t + ti,)

    sspec = pl.BlockSpec((blk,), scalar_block, memory_space=pltpu.SMEM)
    tspec = pl.BlockSpec((1, 1, n_rows, lanes),
                         lambda bi, gi, ti, li: (bi, gi, 0, 0))
    ospec = pl.BlockSpec((1, 1, 1, p, tq, lanes),
                         lambda bi, gi, ti, li: (bi, gi, li, 0, ti, 0))

    def kernel(idx_ref, w_ref, tab_ref, o_ref):
        accumulate_tile(idx_ref, w_ref, tab_ref, o_ref, tq=tq, head_pack=g,
                        n_corners=m, row_words=n_w, dtype=dtype)

    out = pl.pallas_call(
        kernel, grid=(b, ng, n_t, n_l),
        in_specs=[sspec, sspec, tspec], out_specs=ospec,
        out_shape=jax.ShapeDtypeStruct((b, ng, n_l, p, nq_p, lanes),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(n_rows * lanes * 4,
                                        p * tq * lanes * 4)),
        interpret=interpret, name=name,
    )(idx.reshape(-1), w.reshape(-1), words)
    out = out[..., :n_w].transpose(0, 2, 1, 3, 4, 5)  # (B, L, NG, p, Nq, W)
    return unplane(out, g, dh_p, dh)[:, :, :nq]


def _fused(v, x_px, y_px, start, wl, hl, probs, remap, scale, *,
           head_pack: int, block_q: int, interpret: bool, name: str):
    b, n_rows, h, dh = v.shape
    assert h % head_pack == 0, (h, head_pack)
    g = head_pack
    vg = v.reshape(b, n_rows, h // g, g, dh).transpose(0, 2, 1, 3, 4)
    words, _ = stage_words(vg)
    if remap is not None:
        name += "_remap"
    if scale is not None:
        name += "_int8"
    add_l = lambda a: a[:, None]
    out = sample_staged(words, *map(add_l, (x_px, y_px, start, wl, hl,
                                            probs)), remap,
                        dtype=jnp.dtype(v.dtype), head_pack=g, dh=dh,
                        block_q=block_q, name=name,
                        interpret=interpret)[:, 0]
    if scale is not None:
        # int8 codes aggregate exactly in f32; the per-channel scale is
        # shared by every row, so it multiplies once after aggregation
        return (out * scale.astype(jnp.float32)).astype(probs.dtype)
    return out.astype(v.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def msgs_fused_pallas(
    v: jnp.ndarray,                      # (B, N_rows, H, Dh)
    x_px: jnp.ndarray,                   # (B, Nq, H, K)
    y_px: jnp.ndarray,
    start: jnp.ndarray,                  # int32
    wl: jnp.ndarray,                     # int32
    hl: jnp.ndarray,                     # int32
    probs: jnp.ndarray,
    remap: Optional[jnp.ndarray] = None,  # (B, N_pix) int32
    scale: Optional[jnp.ndarray] = None,  # (B, 1, H, Dh) f32 dequant scale
    *,
    block_q: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-head fused MSGS: one head per grid step, (N_rows, Dh) rows."""
    return _fused(v, x_px, y_px, start, wl, hl, probs, remap, scale,
                  head_pack=1, block_q=block_q, interpret=interpret,
                  name="msgs_fused")


@functools.partial(jax.jit, static_argnames=("head_pack", "block_q",
                                             "interpret"))
def msgs_fused_packed_pallas(
    v: jnp.ndarray,                      # (B, N_rows, H, Dh)
    x_px: jnp.ndarray,                   # (B, Nq, H, K)
    y_px: jnp.ndarray,
    start: jnp.ndarray,                  # int32
    wl: jnp.ndarray,                     # int32
    hl: jnp.ndarray,                     # int32
    probs: jnp.ndarray,
    remap: Optional[jnp.ndarray] = None,  # (B, N_pix) int32
    scale: Optional[jnp.ndarray] = None,  # (B, 1, H, Dh) f32 dequant scale
    *,
    head_pack: int = 4,
    block_q: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Head-packed fused MSGS: G = head_pack heads share one staged row
    (grid (B, H/G, Nq/TQ), staged table (N_rows, G·Dh))."""
    return _fused(v, x_px, y_px, start, wl, hl, probs, remap, scale,
                  head_pack=head_pack, block_q=block_q, interpret=interpret,
                  name="msgs_fused_packed")
