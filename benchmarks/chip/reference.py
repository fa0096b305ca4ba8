"""The plain float32 reference's shared parts: Deformable-DETR's
deformable transformer, one image at a time.

Written from the papers and the configuration alone; it imports nothing
of the program. Deformable-DETR (arXiv:2010.04159): encoder blocks of
multi-scale deformable attention (MSDA) with post-norm FFNs over the
flattened pyramid, and decoder layers (self-attention, MSDA
cross-attention on the encoder memory, FFN) over learned queries. An
architecture module (``arch/<name>.py``) puts its feature extractor,
embeddings and heads around these parts. With ``defa`` set, DEFA's semantics
(arXiv:2403.10913) apply in every MSDA call:

* INT12 fake quantisation, symmetric and per tensor, of the sampling
  weights (logit, offset, value and output projections), of the
  attention probabilities, of the kept offsets and of the value table;
* PAP: the ``pap_keep`` most probable of a query-head's L*P points are
  sampled, the rest contribute nothing (the kept mass is not
  renormalised);
* range narrowing: a kept offset is clipped to its level's bound;
* FWP: each encoder block counts how often bilinear sampling touched
  each pixel (the four in-bounds corners of each point whose quantised
  probability is above zero). The next block keeps a pixel when its
  count is at least ``fwp_k`` times its level's mean and it is among the
  level's ``capacity`` best by (kept, count); a pixel not kept has a
  value row of exactly zero. The first block keeps every pixel; the
  decoder's table keeps what the last encoder block's counts decide.
  The quantisation scale of a compacted table is taken over the rows of
  its static capacity.

The departures of the served program from the paper that the reference
follows are listed under ``departures`` in the configuration file.

Every matmul and conv runs at ``Precision.HIGHEST``. MSDA is computed in
blocks of queries so that a 512-px image fits beside nothing else.

``lo`` rounds every matmul and conv operand and the value table through
a lower precision (the control: ``round_fp8``; see ``check.py``)."""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.model import Model

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 2048
Round = Callable[[jnp.ndarray], jnp.ndarray]


def ident(x):
    return x


def round_fp8(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> float8_e4m3fn -> float32 (saturating at +-448)."""
    x = jnp.clip(x, -448.0, 448.0)
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def fake_quant(x: jnp.ndarray, bits: Optional[int],
               scale_of: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Symmetric per-tensor quantise-dequantise; the scale is taken from
    ``scale_of`` where given (default ``x``)."""
    if not bits:
        return x
    qmax = 2 ** (bits - 1) - 1
    src = x if scale_of is None else scale_of
    s = jnp.maximum(jnp.max(jnp.abs(src)), 1e-8) / qmax
    return jnp.clip(jnp.round(x / s), -qmax - 1, qmax) * s


def mm(a, b, lo: Round):
    return jnp.matmul(lo(a), lo(b), precision=HI)


def linear(p, x, lo: Round):
    return mm(x, p["w"], lo) + p["b"]


def ln(p, x, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def sine_embed(h: int, w: int, d: int) -> jnp.ndarray:
    """(h*w, d): [sin y, cos y, sin x, cos x] of pixel indices over
    d/4 frequencies 10000^(-i/(d/4))."""
    d4 = d // 4
    idx = jnp.arange(h * w)
    omega = 1.0 / (10000.0 ** (jnp.arange(d4, dtype=jnp.float32) / d4))
    parts = []
    for c in (idx // w, idx % w):
        a = c.astype(jnp.float32)[:, None] * omega[None]
        parts += [jnp.sin(a), jnp.cos(a)]
    return jnp.concatenate(parts, 1)


def pixel_centres(h: int, w: int) -> np.ndarray:
    ys, xs = np.meshgrid((np.arange(h) + 0.5) / h, (np.arange(w) + 0.5) / w,
                         indexing="ij")
    return np.stack([xs.reshape(-1), ys.reshape(-1)], 1).astype(np.float32)


class Geometry:
    """Static per-level numbers of one pyramid."""

    def __init__(self, m: Model):
        self.shapes = m.level_shapes
        sizes = [h * w for h, w in self.shapes]
        self.starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
        self.n_in = int(sum(sizes))


def msda(p: dict, m: Model, geo: Geometry, query, refs, table,
         lo: Round, count: bool):
    """One MSDA call. query (Nq, D), refs (Nq, 2) in [0, 1], table
    (N_in, H, Dh) with pruned rows already zero. Returns (out (Nq, D),
    per-pixel sampling counts (N_in,) or None)."""
    nq = query.shape[0]
    h_, lp, pts, dh = m.n_heads, m.n_lp, m.points_kept, m.head_dim
    bits_a = m.defa.act_bits if m.defa else None
    bits_w = m.defa.weight_bits if m.defa else None
    wq = lambda w: fake_quant(w, bits_w)
    d = m.d_model

    logits = mm(query, wq(p["attn_w"]).reshape(d, h_ * lp), lo)
    logits = logits.reshape(nq, h_, lp) + p["attn_b"]
    probs = fake_quant(jax.nn.softmax(logits, -1), bits_a)
    if m.defa:
        probs, point = jax.lax.top_k(probs, pts)
    else:
        point = jnp.broadcast_to(jnp.arange(lp), probs.shape)
    offs = mm(query, wq(p["offs_w"]).reshape(d, h_ * lp * 2), lo)
    offs = offs.reshape(nq, h_, lp, 2) + p["offs_b"].reshape(h_, lp, 2)
    offs = jnp.take_along_axis(offs, point[..., None], axis=2)
    level = point // m.n_points
    if m.defa:
        bound = jnp.asarray(m.defa.range_narrow, jnp.float32)[level]
        offs = jnp.clip(offs, -bound[..., None], bound[..., None])
    offs = fake_quant(offs, bits_a)

    hs = jnp.asarray([s[0] for s in geo.shapes])[level]
    ws = jnp.asarray([s[1] for s in geo.shapes])[level]
    start = jnp.asarray(geo.starts, jnp.int32)[level]
    x = refs[:, None, None, 0] * ws + offs[..., 0] - 0.5
    y = refs[:, None, None, 1] * hs + offs[..., 1] - 0.5
    x0, y0 = jnp.floor(x), jnp.floor(y)
    fx, fy = x - x0, y - y0
    rows, weights, valids = [], [], []
    for dy in (0, 1):
        for dx in (0, 1):
            cx, cy = x0 + dx, y0 + dy
            ok = (cx >= 0) & (cx < ws) & (cy >= 0) & (cy < hs)
            pix = start + (jnp.clip(cy, 0, hs - 1) * ws
                           + jnp.clip(cx, 0, ws - 1)).astype(jnp.int32)
            rows.append(pix)
            weights.append((fx if dx else 1 - fx) * (fy if dy else 1 - fy)
                           * ok)
            valids.append(ok)
    pix = jnp.stack(rows, -1)                       # (Nq, H, K, 4)
    wgt = jnp.stack(weights, -1) * probs[..., None]

    flat = table.reshape(-1, dh)                   # row = pixel * H + head
    head = jnp.arange(h_)[None, :, None, None]

    def block(args):
        pix_b, wgt_b = args
        vals = jnp.take(flat, pix_b * h_ + head, axis=0)  # (q, H, K, 4, Dh)
        return jnp.einsum("qhkc,qhkcd->qhd", wgt_b, vals, precision=HI)

    n_blk = -(-nq // QUERY_BLOCK)
    pad = n_blk * QUERY_BLOCK - nq
    if n_blk > 1:
        pix_p = jnp.pad(pix, ((0, pad), (0, 0), (0, 0), (0, 0)))
        wgt_p = jnp.pad(wgt, ((0, pad), (0, 0), (0, 0), (0, 0)))
        shape = (n_blk, QUERY_BLOCK) + pix.shape[1:]
        sampled = jax.lax.map(block, (pix_p.reshape(shape),
                                      wgt_p.reshape(shape)))
        sampled = sampled.reshape(-1, h_, dh)[:nq]
    else:
        sampled = block((pix, wgt))
    out = mm(sampled.reshape(nq, h_ * dh),
             wq(p["out_w"]).reshape(h_ * dh, d), lo) + p["out_b"]

    freq = None
    if count:
        alive = (probs > 0)[..., None]
        hits = (jnp.stack(valids, -1) & alive).astype(jnp.float32)
        freq = jnp.zeros((geo.n_in,), jnp.float32).at[
            pix.reshape(-1)].add(hits.reshape(-1))
    return out, freq


def fwp_keep(m: Model, geo: Geometry, freq):
    """(kept, in_table): pixels whose rows are live, and pixels that hold a
    row of the static table (the scale of the table's quantisation)."""
    kept_parts, table_parts = [], []
    top = jnp.max(freq) + 1.0
    for (h, w), s, cap in zip(geo.shapes, geo.starts, m.level_caps):
        f = freq[int(s):int(s) + h * w]
        above = f >= m.defa.fwp_k * jnp.mean(f)
        score = f + above.astype(jnp.float32) * top
        _, idx = jax.lax.top_k(score, cap)
        member = jnp.zeros((h * w,), bool).at[idx].set(True)
        kept_parts.append(member & above)
        table_parts.append(member)
    return jnp.concatenate(kept_parts), jnp.concatenate(table_parts)


def value_table(p: dict, m: Model, x, keep, lo: Round):
    """(N_in, H, Dh) value rows of the memory ``x``; ``keep`` is None (all
    rows live) or (kept, in_table)."""
    bits_w = m.defa.weight_bits if m.defa else None
    bits_a = m.defa.act_bits if m.defa else None
    d = m.d_model
    v = mm(x, fake_quant(p["value_w"], bits_w).reshape(d, d), lo)
    v = v.reshape(-1, m.n_heads, m.head_dim) + p["value_b"]
    if keep is None:
        v = fake_quant(v, bits_a)
    else:
        kept, in_table = keep
        v = fake_quant(v, bits_a, scale_of=v * in_table[:, None, None])
        v = v * kept[:, None, None]
    return lo(v)


def self_attention(p: dict, m: Model, h, pos, lo: Round):
    n, d = h.shape
    nh, dh = m.n_heads, d // m.n_heads
    q = linear(p["self_q"], h + pos, lo).reshape(n, nh, dh)
    k = linear(p["self_k"], h + pos, lo).reshape(n, nh, dh)
    v = linear(p["self_v"], h, lo).reshape(n, nh, dh)
    att = jnp.einsum("qhd,khd->hqk", lo(q), lo(k), precision=HI)
    att = jax.nn.softmax(att / np.sqrt(dh), -1)
    out = jnp.einsum("hqk,khd->qhd", lo(att), lo(v), precision=HI)
    return linear(p["self_o"], out.reshape(n, d), lo)


def inverse_sigmoid(x, eps=1e-5):
    x = jnp.clip(x, eps, 1 - eps)
    return jnp.log(x) - jnp.log1p(-x)


def encoder(blocks: list, m: Model, geo: Geometry, mem, pos, refs,
            lo: Round):
    """The encoder blocks over the flattened pyramid ``mem`` (N_in, D).
    Returns (memory, keep): keep is what the last block's FWP decides for
    the decoder's table (None without DEFA)."""
    keep = None
    for blk in blocks:
        table = value_table(blk["attn"], m, mem, keep, lo)
        out, freq = msda(blk["attn"], m, geo, mem + pos, refs, table, lo,
                         count=m.defa is not None)
        mem = ln(blk["ln1"], mem + out)
        ff = linear(blk["ffn2"], jax.nn.relu(linear(blk["ffn1"], mem, lo)),
                    lo)
        mem = ln(blk["ln2"], mem + ff)
        if m.defa is not None:
            keep = fwp_keep(m, geo, freq)
    return mem, keep


def decoder_layer(layer: dict, m: Model, geo: Geometry, h, qpos, ref, table,
                  lo: Round):
    """One decoder layer on the queries ``h`` (Nq, D) at reference points
    ``ref`` (Nq, 2): self-attention, MSDA cross-attention on ``table``,
    FFN, each post-norm."""
    h = ln(layer["ln_sa"], h + self_attention(layer, m, h, qpos, lo))
    out, _ = msda(layer["cross"], m, geo, h + qpos, ref, table, lo,
                  count=False)
    h = ln(layer["ln1"], h + out)
    ff = linear(layer["ffn2"], jax.nn.relu(linear(layer["ffn1"], h, lo)),
                lo)
    return ln(layer["ln2"], h + ff)
