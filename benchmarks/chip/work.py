"""Work counts of the served model, from its configuration alone.

These are the yardstick's numerators: they depend on the configuration's
shapes and on DEFA's pruning semantics, never on the kernel, plan or HLO
that computes them, so a rewrite of the implementation cannot change
them. Conventions:

* a multiply-add counts 2 FLOPs; matmuls and convs are counted, norms,
  softmaxes and activations are not;
* pruned work does not count: PAP's dropped points are neither sampled
  nor given offsets (their attention logits are, since PAP needs them to
  choose), and an FWP-compacted table projects only its static capacity;
* the backbone is counted as it runs: the repo's 5-conv stem.

One MSDA sampling call reads its value table once, its sampling
locations (two float32 per point), its kept probabilities (one element
of the model dtype per point) and writes one model-dtype row of
d_model per query. Its FLOPs are the bilinear combination of four
corner rows (4 multiply-adds per channel) plus the probability-weighted
sum (1 multiply-add per channel) for each kept point."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

from benchmarks.chip.model import Model

PEAKS = Path(__file__).resolve().parent / "peaks.json"
COORD_BYTES = 4                         # float32 sampling coordinates
PIX2SLOT_BYTES = 4                      # int32 pixel -> table row


ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def itemsize(dtype: str) -> int:
    return ITEMSIZE[dtype]


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def least_seconds(self, peak: dict) -> tuple:
        """(least time on the device, "compute" or "memory")."""
        t_c = self.flops / peak["bf16_flops_per_s"]
        t_m = self.bytes / peak["hbm_bytes_per_s"]
        return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def peak_for(device_kind: str) -> dict:
    """The chip's peaks; a device not in the table is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def table_rows(m: Model, block: int) -> int:
    """Value-table rows that encoder block ``block`` (0-based; the decoder
    is ``m.enc_layers``) keeps: every pixel in the first block and without
    FWP, else the static capacity."""
    if m.defa is None or block == 0:
        return m.n_in
    return sum(m.level_caps)


def msda_call(m: Model, n_queries: int, rows: int) -> Work:
    """One MSDA sampling call over ``rows`` kept table rows."""
    k, d, h = m.points_kept, m.d_model, m.n_heads
    el = itemsize(m.dtype)
    points = n_queries * h * k
    flops = 2.0 * points * m.head_dim * (4 + 1)
    nbytes = (rows * d * el
              + (m.n_in * PIX2SLOT_BYTES if rows != m.n_in else 0)
              + points * (2 * COORD_BYTES + el)
              + n_queries * d * el)
    return Work(flops, nbytes)


def encoder_call(m: Model, block: int) -> Work:
    return msda_call(m, m.n_in, table_rows(m, block))


def decoder_call(m: Model) -> Work:
    return msda_call(m, m.n_queries, table_rows(m, m.enc_layers))


def msda_calls(m: Model) -> list:
    """The MSDA sampling calls of one image's forward, in program order:
    the encoder blocks, then the decoder layers."""
    return ([encoder_call(m, b) for b in range(m.enc_layers)]
            + [decoder_call(m)] * m.dec_layers)


def _mm(n, k, o):
    return 2.0 * n * k * o


def backbone_flops(m: Model) -> float:
    s, w = m.input_size, m.backbone_width
    total, c_in = 0.0, 3
    for stride in (2, 4, 8, 16, 32):                 # stem, c1..c4
        total += _mm((s // stride) ** 2, c_in * 9, w)
        c_in = w
    return total


def encoder_block_flops(m: Model, block: int) -> float:
    n, d, h = m.n_in, m.d_model, m.n_heads
    return (_mm(table_rows(m, block), d, d)          # value projection
            + _mm(n, d, h * m.n_lp)                  # attention logits
            + _mm(n, d, h * m.points_kept * 2)       # kept offsets
            + encoder_call(m, block).flops           # sampling
            + _mm(n, d, d)                           # output projection
            + _mm(n, d, m.d_ffn) + _mm(n, m.d_ffn, d))


def decoder_flops(m: Model) -> float:
    q, d, h = m.n_queries, m.d_model, m.n_heads
    per_layer = (4 * _mm(q, d, d)                    # self-attn q, k, v, o
                 + 2 * _mm(q, d, q)                  # scores and values
                 + _mm(q, d, h * m.n_lp)
                 + _mm(q, d, h * m.points_kept * 2)
                 + decoder_call(m).flops
                 + _mm(q, d, d)
                 + _mm(q, d, m.d_ffn) + _mm(q, m.d_ffn, d)
                 + _mm(q, d, 2))                     # reference refinement
    table = _mm(table_rows(m, m.enc_layers), d, d)   # one shared table
    return table + _mm(q, d, 2) + m.dec_layers * per_layer


def flops_per_image(m: Model) -> float:
    """Model FLOPs of one image's forward."""
    d = m.d_model
    proj = sum(_mm(h * w, m.backbone_width, d) for h, w in m.level_shapes)
    heads = _mm(m.n_queries, d, m.n_classes + 1) + _mm(m.n_queries, d, 4)
    return (backbone_flops(m) + proj
            + sum(encoder_block_flops(m, b) for b in range(m.enc_layers))
            + decoder_flops(m) + heads)
