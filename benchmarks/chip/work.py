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
* the deformable transformer's counts are here; an architecture module
  (``arch/<name>.py``) counts the rest of its forward as it runs
  (``flops_per_image``) and lists its MSDA calls in program order
  (``msda_calls``), which ``flops_per_image`` and ``msda_calls`` below
  hand on to.

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
    """The MSDA sampling calls of one image's forward, in program order."""
    return m.arch.msda_calls(m)


def mm_flops(n, k, o):
    return 2.0 * n * k * o


def encoder_block_flops(m: Model, block: int) -> float:
    n, d, h = m.n_in, m.d_model, m.n_heads
    return (mm_flops(table_rows(m, block), d, d)     # value projection
            + mm_flops(n, d, h * m.n_lp)             # attention logits
            + mm_flops(n, d, h * m.points_kept * 2)  # kept offsets
            + encoder_call(m, block).flops           # sampling
            + mm_flops(n, d, d)                      # output projection
            + mm_flops(n, d, m.d_ffn) + mm_flops(n, m.d_ffn, d))


def flops_per_image(m: Model) -> float:
    """Model FLOPs of one image's forward."""
    return m.arch.flops_per_image(m)
