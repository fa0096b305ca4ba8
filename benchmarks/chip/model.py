"""A configuration file as numbers: the sizes that the weights, the plain
reference and the work counts all read.

Kept free of the program under test: the harness maps these sizes onto
the program's own config objects (``harness.program_config``)."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Defa:
    pap_keep: int                   # points kept per (query, head)
    fwp_k: float                    # keep pixels with freq >= k * level mean
    fwp_capacity: float             # static table share of each level
    range_narrow: Tuple[float, ...]  # |offset| bound per level (px)
    act_bits: int
    weight_bits: int


@dataclasses.dataclass(frozen=True)
class Model:
    name: str
    d_model: int
    n_heads: int
    n_levels: int
    n_points: int
    enc_layers: int
    dec_layers: int
    d_ffn: int
    n_queries: int
    n_classes: int
    backbone_width: int
    input_size: int
    strides: Tuple[int, ...]
    dtype: str
    defa: Optional[Defa]

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def n_lp(self) -> int:
        return self.n_levels * self.n_points

    @property
    def points_kept(self) -> int:
        """Sampling points per (query, head) that survive PAP."""
        return self.defa.pap_keep if self.defa else self.n_lp

    @property
    def level_shapes(self) -> Tuple[Tuple[int, int], ...]:
        s = self.input_size
        return tuple((s // k, s // k) for k in self.strides)

    @property
    def n_in(self) -> int:
        return sum(h * w for h, w in self.level_shapes)

    @property
    def level_caps(self) -> Tuple[int, ...]:
        """Static FWP table rows per level (Python ``round``, as DEFA's
        compact table sizes them)."""
        c = self.defa.fwp_capacity
        return tuple(max(1, int(round(c * h * w)))
                     for h, w in self.level_shapes)


def from_dict(c: dict) -> Model:
    if c["enc_n_points"] != c["dec_n_points"]:
        raise ValueError("encoder and decoder must sample the same points")
    d = c.get("defa")
    defa = None if d is None else Defa(
        pap_keep=int(d["pap_keep"]), fwp_k=float(d["fwp_k"]),
        fwp_capacity=float(d["fwp_capacity"]),
        range_narrow=tuple(float(x) for x in d["range_narrow"]),
        act_bits=int(d["act_bits"]), weight_bits=int(d["weight_bits"]))
    return Model(
        name=c["name"], d_model=int(c["hidden_dim"]), n_heads=int(c["nheads"]),
        n_levels=int(c["num_feature_levels"]),
        n_points=int(c["enc_n_points"]), enc_layers=int(c["enc_layers"]),
        dec_layers=int(c["dec_layers"]), d_ffn=int(c["dim_feedforward"]),
        n_queries=int(c["num_queries"]), n_classes=int(c["num_classes"]),
        backbone_width=int(c["backbone_width"]),
        input_size=int(c["input_size"]),
        strides=tuple(int(s) for s in c["feature_strides"]),
        dtype=str(c["dtype"]), defa=defa)


def load(path) -> Tuple[Model, dict]:
    raw = json.loads(Path(path).read_text())
    return from_dict(raw), raw
