"""A configuration file as numbers, and the architecture module that reads
it.

Every configuration file names its architecture with the key ``"arch"``:
the module ``arch/<arch>.py``, loaded by path. The module parses the file
(refusing what it does not implement), maps it onto the program, makes
its weights, holds its plain reference and counts its work; the rest of
the benchmark reads a parsed ``Model`` and calls the module through
``Model.arch``. ``Model`` holds the numbers that the shared parts read:
the deformable transformer's sizes, its pyramid and DEFA's pruning.

Kept free of the program under test."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Optional, Tuple


class Refused(RuntimeError):
    """The run cannot be measured here; no result is printed."""


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
    level_shapes: Tuple[Tuple[int, int], ...]
    dtype: str
    defa: Optional[Defa]
    # the architecture module that parsed the file (set by ``load``)
    arch: Optional[ModuleType] = dataclasses.field(default=None,
                                                   kw_only=True)

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
    def n_in(self) -> int:
        return sum(h * w for h, w in self.level_shapes)

    @property
    def level_caps(self) -> Tuple[int, ...]:
        """Static FWP table rows per level (Python ``round``, as DEFA's
        compact table sizes them)."""
        c = self.defa.fwp_capacity
        return tuple(max(1, int(round(c * h * w)))
                     for h, w in self.level_shapes)


def defa_from(d: Optional[dict]) -> Optional[Defa]:
    """The ``defa`` group of a configuration file."""
    return None if d is None else Defa(
        pap_keep=int(d["pap_keep"]), fwp_k=float(d["fwp_k"]),
        fwp_capacity=float(d["fwp_capacity"]),
        range_narrow=tuple(float(x) for x in d["range_narrow"]),
        act_bits=int(d["act_bits"]), weight_bits=int(d["weight_bits"]))


def transformer(c: dict) -> dict:
    """The deformable transformer's sizes under Deformable-DETR's own
    argument names (``main.py``), as ``Model`` fields."""
    if c["enc_n_points"] != c["dec_n_points"]:
        raise Refused("encoder and decoder must sample the same points "
                      "(enc_n_points, dec_n_points)")
    return dict(
        name=c["name"], d_model=int(c["hidden_dim"]), n_heads=int(c["nheads"]),
        n_levels=int(c["num_feature_levels"]),
        n_points=int(c["enc_n_points"]), enc_layers=int(c["enc_layers"]),
        dec_layers=int(c["dec_layers"]), d_ffn=int(c["dim_feedforward"]),
        n_queries=int(c["num_queries"]), n_classes=int(c["num_classes"]),
        dtype=str(c["dtype"]), defa=defa_from(c.get("defa")))


ARCH_DIR = Path(__file__).resolve().parent / "arch"
_ARCHS: dict = {}


def arch_module(path: Path) -> ModuleType:
    """The architecture module at ``path``, loaded once per process."""
    path = Path(path).resolve()
    if path not in _ARCHS:
        # registered under a name of its own, as dataclasses need; two
        # checkouts' modules of one name stay apart
        name = f"bench_arch_{path.stem}_{len(_ARCHS)}"
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        _ARCHS[path] = mod
    return _ARCHS[path]


def load(path, arch_dir: Path = ARCH_DIR) -> Tuple[Model, dict]:
    """The parsed configuration file at ``path`` and its raw dict; its
    ``"arch"`` names a module in ``arch_dir``."""
    raw = json.loads(Path(path).read_text())
    name = raw.get("arch")
    if not isinstance(name, str):
        raise Refused(f"{path}: no \"arch\" key names the architecture "
                      f"module (arch/<name>.py)")
    file = Path(arch_dir) / f"{name}.py"
    if Path(name).name != name or not file.is_file():
        raise Refused(f"{path}: \"arch\" {name!r} names no module "
                      f"{file}")
    mod = arch_module(file)
    return dataclasses.replace(mod.parse(raw), arch=mod), raw
