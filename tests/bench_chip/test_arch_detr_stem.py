"""The ``detr_stem`` architecture module at small sizes on the CPU: its
plain reference against the program's float32 forward, its control, its
weights' layout, and the configuration keys it refuses."""
import dataclasses
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from benchmarks.chip import check, model
from benchmarks.chip.model import Refused

DATA = Path(__file__).resolve().parent / "data"
SEED = 3_000_000_011            # wider than 32 bits hold


def load(name):
    return model.load(DATA / f"{name}.json")


def images(m, n, seed):
    rng = np.random.default_rng(seed)
    side = m.input_size
    return [rng.standard_normal((3, side, side * 3 // 4), dtype=np.float32)
            for _ in range(n)]


@pytest.mark.parametrize("name", ["tiny", "tiny-defa"])
def test_reference_is_the_program_in_float32(name):
    """Same weights, one image, float32 and highest precision on both
    sides: the reference follows the program's mathematics (DEFA's
    pruning and quantisation included) to rounding."""
    from repro.core.detector import detector_apply
    m, _ = load(name)
    arch = m.arch
    cfg32 = arch.program(dataclasses.replace(m, dtype="float32"))
    params = arch.to_f32(arch.make_params(SEED, m))
    img = arch.canvas(images(m, 1, 1)[0], m)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: detector_apply(
            p, cfg32, x, backend="jnp_gather")[:2])(params, img[None])
    got = arch.reference(m)(params, img)
    assert check.rel_err(got[0], want[0][0]) < 1e-5
    assert check.rel_err(got[1], want[1][0]) < 1e-5


@pytest.mark.parametrize("name", ["tiny", "tiny-defa"])
def test_control_is_not_correct(name):
    """The reference in float8 instead of the configuration's bfloat16
    fails at least one compared number."""
    m, raw = load(name)
    worst = check.control_numbers(m, SEED, images(m, 2, 2))
    assert any(worst[k] > lim for k, lim in raw["limits"].items()), worst


def test_weights_have_the_programs_layout():
    m, _ = load("tiny-defa")
    m.arch.check_layout(m.arch.make_params(SEED, m), m.arch.program(m))


@pytest.mark.parametrize("key, value", [
    ("with_box_refine", True), ("two_stage", True), ("level_embed", True),
    ("backbone", "resnet50"), ("class_head", "sigmoid_focal"),
    ("bbox_embed_layers", 3), ("feature_strides", [8, 16, 32, 64]),
    ("arch", None), ("arch", "no_such_module"), ("aux_loss", True)])
def test_unimplemented_key_is_refused(tmp_path, key, value):
    """A configuration that states what the module does not serve, or
    names no module, is refused at load, naming the key; before modules
    these loaded as the stem model."""
    raw = json.loads((DATA / "tiny.json").read_text())
    if value is None:
        del raw[key]
    else:
        raw[key] = value
    path = tmp_path / "conf.json"
    path.write_text(json.dumps(raw))
    with pytest.raises(Refused, match=key):
        model.load(path)
