"""The benchmark's plain reference against the program's float32 forward,
and its control, at small sizes on the CPU."""
import dataclasses
from pathlib import Path

import jax
import numpy as np
import pytest

from benchmarks.chip import check, harness, model, reference, weights

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
    cfg32 = harness.program_config(dataclasses.replace(m, dtype="float32"))
    params = weights.to_f32(weights.make_params(SEED, m))
    img = check.padded(images(m, 1, 1)[0], m.input_size)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: detector_apply(
            p, cfg32, x, backend="jnp_gather")[:2])(params, img[None])
    got = reference.compiled(m)(params, img)
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
    harness.check_layout(weights.make_params(SEED, m),
                         harness.program_config(m))
