"""What the benchmark gives for the configurations it knows, pinned bit
for bit to what it gave before each architecture had a module of its own
(``data/pinned.json``, recorded then on the CPU): the weights drawn from
a seed, the reference's and the control's outputs, the work counts, and
the readers' values on the recorded traces."""
import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from benchmarks.chip import model, work, xplane
from benchmarks.chip.harness import HERE, Run, metric_reader

DATA = Path(__file__).resolve().parent / "data"
PINNED = json.loads((DATA / "pinned.json").read_text())
SEED = PINNED["seed"]
FILES = {"tiny": DATA / "tiny.json", "tiny-defa": DATA / "tiny-defa.json",
         "deformable-detr": HERE / "configs" / "deformable-detr.json",
         "deformable-detr-defa": DATA / "deformable-detr-defa.json"}


def sha(a) -> str:
    return hashlib.sha256(
        np.ascontiguousarray(np.asarray(a)).tobytes()).hexdigest()


@pytest.mark.parametrize("name", sorted(FILES))
def test_weights_are_the_pinned_draw(name):
    m, _ = model.load(FILES[name])
    params = m.arch.make_params(SEED, m)
    got = {jax.tree_util.keystr(k): [list(v.shape), str(v.dtype), sha(v)]
           for k, v in jax.tree_util.tree_flatten_with_path(params)[0]}
    assert got == PINNED["weights"][name]


@pytest.mark.parametrize("name", ["tiny", "tiny-defa"])
def test_reference_and_control_are_pinned(name):
    m, _ = model.load(FILES[name])
    img = np.random.default_rng(1).standard_normal(
        (3, m.input_size, m.input_size * 3 // 4), dtype=np.float32)
    params = m.arch.to_f32(m.arch.make_params(SEED, m))
    for side, control in (("reference", False), ("control", True)):
        logits, boxes = m.arch.reference(m, control)(
            params, m.arch.canvas(img, m))
        want = PINNED["outputs"][name][side]
        np.testing.assert_array_equal(
            np.asarray(logits), np.asarray(want["logits"], np.float32))
        np.testing.assert_array_equal(
            np.asarray(boxes), np.asarray(want["boxes"], np.float32))


@pytest.mark.parametrize("name", sorted(FILES))
def test_work_counts_are_pinned(name):
    m, _ = model.load(FILES[name])
    want = PINNED["work"][name]
    assert work.flops_per_image(m) == want["flops_per_image"]
    assert [[w.flops, w.bytes] for w in work.msda_calls(m)] \
        == want["msda_calls"]


@pytest.mark.parametrize("trace", sorted(PINNED["readers"]))
def test_readers_are_pinned(trace):
    s = xplane.summarize(xplane.load(DATA / trace))
    m, _ = model.load(FILES["deformable-detr"])
    cell = type("Cell", (), {"model": m, "traffic": {"max_batch": 1}})
    run = Run(cell=cell, seconds=s.window_s, setup_s=0.0, t0=0.0,
              t1=s.window_s, recs=[], memory_peak_bytes=0, platform="tpu",
              device_kind="TPU v5 lite", device_count=1, trace=s)
    assert {k: metric_reader(k)(run) for k in PINNED["readers"][trace]} \
        == PINNED["readers"][trace]
