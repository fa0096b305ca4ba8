"""A whole run of the harness at a small size on the CPU, past its look for
a chip: sound, it comes out correct; with the served path broken
underneath, it does not."""
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from benchmarks.chip import harness, model

DATA = Path(__file__).resolve().parent / "data"
SEED = 2 ** 33 + 7
ASPECTS = [[4, 3, 0.5], [3, 4, 0.25], [1, 1, 0.25]]
OPEN = {"loop": "open", "rate_per_s": 4.0, "schedule_seed": 5,
        "max_batch": 1, "long_side": 64, "aspects": ASPECTS}
CLOSED = {"loop": "closed", "max_batch": 2, "queued_images": 4,
          "pool_images": 8, "long_side": 64, "aspects": ASPECTS}
E2E = [{"name": n, "unit": u} for n, u in
       (("latency_p50_ms", "ms"), ("latency_p90_ms", "ms"),
        ("images_per_s", "images/s"), ("setup_s", "s"))]


def run_tiny(monkeypatch, name, traffic, seconds=2.0):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "off")
    m, raw = model.load(DATA / f"{name}.json")
    cell = harness.Cell(name, 1, m, raw, traffic, E2E, [])
    return harness.run_cell(name, SEED, seconds, False,
                            t_start=time.perf_counter(), require_chip=False,
                            cell=cell)


def break_forward(monkeypatch, fault):
    """Pass every compiled bucket forward's outputs through ``fault``."""
    from repro.serve.engine import DetrServeEngine
    init = DetrServeEngine.__init__

    def patched(self, *a, **k):
        init(self, *a, **k)
        for res, exe in list(self._compiled.items()):
            self._compiled[res] = (lambda p, x, exe=exe:
                                   fault(*exe(p, x)))

    monkeypatch.setattr(DetrServeEngine, "__init__", patched)


def test_sound_run_is_correct(monkeypatch):
    r = run_tiny(monkeypatch, "tiny", OPEN)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] == 8
    assert set(r["metrics"]) == {e["name"] for e in E2E}
    assert list(r)[-1] == "checks"


def test_altered_answer_is_not_correct(monkeypatch):
    """Each served answer has one class score raised where it is made."""
    break_forward(monkeypatch, lambda logits, boxes, aux: (
        logits.at[:, :, 1].add(1.0), boxes, aux))
    r = run_tiny(monkeypatch, "tiny", OPEN)
    assert not r["correct"]
    assert r["checks"]["logits_err"]["value"] \
        > r["checks"]["logits_err"]["limit"]


def test_half_the_batch_left_out_is_not_correct(monkeypatch):
    """The second half of each batch gets the first half's answers."""
    def half(logits, boxes, aux):
        n = logits.shape[0] // 2
        return (logits.at[n:].set(logits[:n]), boxes.at[n:].set(boxes[:n]),
                aux)

    break_forward(monkeypatch, half)
    r = run_tiny(monkeypatch, "tiny-defa", CLOSED)
    assert not r["correct"]


def test_compile_in_the_window_is_not_correct(monkeypatch):
    from repro.serve.engine import DetrServeEngine
    step = DetrServeEngine.step

    def compiling_step(self):
        jax.jit(lambda x: x + 1)(jnp.ones(3))       # a new program
        return step(self)

    monkeypatch.setattr(DetrServeEngine, "step", compiling_step)
    r = run_tiny(monkeypatch, "tiny", OPEN)
    assert not r["correct"]
    assert r["checks"]["compiles"]["value"] > 0
