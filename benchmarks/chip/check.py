"""How ``correct`` is decided.

Once the window has closed and the memory peak is read, a sample of the
requests the window finished (drawn from the seed, the largest image
always in it) is run through the plain float32 reference of the
configuration's architecture (``arch/<name>.py``, built on
``reference.py``) on the request's own image on its canvas, with the
same weights made again from the seed. For each compared number the worst
request of the sample counts:

* ``logits_err``: max |served - reference| over the request's class
  logits, over max(1, max |reference|);
* ``boxes_err``: the same over its boxes;
* ``logits_med``: the median of |served - reference| over the request's
  class logits, over the root mean square of the reference's logits;
* ``boxes_med``: the same over its boxes.

The configuration file's ``limits`` names the numbers compared, each
with its limit; PERF.md gives the readings each limit was set from. A
configuration with no limits is never correct.

Besides these, a run is not correct when a request never finished
(``unfinished``), a served output has the wrong shape or is not finite
(``malformed``), or anything compiled between the window's start and the
end of the drain (``compiles``).

The control (``control=True``) is the reference itself with every matmul
and conv operand and the value table rounded through float8_e4m3fn, the
precision below the configuration's bfloat16; it has to fail."""
from __future__ import annotations

import sys
import time

import numpy as np

from benchmarks.chip import traffic as traffic_lib
from benchmarks.chip.model import Model

NUMBERS = ("logits_err", "boxes_err", "logits_med", "boxes_med")
NOT_COMPARED = 1e30         # no finished, well-formed request to compare
CHECK_REQUESTS = 6          # served requests the reference checks a run


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want))
                 / max(1.0, float(np.max(np.abs(want)))))


def reference_outputs(m: Model, seed: int, images: list,
                      control: bool = False) -> list:
    """[(logits, boxes)] of the reference for each image."""
    arch = m.arch
    params = arch.to_f32(arch.make_params(seed, m))
    fn = arch.reference(m, control)
    out = []
    for img in images:
        logits, boxes = fn(params, arch.canvas(img, m))
        out.append((np.asarray(logits), np.asarray(boxes)))
    return out


def med_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.median(np.abs(got - want))
                 / max(1e-30, float(np.sqrt(np.mean(want * want)))))


def compare(served: list, refs: list) -> dict:
    """Worst of each of ``NUMBERS`` over pairs of (logits, boxes)."""
    worst = {k: 0.0 for k in NUMBERS}
    for (gl, gb), (rl, rb) in zip(served, refs):
        for out, got, want in (("logits", gl, rl), ("boxes", gb, rb)):
            worst[f"{out}_err"] = max(worst[f"{out}_err"], rel_err(got, want))
            worst[f"{out}_med"] = max(worst[f"{out}_med"], med_err(got, want))
    return worst


def pick(outputs: list, k: int, seed: int) -> list:
    sizes = [img.shape[1] * img.shape[2] for img, _, _ in outputs]
    return traffic_lib.sample(len(outputs), k, int(np.argmax(sizes)), seed)


def malformed(m: Model, outputs: list) -> int:
    want_logits, want_boxes = m.arch.output_shapes(m)
    bad = 0
    for _, logits, boxes in outputs:
        ok = (logits is not None and boxes is not None
              and logits.shape == want_logits and boxes.shape == want_boxes
              and np.isfinite(logits).all() and np.isfinite(boxes).all())
        bad += not ok
    return bad


def run_checks(m: Model, limits: dict, seed: int, outputs: list, *,
               unfinished: int, compiles: int) -> dict:
    """outputs: [(image, served logits, served boxes)] of finished
    requests. Returns {name: {"value", "limit"}}."""
    bad = malformed(m, outputs)
    worst = {k: NOT_COMPARED for k in NUMBERS}
    if outputs and not bad:
        idx = pick(outputs, CHECK_REQUESTS, seed)
        t = time.perf_counter()
        refs = reference_outputs(m, seed, [outputs[i][0] for i in idx])
        worst = compare([outputs[i][1:] for i in idx], refs)
        print(f"[check] reference on {len(idx)} requests in "
              f"{time.perf_counter() - t:.3f}s; every number: "
              + ", ".join(f"{k}={v!r}" for k, v in worst.items()),
              file=sys.stderr, flush=True)
    checks = {k: {"value": worst[k], "limit": float(lim)}
              for k, lim in limits.items()}
    if not limits:
        checks["limits_given"] = {"value": 0, "limit": -1}
    checks["unfinished"] = {"value": unfinished, "limit": 0}
    checks["malformed"] = {"value": bad, "limit": 0}
    checks["compiles"] = {"value": compiles, "limit": 0}
    return checks


def control_numbers(m: Model, seed: int, images: list) -> dict:
    """The control's worst numbers against the reference on ``images``."""
    refs = reference_outputs(m, seed, images)
    ctrl = reference_outputs(m, seed, images, control=True)
    return compare(ctrl, refs)
