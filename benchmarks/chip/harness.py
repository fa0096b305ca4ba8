"""One cell of the chip benchmark, from set-up to the result line.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a
configuration (``configs/<name>.json``, found through the manifest's
``configs[].file``) under a traffic mix (``traffic/<name>.json``). The
configuration names its architecture module (``arch/<name>.py``, see
``model.py``), which builds the program's server and holds the
architecture's weights, reference and work counts. Every metric is a
reader of its own, ``metrics/<name>.py``. Nothing here names a cell, a
configuration, an architecture, a traffic mix or a metric, so a later
cell, architecture or metric is added with files and manifest entries
alone.

A run:

1. set-up (``setup_s``, from process start): the chip is checked, JAX's
   compilation cache is placed in the checkout, the weights are made on
   the device from the seed, the architecture module builds the
   program's server, which compiles (or loads) its buckets, the run's
   images are made, ``WARM_BATCHES`` batches are served at once to warm
   the path (which measures one batch's time, ``warm_batch_s``), and the
   objects set-up made are frozen out of garbage collection;
2. the window (``--seconds``): the traffic drives ``submit`` and
   ``step`` on this thread, with up to ``AHEAD_S`` of device work
   dispatched (never fewer than ``WARM_BATCHES`` batches), so that the
   device works on through a stall of the host; results complete on the
   engine's post-processing thread. With ``--trace 1`` the profiler
   records the window;
3. the drain: every request of the window is waited for, up to
   ``DRAIN_S`` after the window closes;
4. the check (``check.py``): a sample of finished requests against the
   plain float32 reference, after the memory peak has been read.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Optional

import numpy as np

from benchmarks.chip import check, model as model_lib, traffic as traffic_lib
from benchmarks.chip.model import Refused

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
BENCH = HERE.relative_to(ROOT)          # the benchmark's files in a checkout
DRAIN_S = 60.0
# batches served at once in the warm-up, and the least number the window
# keeps dispatched: one running and one queued behind it
WARM_BATCHES = 2
# seconds of device work the window keeps dispatched at most, by the batch
# time the warm-up measured: the device works on through a host stall
# that is shorter, and nothing waits behind a dispatch queue of no end
AHEAD_S = 6.0
COMPILE_EVENT = "/jax/core/compile/"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    model: model_lib.Model
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def _for_cell(entries: list, cell: str) -> list:
    return [e for e in entries if cell in e.get("workloads", [cell])]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    path = root / "BENCHMARK.json"
    if not path.exists():
        raise Refused(f"no {path}")
    man = json.loads(path.read_text())
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in man["configs"]}[w["config"]]
    return make_cell(name, root / conf["file"], w["traffic"], int(w["chips"]),
                     _for_cell(man["end_to_end"], name),
                     _for_cell(man["per_layer"], name), root=root)


def make_cell(name: str, config_file: Path, traffic: str, chips: int = 1,
              end_to_end: tuple = (), per_layer: tuple = (), *,
              root: Path = ROOT) -> Cell:
    """A cell from a configuration file and a traffic name; its
    architecture module and traffic file are those of the checkout at
    ``root``."""
    m, raw = model_lib.load(config_file, root / BENCH / "arch")
    tr = json.loads((root / BENCH / "traffic" / f"{traffic}.json")
                    .read_text())
    return Cell(name, chips, m, raw, tr, list(end_to_end), list(per_layer))


def metric_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def import_program(root: Path):
    """The program under test from this checkout's ``src``, never from
    anywhere else."""
    src = root / "src"
    if not (src / "repro").is_dir():
        raise Refused(f"no program at {src}")
    sys.path.insert(0, str(src))
    import repro
    if Path(repro.__file__).resolve().parent != (src / "repro").resolve():
        raise Refused(f"repro imported from {repro.__file__}, not {src}")


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else ``<checkout>/.jax_cache``; every program is kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@dataclasses.dataclass
class Rec:
    """One request of the run, timed on this thread's clock."""
    index: int
    image: np.ndarray
    due: float = 0.0             # when it was due (open) or submitted
    submit_s: float = 0.0        # host time in engine.submit
    step_s: float = 0.0          # host time of the step that dispatched it
    postproc_s: float = 0.0      # its batch's post-processing span
    batch: int = -1              # the step that dispatched it
    t_dispatch: float = 0.0      # when that step began
    req: Optional[object] = None
    late_s: float = 0.0          # submitted after it was due, by this much

    @property
    def done(self) -> bool:
        return self.req is not None and self.req.done

    @property
    def t_done(self) -> float:
        return self.req.t_done


@dataclasses.dataclass
class Run:
    """What a metric reader reads."""
    cell: Cell
    seconds: float
    setup_s: float
    t0: float
    t1: float
    recs: list                   # requests of the window, in due order
    memory_peak_bytes: int
    platform: str
    device_kind: str
    device_count: int
    trace: Optional[object] = None    # xplane.Summary of the window

    @property
    def completed_in_window(self) -> list:
        return [r for r in self.recs if r.done and r.t_done <= self.t1]

    def images_per_s(self) -> float:
        """Images completed per second of the window, the batch in flight
        at the window's close counted for the part of its time that lies
        inside (linear between the completions around the close), so that
        the rate does not move in steps of one batch."""
        done = [r for r in self.recs if r.done]
        inside = [r.t_done for r in done if r.t_done <= self.t1]
        after = sorted((r for r in done if r.t_done > self.t1),
                       key=lambda r: r.t_done)
        n = float(len(inside))
        if after:
            # a batch's results reach the host one by one, microseconds
            # apart: the batch is known by the step that dispatched it
            prev = max(inside, default=self.t0)
            nxt = after[0]
            k = sum(1 for r in after if r.batch == nxt.batch)
            n += k * (self.t1 - prev) / (nxt.t_done - prev)
        return n / self.seconds

    def latencies_s(self) -> list:
        return [r.t_done - r.due for r in self.recs if r.done]


def ahead_batches(batch_s: float) -> int:
    """Batches the window keeps dispatched: ``AHEAD_S`` of work at
    ``batch_s`` a batch, and at least ``WARM_BATCHES``."""
    return max(WARM_BATCHES, int(AHEAD_S / max(batch_s, 1e-6)))


class _Drive:
    """The client side of the window: submits as the traffic says and
    keeps at most ``ahead`` batches dispatched to the device."""

    def __init__(self, engine, traffic: dict, images: list, seconds: float,
                 ahead: int = WARM_BATCHES):
        from jax.profiler import TraceAnnotation
        self.ann = TraceAnnotation
        self.engine = engine
        self.traffic = traffic
        self.images = images
        self.seconds = seconds
        self.cap = ahead * int(traffic["max_batch"])
        self.recs: list = []
        self.queued: collections.deque = collections.deque()
        self.dispatched = 0
        self.steps = 0
        self.wake = threading.Event()

    def _on_done(self, req) -> None:
        self.wake.set()

    def _submit(self, index: int, due: float) -> None:
        from repro.serve.engine import DetrRequest
        img = self.images[index % len(self.images)]
        rec = Rec(index=index, image=img, due=due)
        req = DetrRequest(rid=index, image=img, callback=self._on_done)
        t = time.perf_counter()
        with self.ann("bench.submit"):
            ok = self.engine.submit(req)
        rec.submit_s = time.perf_counter() - t
        rec.late_s = t - due
        rec.req = req
        self.recs.append(rec)
        if ok:
            self.queued.append(rec)
        else:
            log(f"[run] request {index} rejected: {req.error}")

    def _dispatch(self) -> None:
        eng = self.engine
        while eng.pending() and \
                self.dispatched - len(eng.finished) < self.cap:
            t = time.perf_counter()
            with self.ann("bench.step"):
                n = eng.step()
            dt = time.perf_counter() - t
            for _ in range(n):
                r = self.queued.popleft()
                r.step_s = dt
                r.batch = self.steps
                r.t_dispatch = t
            self.dispatched += n
            self.steps += 1

    def run(self, t0: float, on_window_end) -> float:
        """Drive from ``t0``; returns the time the drain ended."""
        tr = self.traffic
        is_open = tr["loop"] == "open"
        end = t0 + self.seconds
        due = t0 + traffic_lib.open_schedule(tr, self.seconds) \
            if is_open else None
        queued = int(tr.get("queued_images", 0))
        nxt, window_open = 0, True
        while True:
            self.wake.clear()
            now = time.perf_counter()
            if window_open and now >= end:
                window_open = False
                on_window_end()
            if is_open:
                while nxt < len(due) and due[nxt] <= now:
                    self._submit(nxt, float(due[nxt]))
                    nxt += 1
            elif window_open:
                while self.engine.pending() < queued:
                    self._submit(nxt, time.perf_counter())
                    nxt += 1
            self._dispatch()
            unfinished = len(self.recs) - len(self.engine.finished) \
                - len(self.engine.rejected)
            if not window_open and (unfinished == 0 and
                                    (not is_open or nxt == len(due))):
                return time.perf_counter()
            if not window_open and now > end + DRAIN_S:
                log(f"[run] drain gave up with {unfinished} unfinished")
                return time.perf_counter()
            wait = end - now if window_open else DRAIN_S
            if is_open and nxt < len(due):
                wait = min(wait, due[nxt] - now)
            with self.ann("bench.wait"):
                self.wake.wait(max(0.0, min(wait, 1.0)))


def warm_batch_s(reqs: list, max_batch: int) -> float:
    """One batch's device time: the gap between the last completions of
    the warm-up's first two batches, the second dispatched behind the
    first. Floored at the mean time of the two from the first submit to
    the second's last completion, which no stall can make shorter than
    the device took: a misread gap (the first completion held up behind
    a host stall) then keeps no more than ``AHEAD_S`` of real work
    dispatched."""
    last = [max(r.t_done for r in reqs[i:i + max_batch])
            for i in range(0, 2 * max_batch, max_batch)]
    return max(last[1] - last[0], (last[1] - reqs[0].t_submit) / 2)


def service_gaps(recs: list, batch_s: float, k: int = 3) -> list:
    """The ``k`` longest waits, beyond one batch's time, between the
    completion of a batch and of the next one dispatched before it ended
    (so queued on the device behind it): (request, seconds)."""
    first = {}
    for r in recs:
        if r.done and r.batch >= 0:
            first.setdefault(r.batch, r)
    order = [first[b] for b in sorted(first)]
    gaps = [(b.index, b.t_done - a.t_done - batch_s)
            for a, b in zip(order, order[1:]) if b.t_dispatch < a.t_done]
    return sorted(gaps, key=lambda g: -g[1])[:k]


def _attach_postproc(recs: list, spans: list) -> None:
    post = sorted((s.t0, s.t1) for s in spans if s.name == "postproc")
    for r in recs:
        if not r.done:
            continue
        for t0, t1 in post:
            if t0 <= r.t_done <= t1:
                r.postproc_s = t1 - t0
                break


def serve_window(cell: Cell, seed: int, seconds: float, trace: bool, *,
                 t_start: float, root: Path = ROOT,
                 require_chip: bool = True):
    """Set up, serve the window and drain. Returns (run, outputs,
    compiles): the finished requests' (image, logits, boxes) and the
    compilations counted from the window's start to the drain's end."""
    import_program(root)
    import jax
    devs = jax.devices()
    dev = devs[0]
    if require_chip and (dev.platform != "tpu" or len(devs) < cell.chips):
        raise Refused(f"cell {cell.name} needs {cell.chips} TPU chip(s); "
                      f"JAX found {len(devs)} {dev.platform} device(s)")
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    log(f"[setup] compile cache: {enable_compile_cache(root)}")
    compiles = []

    def on_event(ev, dur, **kw):
        if ev.startswith(COMPILE_EVENT):
            compiles.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        return _serve(cell, seed, seconds, trace, t_start, dev, compiles)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


def _serve(cell, seed, seconds, trace, t_start, dev, compiles):
    import jax
    from repro.serve.engine import DetrRequest
    m, tr = cell.model, cell.traffic
    mark = lambda what: log(f"[setup] {what} at "
                            f"{time.perf_counter() - t_start:.3f}s")
    mark("chip and cache ready")
    params = jax.block_until_ready(m.arch.make_params(seed, m))
    mark("weights made")
    engine = m.arch.engine(m, params, int(tr["max_batch"]))
    log(f"[setup] decoder plan: {engine.describe()}")
    log(f"[setup] bucket compile_seconds={engine.compile_seconds!r}")
    mark("engine built")
    images = traffic_lib.images(tr, traffic_lib.n_images(tr, seconds), seed)
    # warm-up: WARM_BATCHES batches at once
    warm = [DetrRequest(rid=-1 - i, image=img) for i, img in enumerate(
        traffic_lib.images(tr, int(tr["max_batch"]) * WARM_BATCHES,
                           seed + 1))]
    for req in warm:
        engine.submit(req)
    engine.run_until_drained()
    engine.finished.clear()
    batch_s = warm_batch_s(warm, int(tr["max_batch"]))
    ahead = ahead_batches(batch_s)
    log(f"[setup] warm-up batch {batch_s:.6f}s on the device: the window "
        f"keeps up to {ahead} batches dispatched")
    warm_compiles = engine.compile_count
    # what set-up made lives to the end of the run: no garbage collection
    # in the window walks it again
    gc.collect()
    gc.freeze()
    mark("images made and the warm-up served")

    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tdir, profiler_options=opts)
    drive = _Drive(engine, tr, images, seconds, ahead)
    window = jax.profiler.TraceAnnotation("bench.window")
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    window.__enter__()
    t_end = drive.run(t0, lambda: window.__exit__(None, None, None))
    t1 = t0 + seconds
    n_compiles = sum(1 for c in compiles if t0 <= c <= t_end)
    if trace:
        jax.profiler.stop_trace()
    stats = dev.memory_stats() or {}
    # the TPU runtime keeps programs' temporaries apart from allocations:
    # the device's peak is both
    peak = int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))
    log(f"[run] memory_stats after the drain: {stats}")
    engine.close()
    gc.unfreeze()
    _attach_postproc(drive.recs, list(engine.obs.tracer.spans))
    recompiles = engine.compile_count - warm_compiles
    recs = drive.recs
    late = sorted(r.late_s for r in recs)
    log(f"[run] requests {len(recs)} submitted, "
        f"{sum(r.done for r in recs)} finished, "
        f"{sum(1 for r in recs if r.done and r.t_done <= t1)} in the "
        f"window; steps {drive.steps}; drain ended "
        f"{t_end - t1:.3f}s after the window")
    if late:
        worst = sorted(recs, key=lambda r: -r.late_s)[:3]
        log(f"[run] generator lateness: p50 {late[len(late) // 2]:.6f}s "
            f"max {late[-1]:.6f}s (requests "
            + ", ".join(f"{r.index}: {r.late_s:.6f}s" for r in worst) + ")")
    log("[run] longest waits beyond one batch between back-to-back "
        "completions: " + ", ".join(
            f"request {i}: {g:.6f}s" for i, g in service_gaps(recs, batch_s)))
    log(f"[run] compiles from the window's start to the drain's end: "
        f"{n_compiles} events; msda_compiles_total moved by {recompiles}")
    summary = None
    if trace:
        from benchmarks.chip import xplane
        path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                         recursive=True)[0]
        summary = xplane.summarize(xplane.load(path))
        shutil.rmtree(tdir, ignore_errors=True)
    run = Run(cell=cell, seconds=seconds, setup_s=setup_s, t0=t0, t1=t1,
              recs=recs, memory_peak_bytes=peak, platform=dev.platform,
              device_kind=dev.device_kind,
              device_count=len(jax.devices()), trace=summary)
    outputs = [(r.image, r.req.cls_logits, r.req.boxes)
               for r in recs if r.done]
    return run, outputs, n_compiles + recompiles


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: Path = ROOT, require_chip: bool = True,
             cell: Optional[Cell] = None) -> dict:
    """Measure one cell; returns the result line as a dict."""
    cell = cell or load_cell(name, root)
    run, outputs, compiles = serve_window(
        cell, seed, seconds, trace, t_start=t_start, root=root,
        require_chip=require_chip)
    checks = check.run_checks(
        cell.model, cell.config.get("limits", {}), seed, outputs,
        unfinished=sum(1 for r in run.recs if not r.done),
        compiles=compiles)

    entries = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for e in entries:
        value = metric_reader(e["name"])(run)
        if value is None:
            if not trace:
                raise RuntimeError(f"end-to-end metric {e['name']} read "
                                   f"nothing in cell {name}")
            continue
        metrics[e["name"]] = {"value": float(value), "unit": e["unit"]}
    device = {"platform": run.platform, "kind": run.device_kind,
              "count": run.device_count,
              "memory_peak_bytes": run.memory_peak_bytes}
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": len(run.recs),
              "failed": sum(1 for r in run.recs if not r.done),
              "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    result["checks"] = checks
    return result
