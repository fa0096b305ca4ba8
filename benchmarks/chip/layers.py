"""What the metrics of the program's layers read from a traced window.

* Device time by layer. The forward names its layers with
  ``jax.named_scope`` (``encoder/block_3/msda/sample``, ...), and the
  program's ``repro.obs.hlo_scopes`` maps each HLO instruction of a
  compiled program to its scope path. The forward is the live executable
  of the process whose module the window's runs ran (``jit_fwd``) and
  whose instructions hold the most of the window's operations. Each
  operation's time counts once: where operations overlap, the later one
  has only what the earlier left, so the layers add up to
  ``Summary.busy_s()``.
* Each request's way to the device and back. The engine stamps a request
  with the ``step`` of its dispatch and the time that dispatch returned
  (``DetrRequest.step``, ``t_dispatched``). The k-th dispatch of the
  window goes with the k-th run of the forward that lies wholly in the
  window (runs in the order they started, the order of the device's
  queue). Host times (``perf_counter``) go onto the trace's clock through
  the window: the harness takes ``Run.t0`` just before it opens the
  ``bench.window`` annotation, whose start is ``Summary.start``.

A program without scopes or stamps (an older commit) gives nothing here,
and the readers return None.
"""
from __future__ import annotations

from collections import defaultdict

import numpy as np

from benchmarks.chip import xplane

STAGES = ("host_queue", "dispatch", "device_queue", "device_run", "readback")


def op_scopes(run):
    """``{op name: scope path}`` of the forward the window ran, or None."""
    try:
        from repro.obs import hlo_scopes
    except ImportError:
        return None
    if run.trace is None or not run.trace.ops:
        return None
    import jax
    dev = min(run.trace.ops)
    modules = {n.split("(")[0] for _, _, n in run.trace.modules.get(dev, [])}
    ran = defaultdict(float)
    for s, e, n in run.trace.ops[dev]:
        ran[n] += e - s
    best, covered = None, 0.0
    for exe in jax.devices()[0].client.live_executables():
        for mod in exe.hlo_modules():
            if mod.name not in modules:
                continue
            scopes = hlo_scopes(mod.to_string())
            cover = sum(t for n, t in ran.items() if n in scopes)
            if cover > covered:
                best, covered = scopes, cover
    return best


def op_seconds(summary, dev=None) -> dict:
    """Device seconds of each operation name, overlaps counted once."""
    dev = min(summary.ops) if dev is None else dev
    out, reach = defaultdict(float), float("-inf")
    for s, e, n in summary.ops.get(dev, []):        # sorted by start
        s = max(s, reach)
        if e > s:
            out[n] += (e - s) / 1e9
        reach = max(reach, e)
    return out


def device_scopes(summary, scopes: dict, depth: int = 2) -> dict:
    """Device seconds by scope, ``depth`` levels deep
    (``encoder/block_3``, ``decoder/cache_build``), ``unscoped`` for
    operations with no scope."""
    out = defaultdict(float)
    for name, sec in op_seconds(summary).items():
        path = "/".join(scopes.get(name, "").split("/")[:depth])
        out[path or "unscoped"] += sec
    return dict(out)


def ms_per_image(run, keep) -> float | None:
    """Device milliseconds, over the images completed in the window, of the
    operations ``keep(op_name, scope_path)`` selects."""
    scopes = op_scopes(run)
    done = len(run.completed_in_window)
    if not scopes or not done:
        return None
    sec = sum(t for n, t in op_seconds(run.trace).items()
              if keep(n, scopes.get(n, "")))
    return sec / done * 1e3


def under(prefix: str):
    """A ``keep`` for :func:`ms_per_image`: scopes at or below ``prefix``."""
    return lambda name, path: path == prefix or path.startswith(prefix + "/")


def msda_xla(name: str, path: str) -> bool:
    """Operations inside an MSDA call that are not its Pallas kernel."""
    return "msda" in path.split("/") and not xplane.MSDA_KERNELS.match(name)


def request_split(run):
    """For each finished request whose dispatch was paired with a run of
    the forward: (request, seconds of each of ``STAGES``). None where the
    program stamps no dispatches or the trace has no such run.

    The stages cut the request's latency, due time to ``t_done``, into
    consecutive parts. An idle device starts the run while the call that
    launched it is still returning (by up to 1.5 ms on a v5e): the
    dispatch then ends where the run starts, and the device queue, from
    the end of ``serve.dispatch`` to the run's start, is 0."""
    tr = run.trace
    if tr is None or not tr.ops:
        return None
    recs = [r for r in run.recs if r.done and run.t0 <= r.t_dispatch
            and getattr(r.req, "step", None) is not None]
    if len({r.req.bucket for r in recs}) > 1:
        raise ValueError("dispatches of several buckets: runs cannot be "
                         "paired with them in order")
    steps = sorted({r.req.step for r in recs})
    runs = tr.modules.get(min(tr.ops), [])
    if runs:        # the forward: the module the window ran most often
        names = [n for _, _, n in runs]
        runs = [m for m in runs if m[2] == max(names, key=names.count)]
    if not steps or not runs:
        return None
    if len(runs) > len(steps):
        raise ValueError(f"{len(runs)} runs of the forward in the window "
                         f"but {len(steps)} dispatches")
    on_trace = lambda t: tr.start + (t - run.t0) * 1e9
    run_of = dict(zip(steps, runs))
    rows = []
    for r in recs:
        if r.req.step not in run_of:
            continue
        s, e, _ = run_of[r.req.step]
        if s < on_trace(r.t_dispatch) - 1e6:
            raise ValueError(f"step {r.req.step} ran {s} before its "
                             f"dispatch began: dispatches and runs are "
                             f"paired wrongly")
        launched = min(on_trace(r.req.t_dispatched), s)
        rows.append((r, (r.t_dispatch - r.due,
                         (launched - on_trace(r.t_dispatch)) / 1e9,
                         (s - launched) / 1e9,
                         (e - s) / 1e9,
                         (on_trace(r.t_done) - e) / 1e9)))
    return rows or None


def split_line(rows) -> str:
    """The per-request split in one line: median milliseconds of each
    stage, how many requests' stages add up to their latency within 1 ms,
    and how many runs began before their dispatch returned."""
    parts = np.asarray([p for _, p in rows]) * 1e3
    med = ", ".join(f"{k} {v:.3f}" for k, v in
                    zip(STAGES, np.median(parts, axis=0)))
    lat = np.asarray([r.t_done - r.due for r, _ in rows]) * 1e3
    ok = int(np.sum(np.abs(parts.sum(axis=1) - lat) <= 1.0))
    early = int(np.sum(parts[:, STAGES.index("device_queue")] == 0.0))
    return (f"[trace] per-request split over {len(rows)} requests, median "
            f"ms: {med}; stages within 1 ms of the latency for {ok} of "
            f"{len(rows)}; {early} runs began before their dispatch "
            f"returned")
