"""device_queue_ms_p50: median over the window's requests (each request
once, so a dispatch counts by its batch size) of the time from the end
of its ``serve.dispatch`` (DetrRequest.t_dispatched) to the start of the
forward's run on the device that the dispatch is paired with
(layers.request_split), floored at 0. Logs the whole per-request split."""
import numpy as np

from benchmarks.chip import harness, layers


def read(run):
    rows = layers.request_split(run)
    if not rows:
        return None
    harness.log(layers.split_line(rows))
    queue = layers.STAGES.index("device_queue")
    return float(np.percentile([p[queue] for _, p in rows], 50) * 1e3)
