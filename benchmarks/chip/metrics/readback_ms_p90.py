"""readback_ms_p90: 90th percentile over the window's requests of the
time from the end of the forward's run on the device that its dispatch
is paired with (layers.request_split) to its results on the host, decoded
(the engine's t_done, inside its batch's ``postproc`` span): the copy to
the host, ``serve.fetch`` and post-processing."""
import numpy as np

from benchmarks.chip import layers


def read(run):
    rows = layers.request_split(run)
    if not rows:
        return None
    back = layers.STAGES.index("readback")
    return float(np.percentile([p[back] for _, p in rows], 90) * 1e3)
