"""host_ms_p90: 90th percentile over the window's requests of the host
time spent on the request: its engine.submit call, the engine.step call
that dispatched it, and its batch's post-processing span."""
import numpy as np


def read(run):
    host = [r.submit_s + r.step_s + r.postproc_s
            for r in run.recs if r.done]
    return float(np.percentile(host, 90) * 1e3) if host else None
