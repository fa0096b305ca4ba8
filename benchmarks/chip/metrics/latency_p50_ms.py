"""latency_p50_ms: median over the window's requests of due time (on the
open-loop schedule) to results on the host (the engine's t_done)."""
import numpy as np


def read(run):
    lat = run.latencies_s()
    return float(np.percentile(lat, 50) * 1e3) if lat else None
