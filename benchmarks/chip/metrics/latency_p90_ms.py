"""latency_p90_ms: 90th percentile of the same latencies as
latency_p50_ms."""
import numpy as np


def read(run):
    lat = run.latencies_s()
    return float(np.percentile(lat, 90) * 1e3) if lat else None
