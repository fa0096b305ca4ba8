"""encoder_ms_per_image: device time of the operations under the
forward's ``encoder/`` scope (every block: value projection, sampling
points, the MSDA kernel and the XLA work around it, FFN, norms), from
the profiler trace and the program's scope map (layers.op_scopes), over
the images completed in the window."""
from benchmarks.chip import layers


def read(run):
    return layers.ms_per_image(run, layers.under("encoder"))
