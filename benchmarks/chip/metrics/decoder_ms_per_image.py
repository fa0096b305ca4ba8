"""decoder_ms_per_image: device time of the operations under the
forward's ``decoder/`` scope (the shared value-cache build and every
layer), from the profiler trace and the program's scope map
(layers.op_scopes), over the images completed in the window."""
from benchmarks.chip import layers


def read(run):
    return layers.ms_per_image(run, layers.under("decoder"))
