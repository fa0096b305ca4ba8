"""msda_xla_ms_per_image: device time of the XLA operations inside the
forward's MSDA calls (any ``msda/`` scope: value projection, sampling
points and offsets, the layouts and corner operands around the kernel,
output projection, FWP counting), not the Pallas kernels that
msda_roofline_share reads (xplane.MSDA_KERNELS), over the images
completed in the window."""
from benchmarks.chip import layers


def read(run):
    return layers.ms_per_image(run, layers.msda_xla)
