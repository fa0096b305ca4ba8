"""setup_s: process start to the first timed request (JAX and the chip,
weights from the seed, the bucket executable, the run's images, one
warm-up batches)."""


def read(run):
    return run.setup_s
