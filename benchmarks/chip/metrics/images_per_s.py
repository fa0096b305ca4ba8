"""images_per_s: images whose results reached the host within the
window, over the window; the batch in flight when the window closes
counts for the share of its time inside the window (Run.images_per_s)."""


def read(run):
    return run.images_per_s()
