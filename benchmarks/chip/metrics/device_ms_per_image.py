"""device_ms_per_image: device busy time in the window (the union of the
intervals in which an operation ran, from the profiler trace) over the
images completed in the window."""


def read(run):
    done = len(run.completed_in_window)
    busy = run.trace.busy_s() if run.trace is not None else 0.0
    if not busy or not done:
        return None
    return busy / done * 1e3
