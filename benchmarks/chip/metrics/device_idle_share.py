"""device_idle_share: one minus the device's busy time over the traced
window, in percent."""


def read(run):
    busy = run.trace.busy_s() if run.trace is not None else 0.0
    if not busy:
        return None        # no device operation found in the window
    return 100.0 * (1.0 - busy / run.trace.window_s)
