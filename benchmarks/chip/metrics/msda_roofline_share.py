"""msda_roofline_share: over the MSDA sampling calls that the trace
names (xplane.MSDA_KERNELS) in the forwards that ran wholly inside the
window, the least time the chip could take for each
(the larger of its FLOPs over peak and its bytes over bandwidth, from
work.msda_calls, times the batch) summed, over their summed device time,
in percent. The kernels of one forward are matched to the
configuration's calls in program order (encoder blocks, then decoder
layers); a forward with another number of kernels is an error."""
from benchmarks.chip import work


def read(run):
    if run.trace is None:
        return None
    peak = work.peak_for(run.device_kind)
    plan = work.msda_calls(run.cell.model)
    batch = int(run.cell.traffic["max_batch"])
    least = spent = 0.0
    for calls in run.trace.msda_calls():
        if len(calls) != len(plan):
            raise ValueError(f"a forward ran {len(calls)} MSDA kernels, "
                             f"the configuration has {len(plan)} calls")
        least += sum(w.least_seconds(peak)[0] * batch for w in plan)
        spent += sum(calls)
    return 100.0 * least / spent if spent else None
