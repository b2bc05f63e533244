"""Kernels: the least time the window's real blocks need on the chip
(the larger of operations over peak operations and bytes over peak
bandwidth, bench/roofline.py) over the summed device time of the Pallas
kernel operations in the trace, in %, with a note of which term binds.
Nothing to read without a trace, without a peak for the device, or
without kernel time."""

from roofline import least_time_s


def read(run):
    if run.trace is None or run.peaks is None or run.blocks <= 0:
        return None
    kernel_s = sum(run.trace["kernel_s"][: run.chips])
    if kernel_s <= 0:
        return None
    least, term = least_time_s(run.blocks, run.work, run.peaks)
    note = f"{term} bind: least {least!r} s over kernel time {kernel_s!r} s"
    return 100.0 * least / kernel_s, note
