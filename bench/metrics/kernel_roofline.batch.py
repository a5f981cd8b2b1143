"""Share, %, of the device's busy time that the window's least work
would take at the chip's peaks (``roofline.least_time`` summed over
the steps, over busy time from the trace)."""


def read(run):
    return run.kernel_roofline()
