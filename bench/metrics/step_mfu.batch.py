"""The scoring steps' share, %, of the chip's roofline: the least time
of each step (``roofline.least_time`` over its real rows) summed,
over the steps' summed wall time from the start of host work on a step
to its scores on the host."""


def read(run):
    return run.step_mfu()
