"""Mean host time per step, ms: a step's wall time (the runtime's form,
pad, compute and sync phases of one batch, or one ``predict`` call on
the benchmark's clock) less the device's busy time per step in the
trace."""


def read(run):
    return run.host_ms()
