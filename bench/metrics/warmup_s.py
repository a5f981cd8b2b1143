"""Seconds set-up spent warming the cell's shapes: the runtime's
``warmup`` for served cells, the first call for batch cells."""


def read(run):
    return run.setup.warmup_s
