"""Share, %, of the traced window in which no operation ran on the
device."""


def read(run):
    return run.idle_share()
