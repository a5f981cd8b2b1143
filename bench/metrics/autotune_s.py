"""Seconds set-up spent in the system's engine selection
(``engine_select.choose``, inside ``ServingRuntime.from_forests`` for
served cells): a sweep on a checkout's first run, a cache hit after."""


def read(run):
    return run.setup.autotune_s
