"""Device time of the window's kernel custom-calls (the Pallas forest
kernels) per tree-row, ns: their summed seconds in the trace's
``device_ops``, over the rows of the window's steps times the forest's
trees.  Puts forests of every width on one scale; nothing when no
custom-call ran in the window."""


def read(run):
    if run.trace is None or not run.out.steps:
        return None
    kernel_s = sum(s for name, s in run.trace["device_ops"]
                   if "custom-call" in name)
    if kernel_s <= 0:
        return None
    rows = sum(r for r, _ in run.out.steps)
    return kernel_s * 1e9 / (rows * run.model.cfg["n_trees"])
