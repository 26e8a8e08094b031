"""Percent of the device's busy time spent in collective operations
(`collective_s` over `busy_s` of the traced window, each the mean over the
cell's chips): the mesh's all-to-all exchange and its reductions."""


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    return 100.0 * run.trace["collective_s"] / run.trace["busy_s"]
