"""device_idle.batch (%): share of the traced window in which no
operation ran on the device (busy time is the union of the ``XLA Ops``
intervals, averaged over the chips used), in the closed-loop cells."""


def read(run):
    if run.reduced is None:
        return None
    return 100.0 * run.reduced["idle_share"]
