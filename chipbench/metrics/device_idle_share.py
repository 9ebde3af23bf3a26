"""Share of the traced window in which no op ran on the device, in %:
100 * (1 - union of op intervals / window), averaged over the devices."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    return 100.0 * run.trace.idle_share
