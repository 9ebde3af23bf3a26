"""Per-worker load imbalance, mean over the window's batches: the job's
``BatchMetrics.worker_imbalance``, the most loaded worker's records over the
mean worker's, from the shuffle's global per-partition loads folded onto the
workers (partition ``p`` on worker ``p % W``).  1 is perfect balance; it is
what KIP's placement achieved on the batch it routed.  A program without the
counter gives nothing."""


def read(run):
    values = [getattr(m, "worker_imbalance", None) for m in run.window]
    if not values or None in values:
        return None
    return sum(values) / len(values)
