"""Bytes the job put host -> device per window batch, in MB (10^6 B): the
mean of ``BatchMetrics.put_bytes``, which the job counts from the shapes of
the host arrays it places on the workers (a batch's keys, values and valid
flags, and state it re-lays).  A program without the counter gives
nothing."""


def read(run):
    counts = [getattr(m, "put_bytes", None) for m in run.window]
    if not counts or None in counts:
        return None
    return sum(counts) / len(counts) / 1e6
