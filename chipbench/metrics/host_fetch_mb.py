"""Bytes the job fetched device -> host per window batch, in MB (10^6 B):
the mean of ``BatchMetrics.fetch_bytes``, which the job counts from the
shapes of the arrays it fetches (``repro.compat.host_fetch``): control
outputs every batch, and the whole key table for each repartition's
migration plan.  A program without the counter gives nothing."""


def read(run):
    counts = [getattr(m, "fetch_bytes", None) for m in run.window]
    if not counts or None in counts:
        return None
    return sum(counts) / len(counts) / 1e6
