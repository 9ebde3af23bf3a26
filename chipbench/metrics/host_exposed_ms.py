"""Host time of the job loop that the device does not hide, per window batch,
in ms: the part of the ``job.process_batch`` spans (the loop's route set-up,
count sync, DR decision, migration plan and ship) during which no op holds
the device, from the trace, averaged over the devices.  Time the host spends
waiting at a sync while the device works is not in it."""
SPAN = "job.process_batch"


def read(run):
    if run.trace is None or not run.window or not run.trace.span_count(SPAN):
        return None
    return 1e3 * run.trace.idle_within(SPAN) / run.window_batches
