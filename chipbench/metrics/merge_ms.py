"""Device time of the keyed-state merge program per window batch, in ms.

The merge is the jitted ``shard_map`` of ``merge_into`` that ``StreamingJob``
builds (``_make_merge``); its program is named after the mapped function,
``jit_local``.  It runs once for each batch's received rows and once more for
the rows a repartition's migration receives."""
MODULE = "jit_local"


def is_merge(op) -> bool:
    return op.module == MODULE


def read(run):
    if run.trace is None or not run.window or not run.trace.op_count(is_merge):
        return None
    return 1e3 * run.trace.op_seconds(is_merge) / run.window_batches
