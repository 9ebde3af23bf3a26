"""Device time of the keyed-state migration per window batch, in ms: the
programs ``make_migrate_step`` builds, named ``jit_migrate_start``,
``jit_migrate_finish`` and ``jit_migrate_step`` (split-phase and serial),
from the trace.  The merge of the rows a migration receives runs in the
merge program and is in ``merge_ms``.  A program whose migration programs
share the shuffle's names gives nothing."""
MODULES = ("jit_migrate_start", "jit_migrate_finish", "jit_migrate_step")


def is_migrate(op) -> bool:
    return op.module in MODULES


def read(run):
    if run.trace is None or not run.window or not run.trace.op_count(is_migrate):
        return None
    return 1e3 * run.trace.op_seconds(is_migrate) / run.window_batches
