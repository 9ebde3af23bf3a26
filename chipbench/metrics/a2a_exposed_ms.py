"""Device time of the exchange's all-to-all that no other op on the same
device hides, per window batch, in ms, averaged over the devices
(``DeviceTrace.exposed_seconds``).

Selection: the ops that XLA names after ``jax.lax.all_to_all``.  The TPU
compiler keeps the primitive's name on what it lowers to: in the dense
transport's finish programs (``jit_shuffle_finish`` each batch,
``jit_migrate_finish`` each migration) that is the collective itself
(``%all_to_all.22 = ... all-to-all(...)``, one per lane buffer) and the
relayout of its operand (``%all_to_all.21 = ... reshape(...)``), as a
compile for a described v5e 2x2 and a v5e trace of ``wc4-backlog`` name
them.  The unpack of what arrived (``%copy.N``, ``%bitcast.N``) is not
counted.  An async form (``%all-to-all-start``, ``%all-to-all-done``)
counts too.  A trace with none of these gives nothing."""
import re

PATTERN = re.compile(r"^%all[-_]to[-_]all")


def is_a2a(op) -> bool:
    return bool(PATTERN.match(op.name))


def read(run):
    if run.trace is None or not run.window or not run.trace.op_count(is_a2a):
        return None
    return 1e3 * run.trace.exposed_seconds(is_a2a) / run.window_batches
