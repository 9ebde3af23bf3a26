"""Device time of the route kernel (``lookup_dispatch``, the two-pass route
path's Pallas kernel) per window batch, in ms, from the trace."""
import re

PATTERN = re.compile(r"^%lookup_dispatch\b")


def is_route(op) -> bool:
    return bool(PATTERN.search(op.name))


def read(run):
    if run.trace is None or not run.window or not run.trace.op_count(is_route):
        return None
    return 1e3 * run.trace.op_seconds(is_route) / run.window_batches
