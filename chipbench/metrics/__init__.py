"""One reader per metric: ``<name>.read(run)`` returns the metric's value
from a run (``run.Run``), or None where the run holds nothing to read."""
