"""The route kernel's share of its roofline, in %.

The kernel (``lookup_dispatch``) reads each record's key and valid flag as
int32 tiles and writes its partition and slot as int32, and reads the
partitioner tables once per call.  It does no matrix work: its one-hot table
lookups run on the vector unit, so HBM bytes are its only published bound.
The least time is ``route_bytes / peak HBM bytes/s``; the share is that over
the kernel's measured time.  The table sizes are the job's own partitioner's,
as the run records them.
"""
from metrics import route_kernel_ms

TILE = 8 * 128  # records per (8, 128) int32 tile


def route_bytes(records: int, heavy_slots: int, hosts: int, lanes: int) -> int:
    """HBM bytes one ``lookup_dispatch`` call must move for ``records``
    records on one worker: 4 B key + 4 B valid in and 4 B partition + 4 B
    slot out per record (padded to whole tiles), plus the tables once: the
    heavy keys, partitions and replica counts (int32 each), the host map,
    and the per-lane counts written out."""
    padded = -(-records // TILE) * TILE
    return 16 * padded + 4 * (3 * heavy_slots + hosts + lanes)


def read(run):
    ms = route_kernel_ms.read(run)
    calls = run.trace.op_count(route_kernel_ms.is_route) if run.trace else 0
    if ms is None or not calls or "hbm_bytes_per_s" not in run.peaks:
        return None
    records = run.batch_events // run.cell.chips
    nbytes = route_bytes(records, run.heavy_slots, run.hosts, run.cell.chips)
    per_call_s = ms / 1e3 * run.window_batches / calls
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / per_call_s
