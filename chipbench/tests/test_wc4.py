"""The four-worker cell: a tiny run of its configuration on four CPU devices,
and the readers of its metrics (the exchange's all-to-all that no other op
hides, and the program's balance counter)."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import run as harness
import source
import trace_reduce as tr
from metrics import a2a_exposed_ms, worker_imbalance

ROOT = Path(__file__).resolve().parents[2]
TINY = source.Traffic(name="tiny", arrivals="backlog", zipf_exponent=1.2,
                      population=4000, id_range=1 << 30, drift_every_batches=2,
                      drift_fraction=0.3, batch_events_per_chip=1 << 10,
                      prefill="population_sweep")


def test_tiny_four_worker_run_is_correct():
    """The configuration as the cell runs it, cut to a tiny size."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = json.loads((ROOT / "chipbench/configs/wordcount-zipf1.2-4chip.json").read_text())
    config["job"].update(state_capacity=1 << 12, num_partitions=16)
    config["warmup_batches"] = 2
    cell = harness.Cell("wc4-backlog", 4, config, TINY, bench["end_to_end"], bench["per_layer"])
    run, checks = harness.run_cell(cell, 2**31 + 91, 1.0, False, log=lambda s: None)
    assert harness.reference.passed(checks), checks
    assert run.window_batches > 0 and any(m.relative_migration > 0 for m in run.window)
    assert worker_imbalance.read(run) >= 1.0


D0, D1, HOST = "/device:TPU:0", "/device:TPU:1", tr.HOST_PLANE


def ev(plane, name, start, dur, module="", line="XLA Ops"):
    return {"plane": plane, "line": line, "name": name, "module": module,
            "start_ns": start, "dur_ns": dur}


def synthetic():
    """Two devices over a 1000 ns window (0..1000), two batches, with the op
    names the TPU compiler gives ``jax.lax.all_to_all``.  Device 0: in the
    shuffle's finish the operand's relayout 100-150 and the collective
    150-300, with a merge fusion 250-400 over its tail (150 ns exposed),
    then the unpack's copy 300-320 (not selected); in the migration's finish
    an async all-to-all whose start 600-620 and done 700-720 hold the device
    and whose flight 620-700 runs beside a fusion of another program 640-660
    (the rest of the flight, 60 ns, exposed along with start and done:
    100 ns).  Device 1: a collective 100-200 wholly under a sort 50-250, and
    one in the migration's finish 500-540."""
    return [
        ev(HOST, "window", 0, 1000),
        ev(D0, "%all_to_all.21", 100, 50, "jit_shuffle_finish"),
        ev(D0, "%all_to_all.22", 150, 150, "jit_shuffle_finish"),
        ev(D0, "%fusion.4", 250, 150, "jit_local"),
        ev(D0, "%copy.13", 300, 20, "jit_shuffle_finish"),
        ev(D0, "%all-to-all-start.2", 600, 20, "jit_migrate_finish"),
        ev(D0, "%all-to-all-start.2", 620, 80, "jit_migrate_finish", line="Async XLA Ops"),
        ev(D0, "%fusion.7", 640, 20, "jit_local"),
        ev(D0, "%all-to-all-done.2", 700, 20, "jit_migrate_finish"),
        ev(D1, "%all_to_all.22", 100, 100, "jit_shuffle_finish"),
        ev(D1, "%sort.3", 50, 200, "jit_local"),
        ev(D1, "%all_to_all.5", 500, 40, "jit_migrate_finish"),
        ev(D1, "%all-gather.5", 300, 100, "jit_local"),  # neither
    ]


def run_of(events, batches=2, window=None):
    t = tr.DeviceTrace(events, *tr.window_of(events)) if events is not None else None
    return SimpleNamespace(trace=t, window=window or [0] * batches, window_batches=batches)


def test_a2a_exposed_ms_counts_only_the_uncovered_part():
    # device 0: 150 (tail covered) + 100 (async pair, fusion inside);
    # device 1: 0 (covered by the sort) + 40; mean over devices, per batch
    want = (150 + 100 + 0 + 40) / 2 / 2 / 1e6
    assert a2a_exposed_ms.read(run_of(synthetic())) == pytest.approx(want)


def test_a2a_exposed_ms_without_all_to_all_gives_nothing():
    events = [e for e in synthetic() if not a2a_exposed_ms.PATTERN.match(e["name"])]
    assert a2a_exposed_ms.read(run_of(events)) is None
    assert a2a_exposed_ms.read(run_of(None)) is None


def test_worker_imbalance_is_the_mean_over_the_window():
    window = [SimpleNamespace(worker_imbalance=1.5), SimpleNamespace(worker_imbalance=2.5)]
    assert worker_imbalance.read(SimpleNamespace(window=window)) == pytest.approx(2.0)


def test_worker_imbalance_without_the_program_counter_gives_nothing():
    bare = SimpleNamespace(window=[SimpleNamespace(wall_time_s=1.0)])
    assert worker_imbalance.read(bare) is None
    assert worker_imbalance.read(SimpleNamespace(window=[])) is None
