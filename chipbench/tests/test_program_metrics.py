"""The readers of the program's own counters and named programs, the
breakdown by the program's host spans (``program_spans.py``), and the
recorded trace's readings, which stay what they were before the program
named its spans and programs."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import program_spans as ps
import trace_reduce as tr
from metrics import (
    device_idle_share,
    host_exposed_ms,
    host_fetch_mb,
    host_put_mb,
    merge_ms,
    migrate_device_ms,
    route_kernel_ms,
    route_roofline,
)

DATA = Path(__file__).resolve().parent / "data"
D0, HOST = "/device:TPU:0", tr.HOST_PLANE


def ev(plane, name, start, dur, module="", line="XLA Ops"):
    return {"plane": plane, "line": line, "name": name, "module": module,
            "start_ns": start, "dur_ns": dur}


def synthetic():
    """One device over a 1000 ns window (0..1000), two batches.  Batch 0
    (100-500): feed 100-150 (device idle), count sync 150-250 under the
    shuffle start, decide 250-300 (idle), a migration 300-450 whose fetch
    (300-350) waits on the migrate start and whose plan (350-450) leaves the
    device idle, account 450-500.  Batch 1 (600-900): feed 600-700 under
    the migrate finish, the rest idle."""
    return [
        ev(HOST, "window", 0, 1000),
        ev(HOST, "job.process_batch", 100, 400),
        ev(HOST, "stream.batch", 100, 400),
        ev(HOST, "stream.feed", 100, 50),
        ev(HOST, "stream.count_sync", 150, 100),
        ev(HOST, "dr.decide", 250, 50),
        ev(HOST, "dr.migrate", 300, 150),
        ev(HOST, "dr.migrate.fetch", 300, 50),
        ev(HOST, "dr.migrate.plan", 350, 100),
        ev(HOST, "stream.account", 450, 50),
        ev(HOST, "job.process_batch", 600, 300),
        ev(HOST, "stream.batch", 600, 300),
        ev(HOST, "stream.feed", 600, 100),
        ev(HOST, "dr.observe", 700, 200),
        ev(D0, "%lookup_dispatch.1", 150, 100, "jit_shuffle_start"),
        ev(D0, "%fusion.1", 300, 50, "jit_migrate_start"),
        ev(D0, "%all-to-all.2", 600, 60, "jit_migrate_finish"),
        ev(D0, "%sort.0", 660, 40, "jit_local"),
    ]


def trace():
    events = synthetic()
    return tr.DeviceTrace(events, *tr.window_of(events))


def test_migrate_device_ms_reads_the_migration_programs():
    run = SimpleNamespace(trace=trace(), window=[0, 0], window_batches=2)
    assert migrate_device_ms.read(run) == pytest.approx((50 + 60) / 2 / 1e6)
    assert merge_ms.read(run) == pytest.approx(40 / 2 / 1e6)


def test_migrate_device_ms_without_named_programs_gives_nothing():
    events = [e for e in synthetic() if not e["module"].startswith("jit_migrate")]
    events.append(ev(D0, "%fusion.1", 300, 50, "jit__start_local"))
    t = tr.DeviceTrace(events, *tr.window_of(events))
    assert migrate_device_ms.read(SimpleNamespace(trace=t, window=[0], window_batches=1)) is None
    assert migrate_device_ms.read(SimpleNamespace(trace=None, window=[0], window_batches=1)) is None


def test_byte_counters_are_means_per_window_batch():
    window = [SimpleNamespace(put_bytes=9_437_184, fetch_bytes=16_777_216 + 1_000),
              SimpleNamespace(put_bytes=9_437_184, fetch_bytes=1_000)]
    run = SimpleNamespace(window=window)
    assert host_put_mb.read(run) == pytest.approx(9.437184)
    assert host_fetch_mb.read(run) == pytest.approx((16_777_216 + 2_000) / 2 / 1e6)


def test_byte_counters_without_the_counter_give_nothing():
    run = SimpleNamespace(window=[SimpleNamespace(wall_time_s=1.0)])
    assert host_put_mb.read(run) is None and host_fetch_mb.read(run) is None
    assert host_put_mb.read(SimpleNamespace(window=[])) is None


def test_breakdown_by_program_span():
    t = tr.DeviceTrace(synthetic(), 0, 1000)
    out = ps.breakdown(t, 2)
    spans = out["spans"]
    # per batch: feed idle 50 (batch 0) + 0 (batch 1, under the finish)
    assert spans["stream.feed"]["exposed_ms"] == pytest.approx(50 / 2 / 1e6)
    assert spans["stream.feed"]["host_ms"] == pytest.approx(150 / 2 / 1e6)
    assert spans["dr.migrate"]["exposed_ms"] == pytest.approx(100 / 2 / 1e6)
    assert spans["stream.count_sync"]["exposed_ms"] == 0
    # the leaves cover the batches, so their idle time is the batches'
    assert out["leaf_exposed_ms"] == pytest.approx(out["host_exposed_ms"])
    assert out["host_exposed_ms"] == pytest.approx((50 + 50 + 100 + 50 + 200) / 2 / 1e6)
    assert out["programs"]["jit_migrate_finish"] == pytest.approx(60 / 2 / 1e6)
    # each gap is named by the innermost span over its midpoint
    gaps = {round(s * 1e9): n for n, s in out["idle_gaps"]}
    assert gaps == {300: "dr.observe", 250: "stream.account", 150: "host.other",
                    50: "dr.decide"}


def test_program_spans_load_from_a_profile(tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("stream.batch", batch=3):
            with jax.profiler.TraceAnnotation("stream.feed"):
                jax.numpy.ones(8).block_until_ready()
        with jax.profiler.TraceAnnotation("not.a.span"):
            pass
    jax.profiler.stop_trace()
    path = str(sorted(tmp_path.rglob("*.xplane.pb"))[-1])
    names = [e["name"] for e in ps.load_program_spans(path)]
    assert sorted(names) == ["stream.batch", "stream.feed"]  # the argument is a stat
    assert ps.load(path).span_count("stream.feed") == 1


def test_recorded_trace_reads_as_before():
    """The recorded slice of a chip trace gives every existing metric and the
    breakdown exactly as they read before the program named its spans."""
    want = json.loads((DATA / "trace_small_metrics.json").read_text())
    events = json.loads((DATA / "trace_small.json").read_text())
    t = tr.DeviceTrace(events, *tr.window_of(events))
    run = SimpleNamespace(trace=t, window=[0], window_batches=1, batch_events=1 << 20,
                          cell=SimpleNamespace(chips=1), heavy_slots=128, hosts=4096,
                          peaks={"hbm_bytes_per_s": 819e9})
    readers = (route_kernel_ms, route_roofline, merge_ms, host_exposed_ms, device_idle_share)
    got = {r.__name__.rsplit(".", 1)[-1]: r.read(run) for r in readers}
    assert got == pytest.approx(want["metrics"], rel=1e-12)
    assert (t.busy_s, t.window_s) == pytest.approx((want["busy_s"], want["window_s"]))
    breakdown = json.loads(json.dumps({"device_ops": t.top_ops(), "idle_gaps": t.idle_gaps()}))
    assert breakdown == want["breakdown"]
