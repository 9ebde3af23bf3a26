"""The trace reduction on a small recorded trace: busy union and idle share,
op matching, exposed collective time, and the idle gaps of the breakdown."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import trace_reduce as tr
from metrics import device_idle_share, host_exposed_ms, merge_ms, route_kernel_ms

DATA = Path(__file__).resolve().parent / "data" / "trace_small.json"
D0, D1, HOST = "/device:TPU:0", "/device:TPU:1", tr.HOST_PLANE


def ev(plane, name, start, dur, module="", line="XLA Ops"):
    return {"plane": plane, "line": line, "name": name, "module": module,
            "start_ns": start, "dur_ns": dur}


def synthetic():
    """Two devices over a 1000 ns window (100..1100).  Device 0: a route
    kernel 100-300, a merge 250-450 (overlapping it), an all-to-all 500-700
    with a fusion 600-650 inside it, idle elsewhere.  Device 1: one
    all-to-all 100-1100 and an async copy in flight beside it, which holds
    nothing.  Host spans name the idle stretches."""
    return [
        ev(HOST, "window", 100, 1000),
        ev(HOST, "job.process_batch", 100, 600),
        ev(HOST, "source.next_batch", 700, 100),
        ev(HOST, "job.drain", 800, 300),
        ev(D0, "%lookup_dispatch.1", 100, 200, "jit__start_local"),
        ev(D0, "%sort.3", 250, 200, "jit_local"),
        ev(D0, "%all-to-all.1", 500, 200, "jit__finish_local"),
        ev(D0, "%fusion.2", 600, 50, "jit__finish_local"),
        ev(D0, "%fusion.9", 50, 100, "jit_local"),   # clipped to the window
        ev(D1, "%all-to-all.1", 100, 1000, "jit__finish_local"),
        ev(D1, "%copy-start.4", 100, 900, "jit__finish_local", line="Async XLA Ops"),
    ]


def test_busy_union_and_idle_share():
    t = tr.DeviceTrace(synthetic(), *tr.window_of(synthetic()))
    # device 0 busy 100-450 and 500-700 = 550 ns; device 1 busy 1000 ns
    assert t.window_s == pytest.approx(1e-6)
    assert t.busy_s == pytest.approx((550 + 1000) / 2 / 1e9)
    assert t.idle_share == pytest.approx(1 - 0.775)


def test_matching_and_exposed_collective():
    t = tr.DeviceTrace(synthetic(), 100, 1100)
    run = SimpleNamespace(trace=t, window=[0, 0], window_batches=2)
    assert route_kernel_ms.read(run) == pytest.approx(200 / 2 / 2 / 1e6)  # 2 devices, 2 batches
    assert merge_ms.read(run) == pytest.approx((200 + 50) / 2 / 2 / 1e6)
    # exposed all-to-all: device 0 200 - 50 under the fusion; device 1 all 1000
    a2a = t.exposed_seconds(lambda op: op.name.startswith("%all-to-all"))
    assert a2a == pytest.approx((150 + 1000) / 2 / 1e9)
    assert device_idle_share.read(run) == pytest.approx(100 * (1 - 0.775))


def test_host_time_the_device_does_not_hide():
    t = tr.DeviceTrace(synthetic(), 100, 1100)
    run = SimpleNamespace(trace=t, window=[0, 0], window_batches=2)
    # process_batch 100-700: device 0 idle in it 450-500, device 1 never
    assert t.span_count("job.process_batch") == 1
    assert t.idle_within("job.process_batch") == pytest.approx(50 / 2 / 1e9)
    assert host_exposed_ms.read(run) == pytest.approx(50 / 2 / 2 / 1e6)


def test_idle_gaps_named_by_host_span():
    t = tr.DeviceTrace(synthetic(), 100, 1100)
    gaps = t.idle_gaps()
    # device 0 idle 700-1100 (drain covers the midpoint 900) and 450-500
    assert gaps[0] == ["job.drain", pytest.approx(400e-9)]
    assert gaps[1] == ["job.process_batch", pytest.approx(50e-9)]
    assert t.top_ops(1)[0][0] == "jit__finish_local/%all-to-all.1"


def test_ops_named_by_instruction_and_program():
    assert tr.short_name("%fusion.3 = f32[8]{0} fusion(%a), kind=kLoop") == "%fusion.3"
    ops = [{"start_ns": 5, "module": ""}, {"start_ns": 25, "module": ""},
           {"start_ns": 45, "module": ""}]
    tr._with_modules(ops, [{"name": "jit_local(123)", "start_ns": 0, "dur_ns": 10},
                           {"name": "jit__start_local(9)", "start_ns": 20, "dur_ns": 10}])
    assert [o["module"] for o in ops] == ["jit_local", "jit__start_local", ""]


def test_nothing_to_read_gives_nothing():
    t = tr.DeviceTrace([ev(HOST, "window", 0, 10)], 0, 10)
    run = SimpleNamespace(trace=t, window=[0], window_batches=1)
    for reader in (route_kernel_ms, merge_ms, host_exposed_ms, device_idle_share):
        assert reader.read(run) is None


def test_recorded_trace():
    """A slice of a chip trace: every reduction gives a number, shares stay
    within 0-100 %, and the busy union is no longer than the window."""
    events = json.loads(DATA.read_text())
    t = tr.DeviceTrace(events, *tr.window_of(events))
    assert 0 < t.busy_s <= t.window_s
    run = SimpleNamespace(trace=t, window=[0], window_batches=1)
    assert route_kernel_ms.read(run) > 0 and merge_ms.read(run) > 0
    assert 0 <= host_exposed_ms.read(run) <= 1e3 * t.window_s
    assert 0 <= device_idle_share.read(run) <= 100
    assert t.idle_gaps() and t.top_ops()
