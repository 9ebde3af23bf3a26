"""From a profiler trace to the numbers the per-layer metrics read.

``load_xplane`` flattens the ``.xplane.pb`` file that ``jax.profiler`` writes
into plain event records (plane, line, name, module, start, duration in ns).
Everything else here works on those records alone, so the tests can feed it
a small recorded trace.

Device activity is read from each device plane's op lines.  An op on the
"XLA Ops" line holds its device from its start to its end; one on the
"Async XLA Ops" line (a copy or collective in flight) does not.  Each op is
named by its HLO instruction (``%fusion.3``) and the program it ran in, the
"XLA Modules" event that holds it (``jit_local``).  ``DeviceTrace`` clips the
ops to the traced window and answers, per device and averaged over the
devices used:

* busy seconds: the union of the intervals of ops that hold the device, so
  overlapping ops count once;
* seconds of the ops a predicate selects (a kernel, a program, a collective);
* exposed seconds of selected ops: the part of their union during which no
  other op holds that device;
* seconds of a host span during which no op holds the device;
* the longest idle gaps, each named by the innermost host span of the
  benchmark's own (``TraceAnnotation``) that covers the gap's midpoint.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Callable, Iterable

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINES = ("XLA Ops", "Async XLA Ops")  # the second: async copies, collectives
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
#: the host spans the benchmark itself writes (see run.py); "window" marks
#: the traced window, the others name what the host was doing
HOST_SPANS = ("window", "warmup", "source.next_batch", "job.process_batch", "job.drain")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str    # the HLO instruction's name, e.g. "%fusion.3"
    module: str  # the program it ran in, e.g. "jit_local"
    line: str    # OPS_LINES[0] for ops that hold the device, else async
    start: int   # ns
    end: int     # ns


def short_name(hlo_text: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``%fusion.3``."""
    return hlo_text.split(" = ", 1)[0].strip()


def _with_modules(ops: list[dict], modules: list[dict]) -> None:
    """Name each op's program: the module event on its device that holds
    the op's start (a device runs one program at a time)."""
    modules = sorted(modules, key=lambda m: m["start_ns"])
    starts = [m["start_ns"] for m in modules]
    for op in ops:
        i = bisect.bisect_right(starts, op["start_ns"]) - 1
        if i >= 0 and op["start_ns"] < modules[i]["start_ns"] + modules[i]["dur_ns"]:
            op["module"] = modules[i]["name"].split("(", 1)[0]


def load_xplane(path: str) -> list[dict]:
    """Plain event records of one ``.xplane.pb`` file: device ops, each with
    its short name and the program it ran in, and the host spans named in
    ``HOST_SPANS``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name)
        if not device and plane.name != HOST_PLANE:
            continue
        ops, modules = [], []
        for line in plane.lines:
            if device and line.name not in OPS_LINES + (MODULES_LINE,):
                continue
            for ev in line.events:
                if not device and ev.name not in HOST_SPANS:
                    continue
                rec = {"plane": plane.name, "line": line.name,
                       "name": short_name(ev.name) if device else ev.name,
                       "module": "", "start_ns": int(ev.start_ns),
                       "dur_ns": int(ev.duration_ns)}
                if line.name == MODULES_LINE:
                    rec["name"] = ev.name
                    modules.append(rec)
                elif device:
                    ops.append(rec)
                else:
                    out.append(rec)
        _with_modules(ops, modules)
        out += ops
    return out


def union(intervals: Iterable[tuple[int, int]]) -> list[tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def length(intervals: Iterable[tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: list[tuple[int, int]], b: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """``a`` minus ``b``, both disjoint and sorted."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def window_of(events: list[dict]) -> tuple[int, int]:
    """The traced window: the host span named "window"."""
    spans = [ev for ev in events if ev["name"] == "window" and ev["plane"] == HOST_PLANE]
    if len(spans) != 1:
        raise ValueError(f"expected one 'window' span in the trace, found {len(spans)}")
    return spans[0]["start_ns"], spans[0]["start_ns"] + spans[0]["dur_ns"]


class DeviceTrace:
    """Device ops and host spans of one traced window ``[t0, t1)`` (ns)."""

    def __init__(self, events: list[dict], t0: int, t1: int, devices: int | None = None):
        self.t0, self.t1 = t0, t1
        self.ops: dict[int, list[Op]] = {}
        self.spans: list[tuple[int, int, str]] = []
        for ev in events:
            s = max(ev["start_ns"], t0)
            e = min(ev["start_ns"] + ev["dur_ns"], t1)
            if e <= s:
                continue
            m = DEVICE_PLANE.match(ev["plane"])
            if m:
                self.ops.setdefault(int(m.group(1)), []).append(
                    Op(ev["name"], ev.get("module", ""), ev.get("line", OPS_LINES[0]), s, e))
            elif ev["name"] != "window":
                self.spans.append((s, e, ev["name"]))
        ids = sorted(self.ops)
        self.devices = ids[:devices] if devices else ids
        self._busy = {d: union((o.start, o.end) for o in self.ops.get(d, [])
                               if o.line == OPS_LINES[0])
                      for d in self.devices}

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def _mean(self, per_device: dict[int, float]) -> float:
        return sum(per_device.values()) / max(len(per_device), 1)

    @property
    def busy_s(self) -> float:
        """Seconds in which some op held the device (async copies and
        collectives in flight alone do not), averaged over the devices."""
        return self._mean({d: length(b) / 1e9 for d, b in self._busy.items()})

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, select: Callable[[Op], bool]) -> float:
        """Seconds the selected ops cover (their union), averaged over devices."""
        return self._mean({d: length(union((o.start, o.end) for o in self.ops.get(d, [])
                                           if select(o))) / 1e9
                           for d in self.devices})

    def op_count(self, select: Callable[[Op], bool]) -> float:
        """Selected ops per device, averaged."""
        return self._mean({d: float(sum(select(o) for o in self.ops.get(d, [])))
                           for d in self.devices})

    def exposed_seconds(self, select: Callable[[Op], bool]) -> float:
        """Seconds of the selected ops during which no other op holds
        their device, averaged over the devices."""
        out = {}
        for d in self.devices:
            ops = self.ops.get(d, [])
            chosen = union((o.start, o.end) for o in ops if select(o))
            others = union((o.start, o.end) for o in ops
                           if not select(o) and o.line == OPS_LINES[0])
            out[d] = length(subtract(chosen, others)) / 1e9
        return self._mean(out)

    def span_count(self, name: str) -> int:
        """Host spans of that name in the window."""
        return sum(n == name for _, _, n in self.spans)

    def idle_within(self, name: str) -> float:
        """Seconds of the host spans of that name during which no op holds
        the device, averaged over the devices."""
        host = union((s, e) for s, e, n in self.spans if n == name)
        return self._mean({d: length(subtract(host, b)) / 1e9 for d, b in self._busy.items()})

    def top_ops(self, k: int = 10) -> list[list]:
        """The ``k`` ops (``program/op``) that held the device longest,
        seconds summed over the window and averaged over the devices."""
        total: dict[str, float] = {}
        for d in self.devices:
            for o in self.ops.get(d, []):
                if o.line == OPS_LINES[0]:
                    key = f"{o.module}/{o.name}"
                    total[key] = total.get(key, 0.0) + (o.end - o.start) / 1e9
        n = max(len(self.devices), 1)
        return [[name, s / n] for name, s in
                sorted(total.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """The ``k`` longest idle gaps of the first device, each named by the
        innermost host span covering its midpoint ("host.other" if none)."""
        if not self.devices:
            return []
        idle = subtract([(self.t0, self.t1)], self._busy[self.devices[0]])
        idle.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in idle[:k]:
            mid = (s + e) // 2
            covering = [(ss, ee, n) for ss, ee, n in self.spans if ss <= mid < ee]
            name = min(covering, key=lambda c: c[1] - c[0])[2] if covering else "host.other"
            out.append([name, (e - s) / 1e9])
        return out
