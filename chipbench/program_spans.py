"""Where a traced window's device-idle time goes, by the streaming job's own
host spans, and how long each device program ran.

    python chipbench/run.py --workload wc1-backlog --seed 7 --seconds 30 \\
        --trace 1 --trace-dir chiprun_out/trace-7
    python chipbench/program_spans.py chiprun_out/trace-7

``trace_reduce.load_xplane`` loads the benchmark's own host spans
(``trace_reduce.HOST_SPANS``) and no others.  The job writes one span per
batch (``stream.batch``) and one per phase of it (``PROGRAM_SPANS``, the
names of ``repro.core.streaming.SPANS``); this module loads those from the
same ``.xplane.pb`` file and reduces them with ``trace_reduce.DeviceTrace``.
It prints one JSON object, every time in ms per window batch:

* ``spans``: for each program span, its host time and the part of it in
  which no op holds the device (its exposed time);
* ``programs``: device time of each program (``jit_shuffle_start``,
  ``jit_local``, ...);
* ``host_exposed_ms``: the same reading as the metric of that name, and
  ``leaf_exposed_ms``, the device-idle time inside the union of the batch's
  leaf spans, which should come close to it;
* ``idle_gaps``: the longest idle gaps, each named by the innermost span,
  the program's or the benchmark's, that covers its midpoint.

A trace of a program that writes no spans gives empty ``spans``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import trace_reduce as tr  # noqa: E402

#: the streaming job's host spans (``repro.core.streaming.SPANS``)
PROGRAM_SPANS = (
    "stream.batch", "stream.feed", "stream.count_sync", "dr.observe", "dr.decide",
    "stream.drain", "dr.migrate", "dr.migrate.fetch", "dr.migrate.plan",
    "dr.migrate.start", "stream.account", "dr.resize", "dr.switch", "dr.lane",
    "dr.recover",
)
#: the spans that hold no other span of the job (a drain inside a migration's
#: fetch is the one exception, and its time counts once in the union)
LEAF_SPANS = tuple(s for s in PROGRAM_SPANS
                   if s not in ("stream.batch", "dr.migrate", "dr.resize"))


def load_program_spans(path: str) -> list[dict]:
    """The job's host spans in one ``.xplane.pb`` file, as plain records in
    ``trace_reduce.load_xplane``'s form."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != tr.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in PROGRAM_SPANS:
                    out.append({"plane": plane.name, "line": line.name, "name": ev.name,
                                "module": "", "start_ns": int(ev.start_ns),
                                "dur_ns": int(ev.duration_ns)})
    return out


def load(path: str) -> tr.DeviceTrace:
    """The traced window of one file, with the job's spans beside the
    benchmark's."""
    events = tr.load_xplane(path) + load_program_spans(path)
    return tr.DeviceTrace(events, *tr.window_of(events))


def breakdown(trace: tr.DeviceTrace, batches: int) -> dict:
    """Per-batch times in ms (see the module docstring)."""
    per = 1e3 / max(batches, 1)

    def host(names):
        return tr.union((s, e) for s, e, n in trace.spans if n in names)

    def exposed(intervals):
        return trace._mean({d: tr.length(tr.subtract(intervals, b)) / 1e9
                            for d, b in trace._busy.items()})

    spans = {}
    for name in PROGRAM_SPANS:
        if trace.span_count(name):
            spans[name] = {"count": trace.span_count(name),
                           "host_ms": per * tr.length(host((name,))) / 1e9,
                           "exposed_ms": per * trace.idle_within(name)}
    modules = sorted({o.module for ops in trace.ops.values() for o in ops})
    programs = {m: per * trace.op_seconds(lambda o, m=m: o.module == m) for m in modules}
    return {
        "batches": batches,
        "window_ms": 1e3 * trace.window_s,
        "busy_ms": per * trace.busy_s,
        "host_exposed_ms": per * trace.idle_within("job.process_batch"),
        "leaf_exposed_ms": per * exposed(host(LEAF_SPANS)),
        "spans": spans,
        "programs": dict(sorted(programs.items(), key=lambda kv: -kv[1])),
        "idle_gaps": trace.idle_gaps(),
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="a .xplane.pb file, or a directory holding one")
    args = ap.parse_args(argv)
    path = Path(args.trace)
    if path.is_dir():
        path = sorted(path.rglob("*.xplane.pb"))[-1]
    trace = load(str(path))
    batches = trace.span_count("job.process_batch") or trace.span_count("stream.batch")
    print(json.dumps(breakdown(trace, batches), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
