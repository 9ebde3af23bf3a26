"""Run one benchmark cell once on the chips of this machine.

    python chipbench/run.py --workload wc1-backlog --seed 7 --seconds 30 --trace 0

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a deployment
(``chipbench/configs/<config>.json``) and a traffic mix
(``chipbench/traffic/<traffic>.json``).  The run:

1. holds the allocator's thresholds fixed, and starts the traffic generator
   process (it never imports JAX);
2. builds the streaming job (``repro.core.streaming.StreamingJob``) on a
   ``data`` mesh of the cell's chips, with JAX's persistent compilation cache
   in the checkout;
3. warms up on the cell's own traffic: the prefill sweep, then the
   configuration's ``warmup_batches`` batches, then a drain;
4. measures: feeds batches for ``--seconds`` seconds, then drains the keyed
   state (with ``--trace 1`` the window is traced by the profiler);
5. reads the peak device memory, fetches the state, frees the job, and
   compares every key with the reference counts (``reference.py``);
6. prints what it found on earlier lines, the numbers compared beside their
   limits as the last lines of standard error, and one JSON object as the
   last line of standard output.

With ``--trace 0`` the object's metrics are the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics; each metric is read by
``chipbench/metrics/<name>.py``.  Without as many TPU chips as the cell asks
for, the run prints no result and exits with code 3.
"""
from __future__ import annotations

import argparse
import ctypes
import dataclasses
import gc
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path


def hold_allocator() -> bool:
    """Fix glibc's mmap and trim thresholds at 1 GiB, before numpy or JAX
    allocate.  Left dynamic, the mmap threshold follows what the process
    happened to free before the window, and with it how many of the host
    section's large temporaries fault in fresh pages every batch: the host's
    time per batch then differs from run to run by a quarter."""
    try:
        libc = ctypes.CDLL("libc.so.6")
    except OSError:
        return False
    m_trim_threshold, m_mmap_threshold = -1, -3
    return bool(libc.mallopt(m_mmap_threshold, 1 << 30)
                and libc.mallopt(m_trim_threshold, 1 << 30))


ALLOCATOR_HELD = hold_allocator()

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import reference  # noqa: E402
import source  # noqa: E402


def process_age_s() -> float:
    """Seconds since this process started (its set-up clock)."""
    try:
        ticks = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()[19])
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        return uptime - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED_AT


_IMPORTED_AT = time.perf_counter()



# -- the cell, as BENCHMARK.json and the files it names state it ----------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: source.Traffic
    end_to_end: list[dict]
    per_layer: list[dict]

    @classmethod
    def load(cls, name: str, bench_path: Path = ROOT / "BENCHMARK.json") -> "Cell":
        bench = json.loads(bench_path.read_text())
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
        w = cells[name]
        configs = {c["name"]: c for c in bench["configs"]}
        config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())

        def mine(metrics):
            return [m for m in metrics if name in m.get("workloads", [name])]

        return cls(name, int(w["chips"]), config, source.Traffic.load(w["traffic"]),
                   mine(bench["end_to_end"]), mine(bench["per_layer"]))


def peaks(device_kind: str, path: Path = HERE / "peaks.json") -> dict:
    """The published peaks of one chip; an unknown kind is an error."""
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path.name}")
    return table[device_kind]


# -- compile accounting ----------------------------------------------------

class CompileClock:
    """Programs XLA handed out (compiled, or loaded from the persistent
    cache) and the seconds that took, from jax's own monitoring events."""

    def __init__(self):
        import jax

        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += duration

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def reading(self) -> tuple[int, float, int]:
        return self.programs, self.seconds, self.cache_hits


# -- one run ---------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a run measured; the metric readers take their numbers from it."""

    cell: Cell
    seed: int
    setup_s: float = 0.0
    window_s: float = 0.0
    window_batches: int = 0
    batch_events: int = 0
    window: list = dataclasses.field(default_factory=list)   # BatchMetrics
    warmup: list = dataclasses.field(default_factory=list)
    compiles_in_window: int = 0
    heavy_slots: int = 0                                      # the route kernel's tables
    hosts: int = 0
    source_waited_s: float = 0.0
    trace: object = None                                      # DeviceTrace
    peaks: dict = dataclasses.field(default_factory=dict)
    memory_peak_bytes: int = 0
    keys_checked: int = 0                                     # keys compared

    @property
    def events(self) -> int:
        return self.window_batches * self.batch_events


def build_job(cell: Cell, devices):
    from repro.core.drm import DRConfig
    from repro.core.streaming import StreamingJob
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((cell.chips,), ("data",), devices=devices[:cell.chips])
    return StreamingJob(mesh=mesh, dr=DRConfig(**cell.config.get("dr", {})),
                        **cell.config["job"])


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             devices=None, trace_dir: str | None = None, log=print) -> tuple[Run, dict]:
    """Warm up, measure, drain and compare; returns the run and the checks."""
    import jax

    from repro.launch.cache import enable_compile_cache

    devices = devices or jax.devices()
    enable_compile_cache()
    # every program, however quick to compile, comes from the cache on the
    # next run of the cell: set-up then does the same work each time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    run = Run(cell, seed, batch_events=cell.traffic.batch_events(cell.chips))
    clock = CompileClock()
    src = source.SourceProcess(cell.traffic, seed, cell.chips)
    try:
        job = build_job(cell, devices)
        warm = cell.traffic.sweep_batches(cell.chips) + cell.config["warmup_batches"]
        with jax.profiler.TraceAnnotation("warmup"):
            for _ in range(warm):
                run.warmup.append(job.process_batch(src.next_batch()))
            jax.block_until_ready((job.state_keys, job.state_vals))
        before = clock.reading()
        waited = src.waited_s
        run.setup_s = process_age_s()
        log(f"setup: {run.setup_s:.3f} s; {warm} warm-up batches; programs "
            f"{before[0]} ({before[2]} from the cache, {before[1]:.3f} s); "
            f"allocator thresholds held: {ALLOCATOR_HELD}")

        tdir = trace_dir or (tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None)
        if trace:
            # host spans (TraceAnnotation) and device ops; no Python function
            # tracing, which would slow the host section the trace measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("window"):
            while time.perf_counter() - t0 < seconds:
                with jax.profiler.TraceAnnotation("source.next_batch"):
                    keys = src.next_batch()
                with jax.profiler.TraceAnnotation("job.process_batch"):
                    run.window.append(job.process_batch(keys))
            with jax.profiler.TraceAnnotation("job.drain"):
                jax.block_until_ready((job.state_keys, job.state_vals))
        run.window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
        after = clock.reading()
        run.window_batches = len(run.window)
        part = job.drm.partitioner
        run.heavy_slots, run.hosts = len(part.heavy_keys), part.num_hosts
        run.compiles_in_window = after[0] - before[0]
        run.source_waited_s = src.waited_s - waited
        log(f"window: {run.window_batches} batches, {run.events} events in "
            f"{run.window_s:.3f} s; programs in window {run.compiles_in_window} "
            f"({after[2] - before[2]} from the cache, {after[1] - before[1]:.3f} s); "
            f"waited for the generator {run.source_waited_s:.3f} s")
        walls = sorted(m.wall_time_s for m in run.window)
        log(f"batch walls: min {walls[0]:.4f} median {walls[len(walls) // 2]:.4f} "
            f"max {walls[-1]:.4f} s")

        used = devices[:cell.chips]
        run.memory_peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                                    for d in used)
        state_keys = np.asarray(job.state_keys)
        state_vals = np.asarray(job.state_vals)
        everything = run.warmup + run.window
        overflow = sum(m.overflow for m in everything)
        log("repartitions: warm-up {}, window {}; cross-chip migrations in window {}; "
            "actions in window {}".format(
                sum(m.repartitioned for m in run.warmup),
                sum(m.repartitioned for m in run.window),
                sum(m.repartitioned and m.relative_migration > 0 for m in run.window),
                sorted({m.action for m in run.window})))
        del job
        gc.collect()
        ref_counts = src.finish()
    finally:
        src.close()

    ids = source.population_ids(seed, cell.traffic.population, cell.traffic.id_range)
    checks, run.keys_checked = reference.compare(state_keys, state_vals, ids, ref_counts,
                                                 overflow)
    log(f"reference: {int(ref_counts.sum())} events fed, {int((ref_counts > 0).sum())} "
        f"distinct keys, max per-key count {int(ref_counts.max())} "
        f"(float32 counts are exact below {reference.F32_EXACT})")
    if trace:
        import trace_reduce

        files = sorted(Path(tdir).rglob("*.xplane.pb"))
        events = trace_reduce.load_xplane(str(files[-1]))
        run.trace = trace_reduce.DeviceTrace(events, *trace_reduce.window_of(events),
                                             devices=cell.chips)
        if trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)
    return run, checks


def read_metrics(run: Run, specs: list[dict]) -> dict:
    """Each metric from its reader; one that finds nothing is left out."""
    out = {}
    for spec in specs:
        reader = importlib.import_module(f"metrics.{spec['name']}")
        value = reader.read(run)
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out


def result_line(run: Run, checks: dict, trace: bool, devices) -> dict:
    metrics = read_metrics(run, run.cell.per_layer if trace else run.cell.end_to_end)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": reference.passed(checks), "attempted": run.keys_checked,
           "failed": int(checks["keys_wrong"]["value"]), "metrics": metrics,
           "device": device}
    if trace:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.top_ops(),
                            "idle_gaps": run.trace.idle_gaps()}
    out["checks"] = checks
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a temporary "
                         "directory, removed after the reduction)")
    args = ap.parse_args(argv)
    cell = Cell.load(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {devices}", file=sys.stderr)
        return 3
    chip_peaks = peaks(devices[0].device_kind)
    print(f"device: {devices[0].device_kind} x{len(devices)}; cell {cell.name} "
          f"({cell.config['name']}, traffic {cell.traffic.name}); seed {args.seed}",
          flush=True)
    run, checks = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           devices=devices, trace_dir=args.trace_dir,
                           log=lambda s: print(s, flush=True))
    run.peaks = chip_peaks
    line = result_line(run, checks, bool(args.trace), devices)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
