"""Bring-up smoke run: the DR streaming job on TPU, end to end.

    python chip_smoke.py              # one chip (the default)
    python chip_smoke.py --chips 4    # the cross-worker path on four chips

One chip: the compiled route kernels are checked bit for bit against their
jnp twins, then a keyed word-count job (``StreamingJob``, 64 logical
partitions, 2^22 state slots, DR on) takes 8 micro-batches of 2^20 events
from a drifting Zipf stream (2^22 keys, exponent 1.2, drift every 3
batches); partway through it is snapshotted and restored into a fresh job.
Every per-key aggregate must equal a numpy ``np.add.at`` reference over all
events fed, with zero overflow and at least one repartition taken.

Four chips (``--chips 4``): the same stream runs on a 4-device ``data``
mesh through the dense and the native ragged all-to-all; both must match
the reference exactly with zero overflow, and a repartition must move
state across workers.

The run needs a TPU: on any other platform it exits non-zero before doing
anything.  Its last line of output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Times are
wall-clock seconds after ``block_until_ready`` on the job's state; they are
bring-up figures for one run, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))


def make_stream(*, batches: int, batch_size: int, num_keys: int, seed: int):
    from repro.data.generators import drifting_zipf

    return list(drifting_zipf(batches, batch_size, num_keys=num_keys,
                              exponent=1.2, drift_every=3, seed=seed))


def check_kernels(*, seed: int) -> str:
    """The compiled route kernels equal their jnp twins bit for bit on a
    skewed stream: the two-pass path's ``lookup_dispatch`` (with the
    split-key replica pick on) and the fused ``route_bucketize``."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import Histogram, kip_update, uniform_partitioner
    from repro.data.generators import zipf_keys
    from repro.kernels import ops, ref

    n, lanes, cap, parts = 16_384, 4, 1024, 64
    stream = zipf_keys(n, num_keys=1 << 14, exponent=1.2, seed=seed)
    kip = kip_update(uniform_partitioner(parts), Histogram.exact(stream).top(128))
    kip = kip.with_splits({int(kip.heavy_keys[0]): 3})
    t = kip.tables()
    keys = jnp.asarray(stream.astype(np.int32))
    valid = jnp.asarray(np.random.default_rng(seed).random(n) < 0.9)
    vals = jnp.asarray(np.random.default_rng(seed + 1).normal(size=(n, 1)), jnp.float32)
    common = dict(seed=kip.seed, num_hosts=kip.num_hosts, num_lanes=lanes)

    got = ops.route_slots(keys, valid, t, num_partitions=parts, **common)
    want = ref.lookup_dispatch_ref(
        keys, valid, t.heavy_keys, t.heavy_parts, t.host_to_part,
        heavy_repl=t.heavy_repl, num_partitions=parts, **common)
    for name, g, w in zip(("part", "slot", "counts"), got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w)), f"lookup_dispatch {name}"

    got = ops.route_bucketize(keys, valid, t, vals, capacity=cap, key_fill=2**31 - 1,
                              **common)
    want = ref.route_bucketize_ref(
        keys, valid, vals, t.heavy_keys, t.heavy_parts, t.host_to_part,
        capacity=cap, key_fill=2**31 - 1, **common)
    names = ("part", "slot", "counts", "buf_valid", "buf_keys", "buf_vals", "buf_part")
    for name, g, w in zip(names, got, want):
        g, w = np.asarray(g), np.asarray(w)
        if name == "part":  # sentinel-padded heavy rows: compare valid records
            g, w = np.where(np.asarray(valid), g, 0), np.where(np.asarray(valid), w, 0)
        assert np.array_equal(g, w), f"route_bucketize {name}"
    return f"{n} records, {lanes} lanes, capacity {cap}"


class CompileClock:
    """Seconds XLA spent compiling, from jax's own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def run_stream(stream, *, mesh, backend: str, state_capacity: int,
               capacity_factor: float, clock: CompileClock,
               restore_at: int | None = None):
    """Feed ``stream`` batch by batch; with ``restore_at`` the job is
    snapshotted after that many batches and a fresh job restored from the
    snapshot takes the rest.  Returns the final job, its batch metrics and
    per-batch ``(wall seconds, compile seconds)``."""
    import jax

    from repro.core.drm import DRConfig
    from repro.core.streaming import StreamingJob

    def new_job():
        return StreamingJob(mesh=mesh, num_partitions=64, state_capacity=state_capacity,
                            payload_dim=1, capacity_factor=capacity_factor,
                            dr=DRConfig(), exchange_backend=backend, seed=0)

    job, metrics, walls = new_job(), [], []
    for i, batch in enumerate(stream):
        if i == restore_at:
            snap = job.snapshot()
            job = new_job()
            job.restore(snap)
        c0, t0 = clock.seconds, time.perf_counter()
        metrics.append(job.process_batch(batch))
        jax.block_until_ready((job.state_keys, job.state_vals))
        walls.append((time.perf_counter() - t0, clock.seconds - c0))
    return job, metrics, walls


def check_state(job, stream) -> int:
    """Every per-key aggregate equals ``np.add.at`` over all events fed."""
    import numpy as np

    from repro.core.hashing import KEY_SENTINEL

    events = np.concatenate(stream)
    ref_keys, inverse = np.unique(events, return_inverse=True)
    ref_vals = np.zeros(len(ref_keys))
    np.add.at(ref_vals, inverse, 1.0)

    sk = np.asarray(job.state_keys).reshape(-1)
    sv = np.asarray(job.state_vals).reshape(-1)
    live = sk != KEY_SENTINEL
    got_keys, where = np.unique(sk[live], return_inverse=True)
    got_vals = np.zeros(len(got_keys))
    np.add.at(got_vals, where, sv[live].astype(np.float64))
    assert np.array_equal(got_keys, ref_keys), (
        f"state holds {len(got_keys)} keys, reference {len(ref_keys)}")
    bad = np.nonzero(got_vals != ref_vals)[0]
    assert len(bad) == 0, (
        f"{len(bad)} keys disagree, e.g. key {got_keys[bad[0]]}: "
        f"{got_vals[bad[0]]} vs {ref_vals[bad[0]]}")
    return int(live.sum())


def summarize(tag: str, metrics, walls) -> dict:
    steady = [w for w, c in walls if c == 0.0]
    out = {
        "route_path": sorted({m.route_path for m in metrics}),
        "transport": sorted({m.transport for m in metrics}),
        "repartitions": sum(m.repartitioned for m in metrics),
        "cross_worker_migrations": sum(m.repartitioned and m.relative_migration > 0
                                       for m in metrics),
        "overflow": sum(m.overflow for m in metrics),
        "compile_s": sum(c for _, c in walls),
        "steady_s_per_batch": statistics.median(steady) if steady else None,
        "steady_batches": len(steady),
        "batch_walls_s": [w for w, _ in walls],
    }
    print(f"[{tag}] " + json.dumps(out), flush=True)
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the streaming job on one chip; 4: the cross-worker "
                         "path (dense vs native ragged) on a 4-chip mesh")
    ap.add_argument("--seed", type=int, default=0, help="stream seed")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), JAX found {devices}",
              file=sys.stderr)
        return 2

    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_mesh

    print("compile cache:", enable_compile_cache())
    print("devices:", devices)
    print("device kind:", devices[0].device_kind, "count:", len(devices))
    clock = CompileClock()
    stream = make_stream(batches=8, batch_size=1 << 20, num_keys=1 << 22, seed=args.seed)
    mesh = make_mesh((args.chips,), ("data",), devices=devices[:args.chips])

    if args.chips == 1:
        print("kernels == jnp twins:", check_kernels(seed=args.seed), flush=True)
        job, metrics, walls = run_stream(
            stream, mesh=mesh, backend="dense", state_capacity=1 << 22,
            capacity_factor=2.0, clock=clock, restore_at=4)
        out = summarize("1 chip", metrics, walls)
        rows = check_state(job, stream)
        assert out["route_path"] == ["two-pass kernel"], out["route_path"]
        assert out["repartitions"] >= 1, "no repartition fired"
        print(f"snapshot/restore after batch 4; state rows {rows}; "
              f"per-key aggregates == numpy reference")
    else:
        # lanes sized for the whole local batch: overflow cannot hide a fault
        for backend, transport in (("dense", "dense"), ("ragged", "ragged/native")):
            job, metrics, walls = run_stream(
                stream, mesh=mesh, backend=backend, state_capacity=1 << 22,
                capacity_factor=4.0, clock=clock)
            out = summarize(f"4 chips {backend}", metrics, walls)
            rows = check_state(job, stream)
            assert out["transport"] == [transport], out["transport"]
            assert out["cross_worker_migrations"] >= 1, "no state crossed workers"
            print(f"{backend}: state rows {rows}; per-key aggregates == numpy reference")
    assert out["overflow"] == 0, out["overflow"]
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0) for d in devices[:args.chips])
    print("peak_bytes_in_use:", peak, flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
