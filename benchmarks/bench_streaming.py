"""Fig. 6 — relative streaming-throughput increase from DR vs. Zipf
exponent, measured on the real micro-batch runtime (StreamingJob on the
local mesh; stateful count reducer, matching the paper's Flink setup).

Every skewed profile runs under both exchange backends: the dense
capacity-padded transport and the ragged count-first one.  Per backend the
CSV carries rows shipped + wall time (``fig6/exchange_*`` with a backend
column), the ragged rows must be strictly below the dense padded provision
on these power-law profiles, and the two backends must produce *exactly*
the same keyed-state counts — any mismatch raises, failing the bench run
(the CI bench-smoke gate).

The driver's sync audit gets its own row: a steady-state run must perform
no blocking device->host transfer between safe points
(``fig6/host_syncs_per_batch``, gated at 0).

Also measures the elastic-resize cost (rows shipped + wall time for a
grow 4->8 and a shrink 8->4, next to the plain migration rows) and the
control plane under *nonstationary* drift: a sudden hotspot flip, and a
sawtooth-skew workload with the resize-cooldown oscillation guard off vs.
on.  Every scenario row carries the decision log's taken/declined counts
(``fig6/decisions_*`` rows are the counts themselves).

The hot-key scenario (``fig6/split_decisions/*``) drives one key past a
worker's entire fair share — the regime where no repartition or resize can
balance (moving the key just moves the straggler).  The split profile must
reach imbalance <= the grow trigger while the no-split control stays above
it, and both must agree bit-for-bit on every key's aggregate (the split
run's scattered partials sum to the unsplit answer).

The topology scenario (``fig6/inter_host_rows/*``) runs the skewed stream
on a two-host profile — 8 lanes, 4 per host, in a subprocess with 8 forced
XLA host devices (device count must be fixed before jax init; the parent
bench process keeps its default) — under flat dense vs. the hierarchical
two-tier transport.  Both must agree bit-for-bit on the keyed state, the
per-class columns land in the CSV, and the hierarchical run must ship
*strictly fewer* inter-host rows than the flat dense pad (the CI gate).
``fig6/topology_decisions/*`` compares the control plane's recorded
decision trajectory locality-aware vs. locality-blind on one imbalanced
window: the 10x inter-host price must flip at least one candidate-plan
choice in the decision log."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np

from repro.compat import native_ragged
from repro.core.drm import DRConfig
from repro.core.streaming import StreamingJob
from repro.data.generators import drifting_zipf, hotspot_flip, sawtooth_skew, zipf_keys
from repro.exchange import resolve_backend

EXPONENTS = [1.0, 1.3, 1.6, 2.0]


def _worker_time(job_metrics, per_record_us=1.0, per_batch_overhead_us=2000.0):
    """Straggler-bound completion: batches gated by the most loaded worker."""
    t = 0.0
    for m in job_metrics:
        t += m.worker_imbalance * per_record_us + per_batch_overhead_us * 1e-3
    return t


SMOKE = dict(batches=3, batch_size=4_096)  # CI bench-smoke profile


def _assert_backend_equivalence(jobs: dict, stream: list[np.ndarray], exp: float):
    """Exact-count gate: dense and ragged runs must agree bit-for-bit on the
    keyed state (and on overflow totals).  A mismatch raises, which the
    bench harness turns into a FAILED row + nonzero exit."""
    all_keys = np.unique(np.concatenate(stream))
    sample = all_keys[:: max(1, len(all_keys) // 64)]
    for key in sample:
        got = {be: job.state_count(int(key)) for be, (job, _) in jobs.items()}
        if len(set(got.values())) != 1:
            raise AssertionError(
                f"backend count mismatch at exp={exp} key={int(key)}: {got}"
            )
    overflow = {be: sum(m.overflow for m in ms) for be, (_, ms) in jobs.items()}
    if len(set(overflow.values())) != 1:
        raise AssertionError(f"backend overflow mismatch at exp={exp}: {overflow}")


def run(batches: int = 6, batch_size: int = 16_384):
    rows = []
    state_capacity = 16_384
    wall_pairs: list[tuple[float, float]] = []  # (dense, ragged) wall per exp
    for exp in EXPONENTS:
        stream = list(drifting_zipf(batches, batch_size, num_keys=5_000,
                                    exponent=exp, drift_every=100, seed=int(exp * 7)))
        # the DR-on run under both exchange transports (identical results,
        # different traffic); DR-off once for the throughput-gain baseline
        jobs = {}
        for be in ("dense", "ragged"):
            job = StreamingJob(
                num_partitions=8,
                state_capacity=state_capacity,
                dr=DRConfig(imbalance_trigger=1.1, migration_cost_weight=0.2),
                exchange_backend=be,
            )
            # pin both runs to one migration-pricing rule: the equivalence
            # gate below asserts bit-identical state, which needs identical
            # control decisions — backend-specific pricing (the feature
            # test_repartition_cost_uses_host_backend covers) could
            # legitimately flip a gain-vs-cost call between the two runs
            job.drm.exchange_backend = resolve_backend("dense")
            ms = job.run(stream)
            jobs[be] = (job, ms)
            shipped = sum(m.shipped_rows for m in ms)
            padded = sum(m.padded_rows for m in ms)
            rows.append((f"fig6/exchange_rows/exp={exp}", shipped,
                         f"rows shipped over {batches} batches (provisioned {padded})",
                         be))
            rows.append((f"fig6/exchange_wall_ms/exp={exp}",
                         float(np.mean([m.wall_time_s for m in ms[1:]])) * 1e3,
                         "mean batch wall", be))
            # the exchange step alone (shuffle dispatch + collective +
            # reduce), batch 0 excluded (it pays the jit): the wall-clock
            # side of the rows-shipped story, per backend
            rows.append((f"fig6/exchange_step_wall_ms/exp={exp}",
                         float(np.mean([m.exchange_wall_s for m in ms[1:]])) * 1e3,
                         "mean exchange-path wall per batch", be))
        _assert_backend_equivalence(jobs, stream, exp)
        dense_padded = sum(m.padded_rows for m in jobs["dense"][1])
        ragged_shipped = sum(m.shipped_rows for m in jobs["ragged"][1])
        # count-first traffic tracks real rows: strictly below the padded
        # provision on every one of these power-law profiles
        assert ragged_shipped < dense_padded, (exp, ragged_shipped, dense_padded)
        wall_pairs.append((
            float(np.sum([m.exchange_wall_s for m in jobs["dense"][1][1:]])),
            float(np.sum([m.exchange_wall_s for m in jobs["ragged"][1][1:]])),
        ))

        job_off = StreamingJob(
            num_partitions=8,
            state_capacity=state_capacity,
            dr_enabled=False,
            dr=DRConfig(imbalance_trigger=1.1, migration_cost_weight=0.2),
        )
        ms_off = job_off.run(stream)
        job, ms = jobs["dense"]
        # throughput proxy: records / straggler-bound time
        imb_on = np.mean([m.imbalance for m in ms[1:]])
        imb_off = np.mean([m.imbalance for m in ms_off[1:]])
        mig_rows = sum(m.migration_rows for m in ms)
        reparts = sum(m.repartitioned for m in ms)
        gain = imb_off / imb_on - 1.0
        rows.append((f"fig6/throughput_gain/exp={exp}", gain,
                     "relative increase (paper: biggest at moderate exp)"))
        if reparts:
            # bounded exchange: rows shipped per repartition vs. the
            # full-state all-to-all (W * state_capacity rows per worker)
            full = job.num_workers * state_capacity
            rows.append((f"fig6/migration_rows_fraction/exp={exp}",
                         mig_rows / reparts / full,
                         f"{reparts} repartitions, full-state a2a = 1"))
    if native_ragged(job.mesh):
        # with the native collective the wall-clock must follow the rows:
        # ragged no slower than dense across the skewed profiles (aggregated
        # over all exponents; 25% headroom absorbs shared-CI timer noise)
        dense_wall = sum(d for d, _ in wall_pairs)
        ragged_wall = sum(r for _, r in wall_pairs)
        assert ragged_wall <= dense_wall * 1.25, (ragged_wall, dense_wall)
    rows.extend(_sync_free(batches, batch_size, state_capacity))
    rows.extend(_resize_cost(4, 8, batch_size, state_capacity))
    rows.extend(_resize_cost(8, 4, batch_size, state_capacity))
    rows.extend(_nonstationary(batches, batch_size, state_capacity))
    rows.extend(_auto_backend(batches, batch_size, state_capacity))
    rows.extend(_hot_key(batches, batch_size, state_capacity))
    rows.extend(_topology(batches, batch_size))
    rows.extend(_fault_free_identity(batches, batch_size, state_capacity))
    rows.extend(_failure(batches, batch_size))
    return rows


def _sync_free(batches: int, batch_size: int, state_capacity: int):
    """The CI sync-audit gate: a steady-state run (triggers parked,
    every safe point a noop) must perform *zero* audited host transfers
    between safe points — every device->host fetch in the driver goes
    through ``compat.host_fetch`` inside a declared ``safe_point`` region,
    so any stray blocking transfer shows up in the counter and fails the
    bench."""
    from repro import compat

    stream = list(drifting_zipf(max(4, batches), batch_size, num_keys=5_000,
                                exponent=1.3, drift_every=100, seed=3))
    job = StreamingJob(
        num_partitions=8,
        state_capacity=state_capacity,
        dr=DRConfig(imbalance_trigger=1e9),
    )
    job.run(stream[:2])  # warmup: compile + put a finish in flight
    compat.reset_host_sync_count()
    ms = job.run(stream[2:])
    syncs = compat.host_sync_count()
    assert syncs == 0, f"{syncs} host syncs outside safe points"
    assert all(m.action == "noop" for m in ms)
    return [("fig6/host_syncs_per_batch", syncs / max(len(ms), 1),
             f"audited transfers outside safe points over {len(ms)} steady "
             "batches (gate: 0)")]


def _decision_rows(tag: str, job: StreamingJob):
    """Decision-log columns: taken/declined counts for one scenario run."""
    taken, declined = job.drm.decisions.counts()
    return [
        (f"fig6/decisions_taken/{tag}", taken, "control-plane actions executed"),
        (f"fig6/decisions_declined/{tag}", declined, "declined safe points (reasons in log)"),
    ]


def _nonstationary(batches: int, batch_size: int, state_capacity: int):
    """Controller under nonstationary drift (not just static power-law).

    * ``hotspot_flip`` — the whole heavy set swaps identity mid-run; DR must
      re-trigger and re-isolate the new set (imbalance recovers toward the
      pre-flip level instead of staying pinned at the UHP ceiling).
    * ``sawtooth`` — imbalance flips across the grow/shrink triggers every
      half-period.  With the cooldown guard off the elastic policy
      ping-pongs the partition count; with it on (cooldown spanning the
      observation window) the same workload produces zero resize reversals
      — the declined resizes show up in the decision columns instead.
    """
    rows = []
    ticks = max(8, 2 * batches)

    # -- sudden hotspot flip under plain DR (no elastic) -------------------
    job = StreamingJob(
        num_partitions=8,
        state_capacity=state_capacity,
        dr=DRConfig(imbalance_trigger=1.15, migration_cost_weight=0.2),
    )
    ms = job.run(hotspot_flip(ticks, batch_size, num_keys=4_000, exponent=1.6, seed=5))
    flip = ticks // 2
    pre = float(np.mean([m.imbalance for m in ms[1:flip]]))
    post = float(np.mean([m.imbalance for m in ms[flip + 1:]]))
    rows.append(("fig6/hotspot_flip/imbalance_ratio", post / max(pre, 1e-9),
                 "mean imb after flip / before (1 = fully re-isolated)"))
    rows.extend(_decision_rows("hotspot_flip", job))

    # -- sawtooth skew: oscillation guard off vs. on -----------------------
    # plain DR stays on (it rebalances contents during the flat phase, so
    # the measured imbalance genuinely flips across the elastic triggers)
    for guard_on in (False, True):
        job = StreamingJob(
            num_partitions=4,
            state_capacity=state_capacity,
            dr=DRConfig(
                elastic=True, min_partitions=4, max_partitions=8,
                grow_trigger=2.0, shrink_trigger=1.45, resize_patience=1,
                resize_cooldown=ticks if guard_on else 0,
                imbalance_trigger=1.3, migration_cost_weight=0.05,
                sketch_decay=0.5,
            ),
        )
        ms = job.run(sawtooth_skew(ticks, batch_size, num_keys=2_000,
                                   exponent=1.8, period=3, seed=7))
        sizes = [m.num_partitions for m in ms if m.resized]
        prev = [4] + sizes[:-1]
        dirs = [s > p for s, p in zip(sizes, prev)]
        reversals = sum(1 for a, b in zip(dirs, dirs[1:]) if a != b)
        tag = "guard=on" if guard_on else "guard=off"
        rows.append((f"fig6/sawtooth_resize_reversals/{tag}", reversals,
                     f"{len(sizes)} resizes over {ticks} safe points"))
        rows.extend(_decision_rows(f"sawtooth_{tag}", job))
        if guard_on:
            # acceptance: the guard kills the ping-pong outright while the
            # initial grow-under-sustained-skew still fires
            assert reversals == 0, sizes
            assert sizes and sizes[0] == 8, sizes
    return rows


def _auto_backend(batches: int, batch_size: int, state_capacity: int):
    """The transport as an actuator: a generously padded job starts dense,
    the ``BackendPolicy`` watches the measured padding fraction stay low and
    flips it to ragged at a safe point.  The decision trajectory lands in
    the CSV (``fig6/backend_switches/*``) next to decisions_taken/declined,
    so the flip is visible output, not something to infer from row counts.
    """
    ticks = max(6, batches)
    job = StreamingJob(
        num_partitions=8,
        state_capacity=state_capacity,
        capacity_factor=4.0,  # generous pad: the lanes run ~25% full
        dr=DRConfig(imbalance_trigger=1e9, auto_backend=True,
                    backend_patience=2, backend_cooldown=4 * ticks),
    )
    ms = job.run(zipf_keys(batch_size, num_keys=4_000, exponent=1.2, seed=31 + t)
                 for t in range(ticks))
    switches = [(m.batch, m.backend) for m in ms if m.action == "switch_backend"]
    # the flip fires once (patience), lands on ragged, and never reverses
    # inside the cooldown — the oscillation guard, one actuator over
    assert len(switches) == 1, [m.action for m in ms]
    assert job.exchange_backend.name == "ragged", job.exchange_backend.name
    sw = switches[0][0]
    trajectory = "->".join(
        f"{m.backend}@{m.batch}" for m in ms if m.batch in (0, sw, sw + 1)
    )
    rows = [
        ("fig6/backend_switches/auto", len(switches), f"trajectory {trajectory}"),
        ("fig6/backend_switches/flip_batch", sw,
         f"padding fraction stayed under {job.drm.config.backend_ragged_below}"),
        ("fig6/backend_switches/post_flip_shipped_fraction",
         float(np.mean([m.shipped_rows / max(m.padded_rows, 1)
                        for m in ms[sw + 1:]])),
         "shipped/provisioned after the flip (dense = 1)"),
    ]
    rows.extend(_decision_rows("auto_backend", job))
    return rows


def _hot_key(batches: int, batch_size: int, state_capacity: int):
    """Hot-key splitting: one key carries ~40% of the stream — ~3.2 fair
    worker budgets on 8 partitions, so per-partition imbalance is pinned
    near ``share * N`` however the keys are binned.  With
    ``split_keys_enabled`` the SplitPolicy replicates the key (d = ceil of
    its budget share), the route kernels fan its records out, and the
    measured imbalance must drop under the elastic grow trigger — the load
    a resize would otherwise chase without ever balancing.  The no-split
    control (same stream, same DR otherwise) must stay above the trigger,
    and both runs must agree exactly on every key's aggregate: the split
    run's scattered partial aggregates sum to the unsplit answer."""
    ticks = max(10, 2 * batches)
    rng = np.random.default_rng(17)
    stream = []
    for _ in range(ticks):
        ks = rng.integers(100, 4100, size=batch_size).astype(np.int64)
        ks[rng.random(batch_size) < 0.40] = 7
        stream.append(ks)
    rows, jobs = [], {}
    tail_window = max(3, ticks // 3)  # post-split regime (split fires early)
    for tag, enabled in (("control", False), ("split", True)):
        job = StreamingJob(
            num_partitions=8,
            state_capacity=state_capacity,
            dr=DRConfig(split_keys_enabled=enabled, split_patience=1,
                        imbalance_trigger=1.15, migration_cost_weight=0.2),
        )
        ms = job.run(stream)
        jobs[tag] = (job, ms)
        tail = float(np.mean([m.imbalance for m in ms[-tail_window:]]))
        splits = sum(1 for m in ms if m.action in ("split", "unsplit"))
        rows.append((f"fig6/split_decisions/{tag}", splits,
                     f"split/unsplit actions taken ({max(m.split_keys for m in ms)}"
                     " keys replicated at peak)"))
        rows.append((f"fig6/split_imbalance/{tag}", tail,
                     f"mean measured imbalance, last {tail_window} batches"))
        rows.extend(_decision_rows(f"hot_key_{tag}", job))
    grow = jobs["split"][0].drm.config.grow_trigger
    tail = {tag: float(np.mean([m.imbalance for m in ms[-tail_window:]]))
            for tag, (_, ms) in jobs.items()}
    # acceptance: splitting balances what nothing else can — the split run
    # settles under the grow trigger, the control stays pinned above it
    assert jobs["split"][1][-1].split_keys >= 1, "the hot key never split"
    assert tail["split"] <= grow, tail
    assert tail["control"] > grow, tail
    # exactness: the scattered partials sum to the unsplit reference on
    # every sampled key (the combiner-side merge is a sum, bit-exact here)
    sample = np.unique(np.concatenate(stream))[::64]
    for key in sample:
        got = {tag: job.state_count(int(key)) for tag, (job, _) in jobs.items()}
        if len(set(got.values())) != 1:
            raise AssertionError(f"split count mismatch at key={int(key)}: {got}")
    return rows


_TOPOLOGY_SCRIPT = textwrap.dedent(
    """
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.core.drm import DRConfig
    from repro.core.streaming import StreamingJob
    from repro.data.generators import drifting_zipf
    from repro.exchange import ExchangeTopology

    batches, batch_size = int(sys.argv[1]), int(sys.argv[2])
    mesh = jax.make_mesh((8,), ("data",))
    # the two-host profile: 8 lanes, lanes 0-3 on host 0, 4-7 on host 1
    topo = ExchangeTopology(num_lanes=8, lanes_per_host=4)
    stream = list(drifting_zipf(batches, batch_size, num_keys=4_000,
                                exponent=1.4, drift_every=2,
                                drift_fraction=0.4, seed=13))
    out = {}
    jobs = {}
    for be in ("dense", "hierarchical"):
        job = StreamingJob(
            mesh=mesh, num_partitions=8, state_capacity=8_192,
            dr=DRConfig(imbalance_trigger=1.1, migration_cost_weight=0.1),
            exchange_backend=be, topology=topo,
        )
        ms = job.run(stream)
        jobs[be] = job
        out[be] = {
            "by_class": [int(x) for x in
                         np.sum([m.shipped_rows_by_class for m in ms], axis=0)],
            "shipped": int(sum(m.shipped_rows for m in ms)),
            "step_wall_ms": float(np.mean([m.exchange_wall_s for m in ms[1:]])) * 1e3,
            "actions": [m.action for m in ms],
            "overflow": int(sum(m.overflow for m in ms)),
            "inter_host_fraction": float(
                np.sum([m.shipped_rows_by_class[2] for m in ms])
                / max(sum(m.shipped_rows for m in ms), 1)),
        }
    # bit-identity gate: both transports, same keyed state, exactly
    sample = np.unique(np.concatenate(stream))[::64]
    for key in sample:
        got = {be: jobs[be].state_count(int(key)) for be in jobs}
        if len(set(got.values())) != 1:
            raise AssertionError(f"topology count mismatch key={int(key)}: {got}")
    print("TOPOLOGY-RESULT " + json.dumps(out))
    """
)


def _topology(batches: int, batch_size: int):
    """Two-host locality profile: flat dense vs. the hierarchical two-tier
    transport on 8 virtual CPU shards.  The child process is pinned to the
    CPU (``JAX_PLATFORMS=cpu``): the device count must be fixed before jax
    initializes, and a parent that holds an accelerator would keep it from
    the child — its walls are CPU simulations, not device times.  Emits
    per-class shipped rows + exchange wall per backend and gates on
    strictly fewer inter-host rows under the hierarchical transport; the
    decision-flip comparison runs in-process (host-side plan pricing needs
    no collective)."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _TOPOLOGY_SCRIPT, str(batches), str(batch_size)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    marker = "TOPOLOGY-RESULT "
    line = next((l for l in proc.stdout.splitlines() if l.startswith(marker)), None)
    if proc.returncode != 0 or line is None:
        raise AssertionError(
            f"two-host topology subprocess failed:\n{proc.stdout}\n{proc.stderr}"
        )
    out = json.loads(line[len(marker):])
    # identical control trajectories: the transport must not change the
    # control plane's view of the stream (same contract as dense-vs-ragged)
    if out["dense"]["actions"] != out["hierarchical"]["actions"]:
        raise AssertionError(f"transport changed the trajectory: {out}")
    if out["dense"]["overflow"] != out["hierarchical"]["overflow"]:
        raise AssertionError(f"overflow accounting diverged: {out}")
    rows = []
    for be in ("dense", "hierarchical"):
        r = out[be]
        rows.append((f"fig6/inter_host_rows/{be}", r["by_class"][2],
                     f"rows crossing the host boundary over {batches} batches "
                     f"(fraction {r['inter_host_fraction']:.3f})",
                     be, tuple(r["by_class"])))
        rows.append((f"fig6/cpu_sim/topology_exchange_step_wall_ms/{be}",
                     r["step_wall_ms"],
                     "mean exchange-path wall per batch (two-host profile, "
                     "8 virtual CPU devices: not a device time)",
                     be, tuple(r["by_class"])))
    # the CI gate: the two-tier exchange concentrates cross-host traffic
    # into the counted inter hop — strictly fewer inter-host rows than the
    # flat dense pad on this skewed profile
    d, h = out["dense"]["by_class"][2], out["hierarchical"]["by_class"][2]
    assert 0 < h < d, (h, d)
    rows.extend(_topology_decisions())
    return rows


def _topology_decisions():
    """Locality-aware vs. locality-blind control on identical windows: the
    same imbalanced signal sequence through two DRMasters, one carrying the
    two-host topology with the 10x inter-host price, one flat.  Both
    decision logs are recorded; the priced one must flip at least one
    choice (typically declining a repartition whose balance gain does not
    pay for cross-host state movement)."""
    from repro.control import Telemetry
    from repro.core.drm import DRMaster
    from repro.core.partitioner import uniform_partitioner
    from repro.exchange import ExchangeTopology

    rng = np.random.default_rng(29)
    keys = np.repeat(np.arange(64), rng.integers(1, 200, 64)).astype(np.int32)
    # every lane its own host: all cross-worker movement is inter-host,
    # priced 400x — the blind DRM sees the same plans at flat cost
    topo = ExchangeTopology(num_lanes=4, lanes_per_host=1,
                            class_weights=(0.0, 1.0, 400.0))
    logs = {}
    for tag, t in (("blind", None), ("aware", topo)):
        drm = DRMaster(
            uniform_partitioner(4, seed=0),
            DRConfig(imbalance_trigger=1.05, migration_cost_weight=1.0),
            exchange_topology=t,
        )
        for step in range(4):
            drm.observe(keys.reshape(1, -1),
                        np.ones((1, len(keys)), np.int32),
                        total_records=float(len(keys)))
            tel = Telemetry("bench")
            tel.record_batch(float(len(keys)))
            loads = np.bincount(
                drm.partitioner.lookup_np(keys), minlength=4
            ).astype(float)
            sig = tel.snapshot(loads=loads, num_workers=4, at_safe_point=True)
            drm.evaluate(sig)
        logs[tag] = [(r.kind, r.taken) for r in drm.decisions.records]
    flips = sum(1 for a, b in zip(logs["aware"], logs["blind"]) if a != b)
    taken = {tag: sum(1 for _, t in log if t) for tag, log in logs.items()}
    # acceptance: locality pricing flipped at least one recorded choice,
    # in the direction of moving less across hosts
    assert flips >= 1, logs
    assert taken["aware"] < taken["blind"], (taken, logs)
    return [
        ("fig6/topology_decisions/blind", taken["blind"],
         "actions taken with flat plan pricing (4 safe points)"),
        ("fig6/topology_decisions/aware", taken["aware"],
         "actions taken with 400x inter-host pricing (same windows)"),
        ("fig6/topology_decisions/flipped", flips,
         "safe points where locality pricing changed the recorded choice"),
    ]


_FAILURE_SCRIPT = textwrap.dedent(
    """
    import json, os, sys, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.core.drm import DRConfig
    from repro.core.streaming import StreamingJob
    from repro.data.generators import drifting_zipf
    from repro.exchange import FaultPlan, FaultyBackend, LaneFault

    batches, batch_size = int(sys.argv[1]), int(sys.argv[2])
    stream = list(drifting_zipf(batches, batch_size, num_keys=2_000,
                                exponent=1.3, drift_every=100, seed=0))
    total_records = float(sum(len(b) for b in stream))

    def run(backend=None):
        mesh = jax.make_mesh((8,), ("data",))
        kw = {"exchange_backend": backend} if backend is not None else {}
        job = StreamingJob(mesh=mesh, num_partitions=8, state_capacity=8_192,
                           dr=DRConfig(imbalance_trigger=1e9,
                                       snapshot_interval=3), **kw)
        ms = job.run(stream)
        return job, ms

    ref_job, _ = run()
    # kill lane 5 at exchange tick 4: one gap batch sits in the replay
    # buffer (snapshots refresh every 3 batches), so the recovery must
    # restore, replay the gap, and retry the lost batch on 7 workers
    plan = FaultPlan(faults=(LaneFault(4, 5, "kill"),))
    job, ms = run(FaultyBackend("dense", plan))
    assert len(job.recoveries) == 1, job.recoveries
    rec = job.recoveries[0]
    assert rec.kind == "evict", rec

    got = float(np.asarray(job.state_vals).sum())
    want = float(np.asarray(ref_job.state_vals).sum())
    assert want == total_records, (want, total_records)
    # exact per-key conservation, every key — the zero-loss claim
    all_keys = np.concatenate(stream)
    for key in np.unique(all_keys):
        a = job.state_count(int(key))
        b = float((all_keys == key).sum())
        assert a == b, (int(key), a, b)
    out = {
        "rows_lost": int(round(want - got)),
        "recovery_wall_ms": rec.wall_s * 1e3,
        "replayed": rec.replayed,
        "workers_after": rec.workers,
        "lane": rec.lane,
        "kills": job.exchange_backend.kills,
    }
    print("FAILURE-RESULT " + json.dumps(out))
    """
)


def _failure(batches: int, batch_size: int):
    """Kill-a-worker scenario (Fig 6 failure domain): 8 real shards, hard
    loss of lane 5 mid-stream, zero-loss recovery through the safe-point
    protocol — restore the auto-snapshot, replay the gap, resume on the
    shrunk 7-worker topology.  The child process is pinned to the CPU
    (``JAX_PLATFORMS=cpu``) with 8 virtual devices, as in :func:`_topology`:
    its recovery wall is a CPU simulation, not a device time.  Emits the recovery wall and the row-loss
    count; the CI smoke gate greps for ``fig6/rows_lost`` being exactly
    zero."""
    n = max(batches, 6)  # the kill tick needs stream to outlive it
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _FAILURE_SCRIPT, str(n), str(batch_size)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    marker = "FAILURE-RESULT "
    line = next((l for l in proc.stdout.splitlines() if l.startswith(marker)),
                None)
    if proc.returncode != 0 or line is None:
        raise AssertionError(
            f"kill-a-worker subprocess failed:\n{proc.stdout}\n{proc.stderr}"
        )
    out = json.loads(line[len(marker):])
    assert out["rows_lost"] == 0, out
    assert out["workers_after"] == 7, out
    assert out["kills"] == 1, out
    return [
        ("fig6/rows_lost", out["rows_lost"],
         f"rows lost across a hard loss of lane {out['lane']} "
         f"(protocol contract: exactly 0)"),
        ("fig6/cpu_sim/recovery_wall_ms", out["recovery_wall_ms"],
         f"restore + replay of {out['replayed']} gap batch(es) + retry "
         f"onto {out['workers_after']} surviving workers "
         f"(8 virtual CPU devices: not a device time)"),
    ]


def _fault_free_identity(batches: int, batch_size: int, state_capacity: int):
    """An installed, never-firing FaultPlan must be bit-identical to no
    seam at all (the seam fires at the host boundary; the traced program is
    untouched).  Runs in-process on the single-device mesh; the 8-shard
    version gates in tests/test_distributed.py."""
    from repro.exchange import FaultPlan, FaultyBackend

    stream = [zipf_keys(batch_size, num_keys=2_000, exponent=1.3, seed=s)
              for s in range(max(batches, 4))]
    acts = {}
    for tag, backend in (("plain", "dense"),
                         ("seamed", FaultyBackend("dense", FaultPlan()))):
        job = StreamingJob(
            num_partitions=8, state_capacity=state_capacity,
            dr=DRConfig(imbalance_trigger=1.1, migration_cost_weight=0.2),
            exchange_backend=backend,
        )
        ms = job.run(stream)
        acts[tag] = ([(m.action, m.reason, m.overflow, m.shipped_rows)
                      for m in ms],
                     float(np.asarray(job.state_vals).sum()))
    assert acts["plain"] == acts["seamed"], acts
    return [("fig6/fault_free_identity", 1,
             "never-firing FaultPlan bit-identical to no seam "
             "(trajectory + state mass)")]


def _resize_cost(base_n: int, target_n: int, batch_size: int, state_capacity: int):
    """Elastic-resize cost: exchange rows + wall time for one grow/shrink,
    under both exchange backends (the resize migration's sparse lanes are
    where the count-first transport pays off most).

    The resize batch pays the state migration *and* the shuffle-step rebuild
    (jit for the new lane count); a steady-state batch is reported alongside
    so the delta is visible."""
    rows = []
    tag = f"grow_{base_n}to{target_n}" if target_n > base_n else f"shrink_{base_n}to{target_n}"
    for be in ("dense", "ragged"):
        job = StreamingJob(
            num_partitions=base_n,
            state_capacity=state_capacity,
            dr=DRConfig(imbalance_trigger=1e9),  # isolate the resize: no plain DR
            exchange_backend=be,
        )
        warm = [zipf_keys(batch_size, num_keys=2_000, exponent=1.3, seed=s) for s in (20, 21)]
        for b in warm:
            steady = job.process_batch(b)
        job.resize(target_n)
        t0 = time.perf_counter()
        m = job.process_batch(zipf_keys(batch_size, num_keys=2_000, exponent=1.3, seed=22))
        wall_ms = (time.perf_counter() - t0) * 1e3
        assert m.resized, m.reason
        full = job.num_workers * state_capacity
        rows += [
            (f"fig6/resize_rows/{tag}", m.migration_rows,
             f"exchange buffer rows (plan {m.migration_plan_rows}; full-state a2a {full})",
             be),
            (f"fig6/resize_shipped_rows/{tag}", m.shipped_rows,
             "rows the backend measured moving on the resize batch", be),
            (f"fig6/resize_wall_ms/{tag}", wall_ms,
             f"resize batch incl. step rebuild (steady batch {steady.wall_time_s * 1e3:.1f} ms)",
             be),
        ]
    return rows
