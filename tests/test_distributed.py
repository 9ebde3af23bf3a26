"""Multi-device shuffle/migration correctness on 8 XLA host devices.

Runs in a subprocess because device count must be fixed before jax init
(the main test process keeps the default 1 CPU device).
"""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np, jax.numpy as jnp
    assert len(jax.devices()) == 8

    from repro.core import Histogram, kip_update, uniform_partitioner
    from repro.core.drm import DRConfig
    from repro.core.streaming import StreamingJob
    from repro.data.generators import drifting_zipf

    mesh = jax.make_mesh((8,), ("data",))
    job = StreamingJob(
        mesh=mesh, num_partitions=8, state_capacity=4096,
        dr=DRConfig(imbalance_trigger=1.1, migration_cost_weight=0.1),
    )
    batches = list(drifting_zipf(5, 8192, num_keys=2000, exponent=1.3,
                                 drift_every=100, seed=0))
    ms = job.run(batches)

    # 1. exact stateful aggregation across a real 8-way all_to_all
    all_keys = np.concatenate(batches)
    for key in np.unique(all_keys)[:10]:
        got = job.state_count(int(key))
        want = float((all_keys == key).sum())
        assert got == want, (key, got, want)

    # 2. DR fired and improved balance on the skewed stream
    assert any(m.repartitioned for m in ms), [m.reason for m in ms]
    assert ms[-1].imbalance < ms[0].imbalance

    # 3. each worker shard holds only keys the partitioner maps to it
    sk = np.asarray(job.state_keys)
    part = job.drm.partitioner
    for w in range(8):
        keys_w = sk[w][sk[w] != 2**31 - 1]
        if len(keys_w):
            assert np.all(part.lookup_np(keys_w.astype(np.int32)) % 8 == w)

    print("DISTRIBUTED-OK")
    """
)


@pytest.mark.slow
def test_shuffle_and_dr_on_8_devices():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), timeout=600,
    )
    assert "DISTRIBUTED-OK" in out.stdout, out.stdout + "\n" + out.stderr


RESIZE_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    from repro.core.drm import DRConfig
    from repro.core.hashing import KEY_SENTINEL
    from repro.core.streaming import StreamingJob
    from repro.data.generators import zipf_keys

    mesh = jax.make_mesh((4,), ("data",))
    job = StreamingJob(mesh=mesh, num_partitions=4, state_capacity=4096,
                       dr=DRConfig(imbalance_trigger=1e9))
    batches = [zipf_keys(8192, num_keys=1000, exponent=1.4, seed=s) for s in range(5)]
    job.process_batch(batches[0]); job.process_batch(batches[1])

    # grow 4->8 across a real 4-way all_to_all: state must physically move
    job.resize(8)
    m = job.process_batch(batches[2])
    assert m.resized and m.reason == "resize 4->8", m.reason
    assert m.overflow == 0, m.overflow
    assert m.relative_migration > 0  # cross-worker shipping actually happened
    assert m.migration_rows <= 4 * max(8, 2 * m.migration_plan_rows)

    job.resize(4)
    m = job.process_batch(batches[3])
    assert m.resized and m.reason == "resize 8->4", m.reason
    assert m.overflow == 0, m.overflow
    job.process_batch(batches[4])

    # exact per-key counts across both resizes
    all_keys = np.concatenate(batches)
    for key in np.unique(all_keys)[:10]:
        got, want = job.state_count(int(key)), float((all_keys == key).sum())
        assert got == want, (key, got, want)

    # each worker shard holds only keys the resized partitioner maps to it
    sk = np.asarray(job.state_keys)
    part = job.drm.partitioner
    for w in range(4):
        keys_w = sk[w][sk[w] != KEY_SENTINEL]
        if len(keys_w):
            assert np.all(part.lookup_np(keys_w.astype(np.int32)) % 4 == w)

    # ... and that shard lives on its own device: the stacked state is
    # sharded over `data`, one worker's table per device, not replicated
    for arr in (job.state_keys, job.state_vals):
        shards = sorted(arr.addressable_shards, key=lambda s: s.index[0].start)
        assert len(shards) == 4, len(shards)
        assert [s.data.shape[0] for s in shards] == [1] * 4
        assert len({s.device for s in shards}) == 4

    print("RESIZE-DISTRIBUTED-OK")
    """
)


@pytest.mark.slow
def test_elastic_resize_on_4_devices():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", RESIZE_SCRIPT], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), timeout=600,
    )
    assert "RESIZE-DISTRIBUTED-OK" in out.stdout, out.stdout + "\n" + out.stderr


BACKEND_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.core.drm import DRConfig
    from repro.core.streaming import StreamingJob
    from repro.data.generators import drifting_zipf

    mesh = jax.make_mesh((8,), ("data",))
    batches = list(drifting_zipf(5, 8192, num_keys=2000, exponent=1.5,
                                 drift_every=2, drift_fraction=0.4, seed=3))
    # three transports: dense, ragged (native ragged_all_to_all on
    # TPU meshes, masked dense elsewhere), and ragged with the native
    # collective force-disabled — on a TPU mesh that makes the run a real
    # native-vs-fallback bit-identity check across an 8-way all_to_all
    jobs = {}
    for be, force_fallback in (("dense", False), ("ragged", False),
                               ("ragged_fallback", True)):
        if force_fallback:
            os.environ["REPRO_DISABLE_NATIVE_RAGGED"] = "1"
        else:
            os.environ.pop("REPRO_DISABLE_NATIVE_RAGGED", None)
        job = StreamingJob(
            mesh=mesh, num_partitions=8, state_capacity=4096,
            dr=DRConfig(imbalance_trigger=1.05, migration_cost_weight=0.0),
            exchange_backend=be.split("_")[0],
        )
        jobs[be] = (job, job.run(batches))
    os.environ.pop("REPRO_DISABLE_NATIVE_RAGGED", None)

    # 1. backend equivalence across a real 8-way all_to_all: bit-identical
    #    keyed state (exact aggregation) and identical overflow accounting,
    #    native ragged path included
    all_keys = np.concatenate(batches)
    for key in np.unique(all_keys)[:32]:
        got = {be: job.state_count(int(key)) for be, (job, _) in jobs.items()}
        want = float((all_keys == key).sum())
        assert all(g == want for g in got.values()), (key, got, want)
    ov = {be: [m.overflow for m in ms] for be, (_, ms) in jobs.items()}
    assert ov["dense"] == ov["ragged"] == ov["ragged_fallback"], ov

    # 2. all backends repartitioned identically (same decisions, the
    #    transport must not change the control plane's view of the stream)
    acts = {be: [m.action for m in ms] for be, (_, ms) in jobs.items()}
    assert acts["dense"] == acts["ragged"] == acts["ragged_fallback"], acts
    assert any(m.repartitioned for m in jobs["dense"][1])

    # 3. the ragged transport moved strictly fewer rows than the dense pad,
    #    and the native path reports exactly the fallback's accounting
    shipped = {be: sum(m.shipped_rows for m in ms) for be, (_, ms) in jobs.items()}
    padded = {be: sum(m.padded_rows for m in ms) for be, (_, ms) in jobs.items()}
    assert shipped["dense"] == padded["dense"], (shipped, padded)
    assert shipped["ragged"] < padded["ragged"], (shipped, padded)
    assert shipped["ragged"] == shipped["ragged_fallback"], shipped
    print("BACKEND-EQUIVALENCE-OK", shipped, padded)
    """
)


@pytest.mark.slow
def test_backend_equivalence_on_8_devices():
    """Dense vs ragged on 8 real shards: bit-identical state, fewer rows."""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", BACKEND_SCRIPT], capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))), timeout=600,
    )
    assert "BACKEND-EQUIVALENCE-OK" in out.stdout, out.stdout + "\n" + out.stderr


OVERLAP_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro import compat
    from repro.core.drm import DRConfig
    from repro.core.hashing import KEY_SENTINEL
    from repro.core.streaming import StreamingJob
    from repro.data.generators import drifting_zipf

    mesh = jax.make_mesh((8,), ("data",))
    batches = list(drifting_zipf(6, 8192, num_keys=2000, exponent=1.4,
                                 drift_every=2, drift_fraction=0.4, seed=7))
    # a skewed stream through the split-phase driver, across a real 8-way
    # all_to_all, with migrations whose ship stays in flight
    job = StreamingJob(
        mesh=mesh, num_partitions=8, state_capacity=4096,
        dr=DRConfig(imbalance_trigger=1.1, migration_cost_weight=0.1),
    )
    ms = job.run(batches)
    assert any(m.repartitioned for m in ms)
    assert all(m.overflow == 0 for m in ms), [m.overflow for m in ms]

    # 1. exact keyed state after draining the in-flight finish: every
    #    key's aggregate equals np.bincount over every event fed
    want = np.bincount(np.concatenate(batches))
    sk, sv = np.asarray(job.state_keys), np.asarray(job.state_vals)
    live = sk != KEY_SENTINEL
    got = np.zeros(len(want))
    np.add.at(got, sk[live], sv[live][:, 0])
    assert np.array_equal(got, want.astype(float)), np.flatnonzero(got != want)

    # 2. the hidden phase was actually measured
    assert job.telemetry.wall_ewma.get("dense", 0.0) > 0.0

    # 3. steady state is sync-free on real shards: noop batches after the
    #    warmup perform zero audited host transfers
    calm = StreamingJob(mesh=mesh, num_partitions=8, state_capacity=4096,
                        dr=DRConfig(imbalance_trigger=1e9))
    calm.run(batches[:2])  # warmup: compile + put a finish in flight
    compat.reset_host_sync_count()
    ms_c = calm.run(batches[2:])
    assert compat.host_sync_count() == 0, compat.host_sync_count()
    assert all(m.action == "noop" for m in ms_c)
    print("OVERLAP-OK")
    """
)


@pytest.mark.slow
def test_overlap_matches_serial_on_8_devices():
    """The split-phase driver on 8 real shards: exact counts, sync-free."""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", OVERLAP_SCRIPT], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert "OVERLAP-OK" in out.stdout, out.stdout + "\n" + out.stderr


HIERARCHICAL_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.core.drm import DRConfig
    from repro.core.streaming import StreamingJob
    from repro.data.generators import drifting_zipf
    from repro.exchange import ExchangeSpec, ExchangeTopology, Payload, make_exchange
    from repro.exchange.backends import _two_hop_a2a

    mesh = jax.make_mesh((8,), ("data",))

    # 0. the collective itself: the two-tier (intra-host, then inter-host)
    #    all_to_all must equal the flat tiled all_to_all bit for bit, and be
    #    its own inverse (the backhaul reuses the forward permutation)
    x = jnp.arange(8 * 8 * 4, dtype=jnp.int32).reshape(8, 8, 4)
    def body(x):
        flat = jax.lax.all_to_all(x[0], "data", 0, 0, tiled=True)
        two = _two_hop_a2a(x[0], "data", num_hosts=2, lanes_per_host=4)
        back = _two_hop_a2a(two, "data", num_hosts=2, lanes_per_host=4)
        return flat[None], two[None], back[None]
    flat, two, back = shard_map(
        body, mesh=mesh, in_specs=(P("data"),),
        out_specs=(P("data"), P("data"), P("data")), check_vma=False,
    )(x)
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(two))
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))

    # two hosts x four lanes: lanes 0-3 on host 0, lanes 4-7 on host 1
    topo = ExchangeTopology(num_lanes=8, lanes_per_host=4)
    batches = list(drifting_zipf(5, 8192, num_keys=2000, exponent=1.5,
                                 drift_every=2, drift_fraction=0.4, seed=3))
    jobs = {}
    for name, kw in (
        ("flat", dict(exchange_backend="dense")),
        ("dense", dict(exchange_backend="dense", topology=topo)),
        ("hier", dict(exchange_backend="hierarchical", topology=topo)),
    ):
        job = StreamingJob(
            mesh=mesh, num_partitions=8, state_capacity=4096,
            dr=DRConfig(imbalance_trigger=1.05, migration_cost_weight=0.0),
            **kw,
        )
        jobs[name] = (job, job.run(batches))

    # 1. bit-identity across a real two-tier exchange: exact aggregation,
    #    identical overflow, identical control-plane decisions
    all_keys = np.concatenate(batches)
    for key in np.unique(all_keys)[:32]:
        got = {n: job.state_count(int(key)) for n, (job, _) in jobs.items()}
        want = float((all_keys == key).sum())
        assert all(g == want for g in got.values()), (key, got, want)
    ov = {n: [m.overflow for m in ms] for n, (_, ms) in jobs.items()}
    assert ov["flat"] == ov["dense"] == ov["hier"], ov
    acts = {n: [m.action for m in ms] for n, (_, ms) in jobs.items()}
    assert acts["flat"] == acts["dense"] == acts["hier"], acts
    assert any(m.repartitioned for m in jobs["flat"][1])

    # 2. per-class accounting: the flat job reports no classes; the
    #    topology jobs' classes sum to the scalar; hierarchical ships
    #    strictly fewer inter-host rows than the flat dense pad
    assert all(m.shipped_rows_by_class == (0, 0, 0) for m in jobs["flat"][1])
    by = {n: np.sum([m.shipped_rows_by_class for m in ms], axis=0)
          for n, (_, ms) in jobs.items() if n != "flat"}
    tot = {n: sum(m.shipped_rows for m in ms) for n, (_, ms) in jobs.items()}
    for n in ("dense", "hier"):
        assert by[n].sum() == tot[n], (n, by[n], tot[n])
    assert by["hier"][2] < by["dense"][2], by
    assert by["hier"][2] > 0, by  # rows did cross the host boundary
    assert jobs["hier"][0].telemetry.snapshot(
        loads=np.ones(8)).inter_host_fraction < 0.5

    print("HIERARCHICAL-OK", dict(tot), {n: v.tolist() for n, v in by.items()})
    """
)


@pytest.mark.slow
def test_hierarchical_backend_on_8_devices():
    """Two-tier exchange on 8 real shards (2 hosts x 4 lanes): bit-identical
    state + overflow, strictly fewer inter-host rows than flat dense."""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", HIERARCHICAL_SCRIPT], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert "HIERARCHICAL-OK" in out.stdout, out.stdout + "\n" + out.stderr


MOE_BACKHAUL_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.configs.base import MoESpec
    from repro.models.modules import Policy
    from repro.moe.layer import init_moe, moe_ref, moe_apply
    from jax import set_mesh
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    spec = MoESpec(num_experts=8, top_k=2, d_ff_expert=32, shared_expert=False,
                   capacity_factor=8.0)  # generous: nothing drops
    d = 16
    p = init_moe(jax.random.PRNGKey(0), d, spec, "swiglu", jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, d))
    inv = jnp.arange(8, dtype=jnp.int32)
    want = moe_ref(p, x, spec, "swiglu", Policy(), inv)

    got = {}
    for be in ("dense", "ragged"):
        pol = Policy(mesh=mesh, dp_axes=("data",), tp_axis="model",
                     exchange_backend=be)
        with set_mesh(mesh):
            xs = jax.device_put(x, NamedSharding(mesh, P("data", "model", None)))
            ps = dict(jax.device_put(p, NamedSharding(mesh, P())))
            ps["wi"] = jax.device_put(p["wi"], NamedSharding(mesh, P("model")))
            ps["wo"] = jax.device_put(p["wo"], NamedSharding(mesh, P("model")))
            got[be] = jax.jit(
                lambda pp, xx, pol=pol: moe_apply(pp, xx, spec, "swiglu", pol, inv)
            )(ps, xs)

    # bit-identity across a real 4-way dispatch + backhaul: the ragged
    # combine (count-reusing return trip, native collective on TPU meshes)
    # must match the dense pad exactly, and both match the oracle
    np.testing.assert_array_equal(np.asarray(got["dense"].y),
                                  np.asarray(got["ragged"].y))
    np.testing.assert_allclose(np.asarray(got["dense"].y), np.asarray(want.y),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got["dense"].counts),
                                  np.asarray(got["ragged"].counts))
    assert float(got["dense"].overflow) == float(got["ragged"].overflow) == 0.0
    # both directions measured: ragged < the dense round-trip pad
    sd, sr = int(got["dense"].shipped_rows), int(got["ragged"].shipped_rows)
    assert 0 < sr < sd, (sr, sd)
    print("MOE-BACKHAUL-OK", sr, sd)
    """
)


@pytest.mark.slow
def test_moe_ragged_backhaul_on_8_devices():
    """MoE dispatch + ragged combine backhaul vs dense on real shards."""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", MOE_BACKHAUL_SCRIPT], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert "MOE-BACKHAUL-OK" in out.stdout, out.stdout + "\n" + out.stderr


FAULT_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    assert len(jax.devices()) == 8

    from repro.core.drm import DRConfig
    from repro.core.streaming import StreamingJob
    from repro.data.generators import drifting_zipf
    from repro.exchange import FaultPlan, FaultyBackend, LaneFault

    batches = list(drifting_zipf(8, 8192, num_keys=2000, exponent=1.3,
                                 drift_every=100, seed=0))
    all_keys = np.concatenate(batches)
    probe = np.unique(all_keys)[:10]

    def run(dr, backend=None):
        mesh = jax.make_mesh((8,), ("data",))
        kw = {"exchange_backend": backend} if backend is not None else {}
        job = StreamingJob(mesh=mesh, num_partitions=8, state_capacity=4096,
                           dr=dr, **kw)
        ms = job.run(batches)
        return job, ms

    def traj(ms):
        return [(m.action, m.reason, m.overflow, m.shipped_rows) for m in ms]

    # 1. never-firing identity: an installed FaultPlan that never fires is
    #    bit-identical to no seam at all, and both count exactly
    dr = lambda: DRConfig(imbalance_trigger=1.1, migration_cost_weight=0.1)
    ref_job, ref_ms = run(dr())
    seam_job, seam_ms = run(dr(), FaultyBackend("dense", FaultPlan()))
    assert traj(ref_ms) == traj(seam_ms), (traj(ref_ms), traj(seam_ms))
    for key in probe:
        want = float((all_keys == key).sum())
        assert ref_job.state_count(int(key)) == \\
            seam_job.state_count(int(key)) == want, key

    # 2. kill a worker mid-stream: recover via restore + replay onto the
    #    shrunk topology with zero rows lost
    ref_job, _ = run(DRConfig(imbalance_trigger=1e9))
    plan = FaultPlan(faults=(LaneFault(4, 5, "kill"),))
    job, ms = run(DRConfig(imbalance_trigger=1e9, snapshot_interval=3),
                  FaultyBackend("dense", plan))
    assert len(job.recoveries) == 1, job.recoveries
    rec = job.recoveries[0]
    assert rec.kind == "evict" and rec.lane == 5, rec
    assert job.num_workers == 7
    assert ms[-1].lanes == 7
    for key in probe:
        got = job.state_count(int(key))
        want = float((all_keys == key).sum())
        assert got == want, (key, got, want)
    # survivors hold only keys the partitioner folds onto them
    sk = np.asarray(job.state_keys)
    part = job.drm.partitioner
    for w in range(7):
        keys_w = sk[w][sk[w] != 2**31 - 1]
        if len(keys_w):
            assert np.all(part.lookup_np(keys_w.astype(np.int32)) % 7 == w)

    print("FAULTS-OK")
    """
)


@pytest.mark.slow
@pytest.mark.chaos
def test_fault_recovery_on_8_devices():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", FAULT_SCRIPT], capture_output=True, text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=900,
    )
    assert "FAULTS-OK" in out.stdout, out.stdout + "\n" + out.stderr
