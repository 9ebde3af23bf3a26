"""Two-tier exchange topology: distance classes, locality pricing, and the
hierarchical backend's accounting.

The real two-hop collective runs on 8 shards in ``tests/test_distributed.py``
(``test_hierarchical_backend_on_8_devices``); here the single-device suite
covers everything host-side — the :class:`ExchangeTopology` tables, spec
resize survival, the per-class accounting stamped by every backend, the
locality-priced plan cost (and the decision it flips), telemetry folding,
and snapshot round-trips.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.control import Telemetry
from repro.core.drm import DRConfig, DRMaster
from repro.core.migration import MigrationPlan, exchange_lane_cost
from repro.core.partitioner import uniform_partitioner
from repro.core.streaming import StreamingJob
from repro.exchange import (
    ExchangeSpec,
    ExchangeStats,
    ExchangeTopology,
    HierarchicalBackend,
    Payload,
    make_exchange,
    resolve_backend,
)
from repro.exchange.spec import DISTANCE_CLASSES, _class_tables
from repro.launch.mesh import exchange_topology_of


# ---------------------------------------------------------------------------
# ExchangeTopology: distance-class tables
# ---------------------------------------------------------------------------


def test_topology_class_tables():
    topo = ExchangeTopology(num_lanes=8, lanes_per_host=4)
    assert topo.num_hosts == 2
    cm = topo.class_matrix
    assert cm.shape == (8, 8)
    # diagonal = self, same host block = intra, rest = inter
    np.testing.assert_array_equal(np.diag(cm), np.zeros(8))
    assert cm[0, 3] == 1 and cm[4, 7] == 1       # same host
    assert cm[0, 4] == 2 and cm[7, 0] == 2       # across hosts
    # per-lane class histogram: 1 self + 3 intra + 4 inter, rows sum to L
    counts = topo.class_lane_counts
    np.testing.assert_array_equal(counts, np.tile([1, 3, 4], (8, 1)))
    np.testing.assert_array_equal(counts.sum(axis=1), np.full(8, 8))
    # the onehot refines the histogram
    np.testing.assert_array_equal(topo.class_onehot.sum(axis=2), counts)


def test_topology_weight_matrix_and_resize():
    topo = ExchangeTopology(num_lanes=8, lanes_per_host=4,
                            class_weights=(0.0, 1.0, 10.0))
    wm = topo.weight_matrix()
    assert wm[0, 0] == 0.0 and wm[0, 1] == 1.0 and wm[0, 4] == 10.0
    # resize keeps the host width: 8/4 -> 4 lanes is one host (all intra)
    small = topo.resized(4)
    assert small.num_hosts == 1
    assert small.weight_matrix().max() == 1.0
    # and a cross-size weight matrix can be asked for directly (the plan
    # pricing folds to worker granularity, which may differ from num_lanes)
    assert topo.weight_matrix(4).shape == (4, 4)


def test_topology_tables_are_cached_and_frozen():
    """The hoisted class tables are computed once per (L, G) and shared —
    jitted steps close over them instead of rebuilding per trace — and are
    write-protected so nothing can corrupt the shared constant."""
    a = _class_tables(8, 4)
    assert a is _class_tables(8, 4)
    with pytest.raises(ValueError):
        a[0][0, 0] = 7


def test_spec_resized_rederives_topology():
    topo = ExchangeTopology(num_lanes=8, lanes_per_host=4)
    spec = ExchangeSpec(num_lanes=8, capacity=32, axis="data", topology=topo)
    grown = spec.resized(num_lanes=16)
    assert grown.topology.num_lanes == 16
    assert grown.topology.lanes_per_host == 4
    assert grown.topology.num_hosts == 4
    shrunk = spec.resized(num_lanes=4)
    assert shrunk.topology.num_hosts == 1
    # re-capacitating does not disturb the topology
    assert spec.resized(capacity=64).topology == topo
    # a flat spec stays flat
    assert ExchangeSpec(8, 32, axis="data").resized(num_lanes=4).topology is None


def test_spec_snaps_mismatched_topology():
    """Constructing a spec with a stale lane count on the topology snaps it
    to the spec's — the resize path hands the old topology straight in."""
    topo = ExchangeTopology(num_lanes=8, lanes_per_host=4)
    spec = ExchangeSpec(num_lanes=16, capacity=8, axis="data", topology=topo)
    assert spec.topology.num_lanes == 16
    assert spec.topology.lanes_per_host == 4


def test_exchange_topology_of_mesh():
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    # single-process mesh: no process boundary to read -> one host
    topo = exchange_topology_of(mesh)
    assert topo.num_lanes == mesh.shape["data"]
    assert topo.lanes_per_host == topo.num_lanes and topo.num_hosts == 1
    # modeled boundary + custom pricing thread through
    topo = exchange_topology_of(mesh, lanes_per_host=1,
                                class_weights=(0.0, 2.0, 5.0))
    assert topo.num_hosts == mesh.shape["data"]
    assert topo.class_weights == (0.0, 2.0, 5.0)


# ---------------------------------------------------------------------------
# locality-priced plan cost
# ---------------------------------------------------------------------------


def _plan_moving(src: int, dst: int, rows: float, n: int = 4) -> MigrationPlan:
    transfer = np.zeros((n, n))
    transfer[src, dst] = rows
    return MigrationPlan(
        keys=np.zeros(1, np.int64), src=np.array([src], np.int32),
        dst=np.array([dst], np.int32), weights=np.array([rows]),
        transfer=transfer, relative_migration=0.1, num_src=n, num_dst=n,
    )


def test_exchange_lane_cost_topology_flips_plan_choice():
    """Two candidate plans, flat pricing preferring the wrong one: B moves
    slightly less mass but across the host boundary.  The locality price
    (10x inter-host) flips the ordering — the decision the policies gate on.
    """
    topo = ExchangeTopology(num_lanes=4, lanes_per_host=2)
    plan_a = _plan_moving(0, 1, rows=100.0)   # intra-host
    plan_b = _plan_moving(0, 2, rows=90.0)    # inter-host
    flat = {p: exchange_lane_cost(pl, num_workers=4)
            for p, pl in (("a", plan_a), ("b", plan_b))}
    priced = {p: exchange_lane_cost(pl, num_workers=4, topology=topo)
              for p, pl in (("a", plan_a), ("b", plan_b))}
    assert flat["b"] < flat["a"]        # flat: fewer rows wins
    assert priced["a"] < priced["b"]    # priced: intra-host wins
    # self-traffic is free under the topology too
    assert exchange_lane_cost(_plan_moving(1, 1, 50.0), topology=topo) == 0.0


def test_repartition_policy_sees_host_topology():
    """The policy stack prices with the DRM's installed topology: the same
    imbalanced window costs more to fix when every move crosses hosts, so
    the all-inter topology declines a repartition the intra one takes."""
    rng = np.random.default_rng(0)
    keys = np.repeat(np.arange(64), rng.integers(1, 200, 64))
    loads = np.bincount(uniform_partitioner(4, seed=0).lookup_np(
        keys.astype(np.int32)), minlength=4).astype(float)
    decisions = {}
    for name, weights in (("cheap", (0.0, 1.0, 1.0)), ("dear", (0.0, 1e6, 1e6))):
        topo = ExchangeTopology(num_lanes=4, lanes_per_host=1,
                                class_weights=weights)
        drm = DRMaster(
            uniform_partitioner(4, seed=0),
            DRConfig(imbalance_trigger=1.05, migration_cost_weight=1.0),
            exchange_topology=topo,
        )
        drm.observe(keys.reshape(1, -1).astype(np.int32),
                    np.ones((1, len(keys)), np.int32))
        t = Telemetry("t")
        t.record_batch(float(len(keys)))
        sig = t.snapshot(loads=loads, num_workers=4, at_safe_point=True)
        decisions[name] = drm.evaluate(sig)
    assert decisions["cheap"].taken, decisions["cheap"].reason
    assert not decisions["dear"].taken, decisions["dear"].reason


# ---------------------------------------------------------------------------
# per-class accounting on the backends (single device: 1-lane collectives
# and the bucketize layer; the 8-shard split is in test_distributed.py)
# ---------------------------------------------------------------------------


def _run_with_topology(backend, topo, lane, valid, vals, capacity):
    mesh = jax.make_mesh((1,), ("data",))
    ex = make_exchange(
        ExchangeSpec(num_lanes=topo.num_lanes, capacity=capacity, axis="data",
                     topology=topo),
        backend,
    )

    def body(lane, valid, vals):
        res = ex(lane, valid, [Payload(vals, -1.0)])
        va, (v,) = res.unpack()
        return va[None], v[None], res.shipped_rows, res.shipped_rows_by_class

    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data")),
        out_specs=(P("data"), P("data"), P(), P()),
        check_vma=False,
    )
    va, v, shipped, by = mapped(lane, valid, vals)
    return np.asarray(va), np.asarray(v), int(shipped), np.asarray(by)


@pytest.mark.parametrize("backend", ["dense", "ragged", "hierarchical"])
def test_by_class_sums_to_scalar_and_rows_bit_identical(backend):
    """Every backend's per-class split refines its own scalar shipped_rows
    (identical sum), while the unpacked rows stay bit-identical to dense —
    the PR 4 contract extended by the class axis."""
    rng = np.random.default_rng(7)
    n, capacity = 128, 64
    topo = ExchangeTopology(num_lanes=4, lanes_per_host=2)
    lane = rng.integers(0, 4, n).astype(np.int32)
    valid = rng.random(n) < 0.9
    vals = rng.normal(size=(n,)).astype(np.float32)
    args = (jnp.asarray(lane), jnp.asarray(valid), jnp.asarray(vals), capacity)
    va, v, shipped, by = _run_with_topology(backend, topo, *args)
    ref_va, ref_v, _, _ = _run_with_topology("dense", topo, *args)
    np.testing.assert_array_equal(va, ref_va)
    np.testing.assert_array_equal(v, ref_v)
    assert by.shape == (DISTANCE_CLASSES,)
    assert int(by.sum()) == shipped, (by, shipped)


def test_flat_spec_stamps_no_classes():
    """Without a topology the result carries no per-class split — stats()
    then leaves ``rows_by_class`` None and nothing downstream changes."""
    ex = make_exchange(ExchangeSpec(num_lanes=3, capacity=4))
    res = ex(jnp.asarray([0, 1, 2], jnp.int32), jnp.ones(3, bool),
             [Payload(jnp.arange(3, dtype=jnp.float32), 0)])
    assert res.shipped_rows_by_class is None
    assert res.stats().rows_by_class is None


def test_resolve_backend_knows_hierarchical():
    assert isinstance(resolve_backend("hierarchical"), HierarchicalBackend)
    assert resolve_backend("hierarchical").name == "hierarchical"


def test_hierarchical_plan_fallback_conditions():
    be = HierarchicalBackend()
    topo = ExchangeTopology(num_lanes=8, lanes_per_host=4)
    assert be._plan(ExchangeSpec(8, 4, axis="data", topology=topo)) is None  # 1 device
    assert be._plan(ExchangeSpec(8, 4, axis="data")) is None                # no topo
    one_host = ExchangeTopology(num_lanes=8, lanes_per_host=8)
    assert be._plan(ExchangeSpec(8, 4, axis="data", topology=one_host)) is None


# ---------------------------------------------------------------------------
# telemetry + snapshots
# ---------------------------------------------------------------------------


def test_telemetry_folds_rows_by_class_into_signals():
    t = Telemetry("test")
    t.record_exchange(ExchangeStats(rows=30, rows_by_class=np.array([10, 10, 10])))
    t.record_exchange(ExchangeStats(rows=6, rows_by_class=np.array([2, 2, 2])))
    t.record_exchange(ExchangeStats(rows=0))  # class-less record folds fine
    s = t.snapshot(loads=np.ones(3))
    np.testing.assert_array_equal(s.exchange_rows_by_class, [12, 12, 12])
    assert s.inter_host_fraction == pytest.approx(12 / 36)
    # a flat window has no class split and a well-defined zero fraction
    s2 = Telemetry("flat").snapshot(loads=np.ones(3))
    assert s2.exchange_rows_by_class is None
    assert s2.inter_host_fraction == 0.0


def test_drm_snapshot_roundtrips_topology():
    topo = ExchangeTopology(num_lanes=4, lanes_per_host=2,
                            class_weights=(0.0, 2.0, 7.0))
    drm = DRMaster(uniform_partitioner(4, seed=0), DRConfig(),
                   exchange_topology=topo)
    snap = drm.snapshot()
    restored = DRMaster.restore(snap, DRConfig())
    assert restored.exchange_topology == topo
    # flat DRMs write no topology keys (legacy snapshot byte-stability)
    flat_snap = DRMaster(uniform_partitioner(4, seed=0), DRConfig()).snapshot()
    assert not any(k.startswith("topology_") for k in flat_snap)
    assert DRMaster.restore(flat_snap, DRConfig()).exchange_topology is None


def test_streaming_snapshot_carries_topology():
    topo = ExchangeTopology(num_lanes=1, lanes_per_host=1)
    job = StreamingJob(state_capacity=512, topology=topo)
    job.process_batch(np.arange(64, dtype=np.int64))
    snap = job.snapshot()
    fresh = StreamingJob(state_capacity=512)  # built flat
    fresh.restore(snap)
    assert fresh.exchange_topology == topo
    assert fresh.drm.exchange_topology == topo
    m = fresh.process_batch(np.arange(64, dtype=np.int64))
    assert sum(m.shipped_rows_by_class) == m.shipped_rows
