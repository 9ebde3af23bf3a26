"""MoE: routing, KIP placement, and dispatch-vs-oracle equivalence."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import MoESpec
from repro.models.modules import Policy
from repro.moe.kip_placement import (
    ExpertPlacement,
    PlacementController,
    apply_placement_to_weights,
    placement_from_assignment,
)
from repro.moe.layer import init_moe, moe_ref


def test_moe_ref_shapes_and_counts():
    spec = MoESpec(num_experts=8, top_k=2, d_ff_expert=32, shared_expert=True)
    p = init_moe(jax.random.PRNGKey(0), 16, spec, "swiglu", jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
    out = moe_ref(p, x, spec, "swiglu", Policy())
    assert out.y.shape == x.shape
    assert float(out.counts.sum()) == 2 * 8 * 2  # T * top_k
    assert np.isfinite(float(out.aux_loss))


class TestPlacement:
    def test_identity(self):
        pl = ExpertPlacement.identity(8, 4)
        np.testing.assert_array_equal(pl.place, np.arange(8))
        np.testing.assert_array_equal(pl.shard_of(np.arange(8)), np.arange(8) // 2)

    def test_controller_balances_skewed_loads(self):
        ctl = PlacementController(16, 4, trigger=1.05)
        loads = np.ones(16)
        loads[0], loads[1] = 20.0, 15.0  # two hot experts on shard 0
        for _ in range(3):
            ctl.observe(loads)
        before = ctl.shard_loads(ctl.loads_ewma)
        changed, placement, perm = ctl.maybe_update()
        after = ctl.shard_loads(ctl.loads_ewma)
        assert changed
        assert after.max() / after.mean() < before.max() / before.mean()
        # placement is a proper permutation with exactly E/N slots per shard
        assert sorted(placement.place.tolist()) == list(range(16))
        shards = placement.inv_place // 4
        assert np.bincount(shards, minlength=4).tolist() == [4, 4, 4, 4]

    def test_migration_minimal_when_balanced(self):
        ctl = PlacementController(16, 4, trigger=1.15)
        ctl.observe(np.ones(16))
        changed, _, perm = ctl.maybe_update()
        assert not changed
        np.testing.assert_array_equal(perm, np.arange(16))

    def test_weight_permutation_follows_placement(self):
        spec = MoESpec(num_experts=8, top_k=1, d_ff_expert=8, shared_expert=False)
        p = init_moe(jax.random.PRNGKey(0), 4, spec, "swiglu", jnp.float32)
        perm = np.array([3, 1, 2, 0, 4, 5, 6, 7], np.int32)
        p2 = apply_placement_to_weights(p, perm)
        np.testing.assert_allclose(np.asarray(p2["wi"][0]), np.asarray(p["wi"][3]))
        np.testing.assert_allclose(np.asarray(p2["wo"][3]), np.asarray(p["wo"][0]))
        np.testing.assert_allclose(np.asarray(p2["router"]), np.asarray(p["router"]))

    def test_repeated_updates_converge(self):
        rng = np.random.default_rng(0)
        ctl = PlacementController(32, 8, trigger=1.1)
        loads = rng.zipf(1.5, 32).astype(float)
        total_moved = 0
        for _ in range(6):
            ctl.observe(loads)
            changed, _, perm = ctl.maybe_update()
            total_moved += int((perm != np.arange(32)).sum())
        # after converging, further updates move nothing
        ctl.observe(loads)
        changed, _, perm = ctl.maybe_update()
        assert int((perm != np.arange(32)).sum()) == 0


DISPATCH_SCRIPT = textwrap.dedent(
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
    spec = MoESpec(num_experts=8, top_k=2, d_ff_expert=32, shared_expert=True,
                   capacity_factor=8.0)  # generous: nothing drops
    d = 16
    p = init_moe(jax.random.PRNGKey(0), d, spec, "swiglu", jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, d))
    inv = jnp.arange(8, dtype=jnp.int32)

    pol_ref = Policy()
    want = moe_ref(p, x, spec, "swiglu", pol_ref, inv)

    pol = Policy(mesh=mesh, dp_axes=("data",), tp_axis="model")
    with set_mesh(mesh):
        xs = jax.device_put(x, NamedSharding(mesh, P("data", "model", None)))
        ps = jax.device_put(p, NamedSharding(mesh, P()))
        ps["wi"] = jax.device_put(p["wi"], NamedSharding(mesh, P("model")))
        ps["wo"] = jax.device_put(p["wo"], NamedSharding(mesh, P("model")))
        got = jax.jit(lambda pp, xx: moe_apply(pp, xx, spec, "swiglu", pol, inv))(ps, xs)

    np.testing.assert_allclose(np.asarray(got.y), np.asarray(want.y), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got.counts), np.asarray(want.counts))
    assert float(got.overflow) == 0.0
    # skewed placement: put the two hottest experts on the same shard, then
    # verify a permuted placement still matches the oracle exactly
    perm = jnp.asarray([7, 1, 2, 3, 4, 5, 6, 0], jnp.int32)
    inv2 = jnp.zeros(8, jnp.int32).at[perm].set(jnp.arange(8, dtype=jnp.int32))
    from repro.moe.kip_placement import apply_placement_to_weights
    with set_mesh(mesh):
        p3 = dict(ps)
        p3["wi"] = jnp.take(ps["wi"], perm, axis=0)
        p3["wo"] = jnp.take(ps["wo"], perm, axis=0)
        got2 = jax.jit(lambda pp, xx: moe_apply(pp, xx, spec, "swiglu", pol, inv2))(p3, xs)
    np.testing.assert_allclose(np.asarray(got2.y), np.asarray(want.y), rtol=2e-5, atol=2e-5)
    print("MOE-DISPATCH-OK")
    """
)


@pytest.mark.slow
def test_dispatch_matches_oracle_on_8_devices():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", DISPATCH_SCRIPT], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert "MOE-DISPATCH-OK" in out.stdout, out.stdout + "\n" + out.stderr


class TestReplication:
    def test_replicated_assignment_beats_partitioning_floor(self):
        """A 30%-load expert caps pure partitioning at N*f1; replication
        splits it below the floor (the beyond-paper serving feature)."""
        from repro.moe.kip_placement import replicated_assignment

        loads = np.ones(16)
        loads[0] = 8.0  # ~33% of traffic on one expert -> floor ~5.3 @ 16 shards
        owner, shard_of = replicated_assignment(loads, n_shards=8, replicas=8)
        assert len(owner) == 24 and sorted(set(owner.tolist())) == list(range(16))
        counts = np.bincount(owner, minlength=16)
        assert counts[0] >= 3  # the hot expert got extra replicas
        rel = loads / loads.sum()
        eff = (rel / counts)[owner]
        sl = np.zeros(8)
        np.add.at(sl, shard_of, eff)
        floor_unreplicated = 8 * rel.max()
        assert sl.max() / sl.mean() < floor_unreplicated
        # every shard has exactly 3 slots
        assert np.bincount(shard_of, minlength=8).tolist() == [3] * 8
