"""Migration lanes sized from the route counts on the device.

The job routes its state under the new partitioner on the device and
fetches only the ``[W, W]`` counts of rows each worker sends to each other
worker; the lane size follows from their peak.  On 2 and 4 forced CPU host
devices, across KIP re-plans from Zipf histograms and one resize 4 -> 8,
each migration must size its lanes exactly as the host plan over the same
state would (``migration_capacity(plan_migration(...))``), report that
plan's peak worker-to-worker transfer, drop no row, and leave the table bit
for bit as the migrate step called whole (routed in its start program, full
lanes) leaves it: each worker holds exactly the live keys the new
partitioner homes on it, summed, sorted and packed (the layout
``merge_into`` writes).  On one device no migration runs (no lane, no
route), and the table must still be what the whole step leaves.  The
counts after the last batch equal ``np.bincount`` over every event fed.

Runs in a subprocess because the device count must be fixed before jax
starts (the main test process keeps the default 1 CPU device).
"""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os, sys
    W = int(sys.argv[1])
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={W}"
    import jax, numpy as np
    from repro.core.drm import DRConfig
    from repro.core.hashing import KEY_SENTINEL
    from repro.core.migration import fold_to_workers, migration_capacity, plan_migration
    from repro.core.streaming import StreamingJob
    from repro.data.generators import drifting_zipf

    STATE = 2048
    assert len(jax.devices()) == W

    def homed_tables(sk, sv, part):
        # every live row on the worker its key's new home partition maps to,
        # one row per key (partials summed), sorted, packed, sentinel-padded
        live = sk != KEY_SENTINEL
        keys, vals = sk[live], sv[live]
        home = part.lookup_np(keys.astype(np.int32)) % W
        out_k = np.full(sk.shape, KEY_SENTINEL, sk.dtype)
        out_v = np.zeros(sv.shape, sv.dtype)
        for w in range(W):
            u, inv = np.unique(keys[home == w], return_inverse=True)
            s = np.zeros((len(u),) + sv.shape[2:], sv.dtype)
            np.add.at(s, inv, vals[home == w])
            out_k[w, :len(u)], out_v[w, :len(u)] = u, s
        return out_k, out_v

    def run():
        job = StreamingJob(
            mesh=jax.make_mesh((W,), ("data",)), num_partitions=4, state_capacity=STATE,
            dr=DRConfig(imbalance_trigger=1.05, migration_cost_weight=0.0),
        )
        prev, expect = [job.drm.partitioner], []
        migrate_state = job._migrate_state

        def spy(**kw):
            # the pre-action drain already merged the batch: the host plan
            # over this state is what the parent job sized its lanes from
            sk, sv = np.asarray(job._sk), np.asarray(job._sv)
            new = job.drm.partitioner
            plan = plan_migration(prev[0], new, sk[sk != KEY_SENTINEL].astype(np.int64))
            folded = fold_to_workers(plan.transfer, W)
            np.fill_diagonal(folded, 0.0)
            whole, _ = job._migrate_step(STATE)  # the tables in, not a route
            kk, vv, rk, rv, rva = whole(new.tables(), job._sk, job._sv)[:5]
            whole_k, whole_v = job._merge(kk, vv, rk, rv, rva)
            # one worker sizes no lane: a migration there runs no step
            cap = migration_capacity(plan, num_workers=W) if W > 1 else 0
            expect.append((cap, int(folded.max()),
                           homed_tables(sk, sv, new),
                           (np.asarray(whole_k), np.asarray(whole_v))))
            return migrate_state(**kw)

        job._migrate_state = spy
        batches = list(drifting_zipf(7, 2048, num_keys=600, exponent=1.3,
                                     drift_every=2, seed=W))
        ms = []
        for i, b in enumerate(batches):
            if i == 3:
                job.resize(8)
            m = job.process_batch(b)
            ms.append(m)
            prev[0] = job.drm.partitioner
            assert not job.drm.split_keys
            assert m.overflow == 0, m
            # no key table crosses to the host: W * STATE * 4 bytes
            assert m.fetch_bytes < STATE * 4, m.fetch_bytes
            if not m.repartitioned:
                continue
            cap, peak, (want_k, want_v), (whole_k, whole_v) = expect.pop(0)
            assert m.migration_plan_rows == cap, (i, m.migration_plan_rows, cap)
            assert m.migration_peak_rows == peak, (i, m.migration_peak_rows, peak)
            got_k, got_v = np.asarray(job.state_keys), np.asarray(job.state_vals)
            assert np.array_equal(got_k, want_k), i
            assert np.array_equal(got_v.view(np.int32), want_v.view(np.int32)), i
            assert np.array_equal(got_k, whole_k), i
            assert np.array_equal(got_v.view(np.int32), whole_v.view(np.int32)), i
        assert not expect
        assert ms[3].resized and ms[3].num_partitions == 8, ms[3]
        assert sum(m.repartitioned for m in ms) >= 4, [m.reason for m in ms]
        if W > 1:
            assert any(m.migration_peak_rows > 0 for m in ms)
        else:
            assert all(m.migration_peak_rows == 0 and m.migration_plan_rows == 0
                       and m.migration_rows == 0 for m in ms)
            # one worker's migration is the identity: no route program ran
            assert job._migrate_route is None
        # exact counts over everything fed (ids reach 2^30: count over the
        # distinct ones, not a 2^30-long bincount)
        sk, sv = np.asarray(job.state_keys), np.asarray(job.state_vals)
        fed, inv = np.unique(np.concatenate(batches), return_inverse=True)
        want = np.bincount(inv)
        live = sk != KEY_SENTINEL
        got = np.zeros(len(want))
        pos = np.searchsorted(fed, sk[live])
        assert np.array_equal(fed[np.minimum(pos, len(fed) - 1)], sk[live])
        np.add.at(got, pos, sv[live][:, 0])
        assert np.array_equal(got, want.astype(float))

    run()
    print("MIGRATION-SIZING-OK")
    """
)


@pytest.mark.parametrize("workers", [1, 2, 4])
def test_lanes_sized_from_device_counts(workers):
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(workers)], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    assert "MIGRATION-SIZING-OK" in out.stdout, out.stdout + "\n" + out.stderr
