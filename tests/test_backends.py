"""Exchange backends: dense / ragged / local equivalence and cost rules.

The backend contract is bit-identity: on the same routed input every
transport must produce identical unpacked rows and identical overflow
accounting — they differ only in *how much* they ship (``shipped_rows``)
and what a candidate plan costs (``cost``).  Property tests cover the
bucketize layer on random inputs; the collective layer is exercised through
``shard_map`` here (single device) and on 8 real shards in
``tests/test_distributed.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.core.migration import exchange_lane_cost, plan_migration
from repro.core.partitioner import uniform_partitioner
from repro.exchange import (
    DenseBackend,
    ExchangeSpec,
    LocalBackend,
    Payload,
    RaggedBackend,
    backend_name,
    make_exchange,
    resolve_backend,
    take_from,
)

ALL_BACKENDS = ("dense", "ragged", "local")


def _random_input(rng, n, num_lanes, payload_dim=3):
    lane = rng.integers(0, num_lanes, n).astype(np.int32)
    valid = rng.random(n) < 0.8
    vals = rng.normal(size=(n, payload_dim)).astype(np.float32)
    ints = rng.integers(0, 1000, n).astype(np.int32)
    return jnp.asarray(lane), jnp.asarray(valid), jnp.asarray(vals), jnp.asarray(ints)


# ---------------------------------------------------------------------------
# bucketize: transport-independent, bit-identical across backends
# ---------------------------------------------------------------------------


# every drawn shape compiles afresh: time is jit, not the property
@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(min_value=1, max_value=512),
    num_lanes=st.integers(min_value=1, max_value=16),
    capacity=st.sampled_from([1, 4, 8, 32]),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_bucketize_bit_identical_across_backends(n, num_lanes, capacity, seed):
    rng = np.random.default_rng(seed)
    lane, valid, vals, ints = _random_input(rng, n, num_lanes)
    spec = ExchangeSpec(num_lanes=num_lanes, capacity=capacity)
    results = {
        be: make_exchange(spec, be).bucketize(
            lane, valid, [Payload(vals, 0), Payload(ints, -1)]
        )
        for be in ALL_BACKENDS
    }
    ref = results["dense"]
    for be, res in results.items():
        np.testing.assert_array_equal(np.asarray(res.valid), np.asarray(ref.valid), err_msg=be)
        for got, want in zip(res.payloads, ref.payloads):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want), err_msg=be)
        assert int(res.send.overflow) == int(ref.send.overflow), be
        np.testing.assert_array_equal(
            np.asarray(res.send.lane_overflow), np.asarray(ref.send.lane_overflow),
            err_msg=be,
        )
        # unpacked view identical too (the consumer-facing surface)
        va, flat = res.unpack()
        wa, wflat = ref.unpack()
        np.testing.assert_array_equal(np.asarray(va), np.asarray(wa), err_msg=be)
        for g, w in zip(flat, wflat):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=be)


# every drawn shape compiles afresh: time is jit, not the property
@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(min_value=8, max_value=512),
    num_lanes=st.integers(min_value=1, max_value=8),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_lane_overflow_sums_to_scalar_in_range(n, num_lanes, seed):
    """With every lane in range, the per-lane vector is a refinement of the
    scalar: it sums to exactly the total overflow."""
    rng = np.random.default_rng(seed)
    lane, valid, vals, _ = _random_input(rng, n, num_lanes)
    res = make_exchange(ExchangeSpec(num_lanes=num_lanes, capacity=4)).bucketize(
        lane, valid, [Payload(vals, 0)]
    )
    assert int(np.asarray(res.send.lane_overflow).sum()) == int(res.send.overflow)


def test_lane_overflow_localizes_the_hot_lane():
    lane = jnp.asarray([1, 1, 1, 1, 1, 0], jnp.int32)  # lane 1 gets 5 > cap 2
    valid = jnp.ones(6, bool)
    res = make_exchange(ExchangeSpec(num_lanes=3, capacity=2)).bucketize(
        lane, valid, [Payload(jnp.arange(6, dtype=jnp.float32), 0)]
    )
    np.testing.assert_array_equal(np.asarray(res.send.lane_overflow), [0, 3, 0])
    assert int(res.send.overflow) == 3


def test_out_of_range_lane_counts_in_scalar_only():
    """A lane outside [0, L) has no lane to charge: the scalar sees it, the
    vector (by design) does not — the documented asymmetry."""
    lane = jnp.asarray([0, 7, -3], jnp.int32)
    valid = jnp.ones(3, bool)
    res = make_exchange(ExchangeSpec(num_lanes=2, capacity=4)).bucketize(
        lane, valid, [Payload(jnp.zeros(3), 0)]
    )
    assert int(res.send.overflow) == 2
    assert int(np.asarray(res.send.lane_overflow).sum()) == 0


# ---------------------------------------------------------------------------
# the collective: dense vs ragged through a real shard_map
# ---------------------------------------------------------------------------


def _run_collective(backend, lane, valid, vals, num_lanes, capacity):
    mesh = jax.make_mesh((1,), ("data",))
    ex = make_exchange(
        ExchangeSpec(num_lanes=num_lanes, capacity=capacity, axis="data"), backend
    )

    def body(lane, valid, vals):
        res = ex(lane, valid, [Payload(vals, -1.0)])
        va, (v,) = res.unpack()
        return va[None], v[None], res.shipped_rows, res.send.overflow

    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data")),
        out_specs=(P("data"), P("data"), P(), P()),
        check_vma=False,
    )
    va, v, shipped, overflow = mapped(lane, valid, vals)
    return np.asarray(va), np.asarray(v), int(shipped), int(overflow)


@pytest.mark.parametrize("skew", ["uniform", "hot"])
def test_collective_backends_bit_identical(skew):
    rng = np.random.default_rng(3)
    n, num_lanes, capacity = 256, 4, 96
    if skew == "hot":
        lane = np.zeros(n, np.int32)  # everything to lane 0: max raggedness
    else:
        lane = rng.integers(0, num_lanes, n).astype(np.int32)
    valid = rng.random(n) < 0.9
    vals = rng.normal(size=(n,)).astype(np.float32)
    out = {
        be: _run_collective(be, jnp.asarray(lane), jnp.asarray(valid),
                            jnp.asarray(vals), num_lanes, capacity)
        for be in ("dense", "ragged")
    }
    va_d, v_d, shipped_d, ov_d = out["dense"]
    va_r, v_r, shipped_r, ov_r = out["ragged"]
    np.testing.assert_array_equal(va_d, va_r)
    np.testing.assert_array_equal(v_d, v_r)
    assert ov_d == ov_r
    # dense ships the whole pad; ragged ships measured occupancy + counts
    assert shipped_d == num_lanes * capacity
    assert shipped_r <= shipped_d
    assert shipped_r == int(valid.sum() if skew == "uniform" else min(valid.sum(), capacity)) + num_lanes


def test_bucketize_with_precomputed_counts_bit_identical():
    """The fused-route fast path (slot + counts handed in) must produce the
    same buffers, overflow scalar, and per-lane overflow vector as the
    derive-everything path — the lane_overflow scatter it skips is exactly
    recomputable from the counts."""
    rng = np.random.default_rng(11)
    for n, num_lanes, capacity in [(64, 4, 4), (256, 8, 16), (33, 3, 1)]:
        lane, valid, vals, ints = _random_input(rng, n, num_lanes)
        spec = ExchangeSpec(num_lanes=num_lanes, capacity=capacity)
        from repro.kernels import ref as kref

        slot, counts = kref.dispatch_count_ref(lane, valid, num_parts=num_lanes)
        ex = make_exchange(spec)
        derived = ex.bucketize(lane, valid, [Payload(vals, 0), Payload(ints, -1)])
        fused = ex.bucketize(lane, valid, [Payload(vals, 0), Payload(ints, -1)],
                             slot=slot, counts=counts)
        np.testing.assert_array_equal(np.asarray(fused.valid), np.asarray(derived.valid))
        for g, w in zip(fused.payloads, derived.payloads):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        assert int(fused.send.overflow) == int(derived.send.overflow)
        np.testing.assert_array_equal(
            np.asarray(fused.send.lane_overflow), np.asarray(derived.send.lane_overflow)
        )
        # both paths also surface the buffer occupancy for the count phase
        np.testing.assert_array_equal(
            np.asarray(fused.lane_counts), np.asarray(derived.lane_counts)
        )
        np.testing.assert_array_equal(
            np.asarray(derived.lane_counts),
            np.minimum(np.asarray(counts), capacity),
        )


def test_ragged_count_phase_priced_in_row_bytes():
    """The phase-1 count vector is 4 bytes per lane, not a full row per
    lane: a wide-payload exchange pays a fraction of a row for it, a
    narrow-payload exchange up to one row per lane — never more.  (The old
    rule charged num_lanes rows regardless, biasing the policy gate against
    ragged on small records.)"""
    rng = np.random.default_rng(5)
    n, num_lanes, capacity = 128, 8, 32
    lane = rng.integers(0, num_lanes, n).astype(np.int32)
    valid = np.ones(n, bool)

    def shipped_with(payload):
        mesh = jax.make_mesh((1,), ("data",))
        ex = make_exchange(
            ExchangeSpec(num_lanes=num_lanes, capacity=capacity, axis="data"), "ragged"
        )

        def body(lane, valid, data):
            res = ex(lane, valid, [Payload(data, 0)])
            return res.shipped_rows

        mapped = shard_map(
            body, mesh=mesh,
            in_specs=(P("data"), P("data"), P("data")),
            out_specs=P(),
            check_vma=False,
        )
        return int(mapped(jnp.asarray(lane), jnp.asarray(valid), payload))

    rows = int(valid.sum())
    narrow = shipped_with(jnp.zeros(n, jnp.int32))            # 4 B/row
    wide = shipped_with(jnp.zeros((n, 16), jnp.float32))      # 64 B/row
    assert narrow == rows + num_lanes            # 4 B count == one 4 B row
    assert wide == rows + int(np.ceil(4 * num_lanes / 64))  # a fraction, ceil'd
    assert wide < narrow


def test_compat_ragged_all_to_all_shim_contract():
    """The shim itself, called directly: exactly ``send_sizes`` rows per
    lane move, and the unreceived region of the output keeps its initial
    values — the same contract whichever branch the installed jax takes
    (native collective on a TPU mesh, masked dense elsewhere)."""
    from repro.compat import ragged_all_to_all

    mesh = jax.make_mesh((1,), ("data",))
    operand = jnp.arange(8, dtype=jnp.float32)  # one lane of capacity 8

    def body(op):
        out = jnp.full_like(op, -1.0)
        sizes = jnp.asarray([3], jnp.int32)
        off = jnp.zeros(1, jnp.int32)
        return ragged_all_to_all(op, out, off, sizes, off, sizes,
                                 axis_name="data")

    mapped = shard_map(body, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"), check_vma=False)
    got = np.asarray(mapped(operand))
    np.testing.assert_array_equal(got, [0, 1, 2, -1, -1, -1, -1, -1])


# ---------------------------------------------------------------------------
# backhaul: the response hop rides the request lanes back
# ---------------------------------------------------------------------------


def _run_roundtrip(backend, lane, valid, vals, num_lanes, capacity):
    """Request-response through one exchange: ship, transform received rows
    in place, backhaul over the same lanes, gather per-record responses."""
    mesh = jax.make_mesh((1,), ("data",))
    ex = make_exchange(
        ExchangeSpec(num_lanes=num_lanes, capacity=capacity, axis="data"), backend
    )

    def body(lane, valid, vals):
        res = ex(lane, valid, [Payload(vals, -1.0)])
        resp = jnp.where(res.valid, res.payloads[0] * 2.0 + 1.0, 0.0)
        ret, back_shipped, back_occupied = ex.backhaul(resp, forward=res)
        out = take_from(ret, res.send)
        return out, res.shipped_rows + back_shipped, back_occupied

    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data")),
        out_specs=(P("data"), P(), P()),
        check_vma=False,
    )
    out, shipped, occupied = mapped(lane, valid, vals)
    return np.asarray(out), int(shipped), int(occupied)


@pytest.mark.parametrize("skew", ["uniform", "hot"])
def test_backhaul_bit_identical_across_backends(skew):
    """The combine direction (MoE's return trip) is bit-identical dense vs
    ragged, and the ragged round trip ships the measured rows both ways —
    no second count phase."""
    rng = np.random.default_rng(9)
    n, num_lanes, capacity = 192, 4, 64
    lane = (np.zeros(n, np.int32) if skew == "hot"
            else rng.integers(0, num_lanes, n).astype(np.int32))
    valid = rng.random(n) < 0.85
    vals = rng.normal(size=(n,)).astype(np.float32)
    out = {
        be: _run_roundtrip(be, jnp.asarray(lane), jnp.asarray(valid),
                           jnp.asarray(vals), num_lanes, capacity)
        for be in ("dense", "ragged")
    }
    np.testing.assert_array_equal(out["dense"][0], out["ragged"][0])
    # per-record responses: f(x) = 2x + 1 for accepted records, 0 otherwise;
    # hot skew overflows lane 0 beyond capacity and dropped records return 0
    dropped = np.zeros(n, bool)
    if skew == "hot":
        order = np.cumsum(valid) - 1  # rank within lane 0
        dropped = valid & (order >= capacity)
    expect = np.where(valid & ~dropped, 2.0 * vals + 1.0, 0.0)
    np.testing.assert_allclose(out["dense"][0], expect)
    # traffic: dense pays the pad twice, ragged pays counted rows + counts
    rows = int(np.sum(valid & ~dropped))
    assert out["dense"][1] == 2 * num_lanes * capacity
    assert out["ragged"][1] == (rows + num_lanes) + rows  # fwd + backhaul
    assert out["ragged"][1] < out["dense"][1]
    # occupancy is backend-independent: with forward counts threaded the
    # dense backhaul reports the same counted rows the ragged one ships
    assert out["dense"][2] == out["ragged"][2] == rows


def test_ragged_backhaul_without_forward_counts_ships_dense():
    """A backhaul with no forward result to reuse falls back to the padded
    return trip — correctness never depends on the counts being threaded."""
    rng = np.random.default_rng(13)
    n, num_lanes, capacity = 64, 4, 32
    lane = rng.integers(0, num_lanes, n).astype(np.int32)
    valid = np.ones(n, bool)
    vals = rng.normal(size=(n,)).astype(np.float32)
    mesh = jax.make_mesh((1,), ("data",))
    ex = make_exchange(
        ExchangeSpec(num_lanes=num_lanes, capacity=capacity, axis="data"), "ragged"
    )

    def body(lane, valid, vals):
        res = ex(lane, valid, [Payload(vals, 0.0)])
        ret, shipped, _occ = ex.backhaul(res.payloads[0])  # no forward threaded
        return take_from(ret, res.send), shipped

    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data")),
        out_specs=(P("data"), P()),
        check_vma=False,
    )
    out, shipped = mapped(jnp.asarray(lane), jnp.asarray(valid), jnp.asarray(vals))
    np.testing.assert_allclose(np.asarray(out), vals)
    assert int(shipped) == num_lanes * capacity  # the dense pad


def test_moe_combine_backhaul_bit_identical_across_backends():
    """End to end through the MoE layer: dispatch + combine under the dense
    and ragged transports produce the same output bit for bit, match the
    dense oracle, and the ragged layer reports less measured traffic."""
    import dataclasses as dc

    from repro.configs.base import MoESpec
    from repro.models.modules import Policy
    from repro.moe.layer import init_moe, moe_apply, moe_ref

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    spec = MoESpec(num_experts=4, top_k=2, d_ff_expert=16, shared_expert=False,
                   capacity_factor=4.0)
    d = 8
    p = init_moe(jax.random.PRNGKey(0), d, spec, "swiglu", jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, d))
    inv = jnp.arange(4, dtype=jnp.int32)
    want = moe_ref(p, x, spec, "swiglu", Policy(), inv)
    got = {}
    for be in ("dense", "ragged"):
        pol = Policy(mesh=mesh, dp_axes=("data",), tp_axis="model",
                     exchange_backend=be)
        got[be] = jax.jit(
            lambda pp, xx, pol=pol: moe_apply(pp, xx, spec, "swiglu", pol, inv)
        )(p, x)
    np.testing.assert_array_equal(np.asarray(got["dense"].y),
                                  np.asarray(got["ragged"].y))
    np.testing.assert_allclose(np.asarray(got["dense"].y), np.asarray(want.y),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got["dense"].counts),
                                  np.asarray(got["ragged"].counts))
    assert float(got["dense"].overflow) == float(got["ragged"].overflow) == 0.0
    # both directions accounted: the ragged layer moves fewer rows than the
    # padded round trip the dense layer reports
    assert int(got["ragged"].shipped_rows) < int(got["dense"].shipped_rows)


def test_local_backend_refuses_mesh_axis():
    spec = ExchangeSpec(num_lanes=2, capacity=4, axis="data")
    ex = make_exchange(spec, "local")
    res = ex.bucketize(jnp.zeros(3, jnp.int32), jnp.ones(3, bool),
                       [Payload(jnp.zeros(3), 0)])
    with pytest.raises(AssertionError):
        ex.all_to_all(res)


# ---------------------------------------------------------------------------
# split-phase pipeline: start() + finish() == the fused call, bit for bit
# ---------------------------------------------------------------------------


def _run_split_vs_fused(backend, lane, valid, vals, num_lanes, capacity):
    """Run the fused call and the start/finish pipeline side by side under
    one shard_map, returning both unpacked results + control accounting."""
    mesh = jax.make_mesh((1,), ("data",))
    ex = make_exchange(
        ExchangeSpec(num_lanes=num_lanes, capacity=capacity, axis="data"), backend
    )

    def body(lane, valid, vals):
        fused = ex(lane, valid, [Payload(vals, -1.0)])
        pending = ex.start(lane, valid, [Payload(vals, -1.0)])
        # every control output is already final on the in-flight value
        started = pending.buffers
        split = ex.finish(pending)
        return (
            fused.valid[None], fused.payloads[0][None], fused.shipped_rows,
            fused.send.overflow, fused.send.lane_overflow,
            split.valid[None], split.payloads[0][None], split.shipped_rows,
            started.shipped_rows, started.send.overflow,
            started.send.lane_overflow,
        )

    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data")),
        out_specs=(P("data"), P("data"), P(), P(), P(),
                   P("data"), P("data"), P(), P(), P(), P()),
        check_vma=False,
    )
    return mapped(lane, valid, vals)


@pytest.mark.parametrize("backend", ["dense", "ragged"])
@pytest.mark.parametrize("skew", ["uniform", "hot"])
def test_split_phase_bit_identical_to_fused(backend, skew):
    """start() + finish() must reproduce the fused exchange exactly —
    including the overflow scalar, the per-lane overflow vector, and the
    measured shipped_rows, all of which are final at start (the hot skew
    overflows lane 0, exercising the accounting under drops)."""
    rng = np.random.default_rng(21)
    n, num_lanes, capacity = 192, 4, 32  # hot skew overflows lane 0
    lane = (np.zeros(n, np.int32) if skew == "hot"
            else rng.integers(0, num_lanes, n).astype(np.int32))
    valid = rng.random(n) < 0.85
    vals = rng.normal(size=(n,)).astype(np.float32)
    (f_va, f_v, f_ship, f_ov, f_lov,
     s_va, s_v, s_ship, p_ship, p_ov, p_lov) = _run_split_vs_fused(
        backend, jnp.asarray(lane), jnp.asarray(valid), jnp.asarray(vals),
        num_lanes, capacity)
    np.testing.assert_array_equal(np.asarray(f_va), np.asarray(s_va))
    np.testing.assert_array_equal(np.asarray(f_v), np.asarray(s_v))
    assert int(f_ship) == int(s_ship) == int(p_ship)
    assert int(f_ov) == int(p_ov)
    np.testing.assert_array_equal(np.asarray(f_lov), np.asarray(p_lov))
    if skew == "hot":
        assert int(f_ov) > 0  # the accounting was actually exercised


@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(min_value=1, max_value=256),
    num_lanes=st.integers(min_value=1, max_value=8),
    capacity=st.sampled_from([1, 4, 16]),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_split_phase_local_bit_identical(n, num_lanes, capacity, seed):
    """The axis-free local backend: start/finish is the identity pipeline
    around bucketize — random shapes, including overflowing ones."""
    rng = np.random.default_rng(seed)
    lane, valid, vals, _ = _random_input(rng, n, num_lanes)
    ex = make_exchange(ExchangeSpec(num_lanes=num_lanes, capacity=capacity))
    fused = ex(lane, valid, [Payload(vals, 0.0)])
    split = ex.finish(ex.start(lane, valid, [Payload(vals, 0.0)]))
    np.testing.assert_array_equal(np.asarray(fused.valid), np.asarray(split.valid))
    for g, w in zip(split.payloads, fused.payloads):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert int(fused.send.overflow) == int(split.send.overflow)
    np.testing.assert_array_equal(
        np.asarray(fused.send.lane_overflow), np.asarray(split.send.lane_overflow)
    )


# ---------------------------------------------------------------------------
# backend resolution + cost rules
# ---------------------------------------------------------------------------


def test_resolve_backend_auto_and_names():
    assert isinstance(resolve_backend(None, ExchangeSpec(2, 4)), LocalBackend)
    assert isinstance(resolve_backend(None, ExchangeSpec(2, 4, axis="data")), DenseBackend)
    assert isinstance(resolve_backend(None), DenseBackend)
    assert isinstance(resolve_backend("ragged"), RaggedBackend)
    be = RaggedBackend()
    assert resolve_backend(be) is be
    with pytest.raises(ValueError):
        resolve_backend("nccl")
    assert backend_name(None) == "auto"
    assert backend_name("dense") == "dense"
    assert backend_name(be) == "ragged"


@settings(max_examples=10)
@given(seed=st.integers(min_value=0, max_value=2**16))
def test_cost_rules_ordering(seed):
    """Ragged cost (mean real rows) never exceeds dense cost (padded peak);
    a local exchange is free."""
    rng = np.random.default_rng(seed)
    transfer = rng.random((6, 6)) * rng.integers(1, 100)
    np.fill_diagonal(transfer, 0.0)
    dense = DenseBackend().cost(None, transfer)
    ragged = RaggedBackend().cost(None, transfer)
    assert 0.0 <= ragged <= dense
    assert LocalBackend().cost(None, transfer) == 0.0
    assert DenseBackend().cost(None, np.zeros((0, 0))) == 0.0


def test_exchange_lane_cost_backend_rules():
    """The policy-facing cost helper: default == dense rule; ragged strictly
    cheaper on a skewed plan; local free."""
    old = uniform_partitioner(4, seed=0)
    new = uniform_partitioner(4, seed=3)
    plan = plan_migration(old, new, np.arange(512, dtype=np.int64))
    base = exchange_lane_cost(plan, num_workers=2)
    dense = exchange_lane_cost(plan, num_workers=2, backend=DenseBackend())
    ragged = exchange_lane_cost(plan, num_workers=2, backend=RaggedBackend())
    local = exchange_lane_cost(plan, num_workers=2, backend=LocalBackend())
    assert base == dense > 0
    assert 0 < ragged < dense  # a 2-worker fold has an empty diagonal to skip
    assert local == 0.0


def test_make_exchange_default_matches_pre_backend_behavior():
    """axis=None auto-selects the local transport; the collective verbs are
    identity, exactly the old ``Exchange`` with no axis."""
    ex = make_exchange(ExchangeSpec(num_lanes=3, capacity=4))
    assert isinstance(ex.backend, LocalBackend)
    res = ex(jnp.asarray([0, 1, 2], jnp.int32), jnp.ones(3, bool),
             [Payload(jnp.arange(3, dtype=jnp.float32), 0)])
    assert int(res.shipped_rows) == 0  # nothing crossed a mesh axis
    buf = np.asarray(res.payloads[0])
    assert buf[0, 0] == 0 and buf[1, 0] == 1 and buf[2, 0] == 2
