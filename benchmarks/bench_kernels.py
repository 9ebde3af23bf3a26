"""Kernel micro-bench: Pallas (interpret on CPU) + jnp twins per batch."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timer
from repro.kernels import ref as kref
from repro.kernels.ops import apply_partitioner, count_sketch, dispatch_slots
from repro.core import Histogram, kip_update, uniform_partitioner
from repro.data.generators import zipf_keys


SMOKE = dict(n=2_048)  # CI bench-smoke profile


def run(n: int = 8192):
    rows = []
    stream = zipf_keys(n, num_keys=2_000, exponent=1.2, seed=0)
    hist = Histogram.exact(stream).top(64)
    kip = kip_update(uniform_partitioner(16), hist)
    keys = jnp.asarray(stream[:n], jnp.int32)
    tables = kip.tables()

    jit_ref = jax.jit(lambda k: kref.partition_apply_ref(
        k, tables.heavy_keys, tables.heavy_parts, tables.host_to_part,
        num_hosts=kip.num_hosts))
    jit_ref(keys).block_until_ready()
    rows.append(("kernel/partition_apply_jnp", timer(
        lambda: jit_ref(keys).block_until_ready()), f"{n} keys"))
    # pallas interpret mode is NOT a performance path on CPU; correctness only
    out = apply_partitioner(keys, tables, num_hosts=kip.num_hosts)
    ok = bool(jnp.all(out == jit_ref(keys)))
    rows.append(("kernel/partition_apply_pallas_matches", float(ok), "interpret=True"))

    jit_cms = jax.jit(lambda k: kref.sketch_update_ref(k, jnp.ones(n, bool), depth=4, width=2048))
    jit_cms(keys).block_until_ready()
    rows.append(("kernel/sketch_update_jnp", timer(
        lambda: jit_cms(keys).block_until_ready()), f"{n} keys, 4x2048"))

    dest = jnp.asarray(np.random.default_rng(0).integers(0, 16, n), jnp.int32)
    jit_d = jax.jit(lambda d: kref.dispatch_count_ref(d, jnp.ones(n, bool), num_parts=16))
    jit_d(dest)[0].block_until_ready()
    rows.append(("kernel/dispatch_count_jnp", timer(
        lambda: jit_d(dest)[0].block_until_ready()), f"{n} records, 16 parts"))

    # fused exchange-plane hot path: lookup + slot in one pass
    valid = jnp.ones(n, bool)
    jit_f = jax.jit(lambda k: kref.lookup_dispatch_ref(
        k, valid, tables.heavy_keys, tables.heavy_parts, tables.host_to_part,
        num_hosts=kip.num_hosts, num_lanes=8))
    jit_f(keys)[0].block_until_ready()
    rows.append(("kernel/lookup_dispatch_jnp", timer(
        lambda: jit_f(keys)[0].block_until_ready()), f"{n} keys, 8 lanes (fused)"))
    from repro.kernels.ops import route_slots

    part_p, slot_p, _ = route_slots(keys, valid, tables, num_hosts=kip.num_hosts, num_lanes=8)
    part_r, slot_r, _ = jit_f(keys)
    ok = bool(jnp.all(part_p == part_r) & jnp.all(slot_p == slot_r))
    rows.append(("kernel/lookup_dispatch_pallas_matches", float(ok), "interpret=True"))

    # bucketize: deriving slots+counts inside vs. reusing the fused route
    # kernel's outputs (the reuse path also skips the O(n) lane_overflow
    # scatter — per-lane drops fall out of the counts)
    from repro.exchange import ExchangeSpec, Payload
    from repro.exchange.backends import _bucketize

    lanes = 16
    spec = ExchangeSpec(num_lanes=lanes, capacity=int(np.ceil(n / lanes / 8) * 8))
    bvals = jnp.ones((n, 8), jnp.float32)
    jit_slot = jax.jit(lambda d: kref.dispatch_count_ref(d, valid, num_parts=lanes))
    slot, counts = jit_slot(dest)
    slot.block_until_ready()

    jit_derive = jax.jit(
        lambda d: _bucketize(spec, d, valid, [Payload(bvals, 0)]).valid)
    jit_fused = jax.jit(
        lambda d, s, c: _bucketize(spec, d, valid, [Payload(bvals, 0)],
                                   slot=s, counts=c).valid)
    jit_derive(dest).block_until_ready()
    jit_fused(dest, slot, counts).block_until_ready()
    rows.append(("kernel/bucketize_derive_slots", timer(
        lambda: jit_derive(dest).block_until_ready()),
        f"{n} records, {lanes} lanes (dispatch_count + overflow scatter inside)"))
    rows.append(("kernel/bucketize_fused_route", timer(
        lambda: jit_fused(dest, slot, counts).block_until_ready()),
        f"{n} records, {lanes} lanes (slots+counts from the route pass)"))

    # double-buffered send sets: reset+scatter into a recycled [L, cap] set
    # (the streaming driver's ping-pong pool, donated so XLA rewrites it in
    # place) vs. materializing the set fresh every batch.  Values must be
    # bit-identical — reuse is an allocation optimization, not a semantic
    # one.
    def _fill(d, s, c, bufs):
        out = _bucketize(spec, d, valid, [Payload(bvals, 0)], slot=s, counts=c,
                         buffers=bufs)
        return out.valid, tuple(out.payloads)

    jit_realloc = jax.jit(lambda d, s, c: _fill(d, s, c, None))
    donate_bufs = () if jax.default_backend() == "cpu" else (3,)
    jit_reuse = jax.jit(
        lambda d, s, c, bufs: _fill(d, s, c, (bufs[0], tuple(bufs[1]))),
        donate_argnums=donate_bufs)
    fresh = jit_realloc(dest, slot, counts)
    reused = jit_reuse(dest, slot, counts, jit_realloc(dest, slot, counts))
    ok = bool(jnp.all(fresh[0] == reused[0])) and all(
        bool(jnp.all(f == r)) for f, r in zip(fresh[1], reused[1]))
    rows.append(("kernel/bucketize_reuse_matches", float(ok),
                 "recycled set scatters to the fresh-alloc values"))
    pool = [jit_realloc(dest, slot, counts) for _ in range(2)]

    def _ping_pong():
        bufs = pool.pop(0)
        out = jit_reuse(dest, slot, counts, bufs)
        pool.append(out)
        out[0].block_until_ready()

    _ping_pong(), _ping_pong()  # warm both sets through the jit
    rows.append(("kernel/bucketize_realloc", timer(
        lambda: jit_realloc(dest, slot, counts)[0].block_until_ready()),
        f"{n} records, {lanes} lanes (fresh [L, cap] set per batch)"))
    rows.append(("kernel/bucketize_buffer_reuse", timer(_ping_pong),
        f"{n} records, {lanes} lanes (two-set ping-pong, reset+scatter)"))

    # fused route->bucketize (the split-phase exchange's whole start path in
    # one pass) vs. the two-pass route-then-scatter chain it replaces
    from repro.kernels.ops import route_bucketize as rb_pallas

    rl = 8
    cap = int(np.ceil(n / rl / 128) * 128)
    rb_spec = ExchangeSpec(num_lanes=rl, capacity=cap)
    kf = 2**31 - 1

    def _two_pass(k):
        part, slot, counts = kref.lookup_dispatch_ref(
            k, valid, tables.heavy_keys, tables.heavy_parts, tables.host_to_part,
            seed=kip.seed, num_hosts=kip.num_hosts, num_lanes=rl)
        dest = jnp.where(valid, part, 0)
        return _bucketize(rb_spec, dest % rl, valid,
                          [Payload(k, kf), Payload(bvals, 0), Payload(dest, 0)],
                          slot=slot, counts=counts).payloads[0]

    def _fused_rb(k):
        return kref.route_bucketize_ref(
            k, valid, bvals, tables.heavy_keys, tables.heavy_parts,
            tables.host_to_part, seed=kip.seed, num_hosts=kip.num_hosts,
            num_lanes=rl, capacity=cap, key_fill=kf)[4]

    jit_two, jit_frb = jax.jit(_two_pass), jax.jit(_fused_rb)
    jit_two(keys).block_until_ready()
    jit_frb(keys).block_until_ready()
    rows.append(("kernel/route_bucketize_two_pass", timer(
        lambda: jit_two(keys).block_until_ready()),
        f"{n} keys, {rl} lanes (route, then scatter)"))
    rows.append(("kernel/route_bucketize_fused_jnp", timer(
        lambda: jit_frb(keys).block_until_ready()),
        f"{n} keys, {rl} lanes (one fused pass)"))
    got = rb_pallas(keys, valid, tables, bvals, seed=kip.seed,
                    num_hosts=kip.num_hosts, num_lanes=rl, capacity=cap, key_fill=kf)
    want = kref.route_bucketize_ref(
        keys, valid, bvals, tables.heavy_keys, tables.heavy_parts,
        tables.host_to_part, seed=kip.seed, num_hosts=kip.num_hosts,
        num_lanes=rl, capacity=cap, key_fill=kf)
    ok = bool(jnp.all(jnp.where(valid, got[0], 0) == jnp.where(valid, want[0], 0)))
    for g, w in list(zip(got, want))[1:]:
        ok = ok and bool(jnp.all(g == w))
    rows.append(("kernel/route_bucketize_pallas_matches", float(ok), "interpret=True"))
    return rows
