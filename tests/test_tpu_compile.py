"""The main-path Pallas kernels compile for a TPU v5e, at the streaming
job's real widths, with ``interpret=False``.

Interpret mode (every other kernel test) cannot see the TPU compiler's
rules: block tiling, VMEM limits, unsupported casts.  Here each kernel is
lowered for a *described* v5e chip — the TPU compiler runs, nothing
executes — so a kernel the chip would refuse fails in this file instead of
on the chip.  The topology is described inside a fixture only (loading the
TPU library at import time would break parallel test workers); where it
cannot be described the tests skip.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dispatch_count import dispatch_count
from repro.kernels.lookup_dispatch import lookup_dispatch
from repro.kernels.partition_apply import partition_apply
from repro.kernels.route_bucketize import MAX_CAPACITY, MAX_LANES, route_bucketize
from repro.kernels.sketch_update import sketch_update

# the streaming job's real widths: 2^20-event micro-batches, 64 logical
# partitions (heavy table lam * N = 128 rows), 4096 hosts, 1 payload column
N = 1 << 20
PARTS = 64
HEAVY = 128
HOSTS = 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes, kernel: str):
    """Compile ``fn`` for the described chip; return its HLO text.  The
    kernel's op carries its name (``%lookup_dispatch.1``): the device trace
    and the benchmark's readers find it by that name."""
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text  # the Pallas kernel, compiled
    assert re.search(rf"%{kernel}(\.\d+)? = .*custom_call_target=\"tpu_custom_call\"", text)
    return text


def _shapes(one_chip, *specs):
    return [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]


def test_partition_apply_compiles(one_chip):
    keys, hk, hp, h2p = _shapes(one_chip, ((N,), jnp.int32), ((HEAVY,), jnp.int32),
                                ((HEAVY,), jnp.int32), ((HOSTS,), jnp.int32))
    _compile(lambda k, a, b, c: partition_apply(k, a, b, c, num_hosts=HOSTS,
                                                interpret=False), keys, hk, hp, h2p,
             kernel="partition_apply")


@pytest.mark.parametrize("lanes", [1, 4])
def test_lookup_dispatch_compiles(one_chip, lanes):
    """The two-pass route kernel, split-key pick on, for 1- and 4-chip lanes."""
    keys, valid, hk, hp, h2p, hr = _shapes(
        one_chip, ((N,), jnp.int32), ((N,), jnp.bool_), ((HEAVY,), jnp.int32),
        ((HEAVY,), jnp.int32), ((HOSTS,), jnp.int32), ((HEAVY,), jnp.int32))
    _compile(lambda k, v, a, b, c, r: lookup_dispatch(
        k, v, a, b, c, r, num_hosts=HOSTS, num_lanes=lanes, num_partitions=PARTS,
        interpret=False), keys, valid, hk, hp, h2p, hr, kernel="lookup_dispatch")


def test_route_bucketize_compiles(one_chip):
    """The fused kernel at the largest exchange its size rule admits."""
    keys, valid, vals, hk, hp, h2p = _shapes(
        one_chip, ((N,), jnp.int32), ((N,), jnp.bool_), ((N, 1), jnp.float32),
        ((HEAVY,), jnp.int32), ((HEAVY,), jnp.int32), ((HOSTS,), jnp.int32))
    _compile(lambda k, v, w, a, b, c: route_bucketize(
        k, v, w, a, b, c, num_hosts=HOSTS, num_lanes=MAX_LANES, capacity=MAX_CAPACITY,
        interpret=False), keys, valid, vals, hk, hp, h2p, kernel="route_bucketize")


def test_dispatch_count_compiles(one_chip):
    dest, valid = _shapes(one_chip, ((N,), jnp.int32), ((N,), jnp.bool_))
    _compile(lambda d, v: dispatch_count(d, v, num_parts=PARTS, interpret=False),
             dest, valid, kernel="dispatch_count")


def test_sketch_update_compiles(one_chip):
    keys, valid = _shapes(one_chip, ((N,), jnp.int32), ((N,), jnp.bool_))
    _compile(lambda k, v: sketch_update(k, v, depth=4, width=2048, interpret=False),
             keys, valid, kernel="sketch_update")


def test_ragged_transport_is_native_on_a_tpu_mesh(topo, monkeypatch):
    """The ragged row phase picks its collective by the mesh's platform."""
    from jax.sharding import Mesh

    from repro.compat import native_ragged

    monkeypatch.delenv("REPRO_DISABLE_NATIVE_RAGGED", raising=False)
    assert native_ragged(Mesh(np.asarray(topo.devices), ("data",)))
    assert not native_ragged(Mesh(np.asarray(jax.devices("cpu")[:1]), ("data",)))
    monkeypatch.setenv("REPRO_DISABLE_NATIVE_RAGGED", "1")
    assert not native_ragged(Mesh(np.asarray(topo.devices), ("data",)))
