"""Runtime seams shared by the streaming driver and the exchange plane.

* ``ragged_all_to_all`` — the ragged row phase's collective, chosen by the
  platform of the mesh it runs on: ``jax.lax.ragged_all_to_all`` on TPU
  (each shard sends ``send_sizes[i]`` rows to shard ``i`` instead of the
  full capacity pad), and a masked-dense equivalent elsewhere — XLA:CPU
  does not implement the ragged op.  The masked-dense form rides the dense
  tiled all-to-all with the receive buffer masked to ``recv_sizes``:
  bit-identical output, dense traffic.  It supports the *lane-major regular
  layout only* (``input_offsets[i] == i * capacity``,
  ``output_offsets[i] == axis_index * capacity``), which is the one layout
  the exchange plane uses: ``bucketize`` packs each lane's rows
  contiguously from slot 0, so lane ``i``'s live rows start at row
  ``i * capacity`` of the flattened send buffer.

Runtime escape hatch (environment variable):

* ``REPRO_DISABLE_NATIVE_RAGGED=1`` — force the masked-dense ragged
  transport even on TPU (see :func:`native_ragged`).

Host-sync instrumentation (``host_fetch`` / ``safe_point`` /
``host_sync_count``) also lives here: the streaming driver routes its
device->host conversions through :func:`host_fetch`, which counts fetches
of device arrays performed outside a ``with safe_point():`` region.  The
counter is how benches prove the streaming driver's "zero blocking
transfers between safe points" contract.  ``host_fetch_bytes`` adds up the
bytes every fetch moved, inside safe points or not.
"""
from __future__ import annotations

import contextlib
import os

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "ragged_all_to_all",
    "native_ragged",
    "host_fetch",
    "host_fetch_bytes",
    "host_sync_count",
    "reset_host_sync_count",
    "safe_point",
]

# --- host-sync instrumentation -------------------------------------------
#
# The streaming driver's sync-free contract says device->host transfers
# happen only at *safe points* (the per-batch decision section, where the
# control plane must look at the counts anyway).  Every D2H conversion in
# the steady-state loop goes through :func:`host_fetch`; fetches of device
# arrays outside a ``with safe_point():`` region increment
# ``host_sync_count``.  Benches and tests read the counter to prove the
# streaming driver performs zero blocking transfers between safe points —
# a nonzero delta on a no-action batch pinpoints a leaked sync.

_sync_state = {"count": 0, "depth": 0, "bytes": 0}


def host_sync_count() -> int:
    """Device->host fetches observed *outside* safe-point regions."""
    return _sync_state["count"]


def host_fetch_bytes() -> int:
    """Bytes of device arrays fetched to the host so far, inside safe points
    or not (a running total: callers take differences)."""
    return _sync_state["bytes"]


def reset_host_sync_count() -> None:
    """Zero the counter (benches call this before a measured segment)."""
    _sync_state["count"] = 0


@contextlib.contextmanager
def safe_point():
    """Mark a region where blocking device->host fetches are sanctioned."""
    _sync_state["depth"] += 1
    try:
        yield
    finally:
        _sync_state["depth"] -= 1


def host_fetch(x):
    """``np.asarray`` that audits device->host transfers.

    Fetching a ``jax.Array`` outside a :func:`safe_point` region counts as a
    blocking sync; host values (ints, floats, numpy) pass through uncounted.
    Every fetched array's bytes are added to :func:`host_fetch_bytes`.
    """
    if isinstance(x, jax.Array):
        _sync_state["bytes"] += x.nbytes
        if _sync_state["depth"] == 0:
            _sync_state["count"] += 1
    return np.asarray(x)


def native_ragged(mesh=None) -> bool:
    """True when the ragged row phase runs the native collective on
    ``mesh`` (default: the mesh being traced, inside a ``shard_map``).

    Native on TPU meshes, masked-dense everywhere else.
    ``REPRO_DISABLE_NATIVE_RAGGED=1`` forces masked-dense on TPU too — the
    lever benches use to measure it, and tests use to compare the two
    transports bit-for-bit.  (``0``/``false``/unset leave native on.)
    """
    abstract = jax.sharding.get_abstract_mesh() if mesh is None else mesh.abstract_mesh
    device = abstract.abstract_device
    on_tpu = device is not None and device.device_kind.lower().startswith("tpu")
    disabled = os.environ.get("REPRO_DISABLE_NATIVE_RAGGED", "")
    return on_tpu and disabled.lower() in ("", "0", "false")


def ragged_all_to_all(
    operand,
    output,
    input_offsets,
    send_sizes,
    output_offsets,
    recv_sizes,
    *,
    axis_name: str,
):
    """The ragged all-to-all, native on TPU and masked-dense elsewhere.

    Native: shard ``j`` receives ``send_sizes[j]`` rows read from
    ``operand[input_offsets[j]:]`` and writes them at ``output_offsets[j]``
    of *its* ``output``; regions of ``output`` that receive nothing keep
    their initial values.  Only the measured rows cross the interconnect —
    the wall-clock follows the row counts.

    Masked-dense: the dense tiled all-to-all ships the whole padded buffer
    and the receive side is masked to ``recv_sizes``, with unfilled rows
    taken from ``output`` — bit-identical results, padded traffic.
    Requires the lane-major regular layout (see module doc); offsets are
    trusted, not checked, because they are static under that layout.  For
    buffers whose pad rows already equal ``output``'s values (the exchange
    plane's bucketize-packed buffers) the mask selects identical bits — the
    cost of keeping one uniform contract is one fused select XLA folds into
    the all-to-all's consumer.
    """
    if native_ragged():
        return jax.lax.ragged_all_to_all(
            operand, output, input_offsets, send_sizes, output_offsets,
            recv_sizes, axis_name=axis_name,
        )
    num_lanes = send_sizes.shape[0]
    capacity = operand.shape[0] // num_lanes
    bufs = operand.reshape((num_lanes, capacity) + operand.shape[1:])
    recvd = jax.lax.all_to_all(bufs, axis_name, 0, 0, tiled=True)
    live = jnp.arange(capacity, dtype=jnp.int32)[None, :] < recv_sizes[:, None]
    live = live.reshape((num_lanes * capacity,) + (1,) * (operand.ndim - 1))
    return jnp.where(live, recvd.reshape(operand.shape), output)
