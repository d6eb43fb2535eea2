"""1-D agent meshes for partitioned network simulation (DESIGN.md §11).

The network simulator shards the *agent* axis: a ``(P,)`` mesh whose single
axis (``AGENT_AXIS = "shards"``) carries one graph shard per device.  This
is deliberately distinct from the production train/serve meshes in
``launch.mesh`` (("pod", "data", "model")): the simulator has no model
parallelism — every device runs the same per-shard event loop over its own
block of agents and exchanges halo models between event batches.

On a CPU-only host, multi-device runs use XLA's fake host devices::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python benchmarks/bench_network_sim.py --sharded

The flag must be set before the first jax call in the process (jax locks
the device count on first init), which is why the helpers here never force
a device count themselves — a mesh larger than the process's device count
is an error, never silently shrunk.

The engines run their per-shard programs under ``jax.shard_map`` with
``check_vma=False``: those programs mix replicated event streams with
sharded state and gather/ppermute collectives the checker cannot type.

Defined as functions so importing this module never touches jax device
state (same rule as ``launch.mesh``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

AGENT_AXIS = "shards"

#: The recipe for getting P host devices out of a CPU-only process; must be
#: in the environment before the first jax import (see module docstring).
HOST_DEVICES_RECIPE = "XLA_FLAGS=--xla_force_host_platform_device_count=8"


def max_shards() -> int:
    """Largest usable shard count on this process (= device count)."""
    return jax.device_count()


def make_sim_mesh(n_shards: Optional[int] = None):
    """1-D mesh over ``n_shards`` devices (default: all local devices).

    Raises ``ValueError`` when ``n_shards`` is not in ``[1, device
    count]``: a mesh smaller than asked for would silently change what a
    sharded run measures.
    """
    avail = max_shards()
    n = avail if n_shards is None else int(n_shards)
    if not 1 <= n <= avail:
        raise ValueError(
            f"asked for a {n}-shard sim mesh but this process has {avail} "
            f"device(s); on a CPU-only host start the process with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n}")
    # Auto axis: the engines place data with shard_map specs and
    # device_put; jax.make_mesh's default Explicit axis would type every
    # shard_map output as sharded and refuse the uneven row slices the
    # sharded dispatch impls take of them
    return jax.make_mesh((n,), (AGENT_AXIS,),
                         axis_types=(jax.sharding.AxisType.Auto,))


def mesh_shards(mesh) -> int:
    """Shard count of a sim mesh (size of its agent axis)."""
    return int(dict(zip(mesh.axis_names, mesh.devices.shape))[AGENT_AXIS])


def agent_sharding(mesh, *trailing_dims: Optional[str]) -> NamedSharding:
    """NamedSharding splitting the leading (agent) axis across the mesh."""
    return NamedSharding(mesh, P(AGENT_AXIS, *trailing_dims))


def replicated(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


@dataclasses.dataclass(frozen=True)
class HaloCodec:
    """Wire format for the boundary rows a shard publishes each round.

    Three codecs (selected by ``name``), all decoding to f32 *on the
    receiving shard* so every downstream accumulation stays f32:

    ``f32``
        Identity — the bit-for-bit parity anchor.  The exchange path is
        byte-identical to the pre-codec code, so sharded trajectories
        under this codec reproduce the single-device engines exactly.
    ``bf16``
        Rows cast to bfloat16 on the wire (2x cut; relative round-trip
        error <= 2^-8 — bf16 keeps f32's exponent and 8 significand bits).
    ``int8``
        Per-row symmetric int8: each trailing-axis vector (one model /
        dual component of one boundary row) ships as int8 codes plus one
        f32 scale ``max|row| / 127`` (~4x cut; per-row relative error
        <= 2^-6).  Zero rows get scale 1.0 so they round-trip exactly.

    Frozen/hashable so it can ride through ``jax.jit`` static arguments
    (the sharded engines thread it as a static scan parameter).
    """

    name: str = "f32"

    NAMES = ("f32", "bf16", "int8")

    def __post_init__(self):
        if self.name not in self.NAMES:
            raise ValueError(
                f"unknown halo codec {self.name!r}; one of {self.NAMES}")

    @property
    def is_identity(self) -> bool:
        return self.name == "f32"

    def encode(self, x):
        """f32 rows -> tuple of wire arrays (payload first, then scales)."""
        if self.name == "f32":
            return (x,)
        if self.name == "bf16":
            return (x.astype(jnp.bfloat16),)
        amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
        q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
        return (q, scale)

    def decode(self, parts):
        """Tuple of wire arrays -> f32 rows (f32 math on the receiver)."""
        if self.name == "f32":
            return parts[0]
        if self.name == "bf16":
            return parts[0].astype(jnp.float32)
        q, scale = parts
        return q.astype(jnp.float32) * scale

    def row_nbytes(self, row_shape) -> int:
        """Wire bytes for one boundary row of the given trailing shape."""
        elems = int(math.prod(row_shape))
        if self.name == "f32":
            return 4 * elems
        if self.name == "bf16":
            return 2 * elems
        # int8 codes + one f32 scale per trailing-axis vector
        return elems + 4 * (elems // int(row_shape[-1]))


def resolve_halo_codec(codec: Union[str, HaloCodec, None]) -> HaloCodec:
    """Normalize a codec spec (name, instance, or None -> f32)."""
    if codec is None:
        return HaloCodec("f32")
    if isinstance(codec, HaloCodec):
        return codec
    return HaloCodec(str(codec))


def halo_exchange_fn(
    bnd_pos, halo_src_shard, halo_src_pos, n_halo, n_shards,
    exchange="all_gather", codec: Union[str, HaloCodec, None] = None,
):
    """Build the per-shard halo exchange used by the partitioned simulators.

    Returns ``run(x)`` mapping this shard's local rows ``x (m, ...)`` to the
    extended buffer ``[local | halo (H, ...) | zero-row]`` of shape
    ``(m + H + 1, ...)``: each shard publishes its boundary rows
    (``x[bnd_pos]``) and pulls its halo from the gathered boundary buffers —
    ``all_gather`` by default, or a P-1-step ``ppermute`` ring
    (``exchange="ring"``).  Must be called inside a ``shard_map`` over
    ``AGENT_AXIS``.  Works for any trailing shape, so the MP engine
    exchanges (m, p) model rows and the CL-ADMM engine (m, 1 + 3k, p)
    stacked model/dual payloads through the same code path.

    ``codec`` selects the :class:`HaloCodec` wire format: boundary rows are
    encoded *before* the collective (so the quantized representation is
    what crosses the interconnect) and decoded back to f32 on the
    receiving shard after halo selection.  The default f32 codec keeps the
    exchange byte-identical to the uncoded path.
    """
    codec = resolve_halo_codec(codec)

    def run(x):
        zero = jnp.zeros((1,) + x.shape[1:], x.dtype)
        if n_halo == 0:
            return jnp.concatenate([x, zero])
        send = x[bnd_pos]  # (B, ...)
        wire = codec.encode(send)
        if exchange == "ring":
            ring = [(s, (s + 1) % n_shards) for s in range(n_shards)]
            q_id = jax.lax.axis_index(AGENT_AXIS)
            halo = jnp.zeros((n_halo,) + x.shape[1:], x.dtype)
            bufs = wire
            bcast = (n_halo,) + (1,) * (x.ndim - 1)
            for step in range(1, n_shards):
                bufs = tuple(jax.lax.ppermute(b, AGENT_AXIS, ring)
                             for b in bufs)
                src = (q_id - step) % n_shards
                mask = (halo_src_shard == src).reshape(bcast)
                rows = codec.decode(tuple(b[halo_src_pos] for b in bufs))
                halo = jnp.where(mask, rows, halo)
        else:
            allb = tuple(jax.lax.all_gather(b, AGENT_AXIS)
                         for b in wire)  # each (P, B, ...)
            halo = codec.decode(
                tuple(b[halo_src_shard, halo_src_pos] for b in allb))
        return jnp.concatenate([x, halo, zero])

    return run


def halo_payload_bytes(
    n_shards: int, boundary_size: int, row_nbytes: int, halo_size: int
) -> int:
    """Bytes published per halo exchange across the whole mesh.

    Every shard all-gathers its ``boundary_size`` boundary rows each
    exchange regardless of which rows its neighbors actually consume, so
    the wire cost is ``P * B * row_nbytes`` — zero when the partition has
    no halo at all (``halo_size == 0``), in which case the engines skip the
    collective entirely.  ``row_nbytes`` is the *wire* size of one
    boundary row (``HaloCodec.row_nbytes`` for coded exchanges).  The
    telemetry layer multiplies this by the round count for the cumulative
    comm column.
    """
    if halo_size == 0:
        return 0
    return int(n_shards) * int(boundary_size) * int(row_nbytes)


def shard_read_route(owner, local_pos, users):
    """Route per-user state reads to the owning shard's store.

    ``owner``/``local_pos`` are the (n,) shard-assignment tables of a
    ``GraphPartition`` (agent a lives at row ``local_pos[a]`` of shard
    ``owner[a]``'s local block).  Returns the ``(shard, pos)`` int arrays
    for a batch of user ids — the lookup the sharded personalization
    service performs per inference request (DESIGN.md §16): reads go to
    the one shard that owns the user's row, never through a gathered
    global copy, so serving scales with the mesh exactly like the
    simulator state does.
    """
    users = np.asarray(users, np.int64)
    return (np.asarray(owner, np.int32)[users],
            np.asarray(local_pos, np.int32)[users])
