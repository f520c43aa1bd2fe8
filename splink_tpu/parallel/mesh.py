"""Device-mesh helpers: the TPU replacement for Spark's partitioning layer.

The reference delegates all distribution to Spark (SURVEY.md section 2:
"Parallelism & distributed-communication components"). Here the single
distributed axis is the candidate-pair axis — this framework's "sequence
length" — sharded over a 1-D ``data`` mesh axis. M-step reductions then lower
to psum collectives over ICI; parameters are replicated.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..utils.profiling import span

DATA_AXIS = "data"


def make_mesh(n_devices: int | None = None, axis_name: str = DATA_AXIS) -> Mesh:
    """A 1-D mesh over the first ``n_devices`` devices (default: all)."""
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (axis_name,))


def mesh_from_settings(settings: dict) -> Mesh | None:
    """Build the mesh described by the settings ``mesh`` dict, or None.

    ``{"data": N}`` means: shard the pair axis over N devices. ``{"data":
    1}`` is an EXPLICIT single-device mesh — the sharded code path with one
    shard (useful for exercising mesh plumbing anywhere), not the same as
    the empty dict / absent key, which selects the unsharded single-device
    path.
    """
    spec = settings.get("mesh") or {}
    if not spec:
        return None
    supported = (
        f"the supported form is {{{DATA_AXIS!r}: N}} — a 1-D mesh over the "
        f"pair axis with 1 <= N <= jax.device_count()"
    )
    if list(spec.keys()) != [DATA_AXIS]:
        raise ValueError(f"unsupported mesh spec {spec!r}; {supported}")
    n = spec[DATA_AXIS]
    if isinstance(n, bool) or not isinstance(n, int) or n < 1:
        raise ValueError(
            f"unsupported mesh size {n!r} in {spec!r}; {supported}"
        )
    available = len(jax.devices())
    if n > available:
        raise ValueError(
            f"mesh spec {spec!r} requests {n} devices but only {available} "
            f"are visible; {supported}"
        )
    return make_mesh(n)


def pair_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for (n_pairs, ...) arrays: split the leading pair axis."""
    return NamedSharding(mesh, PartitionSpec(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def put_on_mesh(sharding: NamedSharding, *arrays) -> tuple:
    """Place ``arrays`` on the mesh with ``sharding`` under one ``mesh_put``
    span (``bytes``: what the host hands over, ``devices``: how many chips
    take it) and WAIT for them: the span then reads the placement and not
    its dispatch. The wait costs a pass nothing — these are the table and
    plan arrays its first program needs before it can start. Per-batch
    uploads (a metadata row, an accumulator) stay plain async
    ``device_put`` calls outside any span."""
    with span("mesh_put", bytes=sum(int(a.nbytes) for a in arrays),
              devices=sharding.mesh.devices.size):
        return jax.block_until_ready(
            tuple(jax.device_put(a, sharding) for a in arrays)
        )


def gather_from_mesh(x) -> np.ndarray:
    """``np.asarray(x)`` for an array sharded over the mesh, under a
    ``mesh_gather`` span (``bytes``, ``shards``). It waits for the program
    that makes ``x`` BEFORE the span opens, so the span is the copies from
    the chips alone; the driver thread's ``d2h_wait`` is the wait for both
    (``profiling.fetch(x, via=gather_from_mesh)``, or the pooled download
    the virtual pass waits on)."""
    x.block_until_ready()
    with span("mesh_gather", shards=len(x.sharding.device_set)) as sp:
        arr = np.asarray(x)
        sp.count(bytes=arr.nbytes)
    return arr


def pad_to_multiple(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


def shard_pairs(mesh: Mesh, *arrays):
    """Pad the leading axis to a multiple of the mesh size and device_put with
    pair sharding. Returns (padded_arrays..., weights) where weights is 1.0
    for real rows and 0.0 for padding — thread it into EM so padding rows
    contribute nothing (gamma padding value -1 + weight 0; shard_audit
    SA-PAD statically pins that the stats kernels consume the weights)."""
    n = arrays[0].shape[0]
    n_dev = mesh.devices.size
    n_pad = pad_to_multiple(max(n, n_dev), n_dev)
    sharding = pair_sharding(mesh)

    out = []
    for a in arrays:
        if n_pad != n:
            pad_shape = (n_pad - n,) + a.shape[1:]
            fill = -1 if np.issubdtype(a.dtype, np.signedinteger) else 0
            a = np.concatenate([a, np.full(pad_shape, fill, a.dtype)])
        out.append(jax.device_put(a, sharding))
    weights = np.zeros(n_pad, np.float32)
    weights[:n] = 1.0
    out.append(jax.device_put(weights, sharding))
    return tuple(out)
