"""Multi-host (multi-slice) initialisation and work partitioning.

The reference's multi-machine story is "submit to a Spark cluster". The
splink_tpu analogue is JAX multi-controller: each host runs the same program,
``jax.distributed.initialize`` wires the hosts together, and the global mesh
spans every chip; XLA routes the M-step psum over ICI within a slice and DCN
across slices. EM's collective traffic is tiny (the SufficientStats pytree,
a few KB), so DCN latency is irrelevant — the design scales to any slice
count the pair stream can feed.

Support status: the single-process path and the partitioning arithmetic are
tested (tests/test_distributed.py); sharded EM correctness is proven on an
8-virtual-device mesh (tests/test_sharding.py); and the REAL multi-controller
path — two OS processes wired by ``jax.distributed.initialize`` over local
TCP (Gloo CPU collectives), each streaming its ``global_pair_slice`` through
``run_em_streamed`` with ``all_sum_stats`` as the cross-process reduction —
runs in CI with bit-parity against the single-process trajectory
(tests/test_multiprocess_em.py). Physical-pod bring-up uses the identical
code path with auto-detected coordinator arguments.
"""

from __future__ import annotations

import hashlib
import logging

import jax

logger = logging.getLogger("splink_tpu")


def distributed_is_initialized() -> bool:
    """Whether the multi-controller runtime is up (does NOT initialise
    the XLA backend, unlike jax.process_count())."""
    return bool(jax.distributed.is_initialized())


def initialize_multihost(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Initialise JAX's multi-controller runtime.

    On TPU pods the arguments are auto-detected from the environment; pass
    them explicitly for manual bring-up. With no arguments and no cluster
    environment this is a logged no-op (single-process run); explicit
    arguments that fail to connect raise — a misconfigured cluster must not
    silently degrade to one host.
    """
    # NOTE: do not probe jax.process_count() here — it INITIALISES the XLA
    # backend, after which jax.distributed.initialize refuses to run (it
    # must precede any backend use). is_initialized() only inspects the
    # distributed-runtime state.
    if distributed_is_initialized():
        return  # already initialised
    explicit = coordinator_address is not None
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    except (ValueError, RuntimeError) as e:
        if explicit:
            raise RuntimeError(
                f"jax.distributed.initialize failed for coordinator "
                f"{coordinator_address!r}: {e}"
            ) from e
        logger.info(
            "no multi-host environment detected (%s); running single-process",
            e,
        )


def all_sum_stats(stats):
    """Sum a SufficientStats pytree (or any small pytree of arrays) across
    controller processes — the multi-host analogue of the in-mesh psum. The
    payload is a few KB, so one allgather per EM pass is negligible next to
    the pair stream.

    Single-process: identity (so the same code runs everywhere). Pass as
    ``run_em_streamed(..., stats_reduce=all_sum_stats)``.
    """
    if jax.process_count() == 1:
        return stats
    import jax.numpy as jnp
    from jax.experimental import multihost_utils

    # ONE allgather for the whole pytree (process_allgather maps over
    # leaves inside a single collective round), then sum the process axis
    gathered = multihost_utils.process_allgather(
        jax.tree.map(jnp.asarray, stats)
    )
    return jax.tree.map(lambda leaf: jnp.sum(leaf, axis=0), gathered)


def validate_resume_presence(found: bool) -> bool:
    """All processes must agree whether the checkpoint exists BEFORE any
    loader-only work happens: validate_resume_topology is a collective,
    and a resumed process also starts from a different iteration than a
    fresh one — either divergence deadlocks or corrupts the run. Mixed
    found-flags mean checkpoint_dir is per-host storage (only process 0
    writes); raise with that diagnosis instead of hanging. Every process
    must call this when resuming under multi-controller. Returns
    ``found`` unchanged for the single-process case and for agreement."""
    if jax.process_count() == 1:
        return found
    import numpy as np
    from jax.experimental import multihost_utils

    local = np.array([1 if found else 0], np.int64)
    gathered = np.asarray(multihost_utils.process_allgather(local)).ravel()
    if gathered.min() != gathered.max():
        raise RuntimeError(
            "processes disagree on checkpoint presence (found flags "
            f"{gathered.tolist()}): only process 0 writes checkpoints, so "
            "checkpoint_dir must be on storage shared by every controller "
            "process."
        )
    return found


def validate_resume_topology(
    checkpoint_process_count: int, state_hash: str, iteration: int
) -> None:
    """Gate a multi-controller checkpoint resume on topology agreement.

    A resumed run must (a) have the SAME process count the checkpoint was
    written under — global_pair_slice partitions by process count, so a
    different topology would stream different slices than the histories
    assume — and (b) agree ACROSS processes on which checkpoint it is
    resuming (same settings hash, same iteration). Disagreement raises
    before any training continues; the single-process case checks only (a).
    """
    if jax.process_count() != checkpoint_process_count:
        raise RuntimeError(
            f"checkpoint was written by {checkpoint_process_count} "
            f"process(es) but this run has {jax.process_count()}: the "
            "global pair slices would not line up. Resume with the same "
            "topology, or train fresh with resume=False."
        )
    if jax.process_count() == 1:
        return
    import numpy as np
    from jax.experimental import multihost_utils

    digest = np.frombuffer(
        hashlib.sha256(state_hash.encode()).digest()[:8], np.int64
    )[0]
    local = np.array([digest, iteration], np.int64)
    gathered = np.asarray(multihost_utils.process_allgather(local))
    if not (gathered == local[None, :]).all():
        raise RuntimeError(
            "processes disagree on the checkpoint being resumed "
            f"(hash-digest/iteration rows: {gathered.tolist()}); refusing "
            "to continue from inconsistent state."
        )


def host_tags() -> dict:
    """Per-host identity tags stamped on every telemetry event
    (``process_index`` / ``process_count``), so a multi-controller run's
    merged JSONL records attribute each event to its controller.

    Deliberately does NOT call ``jax.process_count()`` unless the
    multi-controller runtime is already up: that call initialises the XLA
    backend, and telemetry sinks are created at linker construction —
    before ``initialize_multihost`` callers may have wired the cluster.
    A single-process run IS process 0 of 1, so the fallback is exact.
    """
    if not distributed_is_initialized():
        return {"process_index": 0, "process_count": 1}
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
    }


def spill_shard_dir(base: str) -> str:
    """Per-controller root for the durable spill write path: under
    multi-controller each process emits ITS shard subset of the pair
    stream into its own ``<base>/proc<k>`` store (single-writer manifests
    — the same discipline as the checkpoint writer), while a
    single-process run uses ``base`` directly so the common case has no
    extra directory level. ``base`` must be shared storage when the
    consuming EM later runs with a different controller layout."""
    import os

    if not distributed_is_initialized():
        return base
    return os.path.join(base, f"proc{jax.process_index()}")


def global_pair_slice(n_pairs_global: int) -> slice:
    """The half-open range of global pair indices this host is responsible
    for feeding. Hosts stream disjoint slices; the psum in the EM stats makes
    the union behave like one global aggregate."""
    per = -(-n_pairs_global // jax.process_count())
    start = min(jax.process_index() * per, n_pairs_global)
    return slice(start, min(start + per, n_pairs_global))
