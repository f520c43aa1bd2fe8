"""Streaming EM: sufficient statistics accumulated across micro-batches.

For pair sets too large for HBM the reference gets global aggregation for
free from Spark's shuffle (/root/reference/splink/maximisation_step.py:54-57).
The TPU equivalent: stream gamma batches host->device (double-buffered via
jax's async dispatch), accumulate ``SufficientStats`` on device per batch,
and apply the parameter update once per pass over the data. The per-batch
kernel is a single jit; with a mesh, batches are sharded over the pair axis
and the stats reduction rides ICI psum.
"""

from __future__ import annotations

import functools
from typing import Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from ..models.fellegi_sunter import (
    FSParams,
    SufficientStats,
    log_likelihood,
    match_probability,
    sufficient_stats,
    update_params,
)
from ..utils.profiling import dispatched, fetch, span
from .mesh import pair_sharding, shard_pairs


@functools.partial(jax.jit, static_argnames=("max_levels", "compute_ll"))
def _batch_stats(G, params: FSParams, max_levels: int, weights=None, compute_ll=False):
    p = match_probability(G, params)
    stats = sufficient_stats(G, p, max_levels, weights)
    ll = log_likelihood(G, params, weights) if compute_ll else jnp.zeros((), p.dtype)
    return stats, ll


@jax.jit
def _update_and_delta(acc: SufficientStats, params: FSParams):
    """M-step update fused with the convergence delta, one compiled program:
    the driver loop then needs a single scalar read per pass instead of one
    sync per jnp reduction (jaxlint JL011)."""
    new = update_params(acc)
    delta = jnp.maximum(
        jnp.max(jnp.abs(new.m - params.m)),
        jnp.max(jnp.abs(new.u - params.u)),
    )
    return new, delta


def run_em_streamed(
    batch_iter_factory: Callable[[], Iterable],
    init: FSParams,
    *,
    max_iterations: int,
    max_levels: int,
    em_convergence: float,
    mesh=None,
    compute_ll: bool = False,
    on_iteration=None,
    stats_reduce=None,
    start_iteration: int = 0,
    retry_policy=None,
    fault_plan=None,
    telemetry=None,
):
    """EM over a re-iterable stream of gamma batches.

    Args:
        batch_iter_factory: zero-arg callable returning an iterable of either
            ``G`` arrays or ``(G, weights)`` tuples, each (b, C) int8. Called
            once per EM iteration (the stream is re-read every pass, like the
            reference re-scans the persisted df_gammas).
        init: starting parameters.
        mesh: optional Mesh; batches are padded + sharded over the pair axis.
        stats_reduce: optional callable applied to the pass's accumulated
            SufficientStats before the parameter update. Multi-controller
            runs pass ``parallel.distributed.all_sum_stats`` here so every
            process updates from the GLOBAL aggregate while streaming only
            its own ``global_pair_slice`` (the reference gets this from
            Spark's global shuffle,
            /root/reference/splink/maximisation_step.py:54-57).
        on_iteration: optional callback(iteration_index, FSParams, ll,
            converged) run after each update — the save_state_fn hook's
            internal analogue (and where resilience.EMCheckpointer plugs
            in); ``converged`` is True on the update that met
            em_convergence.
        start_iteration: resume support — the number of EM updates ``init``
            already embodies (from a checkpoint); iteration indices
            reported to on_iteration continue from here, and at most
            ``max_iterations - start_iteration`` further updates run.
            Histories still start at index 0 = ``init`` (the caller merges
            with pre-resume history).
        retry_policy: optional resilience.RetryPolicy. A transient failure
            anywhere in a pass (batch fetch, device put, execute) restarts
            that WHOLE pass with bounded exponential backoff — partial
            sufficient statistics are never reused, so a retried pass is
            bit-identical to an undisturbed one. Deterministic failures
            propagate immediately. None disables retry.
        fault_plan: optional resilience.FaultPlan consulted at the
            ``batch_fetch`` (per batch) and ``em_iteration`` (per update)
            injection sites; None resolves the process's active plan
            (SPLINK_TPU_FAULTS).
        telemetry: optional ``obs.runtime.RunContext`` — emits one EM
            convergence record per pass (the streamed loop is host-driven,
            so this adds no host callback to any compiled program) plus a
            pass counter.

    Returns (params, histories, n_updates, converged) mirroring run_em.
    """
    from ..resilience import faults as _faults
    from ..resilience.retry import retry_call

    if fault_plan is None:
        fault_plan = _faults.active_plan()

    params = init
    C, L = init.m.shape
    lam_hist = [float(init.lam)]
    m_hist = [np.asarray(init.m)]
    u_hist = [np.asarray(init.u)]
    ll_hist = []
    converged = False
    it = start_iteration

    def one_pass(it, params):
        """One full pass over the stream: (accumulated stats, ll parts)."""
        acc = SufficientStats.zeros(C, L, dtype=init.m.dtype)
        # Per-batch log-likelihoods stay on device (a host-side float(ll)
        # here would sync every micro-batch and serialise the stream) and
        # reduce pairwise at the end of the pass, which keeps f32 error
        # O(log n_batches) instead of O(n_batches) for sequential adds.
        ll_parts = []
        for bi, batch in enumerate(batch_iter_factory()):
            fault_plan.fire("batch_fetch", iter=it, batch=bi)
            if isinstance(batch, tuple):
                G, w = batch
            else:
                G, w = batch, None
            if mesh is not None:
                if w is None:
                    G, w = shard_pairs(mesh, np.asarray(G))
                else:
                    # pad user weights alongside G (padding weight 0)
                    G, w, _auto_w = shard_pairs(
                        mesh, np.asarray(G), np.asarray(w, np.float32)
                    )
            stats, ll = _batch_stats(
                jnp.asarray(G), params, max_levels, w, compute_ll
            )
            dispatched("_batch_stats", ll, rows=len(G))
            acc = acc + stats
            if compute_ll:
                ll_parts.append(ll)
        return acc, ll_parts

    for it in range(start_iteration + 1, max_iterations + 1):
        if retry_policy is not None:
            acc, ll_parts = retry_call(
                lambda: one_pass(it, params),
                policy=retry_policy,
                label=f"EM pass {it}",
            )
        else:
            acc, ll_parts = one_pass(it, params)
        ll_dev = (
            jnp.sum(jnp.stack(ll_parts))
            if ll_parts
            else jnp.zeros((), init.m.dtype)
        )

        if stats_reduce is not None:
            # reduce the log-likelihood with the SAME collective as the
            # stats (one pytree, one allgather): each process streams only
            # its slice, so the local ll is partial too
            if compute_ll:
                acc, ll_dev = stats_reduce((acc, ll_dev))
            else:
                acc = stats_reduce(acc)
        new, delta_dev = _update_and_delta(acc, params)
        params = new
        # The ONE sanctioned sync point per pass: the convergence decision
        # and the histories need these scalars on host, and everything
        # upstream (per-batch stats, ll parts, the update+delta) stayed on
        # device.
        with span("d2h_wait", bytes=0):  # the pass's programs, a few scalars
            delta = float(delta_dev)  # jaxlint: disable=JL011 — sanctioned
            lam_f = float(params.lam)  # jaxlint: disable=JL011 — same sync point
            ll_total = (  # jaxlint: disable=JL011 — same sync point
                float(ll_dev) if compute_ll else 0.0
            )
        lam_hist.append(lam_f)
        m_hist.append(np.asarray(params.m))
        u_hist.append(np.asarray(params.u))
        if compute_ll:
            ll_hist.append(ll_total)
        converged_now = delta < em_convergence
        if telemetry is not None:
            telemetry.em_update(
                it, lam_f, params.m, params.u,
                ll_total if compute_ll else None, converged_now,
            )
            telemetry.count("em_stream_passes")
        if on_iteration is not None:
            # the convergence flag rides along so a checkpoint written at
            # the converging iteration records converged=True — a resume
            # must not append a spurious extra update
            on_iteration(
                it, params, ll_total if compute_ll else None, converged_now
            )
        # after on_iteration so a checkpoint hook persists this update
        # before an injected process death (the kill-and-resume tests)
        fault_plan.fire("em_iteration", iter=it)
        if converged_now:
            converged = True
            break

    histories = {
        "lam": np.asarray(lam_hist),
        "m": np.stack(m_hist),
        "u": np.stack(u_hist),
        "ll": np.asarray(ll_hist) if compute_ll else None,
    }
    return params, histories, it, converged


def score_stream(batch_iter, params: FSParams):
    """Yield match probabilities for each gamma batch in the stream."""
    from ..em import score_pairs

    for batch in batch_iter:
        G = batch[0] if isinstance(batch, tuple) else batch
        p = score_pairs(jnp.asarray(G), params)
        dispatched("score_pairs", p, rows=len(G))
        yield fetch(p)
