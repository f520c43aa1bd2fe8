"""EM training loop: one jit-compiled program, params resident on device.

The reference's EM driver round-trips driver <-> cluster every iteration and
re-plans a fresh SQL query with the parameters baked in as literals
(/root/reference/splink/iterate.py:20,
/root/reference/splink/expectation_step.py:212). Here the
whole loop is a single ``lax.while_loop`` compiled once: parameters are traced
arguments that stay in device memory, the convergence predicate evaluates on
device, and per-iteration parameter history is written into preallocated
buffers so the host reads everything back in one transfer after convergence.

Two execution modes:
  * run_em:        gamma matrix resident in HBM (optionally sharded over a
                   mesh 'data' axis) — the fast path.
  * run_em_streamed (see splink_tpu/parallel/streaming.py): gamma batches
    stream host->device and sufficient statistics accumulate across
    micro-batches before each parameter update, for datasets larger than HBM.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import io_callback

from .models.fellegi_sunter import (
    FSParams,
    log_likelihood,
    match_probability,
    sufficient_stats,
    update_params,
)
from .utils.profiling import dispatched, span


class EMResult(NamedTuple):
    params: FSParams  # final parameters
    n_updates: jnp.ndarray  # number of M-step updates performed
    converged: jnp.ndarray  # bool: stopped because delta < tol
    lam_history: jnp.ndarray  # (max_iter + 1,), entry 0 = initial
    m_history: jnp.ndarray  # (max_iter + 1, C, L)
    u_history: jnp.ndarray  # (max_iter + 1, C, L)
    ll_history: jnp.ndarray  # (max_iter + 1,) log likelihood under params i (nan if not computed)


class _LoopState(NamedTuple):
    params: FSParams
    it: jnp.ndarray
    converged: jnp.ndarray
    lam_hist: jnp.ndarray
    m_hist: jnp.ndarray
    u_hist: jnp.ndarray
    ll_hist: jnp.ndarray


class EMNumericsError(RuntimeError):
    """A non-finite value entered the EM trajectory.

    Raised by :func:`run_em_checkpointed`'s host hook the moment an
    update delivers NaN/Inf in lambda, m, u or the log likelihood —
    BEFORE the poisoned values reach the histories, telemetry or a
    checkpoint, so everything persisted stays finite. Carries the first
    poisoned iteration, which fields were non-finite, the last finite
    iteration, and (when the run checkpoints) the directory plus the
    last boundary iteration already on disk — the state a caller
    restarts from. The same facts go out as a structured
    ``em_numerics`` degradation event (obs/events.publish) before the
    raise, so the incident lands in the run record and the flight ring
    even when the caller swallows the exception.
    """

    def __init__(
        self,
        message: str,
        *,
        iteration: int,
        fields: list,
        last_good_iteration: int,
        checkpoint_dir=None,
        last_checkpoint_iteration=None,
    ):
        super().__init__(message)
        self.iteration = iteration
        self.fields = fields
        self.last_good_iteration = last_good_iteration
        self.checkpoint_dir = checkpoint_dir
        self.last_checkpoint_iteration = last_checkpoint_iteration


# The active host hook for run_em(host_hook=True): a single module-level
# trampoline keeps ONE compiled program per (shape, static args) — a
# per-call closure passed as a static argument would recompile every call.
# run_em_checkpointed sets/clears it around the run (no concurrent fused
# EM runs share a process).
_active_em_hook = None


def _em_hook_trampoline(it, lam, m, u, ll_pre, converged):
    hook = _active_em_hook
    if hook is not None:
        hook(it, lam, m, u, ll_pre, converged)


@functools.partial(
    jax.jit,
    static_argnames=("max_iterations", "max_levels", "compute_ll", "host_hook"),
)
def run_em(
    G,
    init: FSParams,
    *,
    max_iterations: int,
    max_levels: int,
    em_convergence,
    weights=None,
    compute_ll: bool = False,
    host_hook: bool = False,
) -> EMResult:
    """Run EM to convergence in one compiled program.

    Convergence matches the reference (/root/reference/splink/params.py:316-336):
    the largest absolute change across all pi probabilities (lambda excluded)
    must drop below ``em_convergence``. The history layout matches the
    reference's ``param_history``: index i holds the parameters *before*
    update i+1, so index 0 is the initial state.

    ``host_hook`` adds one ordered io_callback per update (iteration, new
    params, pre-update ll, converged flag — a few hundred bytes) through
    which run_em_checkpointed persists progress WITHOUT re-entering the
    program: restarting the while_loop per checkpoint segment re-executes
    the hoisted loop-invariant work (the one-hot gamma expansion XLA
    licms out of the body), measured at ~30% overhead at K=5 on the CPU
    tier versus <5% for the in-loop callback. The callback does not touch
    the dataflow, so the trajectory is bit-identical either way.
    """
    C, L = init.m.shape
    dtype = init.m.dtype
    n_hist = max_iterations + 1

    lam_hist = jnp.full((n_hist,), jnp.nan, dtype).at[0].set(init.lam)
    m_hist = jnp.zeros((n_hist, C, L), dtype).at[0].set(init.m)
    u_hist = jnp.zeros((n_hist, C, L), dtype).at[0].set(init.u)
    ll_hist = jnp.full((n_hist,), jnp.nan, dtype)

    def cond(state: _LoopState):
        return (state.it < max_iterations) & (~state.converged)

    def body(state: _LoopState):
        with jax.named_scope("em_step"):
            p = match_probability(G, state.params)
            stats = sufficient_stats(G, p, max_levels, weights)
            new = update_params(stats)
        delta = jnp.maximum(
            jnp.max(jnp.abs(new.m - state.params.m)),
            jnp.max(jnp.abs(new.u - state.params.u)),
        )
        it = state.it + 1
        lam_h = state.lam_hist.at[it].set(new.lam)
        m_h = state.m_hist.at[it].set(new.m)
        u_h = state.u_hist.at[it].set(new.u)
        ll_h = state.ll_hist
        ll_val = jnp.asarray(jnp.nan, dtype)
        if compute_ll:
            # Log likelihood under the *pre-update* params, stored at the
            # pre-update index — the reference computes ll in the E-step and
            # archives it with those params
            # (/root/reference/splink/expectation_step.py:52-57).
            ll_val = log_likelihood(G, state.params, weights)
            ll_h = ll_h.at[state.it].set(ll_val)
        if host_hook:
            io_callback(
                _em_hook_trampoline,
                None,
                it,
                new.lam,
                new.m,
                new.u,
                ll_val,
                delta < em_convergence,
                ordered=True,
            )
        return _LoopState(
            params=new,
            it=it,
            converged=delta < em_convergence,
            lam_hist=lam_h,
            m_hist=m_h,
            u_hist=u_h,
            ll_hist=ll_h,
        )

    init_state = _LoopState(
        params=init,
        it=jnp.zeros((), jnp.int32),
        converged=jnp.zeros((), bool),
        lam_hist=lam_hist,
        m_hist=m_hist,
        u_hist=u_hist,
        ll_hist=ll_hist,
    )
    final = lax.while_loop(cond, body, init_state)

    ll_hist = final.ll_hist
    if compute_ll:
        ll_hist = ll_hist.at[final.it].set(
            log_likelihood(G, final.params, weights)
        )

    return EMResult(
        params=final.params,
        n_updates=final.it,
        converged=final.converged,
        lam_history=final.lam_hist,
        m_history=final.m_hist,
        u_history=final.u_hist,
        ll_history=ll_hist,
    )


def run_em_checkpointed(
    G,
    init: FSParams,
    *,
    max_iterations: int,
    max_levels: int,
    em_convergence,
    weights=None,
    compute_ll: bool = False,
    checkpoint_dir=None,
    state_hash: str = "",
    checkpoint_every: int = 5,
    resume: bool = False,
    resume_checkpoint=None,
    fault_plan=None,
    on_segment=None,
    telemetry=None,
) -> EMResult:
    """Fused EM with an atomic checkpoint every ``checkpoint_every``
    updates — ONE compiled ``run_em`` execution, persisted from inside.

    The per-iteration computation IS ``run_em``'s (the host hook rides an
    io_callback that touches no dataflow), so the parameter/history
    trajectory is bit-identical to an uninterrupted run —
    tests/test_checkpoint_resume.py pins this. Per update the hook
    receives the new params; at each boundary (iteration divisible by K,
    convergence, or the final update) it writes an atomic checkpoint
    (resilience/checkpoint.py), fires the ``segment`` fault-injection
    site, and calls ``on_segment``. An interrupted run resumes
    (``resume=True``) from the last boundary instead of starting over.

    An earlier revision re-entered the compiled while_loop in
    K-iteration segments; XLA hoists the loop-invariant one-hot gamma
    expansion out of the loop body, so every re-entry re-paid it — ~30%
    wall-clock overhead at K=5, vs <5% for this in-loop form (CPU
    container, PR 1; not reproduced on the chip).

    Histories are host numpy arrays in run_em's layout (index i = params
    before update i+1; ll index i = log likelihood under params i).
    ``on_segment(done, histories, converged)`` runs on the callback
    thread at each boundary — the linker uses it to replay new iterations
    into its Params object (and drive save_state_fn) incrementally; it
    must therefore stay host-side work (no jax dispatch). A hook
    exception (failed write, injected boundary fault) is re-raised after
    the program drains.

    ``telemetry`` (an ``obs.runtime.RunContext``) streams one EM
    convergence record per update through the SAME io_callback — the
    telemetry-only caller (checkpoint_dir=None) therefore runs the
    identical compiled program as the checkpointed one, and the parameter
    trajectory stays bit-identical to a telemetry-off run (the callback
    touches no dataflow). RunContext.em_update never raises, so telemetry
    failures cannot poison the deferred-exception channel.
    """
    import numpy as np

    from .resilience.checkpoint import (
        EMCheckpoint,
        load_checkpoint,
        save_checkpoint,
    )

    if resume and checkpoint_dir is None:
        raise ValueError(
            "resume=True requires checkpoint_dir — silently training from "
            "scratch is exactly the surprise a resume caller cannot afford."
        )
    m0 = np.asarray(init.m)
    C, L = m0.shape
    np_dtype = m0.dtype
    n_hist = max_iterations + 1
    lam_h = np.full((n_hist,), np.nan, np_dtype)
    m_h = np.zeros((n_hist, C, L), np_dtype)
    u_h = np.zeros((n_hist, C, L), np_dtype)
    ll_h = np.full((n_hist,), np.nan, np_dtype)
    lam_h[0] = np.asarray(init.lam)
    m_h[0] = m0
    u_h[0] = np.asarray(init.u)

    done = 0
    converged = False
    params_dev = init
    if resume and checkpoint_dir is not None:
        # a caller that already loaded (and topology-validated) the
        # checkpoint passes it in; re-reading the file here would be a
        # second full parse and a validate/restore race window
        ckpt = (
            resume_checkpoint
            if resume_checkpoint is not None
            else load_checkpoint(checkpoint_dir, expect_hash=state_hash or None)
        )
        if ckpt is not None:
            h = ckpt.history_arrays()
            done = min(ckpt.iteration, max_iterations)
            lam_h[: done + 1] = h["lam"][: done + 1].astype(np_dtype)
            m_h[: done + 1] = h["m"][: done + 1].astype(np_dtype)
            u_h[: done + 1] = h["u"][: done + 1].astype(np_dtype)
            if compute_ll and h["ll"] is not None:
                n_ll = min(len(h["ll"]), done + 1)
                ll_h[:n_ll] = h["ll"][:n_ll].astype(np_dtype)
            if ckpt.iteration > max_iterations:
                # the iteration cap was lowered below the checkpoint:
                # return the truncated trajectory's own params (history
                # index ``done``), not the checkpoint's later ones, and
                # the converged flag at the truncation point is unknown
                params_dev = FSParams(
                    lam=jnp.asarray(lam_h[done]),
                    m=jnp.asarray(m_h[done]),
                    u=jnp.asarray(u_h[done]),
                )
                converged = False
            else:
                lam, m, u = ckpt.params_arrays()
                params_dev = FSParams(
                    lam=jnp.asarray(lam.astype(np_dtype)),
                    m=jnp.asarray(m.astype(np_dtype)),
                    u=jnp.asarray(u.astype(np_dtype)),
                )
                converged = ckpt.converged

    # single-writer directory under multi-controller runs: every process
    # computes the same trajectory (the EM stats are globally reduced), so
    # only process 0 persists it
    is_writer = jax.process_count() == 1 or jax.process_index() == 0

    # the numerics guard reports the newest boundary already on disk as
    # the restart point, so _save records what it persisted
    last_saved = {"iteration": None}

    def _save(iteration, conv):
        if checkpoint_dir is None or not is_writer:
            return
        # Single-writer by design (jaxlint JL009): every process computes
        # the identical trajectory (the EM stats are globally reduced), the
        # save path contains no collective, and readers gate on
        # validate_resume_presence — so only process 0 touching the
        # directory cannot deadlock or diverge.
        save_checkpoint(  # jaxlint: disable=JL009
            checkpoint_dir,
            EMCheckpoint(
                state_hash=state_hash,
                iteration=iteration,
                lam=float(lam_h[iteration]),
                m=m_h[iteration].tolist(),
                u=u_h[iteration].tolist(),
                histories={
                    "lam": lam_h[: iteration + 1].tolist(),
                    "m": m_h[: iteration + 1].tolist(),
                    "u": u_h[: iteration + 1].tolist(),
                    # not-yet-computed entries (the boundary's own ll
                    # arrives one update later) persist as null, never a
                    # 0.0 filler a resumed run could mistake for a value
                    "ll": (
                        [
                            None if np.isnan(v) else float(v)
                            for v in ll_h[: iteration + 1]
                        ]
                        if compute_ll
                        else None
                    ),
                },
                converged=conv,
                process_count=jax.process_count(),
                dtype=np_dtype.name,
            ),
        )
        last_saved["iteration"] = int(iteration)

    checkpoint_every = max(int(checkpoint_every), 1)
    start = done
    remaining = max_iterations - done
    hook_needed = (
        checkpoint_dir is not None
        or on_segment is not None
        or (fault_plan is not None and bool(fault_plan))
        or telemetry is not None
    )
    deferred: list[BaseException] = []

    def hook(it_rel, lam, m, u, ll_pre, conv):
        # runs on the runtime's callback thread, once per completed
        # update, while the compiled loop is still executing
        if deferred:
            return
        try:
            it = start + int(it_rel)
            # numerics guard: a NaN/Inf update halts the trajectory HERE,
            # before the poisoned values can reach the histories, the
            # telemetry stream or a checkpoint. Everything written so far
            # passed this same check, so iteration it-1 is the last finite
            # state — and the newest _save boundary holds it on disk.
            bad = [
                name
                for name, v in (("lam", lam), ("m", m), ("u", u))
                if not np.isfinite(np.asarray(v)).all()
            ]
            if compute_ll and not np.isfinite(ll_pre):
                bad.append("ll")
            if bad:
                from .obs.events import publish

                info = dict(
                    iteration=it,
                    fields=bad,
                    last_good_iteration=it - 1,
                    checkpoint_dir=(
                        str(checkpoint_dir)
                        if checkpoint_dir is not None
                        else None
                    ),
                    last_checkpoint_iteration=last_saved["iteration"],
                )
                publish("em_numerics", **info)
                where = (
                    f"; last checkpoint at iteration "
                    f"{last_saved['iteration']} in {checkpoint_dir}"
                    if last_saved["iteration"] is not None
                    else ""
                )
                raise EMNumericsError(
                    f"non-finite EM update at iteration {it} "
                    f"({', '.join(bad)}); last finite iteration "
                    f"{it - 1}{where}",
                    **info,
                )
            lam_h[it] = lam
            m_h[it] = m
            u_h[it] = u
            if compute_ll and not np.isnan(ll_pre):
                ll_h[it - 1] = ll_pre
            conv = bool(conv)
            if telemetry is not None:
                telemetry.em_update(
                    it, float(lam), m, u,
                    float(ll_pre) if compute_ll else None, conv,
                )
            if conv or it == max_iterations or it % checkpoint_every == 0:
                # durability first: an injected kill at this boundary must
                # find the boundary's own update already on disk
                _save(it, conv)
                if fault_plan is not None:
                    fault_plan.fire("segment", iter=it)
                if on_segment is not None:
                    on_segment(
                        it, {"lam": lam_h, "m": m_h, "u": u_h, "ll": ll_h}, conv
                    )
        except BaseException as e:  # noqa: BLE001 - re-raised after drain
            deferred.append(e)

    if remaining > 0 and not converged:
        global _active_em_hook
        _active_em_hook = hook if hook_needed else None
        try:
            result = run_em(
                G,
                params_dev,
                max_iterations=remaining,
                max_levels=max_levels,
                em_convergence=em_convergence,
                weights=weights,
                compute_ll=compute_ll,
                host_hook=hook_needed,
            )
            dispatched("run_em", result.n_updates, rows=G.shape[0])
            # drain before releasing the hook: dispatch is async and the
            # trailing callbacks may still be in flight
            with span("d2h_wait", bytes=0):
                jax.block_until_ready(result.n_updates)
                jax.effects_barrier()
        finally:
            _active_em_hook = None
        if deferred:
            raise deferred[0]
        n_rel = int(result.n_updates)
        # the hook already wrote indices start+1..start+n_rel; this merge
        # re-writes them with the same values and is what the no-hook
        # (checkpoint_dir=None) path relies on
        lam_h[start + 1 : start + n_rel + 1] = np.asarray(
            result.lam_history[1 : n_rel + 1]
        )
        m_h[start + 1 : start + n_rel + 1] = np.asarray(
            result.m_history[1 : n_rel + 1]
        )
        u_h[start + 1 : start + n_rel + 1] = np.asarray(
            result.u_history[1 : n_rel + 1]
        )
        if compute_ll:
            # local indices 0..n_rel are all populated (in-loop at the
            # pre-update index, post-loop at n_rel)
            ll_h[start : start + n_rel + 1] = np.asarray(
                result.ll_history[: n_rel + 1]
            )
        params_dev = result.params
        done = start + n_rel
        converged = bool(result.converged)
        if checkpoint_dir is not None:
            # the last in-loop boundary save could not include the final
            # log likelihood (computed post-loop); re-save so the persisted
            # state is complete and a resume of a finished run reproduces
            # the uninterrupted run's Params exactly
            _save(done, converged)

    return EMResult(
        params=params_dev,
        n_updates=np.int32(done),
        converged=np.bool_(converged),
        lam_history=lam_h,
        m_history=m_h,
        u_history=u_h,
        ll_history=ll_h,
    )


def trimmed_trajectory(result: EMResult) -> dict:
    """Host-side convergence record of one EM run: the per-iteration log
    likelihood (entry 0 = the initial parameters, reference
    ``param_history`` layout; entry i = the likelihood under params i,
    None where not computed) plus update count and convergence flag —
    ONLY the series the Params history cannot reconstruct. The lambda
    path and max m/u movement live in the diagnostics event's
    ``trajectory`` payload (obs/quality._trajectory_payload); the full
    device histories stay in the result for callers that want them."""
    import numpy as np

    n = int(result.n_updates)
    ll = np.asarray(result.ll_history)[: n + 1]
    return {
        "n_updates": n,
        "converged": bool(result.converged),
        "ll": [None if np.isnan(v) else round(float(v), 4) for v in ll],
    }


@jax.jit
def score_pairs(G, params: FSParams):
    """Final E-step scoring: match probability for every pair."""
    with jax.named_scope("score"):
        return match_probability(G, params)


@jax.jit
def score_pairs_with_intermediates(G, params: FSParams):
    """Scoring plus the per-column m/u lookup probabilities the reference
    retains as prob_gamma_<col>_match / _non_match columns
    (/root/reference/splink/expectation_step.py:196-221)."""
    from .models.fellegi_sunter import gamma_prob_lookup

    with jax.named_scope("score"):
        p = match_probability(G, params)
        prob_m = gamma_prob_lookup(G, params.m)
        prob_u = gamma_prob_lookup(G, params.u)
        return p, prob_m, prob_u


@jax.jit
def score_pairs_with_logits(G, params: FSParams):
    """(p, fold_logit) — the logit is what the term-frequency fold adds
    its per-pair delta to (term_frequencies.make_tf_fold_fn). ``p`` stays
    the canonical ``match_probability`` (byte-identical to
    :func:`score_pairs`); the logit carries the FUSED serve kernel's
    left-to-right accumulation order, which is the TF parity anchor
    (fellegi_sunter.fold_logit docstring)."""
    from .models.fellegi_sunter import fold_logit

    with jax.named_scope("score"):
        return match_probability(G, params), fold_logit(G, params)


@jax.jit
def score_pairs_with_intermediates_logits(G, params: FSParams):
    """score_pairs_with_intermediates plus the fold logit (TF-fold jobs
    that also retain intermediate columns)."""
    from .models.fellegi_sunter import fold_logit, gamma_prob_lookup

    with jax.named_scope("score"):
        p = match_probability(G, params)
        prob_m = gamma_prob_lookup(G, params.m)
        prob_u = gamma_prob_lookup(G, params.u)
        return p, prob_m, prob_u, fold_logit(G, params)
