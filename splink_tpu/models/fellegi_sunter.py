"""Fellegi-Sunter model mathematics as pure JAX functions.

This is the model the reference estimates via generated SQL: the E-step's
naive-Bayes match probability (/root/reference/splink/expectation_step.py:167-185)
and the M-step's grouped sufficient statistics
(/root/reference/splink/maximisation_step.py:41-90). Differences from the
reference are deliberate TPU-first choices:

  * Scoring works in log space (the reference multiplies raw doubles and
    needed a tiny-number regression test for underflow; summing log ratios
    plus a sigmoid is exact and underflow-free).
  * The M-step's SQL ``GROUP BY`` over all gamma combinations becomes a
    one-hot reduction (an (n, C, Lmax) mask contracted against the match
    probabilities) which XLA lowers to MXU-friendly reductions and, when the
    pair axis is sharded over a device mesh, to ``psum`` collectives over ICI.
  * gamma = -1 (null) semantics match the reference exactly: nulls contribute
    probability 1 to both numerator and denominator in scoring, and rows are
    excluded from a column's M-step normaliser when that column is null
    (/root/reference/splink/maximisation_step.py:68-69).

Shapes: G is (n_pairs, n_cols) int8 with entries in {-1, 0, .., L_c - 1};
m/u are (n_cols, max_levels); weights is (n_pairs,) with 0 marking padding.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class FSParams(NamedTuple):
    """Device-side Fellegi-Sunter parameters (the traced EM state)."""

    lam: jnp.ndarray  # scalar: prior P(match)
    m: jnp.ndarray  # (C, L): P(gamma = level | match)
    u: jnp.ndarray  # (C, L): P(gamma = level | non-match)


class SufficientStats(NamedTuple):
    """Streaming-accumulable EM sufficient statistics."""

    m_num: jnp.ndarray  # (C, L): sum of p over rows with gamma_c = level
    u_num: jnp.ndarray  # (C, L): sum of 1-p over rows with gamma_c = level
    m_den: jnp.ndarray  # (C,): sum of p over rows with gamma_c != -1
    u_den: jnp.ndarray  # (C,): sum of 1-p over rows with gamma_c != -1
    sum_p: jnp.ndarray  # scalar: sum of p over all rows
    n_rows: jnp.ndarray  # scalar: number of (real) rows

    def __add__(self, other: "SufficientStats") -> "SufficientStats":
        return SufficientStats(*(a + b for a, b in zip(self, other)))

    @staticmethod
    def zeros(n_cols: int, max_levels: int, dtype=jnp.float32) -> "SufficientStats":
        return SufficientStats(
            m_num=jnp.zeros((n_cols, max_levels), dtype),
            u_num=jnp.zeros((n_cols, max_levels), dtype),
            m_den=jnp.zeros((n_cols,), dtype),
            u_den=jnp.zeros((n_cols,), dtype),
            sum_p=jnp.zeros((), dtype),
            n_rows=jnp.zeros((), dtype),
        )


def _safe_log(x):
    return jnp.log(jnp.maximum(x, jnp.finfo(x.dtype).tiny))


def _select_levels(G, table):
    """(n, C) table[c, G[n, c]] via unrolled compare-and-mask (gather-free).

    TPU gathers serialise badly; with max_levels <= ~4 a masked sum over the
    static level axis is pure VPU work: out = sum_l table[:, l] * [G == l].
    Entries where G = -1 come out as 0."""
    L = table.shape[1]
    out = jnp.zeros(G.shape, table.dtype)
    for lv in range(L):
        out = out + jnp.where(G == lv, table[None, :, lv], jnp.zeros((), table.dtype))
    return out


def gamma_log_probs(G, probs):
    """(n, C) log prob of each row's gamma level under `probs`; 0 where null."""
    lp = _select_levels(G, _safe_log(probs))
    return jnp.where(G >= 0, lp, jnp.zeros((), lp.dtype))


def log_bayes_factor(G, params: FSParams):
    """(n,) per-column log(m/u) evidence, accumulated COLUMN BY COLUMN,
    left to right — :func:`fold_logit`'s order, pinned here too.

    A ``jnp.sum`` over the column axis leaves the association order to the
    backend's reduce lowering: XLA CPU happens to walk six columns in
    order, the TPU's reduce associates differently, and the offline score
    then sits a few float32 ulps from the fused serve kernel's running
    accumulator (first seen on the chip, PR 21: 27 of 1197 pairs, worst
    21 ulps). The column count is static and small, so the explicit chain
    costs nothing a reduce would save."""
    evidence = gamma_log_probs(G, params.m) - gamma_log_probs(G, params.u)
    log_bf = jnp.zeros(evidence.shape[:-1], evidence.dtype)
    for ci in range(evidence.shape[-1]):
        log_bf = log_bf + evidence[..., ci]
    return log_bf


def match_logit(G, params: FSParams):
    """(n,) pre-sigmoid match evidence: logit(lambda) + log Bayes factor,
    bit for bit :func:`fold_logit` on every backend (same per-column
    values, same left-to-right accumulation): the served score of a pair
    and its offline score are the same float."""
    lam = params.lam
    prior_logit = _safe_log(lam) - _safe_log(1.0 - lam)
    return prior_logit + log_bayes_factor(G, params)


def match_probability(G, params: FSParams):
    """E-step: P(match | gamma vector) = sigmoid(logit(lambda) + log BF)."""
    return jax.nn.sigmoid(match_logit(G, params))


def fold_logit(G, params: FSParams):
    """:func:`match_logit` with the log-Bayes-factor accumulated COLUMN BY
    COLUMN, left to right — the exact expression tree of the fused serve
    megakernel (serve/engine.make_score_fused_fn), per-column masked
    level lookups included.

    Bit-identical to ``match_logit``, which pins the same accumulation
    order over (n, C)-shaped intermediates (:func:`log_bayes_factor`); the
    numerics audit's NA-ORD rule holds both to a host left-to-right
    reference. The TF fold anchors on THIS logit on every path (fused
    serve, unfused serve oracle, offline fold kernel), which is what makes
    the TF-adjusted scores bit-identical across all of them at any column
    count."""
    lam = params.lam
    prior_logit = _safe_log(lam) - _safe_log(1.0 - lam)
    log_m = _safe_log(params.m)
    log_u = _safe_log(params.u)
    n_levels = log_m.shape[1]
    log_bf = jnp.zeros(G.shape[0], log_m.dtype)
    for ci in range(G.shape[1]):
        g = G[:, ci]
        lp_m = jnp.zeros(g.shape, log_m.dtype)
        lp_u = jnp.zeros(g.shape, log_u.dtype)
        for lv in range(n_levels):
            hit = g == lv
            zero = jnp.zeros((), log_m.dtype)
            lp_m = lp_m + jnp.where(hit, log_m[ci, lv], zero)
            lp_u = lp_u + jnp.where(hit, log_u[ci, lv], zero)
        null = g >= 0
        zero = jnp.zeros((), log_m.dtype)
        log_bf = log_bf + (
            jnp.where(null, lp_m, zero) - jnp.where(null, lp_u, zero)
        )
    return prior_logit + log_bf


def gamma_prob_lookup(G, probs):
    """(n, C) probability of the observed gamma under `probs`, 1.0 where null.

    This is the reference's per-column prob_gamma_* lookup column
    (/root/reference/splink/expectation_step.py:196-221)."""
    p = _select_levels(G, probs)
    return jnp.where(G >= 0, p, jnp.ones((), p.dtype))


def log_likelihood(G, params: FSParams, weights=None):
    """Sum over rows of ln(lam * prod m + (1-lam) * prod u), log-space safe."""
    log_m = jnp.sum(gamma_log_probs(G, params.m), axis=-1)
    log_u = jnp.sum(gamma_log_probs(G, params.u), axis=-1)
    ll_rows = jnp.logaddexp(
        _safe_log(params.lam) + log_m, _safe_log(1.0 - params.lam) + log_u
    )
    if weights is not None:
        ll_rows = ll_rows * weights
    return jnp.sum(ll_rows)


def sufficient_stats(G, p_match, max_levels: int, weights=None) -> SufficientStats:
    """M-step sufficient statistics from one (shard of a) batch of pairs.

    ``max_levels`` must be static (it fixes the stats shape). Every reduction
    is over the pair axis, so under a sharded-pair jit these lower to
    per-device partial sums + psum over the mesh.
    """
    dtype = p_match.dtype
    if weights is None:
        weights = jnp.ones(p_match.shape, dtype)
    pm = p_match * weights
    pu = (1.0 - p_match) * weights

    onehot = (
        G[:, :, None] == jnp.arange(max_levels, dtype=G.dtype)[None, None, :]
    ).astype(dtype)  # (n, C, max_levels)
    m_num = jnp.einsum("ncl,n->cl", onehot, pm)
    u_num = jnp.einsum("ncl,n->cl", onehot, pu)

    valid = (G >= 0).astype(dtype)  # (n, C)
    m_den = jnp.einsum("nc,n->c", valid, pm)
    u_den = jnp.einsum("nc,n->c", valid, pu)

    return SufficientStats(
        m_num=m_num,
        u_num=u_num,
        m_den=m_den,
        u_den=u_den,
        sum_p=jnp.sum(pm),
        n_rows=jnp.sum(weights),
    )


def update_params(stats: SufficientStats) -> FSParams:
    """M-step parameter update from accumulated sufficient statistics.

    Levels never observed get probability exactly 0, reproducing the
    reference's zero-fill for unseen gamma values
    (/root/reference/splink/params.py:256-274).
    """
    eps = jnp.finfo(stats.m_num.dtype).tiny
    new_m = stats.m_num / jnp.maximum(stats.m_den, eps)[:, None]
    new_u = stats.u_num / jnp.maximum(stats.u_den, eps)[:, None]
    new_lam = stats.sum_p / jnp.maximum(stats.n_rows, eps)
    return FSParams(lam=new_lam, m=new_m, u=new_u)


def em_step(G, params: FSParams, max_levels: int, weights=None):
    """One fused E+M step. Returns (new_params, max_pi_delta)."""
    p = match_probability(G, params)
    stats = sufficient_stats(G, p, max_levels, weights)
    new = update_params(stats)
    delta = jnp.maximum(
        jnp.max(jnp.abs(new.m - params.m)), jnp.max(jnp.abs(new.u - params.u))
    )
    return new, delta
