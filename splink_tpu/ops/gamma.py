"""Similarity -> discrete gamma-level bucketing.

Replaces the reference's SQL CASE threshold chains
(/root/reference/splink/case_statements.py:62-246) with branch-free vector
arithmetic: since a similarity exceeding the top threshold also exceeds every
lower one, the level is simply the count of thresholds passed. Null inputs map
to gamma = -1 (the "uninformative" pseudo-level) exactly as in the reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

GAMMA_DTYPE = jnp.int8


def bucket_similarity(sim, thresholds, null_mask):
    """Levels from a similarity score with *descending* thresholds.

    thresholds[0] gates the top level: gamma = #\\{i : sim > thresholds[i]\\}.
    E.g. thresholds (0.94, 0.88): sim > 0.94 -> 2, sim in (0.88, 0.94] -> 1.
    """
    with jax.named_scope("levels"):
        gamma = jnp.zeros(sim.shape, dtype=GAMMA_DTYPE)
        for t in thresholds:
            gamma = gamma + (sim > t).astype(GAMMA_DTYPE)
        return apply_null(gamma, null_mask)


def bucket_difference(diff, thresholds, null_mask):
    """Levels from a difference/distance with *ascending* thresholds.

    thresholds[0] gates the top level: gamma = #\\{i : diff < thresholds[i]\\}.
    E.g. thresholds (1e-4, 0.05): diff < 1e-4 -> 2, diff in [1e-4, 0.05) -> 1.
    """
    with jax.named_scope("levels"):
        gamma = jnp.zeros(diff.shape, dtype=GAMMA_DTYPE)
        for t in thresholds:
            gamma = gamma + (diff < t).astype(GAMMA_DTYPE)
        return apply_null(gamma, null_mask)


def _two_ulps_up(t, dtype):
    t = np.asarray(t, dtype)
    return np.nextafter(np.nextafter(t, np.inf, dtype=dtype), np.inf, dtype=dtype)


def bucket_difference_le(diff, thresholds, null_mask, equal, top_level):
    """Levenshtein-style levels: exact equality takes the top level, then
    ascending ``<=`` thresholds fill the middle levels
    (cf. /root/reference/splink/case_statements.py:117-141).

    Ties: a ratio that equals a threshold as a rational number (1 / 5 against
    0.2, 3 / 7.5 against 0.4) takes the level on every backend, so each
    threshold is applied two ulps wide — the TPU's float32 division is good
    to one ulp only (it lost 88 of 324 such ties: PERF.md §6, PR 33), and no
    other quotient of an edit distance and a mean length comes that close."""
    with jax.named_scope("levels"):
        gamma = jnp.zeros(diff.shape, dtype=GAMMA_DTYPE)
        for t in thresholds:
            gamma = gamma + (diff <= _two_ulps_up(t, diff.dtype)).astype(GAMMA_DTYPE)
        gamma = jnp.where(equal, jnp.asarray(top_level, GAMMA_DTYPE), gamma)
        return apply_null(gamma, null_mask)


def apply_null(gamma, null_mask):
    """gamma = -1 wherever either side of the comparison is null."""
    if null_mask is None:
        return gamma
    return jnp.where(null_mask, jnp.asarray(-1, GAMMA_DTYPE), gamma)
