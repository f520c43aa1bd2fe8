"""Pallas TPU kernel for batched Jaro-Winkler similarity.

The pure-JAX implementation (splink_tpu/ops/strings.py) runs the greedy
matching scan as vmapped (L,)-vector steps, which XLA executes as L
sequential HBM-resident kernels. This kernel instead keeps the whole working
set of a lane-tile of pairs in VMEM/registers:

  * layout: the PAIR axis rides the 128 VPU lanes, the character axis rides
    sublanes — inputs arrive transposed as (L, B) float32 so one (L, T) tile
    holds T complete pairs;
  * the greedy pass unrolls the L (static, <= 32) steps in-register;
  * every prefix count ("first eligible partner", match ranks, common-prefix
    run) is a small lower-triangular (L, L) x (L, T) matmul on the MXU —
    no cumsum primitive, no scatters, no per-pair control flow;
  * transposition counting walks the L match ranks, selecting each side's
    k-th matched character with compare-and-mask sublane reductions.

Semantics are identical to strings.jaro_winkler (jar-exact commons-text
JaroWinklerDistance: shorter-over-longer matching, integer-halved
transpositions, uncapped prefix with min(0.1, 1/maxlen) scaling, boost
only at jaro >= 0.7), which the tests enforce against the jar bytecode's
golden vectors. ASCII-width-<=32 columns dispatch here on TPU;
wide-unicode or long columns fall back to the vmapped implementation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE_TILE = 512  # pairs per grid step
MAX_PALLAS_WIDTH = 32


def _tril(L: int, strict: bool) -> jnp.ndarray:
    r = jnp.arange(L, dtype=jnp.int32)
    return (r[:, None] > r[None, :] if strict else r[:, None] >= r[None, :]).astype(
        jnp.float32
    )


def _jw_kernel(s1_ref, s2_ref, l1_ref, l2_ref, out_ref, *, L, prefix_scale,
               boost_threshold):
    s1 = s1_ref[:]  # (L, T) f32 character codes (0 = padding)
    s2 = s2_ref[:]
    l1 = l1_ref[:]  # (1, T) f32 lengths
    l2 = l2_ref[:]

    incl = _tril(L, strict=False)  # inclusive prefix-count operator
    # Mosaic requires integer iota; widen to f32 afterwards.
    iota = jax.lax.broadcasted_iota(jnp.int32, (L, s1.shape[1]), 0).astype(
        jnp.float32
    )
    valid2 = iota < l2
    maxlen = jnp.maximum(l1, l2)
    window = jnp.maximum(jnp.floor(maxlen * 0.5) - 1.0, 0.0)

    # Greedy matching: step i claims the first in-window unused s2 position
    # with the same character. used2/matched1 are (L, T) f32 0/1 masks.
    used2 = jnp.zeros_like(s1)
    matched1_rows = []
    for i in range(L):
        ch = s1[i : i + 1, :]  # (1, T)
        cand = (
            (s2 == ch)
            & (jnp.abs(iota - i) <= window)
            & valid2
            & (used2 < 0.5)
            & (i < l1)
        ).astype(jnp.float32)
        prefix = jnp.dot(incl, cand, preferred_element_type=jnp.float32)
        first = cand * (prefix == 1.0)
        used2 = used2 + first
        matched1_rows.append(jnp.sum(first, axis=0, keepdims=True))
    matched1 = jnp.concatenate(matched1_rows, axis=0)  # (L, T)
    m = jnp.sum(matched1, axis=0, keepdims=True)  # (1, T)

    # Half transpositions: compare the k-th matched character of each side.
    # rank = exclusive prefix count of the match mask (MXU matmul).
    strict = _tril(L, strict=True)
    r1 = jnp.dot(strict, matched1, preferred_element_type=jnp.float32)
    r2 = jnp.dot(strict, used2, preferred_element_type=jnp.float32)
    t_half = jnp.zeros_like(m)
    for k in range(L):
        sel1 = matched1 * (r1 == k)  # one-hot over sublanes per lane
        sel2 = used2 * (r2 == k)
        c1 = jnp.sum(s1 * sel1, axis=0, keepdims=True)
        c2 = jnp.sum(s2 * sel2, axis=0, keepdims=True)
        t_half = t_half + ((c1 != c2) & (k < m)).astype(jnp.float32)

    # Jar semantics (commons-text JaroWinklerDistance, see strings.py):
    # transpositions are INTEGER-halved; the boost applies only when
    # jaro >= threshold, with an UNCAPPED prefix run and a scaling factor
    # of min(prefix_scale, 1/maxlen); m == 0 (incl. both empty) -> 0.0.
    t = jnp.floor(t_half * 0.5)
    safe = jnp.maximum(m, 1.0)
    jaro = (
        m / jnp.maximum(l1, 1.0) + m / jnp.maximum(l2, 1.0) + (m - t) / safe
    ) / 3.0
    jaro = jnp.where(m > 0, jaro, 0.0)

    # ell = length of the common prefix, found as the count of positions
    # whose inclusive prefix of mismatches is zero.
    neq = ((s1 != s2) | (iota >= l1) | (iota >= l2)).astype(jnp.float32)
    mismatches_before = jnp.dot(incl, neq, preferred_element_type=jnp.float32)
    ell = jnp.sum(
        (mismatches_before == 0.0).astype(jnp.float32), axis=0, keepdims=True
    )
    scale = jnp.minimum(prefix_scale, 1.0 / jnp.maximum(maxlen, 1.0))
    boosted = jaro + ell * scale * (1.0 - jaro)
    jw = jnp.where(jaro < boost_threshold, jaro, boosted)
    out_ref[:] = jnp.where(m > 0, jw, 0.0)


@functools.partial(
    jax.jit, static_argnames=("prefix_scale", "boost_threshold", "interpret")
)
def jaro_winkler_pallas(
    s1, s2, l1, l2, prefix_scale=0.1, boost_threshold=0.7, interpret=False
):
    """Batched Jaro-Winkler via the Pallas lane-tile kernel.

    Args: s1, s2 (B, L) integer character codes (<= 2^23 so float32 equality
    is exact); l1, l2 (B,) lengths. Returns (B,) float32.
    """
    B, L = s1.shape
    # jar semantics: the greedy match iterates the SHORTER string over the
    # longer (see strings.jaro_winkler_single) — swap per pair up front so
    # the kernel's scan bound (l1) is always the short side
    swap = l1 > l2
    s1, s2 = (
        jnp.where(swap[:, None], s2, s1),
        jnp.where(swap[:, None], s1, s2),
    )
    l1, l2 = jnp.minimum(l1, l2), jnp.maximum(l1, l2)
    T = min(LANE_TILE, max(B, 1))
    pad = (-B) % T
    if pad:
        zf = lambda a, v=0: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))  # noqa: E731
        s1, s2, l1, l2 = zf(s1), zf(s2), zf(l1), zf(l2)
    n = s1.shape[0]

    s1T = s1.astype(jnp.float32).T  # (L, n)
    s2T = s2.astype(jnp.float32).T
    l1r = l1.astype(jnp.float32).reshape(1, n)
    l2r = l2.astype(jnp.float32).reshape(1, n)

    kernel = functools.partial(
        _jw_kernel, L=L, prefix_scale=prefix_scale, boost_threshold=boost_threshold
    )
    col = lambda i: (0, i)  # noqa: E731
    out = pl.pallas_call(
        kernel,
        grid=(n // T,),
        in_specs=[
            pl.BlockSpec((L, T), col, memory_space=pltpu.VMEM),
            pl.BlockSpec((L, T), col, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, T), col, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, T), col, memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, T), col, memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
    )(s1T, s2T, l1r, l2r)
    return out[0, :B]


def _shift_down(x, s, fill):
    """Shift rows down by s sublanes, filling the top with `fill`.

    Mosaic rejects jnp.concatenate inside unrolled loops (the round-1 kernel
    SIGABRTed the TPU compiler), so this uses a circular roll plus an iota
    mask, which lowers to a plain VPU shift.
    """
    rolled = pltpu.roll(x, shift=s, axis=0)
    ridx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(ridx < s, fill, rolled)


def _lev_kernel(s1_ref, s2p_ref, l1_ref, l2_ref, out_ref, *, L):
    """Levenshtein row DP, pairs on lanes, DP row (L+1) on sublanes.

    Row recurrence (strings.levenshtein_single): the insertion chain is a
    prefix-min, computed here by log-step sublane shifts:
        new[j] = j + cummin_{k<=j}(min(prev[k] + 1, prev[k-1] + cost[k]) - k)

    s2p arrives pre-shifted from the wrapper as (L+1, T) with a sentinel in
    row 0 (s2p[j] = s2[j-1]), so the kernel body is concatenate-free.
    """
    s1 = s1_ref[:]  # (L, T)
    s2p = s2p_ref[:]  # (L+1, T), row 0 = sentinel
    l1 = l1_ref[:]  # (1, T)
    l2 = l2_ref[:]
    big = 1e9

    idx = jax.lax.broadcasted_iota(jnp.int32, (L + 1, s1.shape[1]), 0).astype(
        jnp.float32
    )  # 0..L
    row = idx  # row 0: distance from empty prefix
    for i in range(L):
        ch = s1[i : i + 1, :]
        cost = (s2p != ch).astype(jnp.float32)  # (L+1, T); cost[0] unused
        row_prev = _shift_down(row, 1, big)  # row[j-1], big at j=0
        # position 0 resolves to the deletion base row[0]+1 == i+1
        t = jnp.minimum(row_prev + cost, row + 1.0)
        m = t - idx
        s = 1
        while s <= L:
            m = jnp.minimum(m, _shift_down(m, s, big))
            s *= 2
        new_row = idx + m
        row = jnp.where(i < l1, new_row, row)

    # read entry l2 of the final row, per lane
    sel = (idx == l2).astype(jnp.float32)
    out_ref[:] = jnp.sum(row * sel, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def levenshtein_pallas(s1, s2, l1, l2, interpret=False):
    """Batched Levenshtein distance via the Pallas lane-tile kernel.

    Args: s1, s2 (B, L) integer character codes; l1, l2 (B,) lengths.
    Returns (B,) float32 distances.
    """
    B, L = s1.shape
    T = min(LANE_TILE, max(B, 1))
    pad = (-B) % T
    if pad:
        zf = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))  # noqa: E731
        s1, s2, l1, l2 = zf(s1), zf(s2), zf(l1), zf(l2)
    n = s1.shape[0]

    s1T = s1.astype(jnp.float32).T
    # pre-shift s2 on the host side of the kernel: s2p[j] = s2[j-1], row 0 a
    # sentinel no real character code equals (codes are non-negative)
    s2pT = jnp.concatenate(
        [jnp.full((1, n), -1.0, jnp.float32), s2.astype(jnp.float32).T], axis=0
    )
    l1r = l1.astype(jnp.float32).reshape(1, n)
    l2r = l2.astype(jnp.float32).reshape(1, n)

    col = lambda i: (0, i)  # noqa: E731
    out = pl.pallas_call(
        functools.partial(_lev_kernel, L=L),
        grid=(n // T,),
        in_specs=[
            pl.BlockSpec((L, T), col, memory_space=pltpu.VMEM),
            pl.BlockSpec((L + 1, T), col, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, T), col, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, T), col, memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, T), col, memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
    )(s1T, s2pT, l1r, l2r)
    return out[0, :B]


def pallas_supported(s1) -> bool:
    """Whether the Pallas path handles this input on the current backend."""
    return (
        jax.default_backend() == "tpu"
        and s1.ndim == 2
        and s1.shape[1] <= MAX_PALLAS_WIDTH
        and s1.dtype == jnp.uint8
    )
