"""Ex-post term-frequency adjustment of match scores.

Implements the same formulas as the reference
(/root/reference/splink/term_frequencies.py, after moj-analytical-services
issue #17): for each flagged column, pairs that AGREE on a token get a
token-specific lambda (mean match probability among agreeing pairs),
Bayes-combined with (1 - lambda); disagreeing or null pairs are neutral
(0.5); the final ``tf_adjusted_match_prob`` Bayes-combines the base match
probability with every column adjustment.

Two implementations of the per-column aggregation:

  * device path (``compute_token_adjustment_device``): a jitted
    ``segment_sum`` over the encoded table's factorised token ids — the
    per-token lambda table is built on the TPU and gathered back per pair,
    the analogue of the reference's grouped aggregate + BROADCAST join
    (/root/reference/splink/term_frequencies.py:49-95). The linker uses this
    whenever the scored frame still corresponds 1:1 to its pair index.
  * host path (``compute_token_adjustment``): pandas groupby over the raw
    values, kept for arbitrary user-supplied frames (API parity — the
    reference accepts any df_e).
"""

from __future__ import annotations

import functools
import warnings

import numpy as np

from .params import Params
from .check_types import check_types
from .utils.profiling import dispatched, fetch, span


# rows a step of bayes_combine: its intermediates are three buffers of this
# length, reused, in place of seven arrays as long as the input — memory a
# process touches for the first time costs several times a warm write, and
# at frame length every temporary is fresh (the block is the one the frame
# writer's takes settled on)
_COMBINE_ROWS = 1 << 16


def bayes_combine(probs: list[np.ndarray]) -> np.ndarray:
    """prod(p) / (prod(p) + prod(1-p)) — the reference's sql_gen_bayes_string
    (/root/reference/splink/term_frequencies.py:21-46). float64 throughout;
    the only array of the inputs' length it allocates is the one it
    returns."""
    probs = [np.asarray(p) for p in probs]
    n = len(probs[0])
    if any(p.shape != (n,) for p in probs):
        raise ValueError(
            "bayes_combine takes one-dimensional arrays of one length, got "
            f"shapes {[p.shape for p in probs]}"
        )
    out = np.empty(n, np.float64)
    rows = min(n, _COMBINE_ROWS)
    num, den, tmp = (np.empty(rows, np.float64) for _ in range(3))
    tiny = np.finfo(np.float64).tiny
    for a in range(0, n, _COMBINE_ROWS):
        res = out[a : a + _COMBINE_ROWS]
        m = len(res)
        nu, de, t = num[:m], den[:m], tmp[:m]
        nu.fill(1.0)
        de.fill(1.0)
        for p in probs:
            # through float64 first: 1 - p of a float32 p is another number
            np.copyto(t, p[a : a + m])
            np.multiply(nu, t, out=nu)
            np.subtract(1.0, t, out=t)
            np.multiply(de, t, out=de)
        # contradictory evidence (some p exactly 1 AND some p exactly 0, or
        # underflow of both products) drives num and den both to 0; 0.5 is
        # the no-information posterior, matching the disagreeing-pair
        # convention below. On every other input the guarded division is
        # bit-identical to num / (num + den).
        np.add(nu, de, out=t)
        np.maximum(t, tiny, out=res)
        np.divide(nu, res, out=res)
        res[~(t > 0)] = 0.5
    return out


def compute_token_adjustment(values_l, values_r, match_probability, base_lambda):
    """Per-pair adjustment for one column.

    Returns (adj, lookup) where adj is 0.5 for pairs that disagree or are
    null, else the token's Bayes-adjusted lambda; lookup maps token value ->
    adjusted lambda (for diagnostics).
    """
    import pandas as pd

    values_l = np.asarray(values_l, dtype=object)
    values_r = np.asarray(values_r, dtype=object)
    p = np.asarray(match_probability, dtype=np.float64)

    sl, sr = pd.Series(values_l), pd.Series(values_r)
    agree = (
        sl.notna() & sr.notna() & (sl == sr).fillna(False)
    ).to_numpy(dtype=bool)
    adj = np.full(len(p), 0.5)
    if not agree.any():
        return adj, {}

    s = pd.Series(p[agree])
    keys = pd.Series(values_l[agree])
    adj_lambda = s.groupby(keys, sort=False).mean()
    # Bayes-combine each token lambda with (1 - base lambda)
    # (/root/reference/splink/term_frequencies.py:60)
    adjusted = bayes_combine(
        [adj_lambda.to_numpy(), np.full(len(adj_lambda), 1.0 - base_lambda)]
    )
    lookup = dict(zip(adj_lambda.index, adjusted))
    adj[agree] = keys.map(lookup).to_numpy(dtype=np.float64)
    return adj, lookup


def term_frequency_columns(settings: dict):
    """Ordered, deduplicated raw columns to TF-adjust: the col_name of every
    flagged comparison, and for a flagged custom/case_sql multi-column
    comparison each of its custom_columns_used — the token aggregation only
    needs raw values, not kernel knowledge, so any flagged comparison
    participates (the reference's selection at
    /root/reference/splink/term_frequencies.py:130-134 keys on col_name and
    would KeyError on a custom comparison; per-used-column adjustment is the
    natural extension of its per-column formula)."""
    out: dict[str, None] = {}
    for c in settings["comparison_columns"]:
        if not c.get("term_frequency_adjustments"):
            continue
        if "col_name" in c:
            out.setdefault(c["col_name"])
        else:
            used = tuple(c.get("custom_columns_used", ()))
            if used:
                _warn_custom_tf_once(used)
            for used_col in used:
                out.setdefault(used_col)
    return out.keys()


_custom_tf_warned = False


def _warn_custom_tf_once(used: tuple) -> None:
    """The reference does not support TF adjustment on custom comparisons
    (its selection keys on col_name, /root/reference/splink/
    term_frequencies.py:130-134); splink_tpu extends the per-column formula
    to each custom_columns_used. Announce the extension once so previously
    flagged configs know their scores now include these adjustments."""
    global _custom_tf_warned
    if _custom_tf_warned:
        return
    _custom_tf_warned = True
    import logging

    logging.getLogger("splink_tpu").warning(
        "term_frequency_adjustments on a custom comparison applies "
        "per-used-column adjustments to %s — an extension beyond the "
        "reference, which skipped custom comparisons (see docs/api.md).",
        list(used),
    )


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


# Device TF aggregation chunk size: bounds HBM use like pair_batch_size does
# for gammas/scoring, so the fast path holds in the streamed regime too.
TF_DEVICE_CHUNK = 1 << 24


@functools.lru_cache(maxsize=None)
def _device_token_stats_fn(num_segments: int):
    """Jitted per-chunk (sums, counts) accumulation over token ids."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def tf_token_stats(tid_l, tid_r, p, sums, counts):
        agree = (tid_l == tid_r) & (tid_l >= 0)
        af = agree.astype(p.dtype)
        # disagreeing (and padded, tid=-1) pairs go to the overflow bucket
        seg = jnp.where(agree, tid_l, num_segments - 1)
        sums = sums + jax.ops.segment_sum(p * af, seg, num_segments=num_segments)
        counts = counts + jax.ops.segment_sum(af, seg, num_segments=num_segments)
        return sums, counts

    return tf_token_stats


@functools.lru_cache(maxsize=None)
def _device_token_gather_fn(num_segments: int):
    """Jitted per-chunk gather of each pair's token adjustment."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def tf_token_gather(tid_l, tid_r, adjusted):
        agree = (tid_l == tid_r) & (tid_l >= 0)
        return jnp.where(
            agree, adjusted[jnp.minimum(tid_l, num_segments - 1)], 0.5
        )

    return tf_token_gather


def compute_token_adjustment_device(
    tid_l, tid_r, match_probability, base_lambda, n_tokens: int
):
    """Device-side per-column adjustment over factorised token ids.

    Same formulas as compute_token_adjustment, but the segment mean over
    agreeing pairs runs as jitted segment_sums on the accelerator instead of
    a host groupby over object arrays. Processes the pair axis in
    TF_DEVICE_CHUNK chunks so HBM use stays bounded at any pair count.
    Returns (adj, tok_lambda, counts) — per-pair adjustment plus the
    per-token-id lambda table and agree-counts (diagnostics).
    """
    import jax
    import jax.numpy as jnp

    # f64 when enabled (CPU test tier: bit-parity with the host oracle);
    # f32 on TPU, where f64 doesn't exist.
    dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    num_segments = _next_pow2(n_tokens + 1)
    n = len(tid_l)
    if n == 0:
        z = np.zeros(num_segments)
        return np.zeros(0, np.float64), z, z
    chunk = min(TF_DEVICE_CHUNK, max(n, 1))

    def chunks_of(a, fill):
        for s in range(0, n, chunk):
            piece = a[s : s + chunk]
            if len(piece) < chunk:
                piece = np.concatenate(
                    [piece, np.full(chunk - len(piece), fill, piece.dtype)]
                )
            yield s, piece

    p_host = np.asarray(match_probability)
    stats_fn = _device_token_stats_fn(num_segments)
    sums = jnp.zeros(num_segments, dtype)
    counts = jnp.zeros(num_segments, dtype)
    for (s, cl), (_, cr) in zip(
        chunks_of(np.asarray(tid_l), -1), chunks_of(np.asarray(tid_r), -1)
    ):
        pc = p_host[s : s + chunk]
        if len(pc) < chunk:
            pc = np.concatenate([pc, np.zeros(chunk - len(pc), pc.dtype)])
        with span("h2d_put", bytes=cl.nbytes + cr.nbytes + pc.nbytes):
            chunk_dev = jnp.asarray(cl), jnp.asarray(cr), jnp.asarray(pc, dtype)
        sums, counts = stats_fn(*chunk_dev, sums, counts)
        dispatched("tf_token_stats", counts, rows=chunk)

    tok_lambda = sums / jnp.maximum(counts, 1.0)
    # Bayes-combine each token lambda with (1 - base lambda)
    # (/root/reference/splink/term_frequencies.py:60)
    num = tok_lambda * (1.0 - jnp.asarray(base_lambda, dtype))
    den = (1.0 - tok_lambda) * jnp.asarray(base_lambda, dtype)
    # tok_lambda and base_lambda both exactly 0 (or both exactly 1) zero
    # both terms; 0.5 is the no-adjustment value the gather pads with.
    # Everywhere else the guarded division is bit-identical.
    tot = num + den
    adjusted = jnp.where(
        tot > 0,
        num / jnp.maximum(tot, jnp.finfo(dtype).tiny),
        jnp.asarray(0.5, dtype),
    )

    gather_fn = _device_token_gather_fn(num_segments)
    adj = np.empty(n, np.float64)
    pending = None
    for (s, cl), (_, cr) in zip(
        chunks_of(np.asarray(tid_l), -1), chunks_of(np.asarray(tid_r), -1)
    ):
        with span("h2d_put", bytes=cl.nbytes + cr.nbytes):
            chunk_dev = jnp.asarray(cl), jnp.asarray(cr)
        out = gather_fn(*chunk_dev, adjusted)
        dispatched("tf_token_gather", out, rows=chunk)
        if pending is not None:
            ps, pout = pending
            adj[ps : ps + chunk] = fetch(pout)[: max(0, min(chunk, n - ps))]
        pending = (s, out)
    ps, pout = pending
    adj[ps : ps + chunk] = fetch(pout)[: max(0, min(chunk, n - ps))]
    return adj, fetch(tok_lambda), fetch(counts)


# ---------------------------------------------------------------------------
# Serve-time u-probability fold (the first-class scoring step)
#
# The ex-post lambda aggregation above needs the whole scored batch (the
# per-token lambda IS a batch statistic), so it can never run inside a
# serve dispatch. The fold below is the Fellegi-Sunter-native alternative:
# for a TF-flagged comparison whose two sides AGREE on a token t, the
# average u-probability of the comparison's top (exact-agreement) level is
# replaced by the token's own collision probability tf(t) = count(t) / N —
# "John Smith" pairs stop borrowing the rarity of the average surname. In
# log space that is one per-pair delta per TF column,
#
#     delta_c = [tid_l == tid_r >= 0] * (log u_c[L_c - 1] - log tf(t))
#
# folded into the running log-Bayes-factor:
#
#     p_tf = sigmoid(match_logit + sum_c delta_c)
#
# The SAME expression (same table values, same accumulation order, same
# association) runs inside the fused serve megakernel
# (serve/engine.make_score_fused_fn), the unfused serve oracle, and the
# offline fold kernel below — which is what makes serve<->offline and
# fused<->unfused TF-adjusted scores bit-identical, not merely close.
# ---------------------------------------------------------------------------


def tf_fold_spec(settings: dict) -> tuple:
    """((gamma_index, col_name, top_level), ...) for every comparison the
    u-probability fold can serve: TF-flagged, plain ``col_name`` form (the
    u table is per comparison, so a custom multi-column comparison has no
    single token column to fold — those keep the ex-post path and are
    announced by :func:`_warn_custom_tf_once`). ``top_level`` is the
    comparison's exact-agreement gamma level ``num_levels - 1``: a pair
    that agrees on the token sits at that level under every shipped
    comparison kind, so the delta swaps exactly that level's u."""
    out = []
    for ci, c in enumerate(settings["comparison_columns"]):
        if not c.get("term_frequency_adjustments"):
            continue
        if "col_name" not in c:
            used = tuple(c.get("custom_columns_used", ()))
            if used:
                _warn_custom_tf_once(used)
            continue
        out.append((ci, c["col_name"], int(c["num_levels"]) - 1))
    return tuple(out)


def tf_log_table(counts: np.ndarray) -> np.ndarray:
    """(n_tokens,) float64 ``log(count / total)`` relative-frequency table
    for one TF column. Computed ONCE host-side (numpy) and consumed as
    data by both the serve megakernel and the offline fold kernel — the
    two paths gather from arrays with identical values, so no
    cross-library log implementation can split their bits. Zero counts
    (never observed tokens) floor at one occurrence."""
    counts = np.asarray(counts, np.float64)
    total = max(float(counts.sum()), 1.0)
    return np.log(np.maximum(counts, 1.0) / total)


def tf_fold_delta(tid_l, tid_r, log_tf, log_u_top, dtype):
    """The canonical per-column fold delta (traced; the ONE expression
    shared by the serve kernels and :func:`make_tf_fold_fn` — the
    bit-parity contract forbids it forking). Disagreeing or null pairs
    contribute exactly 0."""
    import jax.numpy as jnp

    agree = (tid_l == tid_r) & (tid_l >= 0)
    idx = jnp.clip(tid_l, 0, log_tf.shape[0] - 1)
    zero = jnp.zeros((), dtype)
    return jnp.where(agree, log_u_top - log_tf[idx], zero)


@functools.lru_cache(maxsize=None)
def make_tf_fold_fn(spec: tuple):
    """Jitted offline fold: ``fn(z, u, tid_l.., tid_r.., log_tf..) -> p_tf``
    where ``z`` is :func:`..models.fellegi_sunter.match_logit` for the
    pairs, ``u`` the (C, L) u-probability table in the compute dtype, and
    per spec column one (n,) int32 token-id pair plus the
    :func:`tf_log_table` values cast to the compute dtype. Mirrors the
    fused serve kernel's tail step for step (``_safe_log(u)`` lookup, the
    left-to-right delta accumulation, ``sigmoid(z + tf_sum)``)."""
    import jax
    import jax.numpy as jnp

    from .models.fellegi_sunter import _safe_log

    n_tf = len(spec)

    @jax.jit
    def tf_fold(z, u, *arrs):
        tid_l = arrs[:n_tf]
        tid_r = arrs[n_tf : 2 * n_tf]
        log_tf = arrs[2 * n_tf :]
        log_u = _safe_log(u)
        tf_sum = jnp.zeros(z.shape, z.dtype)
        for t, (ci, _name, top) in enumerate(spec):
            tf_sum = tf_sum + tf_fold_delta(
                tid_l[t], tid_r[t], log_tf[t], log_u[ci, top], z.dtype
            )
        return jax.nn.sigmoid(z + tf_sum)

    return tf_fold


@check_types
def make_adjustment_for_term_frequencies(
    df_e,
    params: Params,
    settings: dict,
    retain_adjustment_columns: bool = False,
    pair_token_ids: dict | None = None,
):
    """Add ``tf_adjusted_match_prob`` to a scored comparisons frame.

    Returns a NEW frame: ``tf_adjusted_match_prob`` and ``match_probability``
    lead, as in the reference, then the other columns of ``df_e`` in their
    order, then (with ``retain_adjustment_columns``) one ``<col>_adj`` a
    flagged column. The pass reads ``match_probability`` (and, on the host
    path, the flagged columns' values) and allocates at the frame's length
    only the columns it adds: every column of ``df_e`` reaches the result
    as it is, its bytes neither read nor written (pandas' copy-on-write).
    ``df_e`` has the same columns and values after the call as before, and
    a write through pandas into either frame never shows in the other.

    pair_token_ids (optional, supplied by the linker): maps column name ->
    (tid_l, tid_r, n_tokens) int32 arrays aligned with df_e's rows; when
    present the per-token aggregation runs on device instead of a host
    groupby.
    """
    tf_cols = list(term_frequency_columns(settings))
    if not tf_cols:
        warnings.warn(
            "No term frequency adjustment columns are specified in your "
            "settings object. Returning original df"
        )
        return df_e

    import pandas as pd

    # tf_frame: the host work round the frame (the combine, the new frame);
    # its self time excludes the tf_device spans below it
    with span("tf_frame", rows=len(df_e)) as sp:
        p = df_e["match_probability"].to_numpy()
        base_lambda = params.params["λ"]
        adj = {}
        for col in tf_cols:
            if pair_token_ids is not None and col in pair_token_ids:
                tid_l, tid_r, n_tokens = pair_token_ids[col]
                with span("tf_device", rows=len(df_e)):
                    adj[f"{col}_adj"], _, _ = compute_token_adjustment_device(
                        tid_l, tid_r, p, base_lambda, n_tokens
                    )
            else:
                adj[f"{col}_adj"], _ = compute_token_adjustment(
                    df_e[f"{col}_l"].to_numpy(dtype=object),
                    df_e[f"{col}_r"].to_numpy(dtype=object),
                    p,
                    base_lambda,
                )

        # Column order: tf_adjusted_match_prob leads, as in the reference
        # (/root/reference/splink/term_frequencies.py:108-115). A Series of
        # df_e carries its reference to df_e's memory into the new frame
        # (copy-on-write); the new columns are arrays nobody else holds.
        new = {"tf_adjusted_match_prob": bayes_combine([p, *adj.values()])}
        if retain_adjustment_columns:
            new.update(adj)
        order = dict.fromkeys(
            ["tf_adjusted_match_prob", "match_probability", *df_e.columns, *new]
        )
        names = [c for c in order if retain_adjustment_columns or c not in adj]
        sp.count(shared_columns=len(names) - len(new), added_columns=len(new))
        return pd.DataFrame(
            {c: new[c] if c in new else df_e[c] for c in names},
            index=df_e.index,
            copy=False,
        )
