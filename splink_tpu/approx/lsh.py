"""LSH-bucket candidate generation, q-gram verification, progressive budget.

The approximate tier of :func:`..blocking.block_using_rules`:

  1. **signatures** — :mod:`.minhash` band keys over the approx columns,
     factorised to dense int32 codes per band;
  2. **candidates** — each band is a symmetric hash join on its band
     codes, run through the SAME device machinery as the exact tier
     (``blocking_device``'s segmented sort, bounded triangle/rectangle
     units and the chunked pair-emit kernel): band ``b``'s kernel carries
     bands ``0..b-1`` as its sequential-dedup predecessors, so every
     colliding pair is emitted exactly once (by its first colliding band);
     pairs any EXACT rule produced are dropped host-side per chunk via the
     exact ``blocking._rule_holds`` semantics (key equality + residual,
     UNKNOWN counts as not-produced);
  3. **verification / ranking** — a jitted kernel counts each pair's band
     collisions and (when ``approx_threshold > 0``) computes the mean
     q-gram Jaccard over the approx columns via the exact
     ``ops.qgram.qgram_jaccard_masked_single`` kernel vmapped over the
     pair chunk; pairs below the threshold are dropped;
  4. **progressive emission** — survivors rank by estimated similarity
     (verified Jaccard first, band-collision count as the tie-break, then
     (i, j) for determinism) and stream into the sink in budget-ordered
     chunks, BEST PAIRS FIRST, until ``approx_pair_budget`` — the
     Progressive Blocking shape (arXiv:2005.14326): downstream EM runs on
     a fixed compute envelope and sees the most promising pairs first.

One ambient ``blocking_approx`` event records the run (bands, raw LSH
candidates, exact-tier overlap removed, verified survivors, emitted pairs,
budget fill, oversize buckets dropped); ``python -m splink_tpu.obs
summarize`` renders it.
"""

from __future__ import annotations

import functools
import logging
from dataclasses import dataclass, field

import numpy as np

from ..blocking import (
    _key_codes,
    _key_codes_asym,
    _rule_holds,
    _split_join_keys,
    _uid_ranks,
    parse_blocking_rule,
)
from ..blocking_device import _pow2
from ..data import EncodedTable
from ..pairgen import (
    CHUNK,
    _pair_counts,
    _uid_mask_codes,
    _units_for_cross_join,
    _units_for_self_join,
)
from .minhash import band_key_arrays, factorise_band_codes

logger = logging.getLogger("splink_tpu")

# Schema defaults (the schema is the source of truth; these are the
# in-code fallbacks for partially-completed dicts).
DEFAULT_Q = 2
DEFAULT_BANDS = 16
DEFAULT_ROWS_PER_BAND = 2
DEFAULT_BUDGET = 1 << 22

# An LSH bucket larger than this is a degenerate band key (near-constant
# signature): its pairs are the lowest-information candidates and alone
# would dwarf any realistic budget, so the plan drops the bucket and the
# ``blocking_approx`` event reports how many were dropped (no silent cap).
MAX_BUCKET_ROWS = 4096

# Pairs per verification chunk (power-of-two bucketed): bounds the
# transient (chunk, n_windows, n_windows) cross-equality matrix.
VERIFY_CHUNK = 1 << 13

_IMAX = np.iinfo(np.int32).max


def _null_oversize_buckets(band_codes: np.ndarray) -> int:
    """Null (-1) every row of every LSH bucket wider than
    :data:`MAX_BUCKET_ROWS`, IN PLACE, returning the dropped-bucket count.

    Nulling the codes — rather than merely dropping the bucket from its
    band's emission units — is what keeps the cross-band sequential dedup
    honest: the emit kernel masks band b's pairs when an EARLIER band's
    codes collide (``(cl[i] == cr[j]) & (cl[i] >= 0)``), so a bucket
    silently removed from band 0's emission while keeping its codes would
    suppress the same pair in every later band too (lost entirely). With
    the codes nulled the pair emits through its first HEALTHY band, and
    the serve fallback — whose dictionaries simply never resolve an
    oversize bucket — agrees with the offline tier about which pairs
    exist."""
    dropped = 0
    for b in range(band_codes.shape[0]):
        codes = band_codes[b]
        valid = codes >= 0
        if not valid.any():
            continue
        sizes = np.bincount(codes[valid])
        big = np.flatnonzero(sizes > MAX_BUCKET_ROWS)
        if len(big):
            dropped += len(big)
            codes[np.isin(codes, big)] = -1
    return dropped


# --------------------------------------------------------------------------
# Configuration
# --------------------------------------------------------------------------


def approx_columns(settings: dict, table: EncodedTable) -> list[str]:
    """The string columns the approximate tier sketches, in deterministic
    order: the PLAIN string columns named by the blocking rules' equality
    keys (both sides of an asymmetric key), falling back to the string
    comparison columns when no blocking key is a plain string column
    (derived-key-only rules, numeric keys). Empty means the tier is
    unavailable for this job."""
    cols: list[str] = []

    def add(name: str) -> None:
        if name in table.strings and name not in cols:
            cols.append(name)

    for rule in settings.get("blocking_rules") or []:
        try:
            eq_pairs, residual = parse_blocking_rule(rule)
        except Exception:  # noqa: BLE001 - unparseable rule: no columns
            continue
        sym, asym, _ = _split_join_keys(eq_pairs, residual)
        for c in sym:
            add(c)
        for lc, rc in asym:
            add(lc)
            add(rc)
    if not cols:
        for c in settings.get("comparison_columns") or []:
            name = c.get("custom_name") or c.get("col_name")
            if name:
                add(name)
    return cols


@dataclass(frozen=True)
class ApproxConfig:
    cols: tuple[str, ...]
    q: int
    bands: int
    rows_per_band: int
    threshold: float
    budget: int
    # TF-weighted tier (approx_tf_weighting): IDF-weighted minhash
    # sampling + TF-weighted Jaccard verification/ranking
    tf_weighting: bool = False

    @classmethod
    def from_settings(
        cls, settings: dict, table: EncodedTable
    ) -> "ApproxConfig | None":
        """None when the tier is off or no sketchable column exists."""
        if not settings.get("approx_blocking"):
            return None
        cols = approx_columns(settings, table)
        if not cols:
            logger.warning(
                "approx_blocking is on but no blocking key or comparison "
                "column is an encoded string column; the approximate tier "
                "is skipped"
            )
            return None
        q = int(settings.get("approx_q") or DEFAULT_Q)
        if not 1 <= q <= 8:
            raise ValueError(f"approx_q={q} must be in [1, 8]")
        bands = int(settings.get("approx_bands") or DEFAULT_BANDS)
        rpb = int(settings.get("approx_rows_per_band") or DEFAULT_ROWS_PER_BAND)
        if bands < 1 or rpb < 1:
            raise ValueError(
                "approx_bands and approx_rows_per_band must be >= 1"
            )
        thr = float(settings.get("approx_threshold") or 0.0)
        if not 0.0 <= thr <= 1.0:
            raise ValueError(f"approx_threshold={thr} must be in [0, 1]")
        budget = int(settings.get("approx_pair_budget") or DEFAULT_BUDGET)
        if budget < 1:
            raise ValueError("approx_pair_budget must be >= 1")
        return cls(
            cols=tuple(cols), q=q, bands=bands, rows_per_band=rpb,
            threshold=thr, budget=budget,
            tf_weighting=bool(settings.get("approx_tf_weighting")),
        )


def column_arrays(
    table: EncodedTable, cols
) -> list[tuple[np.ndarray, np.ndarray]]:
    """(bytes, lengths) per approx column, null rows forced to length 0 so
    a null value contributes no grams (SQL equality spirit)."""
    out = []
    for name in cols:
        sc = table.strings[name]
        lengths = np.where(sc.null_mask, 0, sc.lengths).astype(np.int32)
        out.append((sc.bytes_, lengths))
    return out


# --------------------------------------------------------------------------
# Verification / ranking kernel
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def make_verify_fn(q: int, bands: int, col_shapes: tuple, with_jaccard: bool,
                   weighted: bool = False):
    """Jitted per-pair estimator: band-collision count and (optionally) the
    mean exact q-gram Jaccard over the approx columns.

    fn(i, j, band_codes, *[bytes_c, len_c, mask_c, count_c per column]
       [, idf]) -> (collisions (n,) int32, sim (n,) float32)

    ``band_codes`` is the (bands, n_rows) int32 code matrix (code -1 never
    collides). The Jaccard reuses ``ops.qgram.qgram_jaccard_masked_single``
    verbatim — the per-side distinct-gram masks/counts are the
    ``qgram_row_aux`` precomputation, so only the cross-equality matrix
    runs per pair; a column null on either side contributes Jaccard 0 (its
    union is empty). ``sim`` is the plain mean over the static column
    count: deterministic, order-free.

    ``weighted=True`` is the TF-WEIGHTED Jaccard (approx_tf_weighting):
    per column ``sum_{g in A∩B} idf(g) / sum_{g in A∪B} idf(g)`` over the
    distinct grams, with ``idf`` gathered at each gram's
    :func:`~.minhash._fold_gram_hash` top bits (the same IDF table the
    weighted sampler draws from). A shared rare gram now certifies a pair
    far more strongly than a shared common one, which is what lets the
    progressive best-first emission put true typo twins ahead of
    common-suffix near-duplicates at a fixed budget.
    """
    import jax
    import jax.numpy as jnp

    from ..ops.qgram import _gram_codes, qgram_jaccard_masked_single
    from .minhash import DF_TABLE_BITS, _fold_gram_hash, column_salts

    n_cols = len(col_shapes)
    salts = column_salts(n_cols)

    def _wjac_single(s1, s2, l1, l2, m1, m2, salt, idf):
        w1, v1 = _gram_codes(s1, l1, q)
        w2, v2 = _gram_codes(s2, l2, q)
        eq12 = jnp.all(w1[:, None, :] == w2[None, :, :], axis=-1) & (
            v1[:, None] & v2[None, :]
        )
        shift = jnp.uint32(32 - DF_TABLE_BITS)
        h1 = _fold_gram_hash(w1, salt)
        h2 = _fold_gram_hash(w2, salt)
        g1 = idf[(h1 >> shift).astype(jnp.int32)]
        g2 = idf[(h2 >> shift).astype(jnp.int32)]
        idx1 = jnp.arange(v1.shape[0], dtype=jnp.int32)
        idx2 = jnp.arange(v2.shape[0], dtype=jnp.int32)
        first1 = (
            (m1[idx1 // 32] >> (idx1 % 32).astype(jnp.uint32)) & 1
        ) == 1
        first2 = (
            (m2[idx2 // 32] >> (idx2 % 32).astype(jnp.uint32)) & 1
        ) == 1
        zero = jnp.float32(0.0)
        inter = jnp.sum(jnp.where(first1 & eq12.any(axis=1), g1, zero))
        u1 = jnp.sum(jnp.where(first1, g1, zero))
        u2 = jnp.sum(jnp.where(first2, g2, zero))
        union = u1 + u2 - inter
        return jnp.where(union > 0, inter / union, 0.0).astype(jnp.float32)

    @jax.jit
    def fn(i, j, band_codes, *colarrs):
        if weighted:
            idf = colarrs[-1]
            colarrs = colarrs[:-1]
        coll = jnp.zeros(i.shape[0], jnp.int32)
        for b in range(bands):
            cb = band_codes[b]
            coll = coll + ((cb[i] == cb[j]) & (cb[i] >= 0)).astype(jnp.int32)
        if not with_jaccard:
            return coll, jnp.zeros(i.shape[0], jnp.float32)
        sims = jnp.zeros(i.shape[0], jnp.float32)
        for c in range(n_cols):
            bytes_, lens, mask, cnt = colarrs[4 * c : 4 * c + 4]
            if weighted:
                salt = jnp.uint32(salts[c])
                jac = jax.vmap(
                    lambda s1, s2, l1, l2, m1, m2: _wjac_single(
                        s1, s2, l1, l2, m1, m2, salt, idf  # noqa: B023
                    )
                )(
                    bytes_[i], bytes_[j], lens[i], lens[j],
                    mask[i], mask[j],
                )
            else:
                jac = jax.vmap(
                    lambda s1, s2, l1, l2, m1, n1, n2:
                    qgram_jaccard_masked_single(
                        s1, s2, l1, l2, m1, n1, n2, q
                    )
                )(
                    bytes_[i], bytes_[j], lens[i], lens[j],
                    mask[i], cnt[i], cnt[j],
                )
            sims = sims + jac
        return coll, sims / jnp.float32(n_cols)

    return fn


def _verify_aux(table: EncodedTable, cfg: ApproxConfig):
    """Per-column (bytes, lengths, first_mask, distinct_count) numpy arrays
    for the verification kernel (``qgram_row_aux`` runs once per unique
    token per column)."""
    from ..ops.qgram import qgram_row_aux

    out = []
    for name, (bytes_, lengths) in zip(
        cfg.cols, column_arrays(table, cfg.cols)
    ):
        token_ids = np.where(
            lengths > 0, table.strings[name].token_ids, -1
        ).astype(np.int32)
        mask, count, _sumsq = qgram_row_aux(bytes_, lengths, token_ids, cfg.q)
        out.append((bytes_, lengths, mask, count))
    return out


# --------------------------------------------------------------------------
# Plan build (band codes -> device join plans, one per band)
# --------------------------------------------------------------------------


@dataclass
class ApproxPlan:
    """Everything the candidate generator needs, band joins included."""

    config: ApproxConfig
    band_codes: np.ndarray  # (bands, n) int32, -1 = no signature
    device_plan: object  # blocking_device.DeviceBlockPlan over the bands
    oversize_buckets: int  # degenerate LSH buckets dropped from the join
    band_uniq_keys: list = field(default_factory=list)  # per-band uint32 keys
    idf: np.ndarray | None = None  # TF-weighting IDF table (minhash.idf_weights)

    @property
    def n_candidates(self) -> int:
        return self.device_plan.n_candidates


def compute_band_codes(
    table: EncodedTable, cfg: ApproxConfig, idf: np.ndarray | None = None
) -> tuple[np.ndarray, list[np.ndarray], np.ndarray | None]:
    """(bands, n_rows) int32 band codes + the per-band ascending unique
    key arrays (the serve bucket dictionaries key on them) + the IDF
    table when TF weighting is on (built from the corpus's hashed gram
    DF sketch unless the caller supplies one — the serve index stores it
    so query-side signatures share the exact weights)."""
    from .minhash import gram_df_table, idf_weights

    columns = column_arrays(table, cfg.cols)
    if cfg.tf_weighting and idf is None:
        df_counts, n_records = gram_df_table(columns, cfg.q)
        idf = idf_weights(df_counts, n_records)
    keys, has = band_key_arrays(
        columns, cfg.q, cfg.bands, cfg.rows_per_band,
        idf=idf if cfg.tf_weighting else None,
    )
    codes, uniqs = factorise_band_codes(keys, has)
    return codes, uniqs, idf if cfg.tf_weighting else None


def build_approx_plan(
    settings: dict,
    table: EncodedTable,
    n_left: int | None = None,
    chunk: int | None = None,
) -> ApproxPlan | None:
    """Build the per-band device join plans, or None when the tier is off /
    unavailable. Mirrors ``blocking_device.build_device_plan``'s symmetric
    and link_only branches with band codes as the join keys; oversize LSH
    buckets (> :data:`MAX_BUCKET_ROWS` rows) are dropped and counted."""
    from ..blocking_device import (
        DeviceBlockPlan,
        DeviceRule,
        make_segment_sort_fn,
    )

    cfg = ApproxConfig.from_settings(settings, table)
    if cfg is None or table.n_rows == 0:
        return None
    chunk = chunk or CHUNK
    link_type = settings["link_type"]
    n = table.n_rows
    band_codes, uniq_keys, idf = compute_band_codes(table, cfg)
    # degenerate (near-constant-signature) buckets null their codes so
    # they neither emit NOR mask later bands' pairs (docstring of
    # _null_oversize_buckets); counted, never silent
    oversize = _null_oversize_buckets(band_codes)

    if link_type == "link_only":
        assert n_left is not None
        ranks = np.zeros(n, np.int32)
        uid_codes = None
    else:
        ranks, _ = _uid_ranks(table, link_type)
        uid_codes = _uid_mask_codes(table, link_type)

    sort_fn = make_segment_sort_fn()
    all_rows = np.arange(n, dtype=np.int32)
    rules: list[DeviceRule] = []
    for b in range(cfg.bands):
        codes = band_codes[b]
        if link_type == "link_only":
            ent_codes = codes
            ent_side = np.zeros(n, np.int32)
            ent_side[n_left:] = 1
            ent_rank = np.zeros(n, np.int32)
            triangle = False
        else:
            # symmetric self-join: ranks as the tertiary sort key orient
            # the triangle decode (rank_i < rank_j for free, the
            # blocking_device symmetric-branch construction)
            ent_codes = codes
            ent_side = np.zeros(n, np.int32)
            ent_rank = ranks.astype(np.int32)
            triangle = True
        m0 = n
        m = _pow2(m0)
        ent_rows = all_rows
        if m != m0:
            pad = m - m0
            ent_codes = np.concatenate([ent_codes, np.full(pad, -1, np.int32)])
            ent_side = np.concatenate([ent_side, np.zeros(pad, np.int32)])
            ent_rank = np.concatenate([ent_rank, np.zeros(pad, np.int32)])
            ent_rows = np.concatenate([ent_rows, np.zeros(pad, np.int32)])
        row_s, seg_start, l_cnt, r_cnt, n_seg, n_valid = sort_fn(
            ent_codes, ent_side, ent_rank, ent_rows
        )
        order = np.asarray(row_s)
        n_seg_h = int(np.asarray(n_seg))
        n_valid_h = int(np.asarray(n_valid))
        starts = np.asarray(seg_start)[:n_seg_h].astype(np.int64)
        lz = np.asarray(l_cnt)[:n_seg_h].astype(np.int64)
        rz = np.asarray(r_cnt)[:n_seg_h].astype(np.int64)
        live = starts < n_valid_h
        starts, lz, rz = starts[live], lz[live], rz[live]
        if triangle:
            units = _units_for_self_join(starts, lz, chunk)
        else:
            both = (lz > 0) & (rz > 0)
            units = _units_for_cross_join(
                starts[both], lz[both], starts[both] + lz[both], rz[both],
                chunk,
            )
        if units is None:  # pragma: no cover - MAX_BUCKET_ROWS forbids it
            return None
        ua, la, ub, lb = units
        rules.append(
            DeviceRule(
                rule=f"approx:band{b}",
                order=np.ascontiguousarray(order, dtype=np.int32),
                ua=ua.astype(np.int32),
                la=la.astype(np.int32),
                ub=ub.astype(np.int32),
                lb=lb.astype(np.int32),
                pc=_pair_counts(ua, la, ub, lb),
                rank_filter=False,
            )
        )
    device_plan = DeviceBlockPlan(
        rules=rules,
        codes_l=band_codes,
        codes_r=band_codes,
        ranks=np.ascontiguousarray(ranks, dtype=np.int32),
        uid_codes=uid_codes,
        res_ops=[],
        chunk=chunk,
    )
    return ApproxPlan(
        config=cfg,
        band_codes=band_codes,
        device_plan=device_plan,
        oversize_buckets=oversize,
        band_uniq_keys=uniq_keys,
        idf=idf,
    )


# --------------------------------------------------------------------------
# Candidate generation + exact-rule dedup + verification
# --------------------------------------------------------------------------


def _exact_rule_predicates(settings: dict, table: EncodedTable):
    """[(codes_l, codes_r, residual)] for every exact blocking rule — the
    predicates the approx tier's candidates are deduplicated against
    (``blocking._rule_holds`` semantics, the reference's ``AND NOT
    ifnull(previous_rule, false)``). Key-code arrays come from the same
    per-table cache the exact tier warmed."""
    out = []
    for rule in settings.get("blocking_rules") or []:
        eq_pairs, residual = parse_blocking_rule(rule)
        sym, asym, residual = _split_join_keys(eq_pairs, residual)
        if not sym and not asym:
            out.append((None, None, residual))
        elif asym:
            cl, cr = _key_codes_asym(table, sym, asym)
            out.append((cl, cr, residual))
        else:
            c = _key_codes(table, sym)
            out.append((c, c, residual))
    return out


def generate_approx_candidates(
    settings: dict,
    table: EncodedTable,
    n_left: int | None = None,
    plan: ApproxPlan | None = None,
):
    """The top LSH candidate pairs with their ranking estimates.

    Returns ``(i, j, collisions, sim, stats)`` host arrays (``sim`` is
    all-zero when ``approx_threshold == 0`` — verification off) with the
    exact-tier overlap already removed and the threshold filter applied.
    The arrays hold at most ~2x ``approx_pair_budget`` candidates: the
    accumulation prunes to the running top-``budget`` under the emission
    ranking whenever it grows past the cap, so host RAM is O(budget), not
    O(all LSH collisions) — and since the top-B of a superset always
    contains the final top-B, the pruning never changes what
    :func:`approx_block_into` emits. ``stats["survivors"]`` counts EVERY
    threshold-surviving candidate, pruned or not. Returns None when the
    tier is unavailable.
    """
    import jax.numpy as jnp

    from ..blocking_device import iter_device_pairs

    if plan is None:
        plan = build_approx_plan(settings, table, n_left)
    if plan is None:
        return None
    cfg = plan.config
    with_jaccard = cfg.threshold > 0.0
    preds = _exact_rule_predicates(settings, table)

    col_shapes = tuple(
        (int(table.strings[c].width),
         "ascii" if table.strings[c].bytes_.dtype == np.uint8 else "wide")
        for c in cfg.cols
    )
    weighted = bool(cfg.tf_weighting and with_jaccard and plan.idf is not None)
    vfn = make_verify_fn(
        cfg.q, cfg.bands, col_shapes, with_jaccard, weighted=weighted
    )
    bc_dev = jnp.asarray(plan.band_codes)
    aux_dev = []
    if with_jaccard:
        for bytes_, lengths, mask, count in _verify_aux(table, cfg):
            aux_dev.extend(
                [jnp.asarray(bytes_), jnp.asarray(lengths),
                 jnp.asarray(mask), jnp.asarray(count)]
            )
        if weighted:
            aux_dev.append(jnp.asarray(plan.idf, jnp.float32))

    chunk_cap = int(settings.get("blocking_chunk_pairs") or 0) or (1 << 22)
    # the budget shapes nothing in the plan (bands/threshold do), so read
    # it from the CALLER's settings — a reused plan composes with a
    # different budget (an unbudgeted-coverage pass over it relies on this)
    budget = int(settings.get("approx_pair_budget") or cfg.budget)
    # bounded pre-ranking working set: the host accumulates AT MOST
    # ~2x budget candidates — whenever the accumulation exceeds the cap it
    # prunes to the running top-``budget`` under the SAME ranking key the
    # emission uses (the top-B of a superset always contains the final
    # top-B, so pruning never changes what gets emitted). Without this, a
    # corpus with many mid-size LSH buckets could materialise billions of
    # candidates before the final ranking — unbounded host RAM the exact
    # tier's spill machinery exists to avoid.
    prune_cap = budget + max(budget, 4 * VERIFY_CHUNK)
    out_i: list[np.ndarray] = []
    out_j: list[np.ndarray] = []
    out_c: list[np.ndarray] = []
    out_s: list[np.ndarray] = []
    held = 0
    raw = 0
    survivors = 0
    overlap_removed = 0

    def _concat():
        if not out_i:
            z = np.zeros(0, np.int32)
            return z, z.copy(), np.zeros(0, np.int32), np.zeros(0, np.float32)
        return (
            np.concatenate(out_i),
            np.concatenate(out_j),
            np.concatenate(out_c),
            np.concatenate(out_s),
        )

    def _prune():
        nonlocal held
        i, j, c, sm = _concat()
        order = np.lexsort((j, i, -c, -sm))[:budget]
        out_i[:] = [i[order]]
        out_j[:] = [j[order]]
        out_c[:] = [c[order]]
        out_s[:] = [sm[order]]
        held = len(order)

    for _r, ci, cj in iter_device_pairs(plan.device_plan, chunk_cap):
        raw += len(ci)
        keep = np.ones(len(ci), bool)
        for cl, cr, residual in preds:
            keep &= ~_rule_holds(table, cl, cr, residual, ci, cj)
        kept = np.count_nonzero(keep)  # host numpy, no device sync
        overlap_removed += len(ci) - kept
        ci, cj = ci[keep], cj[keep]
        if not len(ci):
            continue
        # estimate in power-of-two bucketed sub-chunks (zero steady-state
        # recompiles; padding pairs are sliced off after the fetch)
        for s in range(0, len(ci), VERIFY_CHUNK):
            e = min(s + VERIFY_CHUNK, len(ci))
            m = _pow2(max(e - s, 1))
            ib = np.zeros(m, np.int32)
            jb = np.zeros(m, np.int32)
            ib[: e - s] = ci[s:e]
            jb[: e - s] = cj[s:e]
            coll, sim = vfn(
                jnp.asarray(ib), jnp.asarray(jb), bc_dev, *aux_dev
            )
            si = ci[s:e]
            sj = cj[s:e]
            sc = np.asarray(coll)[: e - s]
            ss = np.asarray(sim)[: e - s]
            if with_jaccard:
                thr = ss >= np.float32(cfg.threshold)
                si, sj, sc, ss = si[thr], sj[thr], sc[thr], ss[thr]
            survivors += len(si)
            if not len(si):
                continue
            out_i.append(si)
            out_j.append(sj)
            out_c.append(sc)
            out_s.append(ss)
            held += len(si)
            if held > prune_cap:
                _prune()
    i, j, coll, sim = _concat()
    stats = {
        "bands": cfg.bands,
        "rows_per_band": cfg.rows_per_band,
        "q": cfg.q,
        "cols": list(cfg.cols),
        "candidates": raw,
        "exact_overlap_removed": int(overlap_removed),
        "verified": with_jaccard,
        "tf_weighted": weighted,
        "survivors": survivors,
        "oversize_buckets_dropped": plan.oversize_buckets,
    }
    return i, j, coll, sim, stats


def approx_block_into(
    settings: dict,
    table: EncodedTable,
    n_left: int | None,
    sink,
    pair_consumer=None,
) -> int:
    """Run the approximate tier into the caller's sink AFTER the exact
    rules: rank the candidates best-first and emit budget-ordered chunks
    up to ``approx_pair_budget``. Returns the number of pairs emitted (0
    when the tier is unavailable). A tier failure degrades to 0 emitted
    pairs with a warning — it never loses the run (the exact pairs are
    already in the sink).
    """
    from ..obs.events import publish

    try:
        res = generate_approx_candidates(settings, table, n_left)
    except Exception as e:  # noqa: BLE001 - recall tier must not kill the run
        logger.warning(
            "approximate blocking failed (%s: %s); continuing with the "
            "exact tier's pairs only", type(e).__name__, e,
        )
        return 0
    if res is None:
        return 0
    i, j, coll, sim, stats = res
    budget = int(
        settings.get("approx_pair_budget") or DEFAULT_BUDGET
    )
    # progressive ranking: verified Jaccard first (all-zero when
    # verification is off), band-collision count second, (i, j) as the
    # deterministic final tie-break. np.lexsort sorts by the LAST key
    # first.
    order = np.lexsort((j, i, -coll, -sim))
    if len(order) > budget:
        order = order[:budget]
    emitted = len(order)
    chunk_cap = int(settings.get("blocking_chunk_pairs") or 0) or (1 << 22)
    for s in range(0, emitted, chunk_cap):
        sel = order[s : s + chunk_cap]
        ei = i[sel].astype(sink.idx_dtype, copy=False)
        ej = j[sel].astype(sink.idx_dtype, copy=False)
        sink.append(ei, ej)
        if pair_consumer is not None:
            pair_consumer(ei, ej)
    try:
        publish(
            "blocking_approx",
            **stats,
            budget=budget,
            emitted=emitted,
            budget_fill=round(emitted / budget, 4) if budget else 0.0,
        )
    except Exception as e:  # noqa: BLE001 - telemetry must never break emission
        logger.debug("blocking_approx telemetry publish failed: %s", e)
    logger.info(
        "approximate blocking: %d candidate(s), %d emitted under budget %d",
        stats["candidates"], emitted, budget,
    )
    return emitted
