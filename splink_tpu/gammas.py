"""Comparison-vector (gamma) computation: settings spec -> jitted program.

The reference builds one SQL SELECT applying each column's CASE expression to
the blocked pairs (/root/reference/splink/gammas.py:65-124), executed row-wise
by Spark with per-row JVM UDF calls. Here the completed settings compile ONCE
into a single jitted function: encoded columns live in HBM, a batch of pair
indices is transferred, device gathers assemble both sides, and every
comparison kernel runs vmapped over the whole batch — one fused XLA program
per settings signature, reused across batches and EM runs.

Gather layout: random row gathers are the measured bottleneck on TPU (a
(1M, 8) uint8 gather costs ~17 ms on v5e while the Jaro-Winkler kernel on the
gathered batch costs ~11 ms), so all encoded columns are packed host-side
into ONE (n_rows, n_lanes) uint32 matrix — chars, lengths, token ids and
bitcast numerics side by side — and each pair batch issues exactly two row
gathers (left + right). Fields are unpacked on device with bitcasts/shifts,
which is free VPU work compared to extra HBM gather passes.
"""

from __future__ import annotations

import copy
import functools
import json
import logging
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .data import EncodedTable
from .ops import numeric as numeric_ops
from .ops import qgram as qgram_ops
from .ops import strings as string_ops
from .ops.gamma import (
    GAMMA_DTYPE,
    apply_null,
    bucket_difference,
    bucket_difference_le,
    bucket_similarity,
)
from .settings import comparison_column_name
from .utils import kernel_registry
from .utils.logging_utils import log_jaxpr
from .utils.profiling import count, dispatched, fetch, span

logger = logging.getLogger("splink_tpu")

DEFAULT_PAIR_BATCH = 1 << 20

# What a pattern of the SPACE costs on the device, where the pattern-id
# pipeline keeps dense per-pattern state: the int32 histogram accumulator, a
# batch's int32 scatter target and their int32 sum. Nothing else is sized by
# the space: the host's per-pattern tables are sized by the patterns a job
# SEES (linker._SeenPatterns), its one space-sized structure the int32
# id -> row remap, 4 B a pattern more.
PATTERN_SPACE_BYTES = 3 * 4
# The device bytes those accumulators may take: 1 GiB of a v5e's 16 GB.
MAX_PATTERN_SPACE_BYTES = 1 << 30
# Largest gamma-pattern space the pattern-id pipeline handles (89,478,485
# patterns), derived from the two above; beyond it the linker streams
# sufficient statistics instead. A module name tests patch (= 1 forces the
# streamed-stats regime).
MAX_PATTERNS = MAX_PATTERN_SPACE_BYTES // PATTERN_SPACE_BYTES


def pattern_space_fits(n_patterns: int) -> bool:
    """Whether the pattern-id pipeline takes a space of ``n_patterns``: its
    dense device accumulators fit ``MAX_PATTERN_SPACE_BYTES``."""
    return n_patterns <= MAX_PATTERNS


def _too_many_patterns(n_patterns: int, instead: str) -> ValueError:
    return ValueError(
        f"pattern space {n_patterns} needs "
        f"{n_patterns * PATTERN_SPACE_BYTES} B of dense device accumulators, "
        f"over the {MAX_PATTERNS * PATTERN_SPACE_BYTES} B the pattern-id "
        f"pipeline may take (MAX_PATTERNS {MAX_PATTERNS}); use {instead}"
    )

# Registry for custom comparisons: name -> callable(ctx, col_settings) -> gamma
_CUSTOM_COMPARISONS: dict[str, callable] = {}


def register_comparison(name: str, fn) -> None:
    """Register a custom comparison kernel.

    ``fn(ctx, col_settings) -> int8 gamma array`` where ctx is a
    :class:`PairContext`; it must be jax-traceable. This replaces the
    reference's arbitrary SQL ``case_expression`` escape hatch
    (/root/reference/splink/settings.py:133-139) with a JAX-native one.
    """
    _CUSTOM_COMPARISONS[name] = fn


@dataclass
class PairColumn:
    """Both sides of one column for a batch of pairs (device arrays)."""

    chars_l: jnp.ndarray | None = None  # (b, width) uint8/uint32
    chars_r: jnp.ndarray | None = None
    len_l: jnp.ndarray | None = None  # (b,) int32
    len_r: jnp.ndarray | None = None
    tok_l: jnp.ndarray | None = None  # (b,) int32 (-1 null)
    tok_r: jnp.ndarray | None = None
    num_l: jnp.ndarray | None = None  # (b,) float
    num_r: jnp.ndarray | None = None
    null: jnp.ndarray | None = None  # (b,) bool: either side null
    null_l: jnp.ndarray | None = None  # (b,) bool: left side null
    null_r: jnp.ndarray | None = None  # (b,) bool: right side null


def _u32_bytes_le(lanes):
    """(..., k) uint32 -> (..., k, 4) uint8 in little-endian byte order.

    Width-changing bitcasts carry two costs the elementwise shift+mask form
    avoids: XLA documents their bit order as implementation defined (the old
    code probed the backend with a known word and conditionally reversed),
    and GSPMD cannot partition them along a sharded dimension — under a
    sharded pair axis the bitcast all-gathered the WHOLE batch onto every
    device (shard_audit SA-COLL pins the gamma kernels all-gather-free).
    Shifts are elementwise, so the byte order is deterministic everywhere
    and the op partitions trivially.
    """
    shifts = jnp.arange(0, 32, 8, dtype=jnp.uint32)
    return ((lanes[..., None] >> shifts) & jnp.uint32(0xFF)).astype(jnp.uint8)




class _StringField:
    """Lane layout of one packed string column."""

    __slots__ = ("kind", "width", "chars", "len_lane", "tok_lane")

    def __init__(self, kind, width, chars, len_lane, tok_lane):
        # "ascii" (4 chars/lane) | "wide" (1 codepoint/lane) | "token" (the
        # token lane alone: no chars, no length lane)
        self.kind = kind
        self.width = width
        self.chars = chars  # lane slice, None for "token"
        self.len_lane = len_lane  # None for "token"
        self.tok_lane = tok_lane


class _NumericField:
    """Lane layout of one packed numeric column."""

    __slots__ = ("val", "f64", "null_lane", "null_bit")

    def __init__(self, val, f64, null_lane, null_bit):
        self.val = val  # lane slice (1 lane f32, 2 lanes f64)
        self.f64 = f64
        self.null_lane = null_lane
        self.null_bit = null_bit


class _QgramField:
    """Lane layout of one column's precomputed q-gram auxiliaries
    (qgram_ops.qgram_row_aux): distinct-gram first-occurrence bitmask,
    distinct count, squared gram-count norm. Lanes are packed only for the
    comparison kinds present (jaccard needs mask+count, cosine needs
    sumsq); absent components are None."""

    __slots__ = ("mask", "count_lane", "sq_lane")

    def __init__(self, mask, count_lane, sq_lane):
        self.mask = mask  # lane slice, ceil(n_windows/32) uint32 lanes
        self.count_lane = count_lane
        self.sq_lane = sq_lane


def _qgram_key(name: str, q: int) -> str:
    return f"\x00qgram:{name}:{q}"


def _charset_key(name: str) -> str:
    return f"\x00charset:{name}"


class _CharsetField:
    """Lane layout of one column's precomputed charset auxiliaries
    (qgram_ops.charset_row_aux) for the CASE compiler's jaccard_sim fast
    path: first-occurrence-and-non-space bitmask, non-space distinct
    count, has-space flag."""

    __slots__ = ("mask", "count_lane", "space_lane")

    def __init__(self, mask, count_lane, space_lane):
        self.mask = mask
        self.count_lane = count_lane
        self.space_lane = space_lane


def int32_histogram(ids, length: int):
    """``jnp.bincount(ids, length=length)`` with the count dtype pinned to
    int32 via an explicit scatter-add — bincount counts in int64 under x64,
    while the device pattern-histogram accumulators are int32 BY PROTOCOL
    (partial sums stay below 2^30 and flush to host int64 every
    _HIST_FLUSH_BATCHES batches). Out-of-range ids drop, matching bincount
    on in-range input. The single histogram used by every pattern kernel
    (gamma pattern batch, host-G batch, pairgen's virtual twin) so the
    dtype discipline cannot drift between them."""
    with jax.named_scope("pattern_hist"):
        return jnp.zeros(length, jnp.int32).at[ids].add(1, mode="drop")


def pattern_ids_fit_uint16(n_patterns: int) -> bool:
    """True when every pattern id AND the mask sentinel (== n_patterns)
    fit uint16 — the single predicate deciding both the device-side
    narrowing before D2H and the host-side array dtype. One definition so
    the sites cannot drift (a host uint16 with a device int32 would
    silently double the download bytes)."""
    return n_patterns + 1 <= (1 << 16)


def _comparison_input_column(col_settings: dict) -> str | None:
    """The encoded column a comparison column reads: ``col_name``, else the
    comparison spec's ``column``, else the first ``custom_columns_used``
    entry. The single source of truth for this resolution — used by the
    include-set, the gamma dispatcher and the q-gram aux packing, which must
    agree or a comparison silently misses its packed lanes."""
    spec = col_settings.get("comparison") or {}
    name = col_settings.get("col_name") or spec.get("column")
    if name is None:
        name = (col_settings.get("custom_columns_used") or [None])[0]
    return name


def qgram_specs_for(settings: dict) -> tuple[tuple[str, int, bool, bool], ...]:
    """(column, q, want_jaccard_aux, want_cosine_aux) tuples describing the
    per-row q-gram aux lanes to pack: one per native qgram_jaccard/
    qgram_cosine comparison, packing only the components its kind reads
    (row gathers are the measured bottleneck — unused lanes widen every
    gather). CASE cosine_distance calls whose arguments are ALL plain
    column references register their sumsq lanes too (the compiler's fast
    path); any other CASE argument shape keeps the self-contained
    kernels."""
    flags: dict[tuple[str, int], list[bool]] = {}
    for c in settings["comparison_columns"]:
        spec = c.get("comparison") or {}
        kind = spec.get("kind")
        if kind in ("qgram_jaccard", "qgram_cosine"):
            name = _comparison_input_column(c)
            if name:
                f = flags.setdefault((name, int(spec.get("q", 2))), [False, False])
                f[0] |= kind == "qgram_jaccard"
                f[1] |= kind == "qgram_cosine"
        elif kind == "case_sql":
            # CASE cosine_distance on plain columns reuses the qgram
            # sumsq lanes (jaccard_sim needs charset aux instead,
            # charset_specs_for)
            from .case_compiler import precompute_aux_requirements

            _, cos = precompute_aux_requirements(spec["expr"])
            for name, q in cos:
                f = flags.setdefault((name, q), [False, False])
                f[1] = True
    return tuple((n, q, f[0], f[1]) for (n, q), f in flags.items())


def charset_specs_for(settings: dict) -> tuple[str, ...]:
    """Columns whose per-row charset aux (distinct-char mask/count/space)
    should ride in the packed table: plain column references in CASE
    jaccard_sim calls (the CASE compiler's fast path)."""
    cols: dict[str, None] = {}
    for c in settings["comparison_columns"]:
        spec = c.get("comparison") or {}
        if spec.get("kind") == "case_sql":
            from .case_compiler import precompute_aux_requirements

            charset, _ = precompute_aux_requirements(spec["expr"])
            for name in sorted(charset):
                cols.setdefault(name)
    return tuple(cols)


def comparison_columns_used(settings: dict) -> set[str] | None:
    """Encoded-column names the gamma program reads, or None for 'all'
    (a registered custom comparison may touch any column)."""
    from .data import phonetic_column_name

    used: set[str] = set()
    for col in settings["comparison_columns"]:
        spec = col.get("comparison") or {}
        kind = spec.get("kind")
        if kind == "custom":
            return None
        name = _comparison_input_column(col)
        if name:
            used.add(name)
            if kind == "dmetaphone":
                used.add(phonetic_column_name(name))
        used.update(spec.get("other_columns", []))
        used.update(spec.get("columns_used", []))
        used.update(
            phonetic_column_name(c) for c in spec.get("phonetic_columns", [])
        )
    return used


def token_only_columns(settings: dict) -> frozenset[str]:
    """Columns every comparison reads through the token alone: the input
    column of ``exact`` comparisons that no other comparison names — not as
    its input, not as a name inversion's other column, a CASE expression's
    column or a phonetic column. ``pack_table`` gives such a string column
    its token lane and nothing else (its characters and length would widen
    every row gather unread). Empty where a ``custom`` comparison may read
    any lane; numeric columns are never narrowed (``pack_table`` applies
    the set to string columns only)."""
    from .data import phonetic_column_name

    exact: set[str] = set()
    other: set[str] = set()
    for col in settings["comparison_columns"]:
        spec = col.get("comparison") or {}
        kind = spec.get("kind")
        if kind == "custom":
            return frozenset()
        name = _comparison_input_column(col)
        if name:
            (exact if kind == "exact" else other).add(name)
            if kind == "dmetaphone":
                other.add(phonetic_column_name(name))
        other.update(spec.get("other_columns", []))
        other.update(spec.get("columns_used", []))
        other.update(
            phonetic_column_name(c) for c in spec.get("phonetic_columns", [])
        )
    return frozenset(exact - other)


def pack_table(
    table: EncodedTable,
    float_dtype=jnp.float32,
    include=None,
    qgram_specs=(),
    charset_specs=(),
    token_only=(),
):
    """Pack encoded columns into one (n_rows, n_lanes) uint32 matrix.

    Layout per string column: chars (width/4 lanes for ASCII, width lanes for
    wide-unicode), then a length lane and a token-id lane (token -1 doubles as
    the null flag). A string column named in ``token_only``
    (:func:`token_only_columns`) gets the token lane alone: a field of kind
    ``"token"`` whose ``chars`` and ``len_lane`` are None. Numeric columns contribute one (f32)
    or two (f64) bitcast value lanes; their null bits are packed 32-per-lane
    at the end.

    ``include`` limits packing to those column names (row gathers are the
    measured bottleneck, so columns used only host-side — e.g. derived
    phonetic blocking keys — must not ride along); None packs everything.

    Returns (packed uint32 ndarray, {name: field layout}).
    """
    n = table.n_rows
    lanes: list[np.ndarray] = []
    layout: dict[str, object] = {}
    cursor = 0

    def add(arr: np.ndarray) -> slice:
        nonlocal cursor
        # lane count computed explicitly so zero-row tables still pack
        k = arr.size // n if n else (arr.shape[1] if arr.ndim > 1 else 1)
        arr = np.ascontiguousarray(arr).reshape(n, k)
        lanes.append(arr)
        s = slice(cursor, cursor + k)
        cursor += k
        return s

    for name, sc in table.strings.items():
        if include is not None and name not in include:
            continue
        if name in token_only:
            tok_lane = add(sc.token_ids.astype(np.int32).view(np.uint32)).start
            layout[name] = _StringField("token", sc.width, None, None, tok_lane)
            continue
        if sc.bytes_.dtype == np.uint8:
            w = sc.width
            if w % 4:  # pad to a whole number of lanes
                padded = np.zeros((n, w + 4 - w % 4), np.uint8)
                padded[:, :w] = sc.bytes_
            else:
                padded = np.ascontiguousarray(sc.bytes_)
            chars = add(padded.view(np.uint32))
            kind = "ascii"
        else:
            chars = add(sc.bytes_.astype(np.uint32))
            kind = "wide"
        len_lane = add(sc.lengths.astype(np.int32).view(np.uint32)).start
        tok_lane = add(sc.token_ids.astype(np.int32).view(np.uint32)).start
        layout[name] = _StringField(kind, sc.width, chars, len_lane, tok_lane)

    for qname, q, want_jac, want_cos in qgram_specs:
        sc = table.strings.get(qname)
        if sc is None or (include is not None and qname not in include):
            continue
        mask, count, sumsq = qgram_ops.qgram_row_aux(
            sc.bytes_, sc.lengths, sc.token_ids, q
        )
        mslice = add(mask) if want_jac else None
        count_lane = add(count.view(np.uint32)).start if want_jac else None
        sq_lane = add(sumsq.view(np.uint32)).start if want_cos else None
        layout[_qgram_key(qname, q)] = _QgramField(mslice, count_lane, sq_lane)

    for cname in charset_specs:
        sc = table.strings.get(cname)
        if sc is None or (include is not None and cname not in include):
            continue
        mask, count, space = qgram_ops.charset_row_aux(
            sc.bytes_, sc.lengths, sc.token_ids
        )
        layout[_charset_key(cname)] = _CharsetField(
            add(mask),
            add(count.view(np.uint32)).start,
            add(space.view(np.uint32)).start,
        )

    f64 = float_dtype == jnp.float64
    num_names = [
        c for c in table.numerics if include is None or c in include
    ]
    null_words = np.zeros((n, max(1, (len(num_names) + 31) // 32)), np.uint32)
    num_fields = {}
    for i, name in enumerate(num_names):
        nc = table.numerics[name]
        if f64:
            vals = np.ascontiguousarray(nc.values_f64).view(np.uint32)
        else:
            vals = nc.values_f64.astype(np.float32).view(np.uint32)
        num_fields[name] = add(vals)
        null_words[:, i // 32] |= nc.null_mask.astype(np.uint32) << (i % 32)
    if num_names:
        null_slice = add(null_words)
        for i, name in enumerate(num_names):
            layout[name] = _NumericField(
                num_fields[name], f64, null_slice.start + i // 32, i % 32
            )

    if not lanes:
        return np.zeros((n, 1), np.uint32), layout
    return np.concatenate(lanes, axis=1), layout


class PairContext:
    """Lazy per-column unpack context handed to comparison kernels.

    Holds the two gathered row blocks (one per pair side) and decodes each
    requested column's fields out of them with bitcasts — no further HBM
    gathers happen after construction.
    """

    def __init__(self, layout: dict, rows_l, rows_r):
        self._layout = layout
        self._rows_l = rows_l
        self._rows_r = rows_r

    def _string_side(self, f: _StringField, rows):
        tok = jax.lax.bitcast_convert_type(rows[:, f.tok_lane], jnp.int32)
        if f.kind == "token":
            return None, None, tok
        lanes = rows[:, f.chars]
        if f.kind == "ascii":
            chars = _u32_bytes_le(lanes)
            chars = chars.reshape(rows.shape[0], -1)[:, : f.width]
        else:
            chars = lanes
        ln = jax.lax.bitcast_convert_type(rows[:, f.len_lane], jnp.int32)
        return chars, ln, tok

    def _numeric_side(self, f: _NumericField, rows):
        lanes = rows[:, f.val]
        if f.f64:
            # Assemble the f64 from its two little-endian u32 words with a
            # SAME-width u64 bitcast: the width-changing u32[2]->f64 bitcast
            # is unpartitionable under GSPMD (it all-gathers the sharded
            # batch) and its word order is implementation defined.
            lo = lanes[:, 0].astype(jnp.uint64)
            hi = lanes[:, 1].astype(jnp.uint64)
            val = jax.lax.bitcast_convert_type(
                lo | (hi << jnp.uint64(32)), jnp.float64
            )
        else:
            val = jax.lax.bitcast_convert_type(lanes[:, 0], jnp.float32)
        word = rows[:, f.null_lane]
        null = ((word >> np.uint32(f.null_bit)) & np.uint32(1)) == 1
        return val, null

    def qgram_aux(self, name: str, q: int):
        """Per-side precomputed q-gram aux lanes, or None when the packed
        table does not carry them (CASE-compiled or custom callers). Each
        side is (mask, count, sumsq) with None for components the packed
        kinds did not need."""
        f = self._layout.get(_qgram_key(name, q))
        if f is None:
            return None

        def side(rows):
            mask = rows[:, f.mask] if f.mask is not None else None
            count = (
                jax.lax.bitcast_convert_type(rows[:, f.count_lane], jnp.int32)
                if f.count_lane is not None
                else None
            )
            sumsq = (
                jax.lax.bitcast_convert_type(rows[:, f.sq_lane], jnp.float32)
                if f.sq_lane is not None
                else None
            )
            return mask, count, sumsq

        return side(self._rows_l), side(self._rows_r)

    def charset_aux(self, name: str):
        """Per-side precomputed charset aux (mask, count, space flag), or
        None when the packed table does not carry it for this column."""
        f = self._layout.get(_charset_key(name))
        if f is None:
            return None

        def side(rows):
            return (
                rows[:, f.mask],
                jax.lax.bitcast_convert_type(rows[:, f.count_lane], jnp.int32),
                jax.lax.bitcast_convert_type(rows[:, f.space_lane], jnp.int32),
            )

        return side(self._rows_l), side(self._rows_r)

    def col(self, name: str) -> PairColumn:
        f = self._layout[name]
        out = PairColumn()
        if isinstance(f, _StringField):
            out.chars_l, out.len_l, out.tok_l = self._string_side(f, self._rows_l)
            out.chars_r, out.len_r, out.tok_r = self._string_side(f, self._rows_r)
            out.null_l = out.tok_l < 0
            out.null_r = out.tok_r < 0
        else:
            out.num_l, out.null_l = self._numeric_side(f, self._rows_l)
            out.num_r, out.null_r = self._numeric_side(f, self._rows_r)
        out.null = out.null_l | out.null_r
        return out


def _pad_chars(chars, width: int):
    """Zero-pad a (b, w) char array to (b, width) and unify the dtype."""
    out = chars.astype(jnp.uint32) if chars.dtype != jnp.uint8 else chars
    if out.shape[1] < width:
        out = jnp.pad(out, ((0, 0), (0, width - out.shape[1])))
    return out


def _spec_gamma(col_settings: dict, ctx: PairContext) -> jnp.ndarray:
    """Compute one comparison column's gamma levels for a pair batch."""
    spec = col_settings["comparison"]
    kind = spec["kind"]
    levels = col_settings["num_levels"]
    name = _comparison_input_column(col_settings)

    if kind == "custom":
        fn = _CUSTOM_COMPARISONS.get(spec.get("fn", ""))
        if fn is None:
            raise ValueError(
                f"comparison kind 'custom' requires a registered fn; got "
                f"{spec.get('fn')!r}. Use splink_tpu.register_comparison()."
            )
        return fn(ctx, col_settings).astype(GAMMA_DTYPE)

    if kind == "case_sql":
        # Hand-written SQL CASE expression (the reference's arbitrary
        # case_expression escape hatch), compiled by case_compiler into
        # jax-traceable ops over the same PairContext.
        from .case_compiler import compile_case_expression

        return compile_case_expression(spec["expr"], levels)(ctx)

    pc = ctx.col(name)
    thresholds = tuple(spec.get("thresholds", ()))

    if kind == "exact":
        if pc.tok_l is not None:
            eq = pc.tok_l == pc.tok_r
        else:
            eq = pc.num_l == pc.num_r
        gamma = eq.astype(GAMMA_DTYPE)
        return apply_null(gamma, pc.null)

    if kind == "dmetaphone":
        # Phonetic comparison against the host-precomputed double-metaphone
        # column (the reference jar's DoubleMetaphone UDF use case):
        # num_levels 2 -> phonetic equality; 3 -> exact match above phonetic.
        from .data import phonetic_column_name

        if levels not in (2, 3):
            raise ValueError(
                f"dmetaphone comparison supports num_levels 2 or 3, got {levels}"
            )
        dm = ctx.col(phonetic_column_name(name))
        phon_eq = dm.tok_l == dm.tok_r
        if levels >= 3:
            exact = pc.tok_l == pc.tok_r
            gamma = jnp.where(
                exact, jnp.int8(2), jnp.where(phon_eq, jnp.int8(1), jnp.int8(0))
            )
        else:
            gamma = phon_eq.astype(GAMMA_DTYPE)
        return apply_null(gamma, pc.null)

    if kind == "jaro_winkler":
        sim = string_ops.jaro_winkler(
            pc.chars_l, pc.chars_r, pc.len_l, pc.len_r, 0.1, 0.7
        )
        return bucket_similarity(sim, thresholds, pc.null)

    if kind == "levenshtein":
        ratio = string_ops.levenshtein_ratio(pc.chars_l, pc.chars_r, pc.len_l, pc.len_r)
        equal = pc.tok_l == pc.tok_r
        return bucket_difference_le(ratio, thresholds, pc.null, equal, levels - 1)

    if kind == "numeric_abs":
        diff = numeric_ops.abs_difference(pc.num_l, pc.num_r)
        return bucket_difference(diff, thresholds, pc.null)

    if kind == "numeric_perc":
        diff = numeric_ops.relative_difference(pc.num_l, pc.num_r)
        return bucket_difference(diff, thresholds, pc.null)

    if kind == "qgram_jaccard":
        q = int(spec.get("q", 2))
        aux = ctx.qgram_aux(name, q)
        if aux is not None and aux[0][0] is not None:
            (m_l, n_l, _), (_, n_r, _) = aux
            sim = qgram_ops.qgram_jaccard_masked(
                pc.chars_l, pc.chars_r, pc.len_l, pc.len_r,
                m_l, n_l, n_r, q,
            )
        else:
            sim = qgram_ops.qgram_jaccard(
                pc.chars_l, pc.chars_r, pc.len_l, pc.len_r, q
            )
        return bucket_similarity(sim, thresholds, pc.null)

    if kind == "qgram_cosine":
        q = int(spec.get("q", 2))
        aux = ctx.qgram_aux(name, q)
        if aux is not None and aux[0][2] is not None:
            (_, _, x11), (_, _, x22) = aux
            dist = qgram_ops.qgram_cosine_masked(
                pc.chars_l, pc.chars_r, pc.len_l, pc.len_r, x11, x22, q
            )
        else:
            dist = qgram_ops.qgram_cosine_distance(
                pc.chars_l, pc.chars_r, pc.len_l, pc.len_r, q
            )
        sim = 1.0 - dist
        return bucket_similarity(sim, thresholds, pc.null)

    if kind == "name_inversion":
        # 4-level cross-column comparison handling inverted name fields
        # (/root/reference/splink/case_statements.py:248-277):
        #   3: jw(col_l, col_r) > t1
        #   2: jw(col_l, other_r) > t1 for any other name column (inversion)
        #   1: jw(col_l, col_r) > t2
        #   0: otherwise; null(col) -> -1. The reference only null-guards the
        #      *right* side of the other column (ifnull({n}_r, '1234')), so a
        #      null other_l does not suppress the inversion check.
        if not thresholds:
            thresholds = (0.94, 0.88)  # the reference's defaults
        t1, t2 = thresholds[0], thresholds[1]
        sim_self = string_ops.jaro_winkler(
            pc.chars_l, pc.chars_r, pc.len_l, pc.len_r, 0.1, 0.7
        )
        inverted = jnp.zeros(sim_self.shape, bool)
        for other in spec.get("other_columns", []):
            oc = ctx.col(other)
            # columns may be encoded at different widths/dtypes: align them
            width = max(pc.chars_l.shape[1], oc.chars_r.shape[1])
            a = _pad_chars(pc.chars_l, width)
            b = _pad_chars(oc.chars_r, width)
            sim_o = string_ops.jaro_winkler(a, b, pc.len_l, oc.len_r, 0.1, 0.7)
            inverted = inverted | ((sim_o > t1) & ~oc.null_r)
        gamma = jnp.where(
            sim_self > t1,
            jnp.int8(3),
            jnp.where(inverted, jnp.int8(2), jnp.where(sim_self > t2, jnp.int8(1), jnp.int8(0))),
        )
        return apply_null(gamma, pc.null)

    raise ValueError(f"Unknown comparison kind {kind!r}")


class _Parts(NamedTuple):
    """Everything the gamma kernels close over that is not an argument. A
    kernel is built from these and nothing else — never from a
    :class:`GammaProgram`, whose packed device table and encoded table a
    registered closure would pin for the life of the process."""

    cols: tuple  # the comparison columns' settings (private copies)
    layout: dict  # packed-table field layout: lane metadata, no data
    strides: tuple  # mixed-radix pattern strides
    n_patterns: int


def _layout_signature(layout: dict) -> tuple:
    """The packed-table layout as a hashable value: per field its kind and
    every lane index / lane slice. Metadata only — table contents and row
    counts are arguments, and shapes are jax's business."""

    def plain(v):
        return (v.start, v.stop) if isinstance(v, slice) else v

    return tuple(sorted(
        (name, type(f).__name__, tuple(plain(getattr(f, a)) for a in f.__slots__))
        for name, f in layout.items()
    ))


def _signature(cols, layout, float_dtype):
    """The registry key of a gamma program — the comparison columns'
    settings by CONTENT (a caller may deep-copy its settings per job), the
    layout and the float dtype; level counts, strides and the pattern count
    follow from the columns. None when it cannot be signed: a ``custom``
    column runs whatever callable is registered under its name right now,
    and settings json cannot write have no canonical form. Such a program
    is built per linker."""
    if any((c.get("comparison") or {}).get("kind") == "custom" for c in cols):
        return None
    try:
        cols_json = json.dumps(cols, sort_keys=True)
    except (TypeError, ValueError):
        return None
    return (
        cols_json,
        _layout_signature(layout),
        jnp.dtype(float_dtype).name,
    )


# ONE gamma body: every kernel — one chip or a mesh, virtual or materialised
# pairs, ids kept or not — composes this function and no other.
def _make_gamma_body(parts: _Parts):
    cols, layout = parts.cols, parts.layout

    # The packed table is an explicit argument, NOT a closure capture: a
    # captured device array becomes a jaxpr constant, and at millions of
    # rows that constant is serialised into the compiled program
    # (trace_audit TA-CONST pins this).
    def _gamma_body(packed, idx_l, idx_r):
        # named scopes: how the device trace's ops say which part
        # of the program they belong to (docs/observability.md)
        with jax.named_scope("row_gather"):
            rows_l = packed[idx_l]
            rows_r = packed[idx_r]
        ctx = PairContext(layout, rows_l, rows_r)
        gammas = []
        for c in cols:
            with jax.named_scope(f"cmp/{comparison_column_name(c)}"):
                gammas.append(_spec_gamma(c, ctx))
        return jnp.stack(gammas, axis=1)

    return _gamma_body


def _mesh_gamma_body(parts: _Parts, mesh):
    """The gamma body as a per-shard program over the mesh's data axis
    (packed table replicated, pair indices and G split).

    ``jit(out_shardings=...)`` alone cannot do this on a TPU: the
    string kernels are Pallas (Mosaic) custom calls there, which XLA's
    partitioner does not split — it would gather every pair onto every
    chip in front of the call. Every op in the body is per-pair, so
    under shard_map each chip runs the single-device program on its
    own ``batch / N`` slice."""
    from jax.sharding import PartitionSpec as P

    from .parallel.mesh import DATA_AXIS

    return jax.shard_map(
        _make_gamma_body(parts),
        mesh=mesh,
        in_specs=(P(), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=P(DATA_AXIS),
        # the kernels' scans start from unvarying constants (a carry
        # type mismatch under the check)
        check_vma=False,
    )


# ONE pattern-kernel template over a gamma body: a batch's pattern ids
# (exactly ``batch`` of them) and the histogram accumulator with the batch
# added.
def _make_pattern_kernel(parts: _Parts, gamma_body):
    strides_dev = jnp.asarray(parts.strides, jnp.int32)
    n_patterns = parts.n_patterns

    def _pattern_kernel(packed, idx_l, idx_r, valid, acc):
        G = gamma_body(packed, idx_l, idx_r).astype(jnp.int32)
        pid = jnp.sum(
            (G + 1) * strides_dev[None, :], axis=1, dtype=jnp.int32
        )
        masked = jnp.where(
            jnp.arange(pid.shape[0], dtype=jnp.int32) < valid,
            pid,
            n_patterns,
        )
        acc = acc + int32_histogram(masked, n_patterns + 1)
        if pattern_ids_fit_uint16(n_patterns):
            # narrow on device: halves the per-batch D2H (all
            # real ids < n_patterns <= 65535; padding-tail pids
            # are sliced off host-side before use)
            pid = pid.astype(jnp.uint16)
        return pid, acc

    return _pattern_kernel


# The jitted programs, each built from a _Parts alone: what
# GammaProgram._kernel hands the registry as ``build``.


def _jit_gamma_batch(parts: _Parts):
    body = _make_gamma_body(parts)

    # Named ``fn`` on purpose: the benchmark's gamma metrics match the XLA
    # module ``jit_fn(`` (see pairgen.make_virtual_pattern_fn, the only
    # other program of that name).
    def fn(packed, idx_l, idx_r):
        return body(packed, idx_l, idx_r)

    return jax.jit(fn)


def _jit_pattern_batch(parts: _Parts):
    return jax.jit(_make_pattern_kernel(parts, _make_gamma_body(parts)))


def _jit_pattern_batch_mesh(parts: _Parts, mesh):
    from .parallel.mesh import pair_sharding, replicated

    return jax.jit(
        _make_pattern_kernel(parts, _mesh_gamma_body(parts, mesh)),
        out_shardings=(pair_sharding(mesh), replicated(mesh)),
    )


def _string_evals(spec: dict) -> int:
    """String-kernel evaluations one comparison issues a pair position."""
    if spec["kind"] == "name_inversion":  # the self pair and each cross pair
        return 1 + len(spec.get("other_columns", []))
    return int(spec["kind"] in (
        "jaro_winkler", "levenshtein", "qgram_jaccard", "qgram_cosine"
    ))


def _vector_string_evals(col_settings: dict, layout: dict, backend) -> int:
    """Of :func:`_string_evals`, those a pair position takes through a
    vmapped vector form rather than a Pallas kernel on ``backend``: a
    Jaro-Winkler or Levenshtein evaluation whose width
    ``strings_pallas.pallas_takes`` refuses (name inversion's cross pairs at
    the wider of the two columns, as ``_spec_gamma`` pads them), and every
    q-gram evaluation (no Pallas form)."""
    from .ops.strings_pallas import pallas_takes

    spec = col_settings["comparison"]
    kind = spec["kind"]
    if kind in ("qgram_jaccard", "qgram_cosine"):
        return 1
    if kind not in ("jaro_winkler", "levenshtein", "name_inversion"):
        return 0
    width = layout[_comparison_input_column(col_settings)].width
    widths = [width] + [max(width, layout[other].width)
                        for other in spec.get("other_columns", [])
                        if kind == "name_inversion"]
    # every packed kind hands the kernels integer codes (uint8 or uint32)
    return sum(not pallas_takes(w, np.uint32, backend) for w in widths)


# The fewest rows the device's table has on a TPU. XLA's TPU compiler stages a
# gather operand of under ~300,000 rows row-major in its fast memory and then
# writes every gathered row padded from its 12-30 words to 128 lanes: 2,070 B
# of scratch a pair position for a 10,000-row table of 12 words, against 336 B
# from a table past that size, whose rows it gathers column-major (PERF.md
# section 6, PR 39: no batch above 2^23 compiled for the small table, and the
# program moved ten times the bytes). Neither a transposed operand nor a layout
# constraint on the result changes its mind; rows nobody indexes do.
# tests/test_mesh_tpu_compile.py holds the scratch of both sizes.
_MIN_TPU_TABLE_ROWS = 1 << 19


def _device_table(packed: np.ndarray) -> np.ndarray:
    """``packed`` as it is uploaded: on a TPU with zero rows appended up to
    ``_MIN_TPU_TABLE_ROWS`` (no pair index reaches them)."""
    short = _MIN_TPU_TABLE_ROWS - packed.shape[0]
    if short <= 0 or jax.default_backend() != "tpu":
        return packed
    return np.concatenate(
        [packed, np.zeros((short, packed.shape[1]), packed.dtype)]
    )


class GammaProgram:
    """One encoded table packed on the device, and the gamma kernels of its
    settings. The table (``_packed``) belongs to this program; the jitted
    kernels take it as an argument and are the PROCESS's — shared through
    ``utils.kernel_registry`` with every program whose comparison columns,
    packed layout and float dtype are equal, so a second linker on the same
    model traces, lowers and reads back nothing. A program that cannot be
    signed (:func:`_signature`) builds its own."""

    def __init__(self, settings: dict, table: EncodedTable, float_dtype=jnp.float32):
        self.settings = settings
        self.n_cols = len(settings["comparison_columns"])
        self.max_levels = max(
            c["num_levels"] for c in settings["comparison_columns"]
        )

        # Pack the compared columns into one uint32 matrix and push it to
        # device once: each pair batch then costs exactly two row gathers.
        with span("pack_table", rows=int(table.n_rows)) as sp:
            packed, layout = pack_table(
                table,
                float_dtype,
                include=comparison_columns_used(settings),
                qgram_specs=qgram_specs_for(settings),
                charset_specs=charset_specs_for(settings),
                token_only=token_only_columns(settings),
            )
            sp.count(
                lanes=int(packed.shape[1]),  # words of a packed row
                token_only_columns=sum(
                    getattr(f, "kind", None) == "token" for f in layout.values()
                ),
            )
        packed = _device_table(packed)
        with span("h2d_put", bytes=packed.nbytes):
            self._packed = jnp.asarray(packed)
        self._layout = layout

        # Pattern-id pipeline: gamma vectors mixed-radix-encode into a single
        # pattern id (strides over levels_c + 1), the complete sufficient
        # statistic per pair. One device pass then yields BOTH the per-pair
        # ids (int16/int32 host array, 3x smaller than the gamma matrix) and
        # their histogram (EM's input); scoring afterwards is a host LUT
        # gather with no further device traffic.
        cols = settings["comparison_columns"]
        self.level_counts = [int(c["num_levels"]) for c in cols]
        strides, self.n_patterns = pattern_strides_for(self.level_counts)
        self._pattern_strides = strides

        self._sig = _signature(cols, layout, float_dtype)
        self._parts = _Parts(
            # a shared kernel may retrace for new shapes long after this
            # linker mutated or dropped its settings: it reads its own copy
            cols=tuple(cols if self._sig is None else copy.deepcopy(cols)),
            layout=dict(layout),
            strides=tuple(strides),
            n_patterns=self.n_patterns,
        )
        # this program's kernels by (fun, variant): the registry is asked
        # once per kernel, and an eviction cannot take a kernel in use
        self._kernels: dict = {}

        # The compiled-artifact analogue of the reference logging its
        # generated SQL at debug level (/root/reference/splink/gammas.py:120).
        probe = jnp.zeros(8, jnp.int32)
        log_jaxpr("gamma_program", self._gamma_batch, probe, probe)

    def kernel_counts(self, positions: int, backend: str | None = None) -> dict:
        """Which kernel forms the pass this program just made over
        ``positions`` pair positions was made of, for its stage span
        (docs/observability.md): ``string_evals`` — string-kernel evaluations
        issued, positions times the columns' sum (Jaro-Winkler, Levenshtein
        and q-gram columns 1 each, name inversion 1 + its other columns);
        ``string_evals_vector`` — those of them the vmapped vector forms
        made, not a Pallas kernel on ``backend`` (the default backend when
        None): every one off a TPU, 0 on a TPU when every column is
        Jaro-Winkler or Levenshtein within the kernels' width; how many
        columns are Levenshtein and how many name inversion."""
        cols = self.settings["comparison_columns"]
        specs = [c["comparison"] for c in cols]
        kinds = [spec["kind"] for spec in specs]
        vector = sum(_vector_string_evals(c, self._layout, backend) for c in cols)
        return {
            "string_evals": int(positions) * sum(map(_string_evals, specs)),
            "string_evals_vector": int(positions) * vector,
            "levenshtein_columns": kinds.count("levenshtein"),
            "name_inversion_columns": kinds.count("name_inversion"),
        }

    def _kernel(self, fun: str, variant: tuple, build, shareable: bool = True,
                mesh=None):
        """The jitted program ``build(parts)`` makes, from the process's
        registry when this program has a signature and the caller could
        sign ``variant`` (the rest of what ``build`` closes over); else
        this program's own. ``build`` must not capture the program.
        ``mesh``: the mesh the program shards over (its key is in
        ``variant``), for the lookup span's ``devices``."""
        fn = self._kernels.get((fun, variant))
        if fn is None:
            key = (
                (fun, self._sig, variant)
                if self._sig is not None and shareable else None
            )
            fn = self._kernels[(fun, variant)] = kernel_registry.lookup(
                fun, key, functools.partial(build, self._parts),
                devices=1 if mesh is None else mesh.devices.size,
            )
        return fn

    @property
    def _gamma_batch_fn(self):
        """The jitted gamma program (packed-explicit): what the host-batched
        G paths and ad-hoc scoring run, one (batch, columns) array a call."""
        return self._kernel("gamma_batch", (), _jit_gamma_batch)

    def _gamma_batch(self, il, ir):
        G = self._gamma_batch_fn(self._packed, il, ir)
        dispatched("fn", G, rows=il.shape[0])
        return G

    @property
    def _pattern_kernel(self):
        """The un-jitted pattern kernel (audits)."""
        return _make_pattern_kernel(
            self._parts, _make_gamma_body(self._parts)
        )

    def _run_pattern_batch(self, il, ir, valid, acc):
        fn = self._kernel("pattern_batch", (), _jit_pattern_batch)
        pid, acc = fn(self._packed, il, ir, valid, acc)
        dispatched("_pattern_kernel", acc, rows=il.shape[0])
        return pid, acc

    @property
    def _pattern_batch(self):
        """The pattern-batch call, or None when the pattern space's dense
        device accumulators would pass their bound (``pattern_space_fits``)
        and callers must use the gamma-matrix paths. A property, not an
        attribute: a bound method stored on the program would be a reference
        cycle, and the packed table would stay on the device until the
        cyclic collector happened to run."""
        return (self._run_pattern_batch
                if pattern_space_fits(self.n_patterns) else None)

    def _pattern_batch_for_mesh(self, mesh):
        """Mesh-sharded twin of the pattern-batch kernel (same
        _pattern_kernel body): the pair index arrays shard over the data
        axis (the only sharded inputs — packed table data and the
        accumulator replicate), each chip runs the gather + gamma body on
        its slice (_mesh_gamma_body), XLA partitions the bincount along
        pairs and inserts the histogram psum. Mirrors
        pairgen.make_virtual_pattern_fn's sharding layout so materialised
        pattern jobs compose with multi-chip EM the same way virtual ones
        do. Keyed by the mesh's VALUE (kernel_registry.mesh_key), so equal
        meshes from repeated mesh_from_settings calls share one compile."""
        return self._kernel(
            "pattern_batch_mesh", (kernel_registry.mesh_key(mesh),),
            functools.partial(_jit_pattern_batch_mesh, mesh=mesh),
            mesh=mesh,
        )

    def _mesh_pattern_context(self, mesh):
        """(run_batch, zero_acc) for a mesh pattern pass — the shared
        setup compute_pattern_ids and PatternStream both need: replicated
        packed table, sharded index uploads, replicated accumulator."""
        import jax

        from .parallel.mesh import pair_sharding, put_on_mesh, replicated

        shard = pair_sharding(mesh)
        repl = replicated(mesh)
        (packed_dev,) = put_on_mesh(repl, self._packed)
        fn = self._pattern_batch_for_mesh(mesh)

        def run_batch(bl, br, valid, acc):
            pid, acc = fn(
                packed_dev,
                jax.device_put(bl, shard),
                jax.device_put(br, shard),
                valid,
                acc,
            )
            dispatched(
                "_pattern_kernel", acc, rows=len(bl),
                devices=mesh.devices.size,
            )
            return pid, acc

        def zero_acc():
            return jax.device_put(
                np.zeros(self.n_patterns + 1, np.int32), repl
            )

        return run_batch, zero_acc

    def compute_pattern_ids(
        self,
        idx_l: np.ndarray,
        idx_r: np.ndarray,
        batch_size: int = DEFAULT_PAIR_BATCH,
        mesh=None,
    ):
        """One pass over the pair set: (pattern_ids, counts).

        pattern_ids is (n,) uint16 when the pattern space allows (int32
        otherwise); counts is the (n_patterns,) int64 histogram. The int32
        device accumulator flushes to host int64 every _HIST_FLUSH_BATCHES
        batches so counts cannot overflow.

        With ``mesh``, each batch shards over the mesh's data axis
        (_pattern_batch_for_mesh) — bit-identical output, per-chip work
        divided by the mesh size.
        """
        if self._pattern_batch is None:
            raise _too_many_patterns(self.n_patterns, "the gamma-matrix paths")
        n = len(idx_l)
        id_dtype = (
            np.uint16 if pattern_ids_fit_uint16(self.n_patterns) else np.int32
        )
        pids = np.empty(n, id_dtype)
        total = np.zeros(self.n_patterns, np.int64)
        if n == 0:
            return pids, total
        batch_size = min(batch_size, max(n, 1))
        if mesh is not None:
            from .parallel.mesh import gather_from_mesh, pad_to_multiple

            batch_size = pad_to_multiple(batch_size, mesh.devices.size)
            run_batch, zero_acc = self._mesh_pattern_context(mesh)
            ids_home = gather_from_mesh  # sharded ids: span mesh_gather
        else:
            ids_home = np.asarray
            run_batch = lambda bl, br, valid, acc: self._pattern_batch(  # noqa: E731
                *_put_pair_batch(bl, br), valid, acc
            )
            zero_acc = lambda: jnp.zeros(self.n_patterns + 1, jnp.int32)  # noqa: E731
        flush_every = max(min(_HIST_FLUSH_BATCHES, (1 << 30) // batch_size), 1)
        acc = zero_acc()
        in_acc = 0
        pending = None

        def read_pending(pending):
            ps, pe, prev = pending
            pids[ps:pe] = fetch(prev, via=ids_home)[: pe - ps].astype(id_dtype)

        for start in range(0, n, batch_size):
            stop = min(start + batch_size, n)
            bl = idx_l[start:stop]
            br = idx_r[start:stop]
            if stop - start < batch_size:
                pad = batch_size - (stop - start)
                bl = np.concatenate([bl, np.zeros(pad, bl.dtype)])
                br = np.concatenate([br, np.zeros(pad, br.dtype)])
            pid, acc = run_batch(bl, br, stop - start, acc)
            count(batches=1)
            if pending is not None:
                read_pending(pending)
            pending = (start, stop, pid)
            in_acc += 1
            if in_acc >= flush_every:
                read_pending(pending)
                pending = None
                total += fetch(acc)[:-1]
                acc = zero_acc()
                in_acc = 0
        if pending is not None:
            read_pending(pending)
        if in_acc:
            total += fetch(acc)[:-1]
        return pids, total

    def pattern_rows(self, ids: np.ndarray) -> np.ndarray:
        """(len(ids), n_cols) int8: the gamma row each of the pattern ids
        ``ids`` decodes to."""
        return pattern_rows_for(self.level_counts, ids)

    def compute(
        self, idx_l: np.ndarray, idx_r: np.ndarray, batch_size: int = DEFAULT_PAIR_BATCH
    ) -> np.ndarray:
        """Gamma matrix (n_pairs, n_cols) int8, batched to bound HBM use.

        The final short batch is padded to ``batch_size`` so every call hits
        the same compiled program (no shape-driven recompiles).
        """
        return self.compute_with_device(idx_l, idx_r, batch_size)[0]

    def compute_with_device(
        self,
        idx_l: np.ndarray,
        idx_r: np.ndarray,
        batch_size: int = DEFAULT_PAIR_BATCH,
        keep_device: bool = False,
    ):
        """(host gamma matrix, device gamma matrix | None).

        With ``keep_device`` the per-batch device outputs are also
        concatenated on device and returned, so a resident-EM caller can feed
        them straight into the EM loop without re-uploading the matrix it
        just downloaded (a full extra round-trip over the host<->TPU link).
        """
        n = len(idx_l)
        if n == 0:
            host = np.zeros((0, self.n_cols), np.int8)
            return host, (jnp.asarray(host) if keep_device else None)
        out = np.empty((n, self.n_cols), np.int8)
        device_batches = []
        pos = 0
        for arr, pG, valid in self._iter_gamma_batches(
            idx_l, idx_r, batch_size
        ):
            out[pos : pos + valid] = arr
            if keep_device:
                device_batches.append(pG[:valid])
            pos += valid
        dev = None
        if keep_device:
            dev = (
                device_batches[0]
                if len(device_batches) == 1
                else jnp.concatenate(device_batches)
            )
        return out, dev

    def _iter_gamma_batches(
        self, idx_l: np.ndarray, idx_r: np.ndarray, batch_size: int
    ):
        """The ONE batched gamma loop, yielding ``(host_rows, device_G,
        valid)`` per ``batch_size`` batch (host_rows already sliced to the
        valid count; device_G still padded — consumers slice only if they
        keep it, so the lazy device slice is never dispatched for nothing).

        Double-buffered: batch k+1 is dispatched before batch k's result is
        pulled to the host, so device compute overlaps the D2H transfer
        (JAX dispatch is async; np.asarray is the only sync point). Shared by
        :meth:`compute_with_device` (resident G) and
        :meth:`iter_gamma_chunks` (the spill-fed stream) — their
        bit-identity contract is this single implementation.
        """
        n = len(idx_l)
        batch_size = min(batch_size, max(n, 1))
        pending = None  # (rows_in_batch, device result)

        def read_pending(pending):
            valid, pG = pending
            return fetch(pG)[:valid], pG, valid

        for start in range(0, n, batch_size):
            stop = min(start + batch_size, n)
            bl = np.asarray(idx_l[start:stop])
            br = np.asarray(idx_r[start:stop])
            if stop - start < batch_size:
                pad = batch_size - (stop - start)
                bl = np.concatenate([bl, np.zeros(pad, bl.dtype)])
                br = np.concatenate([br, np.zeros(pad, br.dtype)])
            G = self._gamma_batch(*_put_pair_batch(bl, br))
            count(batches=1)
            if pending is not None:
                yield read_pending(pending)
            pending = (stop - start, G)
        yield read_pending(pending)

    def iter_gamma_chunks(
        self,
        idx_l: np.ndarray,
        idx_r: np.ndarray,
        batch_size: int = DEFAULT_PAIR_BATCH,
    ):
        """Yield host gamma blocks of ``batch_size`` pairs — the bounded-
        working-set twin of :meth:`compute_with_device` for consumers that
        must never hold the full G (the spill-fed streamed EM: at billions
        of pairs even int8 G is tens of GB of host RAM). Both ride the
        SAME :meth:`_iter_gamma_batches` loop, so the yielded blocks
        concatenate to exactly the matrix ``compute_with_device`` returns —
        batch boundaries at multiples of ``batch_size`` from the slice
        start, which is what keeps a spill-streamed EM trajectory
        bit-identical to the resident streamed one. ``idx_l`` / ``idx_r``
        may be memmaps; each slice is read once per pass."""
        if len(idx_l) == 0:
            return
        for arr, _pG, _valid in self._iter_gamma_batches(
            idx_l, idx_r, batch_size
        ):
            yield arr


def _put_pair_batch(bl, br):
    """One batch's pair indices on the device, under an ``h2d_put`` span."""
    with span("h2d_put", bytes=bl.nbytes + br.nbytes):
        return jnp.asarray(bl), jnp.asarray(br)


class _StreamBatcher:
    """Re-batches arbitrary-size (idx_l, idx_r) chunks into fixed
    ``batch_size`` device batches (same boundaries as a single pass over the
    concatenated pair order, so results are bitwise identical to the
    non-streamed paths). Subclasses implement _emit(bl, br, valid)."""

    def __init__(self, batch_size: int):
        self.batch_size = batch_size
        self.total = 0
        self._buf_l: np.ndarray | None = None
        self._buf_r: np.ndarray | None = None
        self._fill = 0

    def feed(self, i: np.ndarray, j: np.ndarray) -> None:
        b = self.batch_size
        self.total += len(i)
        pos = 0
        if self._fill:
            take = min(b - self._fill, len(i))
            self._buf_l[self._fill : self._fill + take] = i[:take]
            self._buf_r[self._fill : self._fill + take] = j[:take]
            self._fill += take
            pos = take
            if self._fill == b:
                self._emit(self._buf_l.copy(), self._buf_r.copy(), b)
                self._fill = 0
        # full batches straight from the chunk (no buffering copy)
        while len(i) - pos >= b:
            self._emit(i[pos : pos + b], j[pos : pos + b], b)
            pos += b
        rest = len(i) - pos
        if rest:
            if self._buf_l is None:
                self._buf_l = np.empty(b, i.dtype)
                self._buf_r = np.empty(b, j.dtype)
            self._buf_l[self._fill : self._fill + rest] = i[pos:]
            self._buf_r[self._fill : self._fill + rest] = j[pos:]
            self._fill += rest

    def _flush_tail(self) -> None:
        if self._fill:
            bl = self._buf_l.copy()
            br = self._buf_r.copy()
            bl[self._fill :] = 0  # padded rows, masked by valid
            br[self._fill :] = 0
            self._emit(bl, br, self._fill)
            self._fill = 0

    @staticmethod
    def _drain_parts(parts: list[np.ndarray], out: np.ndarray) -> None:
        """Fill a preallocated output from the buffered parts, releasing
        each as it is copied — peak host RAM is output + one batch, not 2x
        output (np.concatenate)."""
        pos = 0
        parts.reverse()
        while parts:
            part = parts.pop()
            out[pos : pos + len(part)] = part
            pos += len(part)
        assert pos == len(out)


class GammaStream(_StreamBatcher):
    """Incremental gamma computation: feed pair chunks as blocking emits
    them; device batches dispatch asynchronously so scoring overlaps the
    host's next join. finish() returns (host G, device G | None) exactly as
    GammaProgram.compute_with_device would for the concatenated pairs.

    ``keep_device_limit`` bounds the HBM held by kept batches: once total
    fed pairs exceed it the device copies are dropped (the run is headed
    for a streamed/pattern regime that re-uploads anyway).
    """

    def __init__(self, program: "GammaProgram", batch_size: int,
                 keep_device_limit: int = 0):
        super().__init__(batch_size)
        self.program = program
        self.keep_limit = keep_device_limit
        self._pending = None
        self._out_parts: list[np.ndarray] = []
        self._device_batches: list[jnp.ndarray] | None = (
            [] if keep_device_limit > 0 else None
        )

    def _read_pending(self):
        v, prev = self._pending
        self._out_parts.append(fetch(prev)[:v])
        if self._device_batches is not None:
            self._device_batches.append(prev[:v])
        self._pending = None

    def _emit(self, bl, br, valid):
        G = self.program._gamma_batch(*_put_pair_batch(bl, br))
        if self._device_batches is not None and self.total > self.keep_limit:
            self._device_batches = None  # too big: free HBM
        # double buffer: read back the PREVIOUS batch (it has finished by
        # the time the next one is dispatched), keeping dispatch async
        if self._pending is not None:
            self._read_pending()
        self._pending = (valid, G)

    def finish(self):
        self._flush_tail()
        if self._pending is not None:
            self._read_pending()
        n_cols = self.program.n_cols
        if not self._out_parts:
            host = np.zeros((0, n_cols), np.int8)
            return host, None
        host = np.empty((self.total, n_cols), np.int8)
        parts = self._out_parts
        self._out_parts = []
        self._drain_parts(parts, host)
        dev = None
        if self._device_batches is not None and self.total <= self.keep_limit:
            dev = (
                self._device_batches[0]
                if len(self._device_batches) == 1
                else jnp.concatenate(self._device_batches)
            )
        return host, dev


class PatternStream(_StreamBatcher):
    """Incremental pattern-id pipeline: feed pair chunks, finish() returns
    (pattern_ids, counts) exactly as compute_pattern_ids would — the gamma
    matrix never materialises, and the device pass happens WHILE blocking
    still runs instead of as a second sweep over the (possibly spilled)
    pair index."""

    def __init__(self, program: "GammaProgram", batch_size: int, mesh=None):
        if program._pattern_batch is None:
            raise _too_many_patterns(program.n_patterns, "GammaStream")
        self.mesh = mesh
        self._ids_home = np.asarray
        if mesh is not None:
            from .parallel.mesh import gather_from_mesh, pad_to_multiple

            batch_size = pad_to_multiple(batch_size, mesh.devices.size)
            self._run_batch, self._zero_acc = program._mesh_pattern_context(
                mesh
            )
            self._ids_home = gather_from_mesh  # sharded ids: span mesh_gather
        else:
            self._zero_acc = lambda: jnp.zeros(
                program.n_patterns + 1, jnp.int32
            )
        super().__init__(batch_size)
        self.program = program
        self.id_dtype = (
            np.uint16
            if pattern_ids_fit_uint16(program.n_patterns)
            else np.int32
        )
        self._parts: list[np.ndarray] = []
        self._pending = None
        self._acc = self._zero_acc()
        self._in_acc = 0
        self._flush_every = max(
            min(_HIST_FLUSH_BATCHES, (1 << 30) // batch_size), 1
        )
        self._total_counts = np.zeros(program.n_patterns, np.int64)

    def _read_pending(self):
        v, prev = self._pending
        arr = fetch(prev, via=self._ids_home)
        self._parts.append(arr[:v].astype(self.id_dtype))
        self._pending = None

    def _emit(self, bl, br, valid):
        if self.mesh is not None:
            pid, self._acc = self._run_batch(bl, br, valid, self._acc)
        else:
            pid, self._acc = self.program._pattern_batch(
                *_put_pair_batch(bl, br), valid, self._acc
            )
        if self._pending is not None:
            self._read_pending()
        self._pending = (valid, pid)
        self._in_acc += 1
        if self._in_acc >= self._flush_every:
            self._total_counts += fetch(self._acc)[:-1]
            self._acc = self._zero_acc()
            self._in_acc = 0

    def finish(self):
        self._flush_tail()
        if self._pending is not None:
            self._read_pending()
        if self._in_acc:
            self._total_counts += fetch(self._acc)[:-1]
            self._in_acc = 0
        pids = np.empty(self.total, self.id_dtype)
        parts = self._parts
        self._parts = []
        self._drain_parts(parts, pids)
        return pids, self._total_counts


def pattern_strides_for(level_counts: list[int]) -> tuple[list[int], int]:
    """Mixed-radix strides and total pattern count for gamma vectors with
    the given per-column level counts (digit c = gamma_c + 1)."""
    strides, n_patterns = [], 1
    for lc in level_counts:
        strides.append(n_patterns)
        n_patterns *= int(lc) + 1
    return strides, n_patterns


@functools.partial(jax.jit, static_argnames=("n_patterns",))
def _pattern_counts_batch(G, valid, strides, n_patterns, acc):
    pattern = jnp.sum(
        (G.astype(jnp.int32) + 1) * strides[None, :], axis=1, dtype=jnp.int32
    )
    pattern = jnp.where(
        jnp.arange(pattern.shape[0], dtype=jnp.int32) < valid,
        pattern,
        n_patterns,
    )
    return acc + int32_histogram(pattern, n_patterns + 1)


# Flush the device int32 histogram accumulator to the host int64 total at
# least this often. Without x64 enabled (the TPU default) jax silently
# downgrades an int64 accumulator to int32, so the device-side partial sum
# must stay safely below 2^31: flush_every * batch_size <= 2^30.
_HIST_FLUSH_BATCHES = 1 << 10


def pattern_counts_from_gammas(
    G: np.ndarray, level_counts: list[int], batch_size: int = DEFAULT_PAIR_BATCH
) -> np.ndarray:
    """(n_patterns,) int64 pattern counts from a host gamma matrix, batched
    through the device.

    The device accumulator is int32 (int64 does not exist on TPU without
    x64) and is flushed into a host int64 total every _HIST_FLUSH_BATCHES
    batches, so counts cannot overflow at any pair count.
    """
    strides, n_patterns = pattern_strides_for(level_counts)
    strides_dev = jnp.asarray(strides, jnp.int32)
    n = len(G)
    total = np.zeros(n_patterns, np.int64)
    if n == 0:
        return total
    batch_size = min(batch_size, max(n, 1))
    # keep the int32 partial sum below 2^30 regardless of batch size
    flush_every = max(min(_HIST_FLUSH_BATCHES, (1 << 30) // batch_size), 1)
    acc = jnp.zeros(n_patterns + 1, jnp.int32)
    batches_in_acc = 0
    for start in range(0, n, batch_size):
        stop = min(start + batch_size, n)
        Gb = G[start:stop]
        if stop - start < batch_size:
            Gb = np.concatenate(
                [Gb, np.zeros((batch_size - (stop - start), G.shape[1]), G.dtype)]
            )
        acc = _pattern_counts_batch(
            jnp.asarray(Gb), stop - start, strides_dev, n_patterns, acc
        )
        dispatched("_pattern_counts_batch", acc, rows=batch_size)
        batches_in_acc += 1
        if batches_in_acc >= flush_every:
            total += fetch(acc)[:-1]
            acc = jnp.zeros(n_patterns + 1, jnp.int32)
            batches_in_acc = 0
    if batches_in_acc:
        total += fetch(acc)[:-1]
    return total


@functools.partial(jax.jit, static_argnames=("levels",))
def level_counts(G, levels: tuple[int, ...]):
    """int32[C, max(levels) + 1]: per column c of the gamma matrix ``G``
    (int8[n, C], on the device), the count at level -1 (null), 0, ...,
    ``levels[c] - 1`` — ``linker._gamma_histograms``'s bins, a value below
    -1 or above ``levels[c] - 1`` counted in the end bin it is nearest, and
    a column's bins past its own ``levels[c] + 1`` left 0. One compare-and-sum
    on ``G`` as it is, which XLA fuses into a single read of it: no
    (n, C, L) temporary, no widened copy. Exact while n < 2^31 (the resident
    regime's ``max_resident_pairs``)."""
    n, n_cols = G.shape
    top = max(levels)
    # at_least[c, k]: the values of column c at level k or above (k < L_c)
    at_least = jnp.sum(G[:, :, None] >= jnp.arange(top, dtype=G.dtype),
                       axis=0, dtype=jnp.int32)
    at_least = jnp.where(
        jnp.arange(top, dtype=jnp.int32) < jnp.asarray(levels, jnp.int32)[:, None],
        at_least, 0,
    )
    at_least = jnp.concatenate([
        jnp.full((n_cols, 1), n, jnp.int32), at_least,
        jnp.zeros((n_cols, 1), jnp.int32),
    ], axis=1)
    return at_least[:, :-1] - at_least[:, 1:]


def pattern_rows_for(level_counts: list[int], ids: np.ndarray) -> np.ndarray:
    """(len(ids), C) int8: the gamma vector each mixed-radix pattern id of
    ``ids`` decodes to."""
    strides, _ = pattern_strides_for(level_counts)
    ids = np.asarray(ids, np.int64)
    out = np.empty((len(ids), len(level_counts)), np.int8)
    for c, lc in enumerate(level_counts):
        out[:, c] = ((ids // strides[c]) % (int(lc) + 1)).astype(np.int8) - 1
    return out


def patterns_matrix_for(level_counts: list[int]) -> np.ndarray:
    """(n_patterns, C) int8 gamma vectors in mixed-radix pattern-id order:
    every pattern of the space (tests and oracles; the linker decodes only
    the patterns a job sees)."""
    _, n_patterns = pattern_strides_for(level_counts)
    return pattern_rows_for(level_counts, np.arange(n_patterns))
