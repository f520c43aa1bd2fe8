"""User-facing linker: the TPU-native counterpart of the reference's Splink
class (/root/reference/splink/__init__.py:33-195).

Same API shape — ``Splink(settings, df=... | df_l=..., df_r=...)``,
``get_scored_comparisons()``, ``manually_apply_fellegi_sunter_weights()``,
``make_term_frequency_adjustments()``, ``save_model_as_json()`` and module
level ``load_from_json`` — but the inputs/outputs are pandas DataFrames and
the execution pipeline is: host encode -> host hash-join blocking -> device
gamma program -> one fused jitted EM -> device scoring, instead of generated
Spark SQL.
"""

from __future__ import annotations

import contextlib
import copy
import functools
import logging
import math
import operator
import os
import warnings
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .blocking import PairIndex, block_using_rules
from .check_types import check_types
from .data import EncodedTable, concat_tables, encode_table
from .em import (
    run_em,
    run_em_checkpointed,
    score_pairs,
    score_pairs_with_intermediates,
    score_pairs_with_intermediates_logits,
    score_pairs_with_logits,
)
from .gammas import GammaProgram, level_counts, register_comparison  # noqa: F401 (re-export)
from .models.fellegi_sunter import FSParams
from .params import Params, load_params_from_json
from .parallel.mesh import mesh_from_settings, shard_pairs
from .regime import Facts, Wait, decide
from .settings import comparison_column_name, complete_settings_dict
from .utils.compile_cache import enable_compilation_cache
from .utils.profiling import StageTimer, count, dispatched, fetch, span

logger = logging.getLogger("splink_tpu")

try:  # pandas is required for the linker facade (not for the kernels)
    import pandas as pd
except ImportError:  # pragma: no cover
    pd = None


def _gamma_histograms(settings, G, weights=None, chunk: int = 1 << 22) -> dict:
    """Per-comparison-column gamma-level histogram (telemetry record):
    column name -> [count at level -1 (null), level 0, ..., level L-1].
    ``G`` is either the per-pair gamma matrix or — with ``weights`` (the
    pattern-count vector) — the pattern matrix. Counted on the host, where
    no device copy of ``G`` is kept (``device_rows`` 0; where one is,
    ``Splink._run_em`` counts it there with ``gammas.level_counts``).
    Chunked so the int64 promotion temporaries stay O(chunk): the streamed
    regime reaches here with a G that is huge by definition, and
    observability must not multiply that path's host footprint."""
    cols = settings["comparison_columns"]
    acc = [np.zeros(int(col["num_levels"]) + 1, np.float64) for col in cols]
    with span("gamma_histogram", rows=len(G), device_rows=0):
        for s in range(0, len(G), chunk):
            Gc = G[s : s + chunk]
            w = weights[s : s + chunk] if weights is not None else None
            for c, col in enumerate(cols):
                levels = int(col["num_levels"])
                g = np.asarray(Gc[:, c], np.int64) + 1  # -1 (null) -> bin 0
                acc[c] += np.bincount(
                    np.clip(g, 0, levels), weights=w, minlength=levels + 1
                )[: levels + 1]
    return _level_dict(settings, acc)


def _level_dict(settings, counts) -> dict:
    """The histogram's dict from per-column bin counts (row c: at least the
    column's ``num_levels + 1`` bins, level -1 first)."""
    return {
        comparison_column_name(col): [
            int(v) for v in counts[c][: int(col["num_levels"]) + 1]
        ]
        for c, col in enumerate(settings["comparison_columns"])
    }


class _FrameWriter:
    """The scored frame's columns (the reference's layout and order,
    /root/reference/splink/expectation_step.py:128-165), allocated once at
    the length the caller knows — a whole job's pairs on the one-frame
    path, one chunk's on the streaming API — and written chunk by chunk at
    the chunk's offset: each byte of the frame is written once, into the
    column the caller receives. ``frame()`` hands the columns to pandas as
    they are (one block a column, nothing consolidated, nothing copied), so
    the frame owns its memory: no column is a view of a linker table, a
    per-pattern table or the ``frame_column`` cache.

    With the TF u-probability fold active (``_tf_fold_ctx``) the frame
    carries a ``tf_match_probability`` column — the first-class TF-adjusted
    score, bit-identical to what the serve megakernel returns for the same
    pairs.

    The dtype rule: a retained column's dtype is what pandas infers for the
    WHOLE input column (``EncodedTable.frame_column``), in every chunk and
    in the zero-row frame — never for the subset a chunk holds. pyarrow
    stays optional: with it a column of strings is an Arrow-backed ``str``
    column taken by the pair index (counted as ``columnar_strings``),
    without it the same code gathers the objects pandas keeps."""

    def __init__(self, linker: "Splink", n: int):
        self._linker = linker
        self.n = n  # rows allocated: the pairs the caller expects, or a bound
        self.rows = 0  # rows written so far
        self.chunks = 0
        self.cols = None  # allocated by the first write (or an empty frame)

    def _allocate(self) -> None:
        """The frame's columns, in its column order. Runs under the first
        chunk's ``assemble_frame`` span: typing a retained input column
        (``frame_column``, once a table) is part of the frame's cost."""
        linker, n = self._linker, self.n
        settings = linker.settings
        table = linker._ensure_encoded()
        self._n_table_rows = table.n_rows
        dtype = linker._float_dtype
        self._tf_ctx = linker._tf_fold_ctx()
        # name -> the (n,) numpy column, or the list of per-chunk takes of
        # an extension (Arrow string) column; in the frame's column order
        cols: dict[str, np.ndarray | list] = {"match_probability": np.empty(n, dtype)}
        if self._tf_ctx is not None:
            cols["tf_match_probability"] = np.empty(n, dtype)
        # retained column's name -> (the input column, 0: left / 1: right)
        self._retained: dict[str, tuple] = {}

        def add_lr(name, make=None):
            if f"{name}_l" in cols:
                return
            values = table.frame_column(name, make)
            for side, column in enumerate((f"{name}_l", f"{name}_r")):
                self._retained[column] = (values, side)
                cols[column] = (
                    np.empty(n, values.dtype)
                    if isinstance(values, np.ndarray) else []
                )

        add_lr(settings["unique_id_column_name"], lambda: table.unique_id)
        # per comparison column: its gamma_, prob_.._non_match, prob_.._match names
        self._scored = []
        for col in settings["comparison_columns"]:
            name = comparison_column_name(col)
            if settings["retain_matching_columns"] or col["term_frequency_adjustments"]:
                for used in [name] if "col_name" in col else col["custom_columns_used"]:
                    add_lr(used)
            names = [f"gamma_{name}"]
            cols[names[0]] = np.empty(n, np.int64)
            if settings["retain_intermediate_calculation_columns"]:
                names += [f"prob_gamma_{name}_non_match", f"prob_gamma_{name}_match"]
                for prob in names[1:]:
                    cols[prob] = np.empty(n, dtype)
            self._scored.append(names)
        if settings["link_type"] == "link_and_dedupe":
            add_lr(
                "_source_table",
                lambda: np.array(["left", "right"], dtype=object)[table.source_table],
            )
        for extra in settings["additional_columns_to_retain"]:
            add_lr(extra)
        self.cols = cols

    def _counts(self) -> dict:
        dtypes = [values.dtype for values, _ in self._retained.values()]
        typed = [d for d in dtypes if isinstance(d, pd.StringDtype)]
        return dict(
            columns=len(self.cols),
            string_columns=len(typed) + sum(d == object for d in dtypes),
            # string columns that hold no Python object per pair
            columnar_strings=sum(d.storage == "pyarrow" for d in typed),
        )

    def write(self, il, ir, levels, p, prob_m, prob_u, z, by=None) -> None:
        """One chunk's rows at the next offset, under one ``assemble_frame``
        span (``in_place_rows``: the rows that go straight into the frame's
        own columns). ``levels[c]``, ``prob_m[c]`` and ``prob_u[c]`` are
        comparison column c's values and ``p``, ``z`` the scores and match
        logits: one entry a pair of the chunk — or, with the pairs' pattern
        ids in ``by``, one a PATTERN, taken by id.

        A chunk of ``_POOL_ROWS`` rows or more is filled by a pool of host
        threads (``pooled_rows``: its rows, ``fill_threads``: the workers;
        0 and 1 where the chunk was filled on the driver alone). The
        pattern-id takes run together with the retained columns' takes, so
        with ``by`` a ``lut_gather`` span covers the driver's wait for the
        whole fill; the TF fold (a device call) stays on the driver and runs
        before that wait, while the pool fills the other columns."""
        with span("assemble_frame", rows=len(il), in_place_rows=len(il)) as sp:
            if self.cols is None:
                self._allocate()
            sp.count(**self._counts())
            threads = self._write(il, ir, levels, p, prob_m, prob_u, z, by)
            sp.count(pooled_rows=len(il) if threads > 1 else 0,
                     fill_threads=threads)
            self.chunks += 1

    def _write(self, il, ir, levels, p, prob_m, prob_u, z, by) -> int:
        """Fills the chunk's rows of every column; returns the threads that
        did it. Each task writes a part of the frame no other task writes —
        a row span of a numpy column, or one side of an extension column —
        so the bytes are the driver's own, whoever wrote them."""
        start, stop = self.rows, self.rows + len(il)
        if stop > self.n:
            raise ValueError(
                f"the pair stream holds more than the {self.n} pairs its "
                "frame was allocated for"
            )
        cols = {
            name: col[start:stop] if isinstance(col, np.ndarray) else col
            for name, col in self.cols.items()
        }
        _check_index(il, self._n_table_rows)
        _check_index(ir, self._n_table_rows)
        if by is None:
            gather = contextlib.nullcontext()
        else:
            _check_index(by, len(p))
            gather = span("lut_gather", rows=len(il))
        # (out, src, index) of every numpy column, and the extension columns'
        # takes: whole columns, appended to the column's chunks
        puts = [(cols["match_probability"], p, by)]
        for c, names in enumerate(self._scored):
            for name, src in zip(names, (levels, prob_u, prob_m)):
                puts.append((cols[name], src[c], by))
        tasks = []
        for name, (values, side) in self._retained.items():
            idx = ir if side else il
            if isinstance(values, np.ndarray):
                puts.append((cols[name], values, idx))
            else:  # a pandas array: taken as a column, typed as it arrives
                tasks.append(functools.partial(_take_into, cols[name], values, idx))
        pooled = len(il) >= _POOL_ROWS
        step = _SPAN_BLOCKS * _TAKE_ROWS if pooled else None
        # the takes first: the longest tasks, none of them split
        tasks += [functools.partial(_put, *part)
                  for put in puts for part in _row_spans(*put, step)]
        threads = max(min(_host_cores(), len(tasks)), 1) if pooled else 1
        fold = None
        if self._tf_ctx is not None and len(il):
            def fold():
                logits = z[by] if by is not None and z is not None else z
                self._linker._tf_fold_pairs(
                    logits, il, ir, self._tf_ctx, out=cols["tf_match_probability"]
                )
        pool = (ThreadPoolExecutor(threads, thread_name_prefix="frame_fill")
                if threads > 1 else None)
        try:
            # the pool's tasks are all submitted here, the driver's wait after
            # the fold; without a pool the driver runs them in the same place
            done = (map if pool is None else pool.map)(operator.call, tasks)
            if fold is not None:
                fold()
            with gather:
                for _ in done:  # the first task's exception raises here
                    pass
        finally:
            if pool is not None:
                pool.shutdown(wait=True, cancel_futures=True)
        self.rows = stop
        return threads

    def frame(self) -> "pd.DataFrame":
        """The rows written, as the frame the caller keeps, under one
        ``concat_frame`` span: what is left of joining chunks. An extension
        column's per-chunk takes become one column (Arrow: one chunked
        array, nothing copied), no numeric byte moves, and pandas is handed
        the columns as they are. Where fewer rows came than were allocated
        for (``n`` was a bound) the columns are handed out as their leading
        rows; no row at all is the typed zero-row frame."""
        with span("concat_frame", chunks=self.chunks, rows=self.rows):
            if self.cols is None:
                self._allocate()
            cols = {}
            for name, col in self.cols.items():
                if isinstance(col, np.ndarray):
                    cols[name] = col[: self.rows]
                elif len(col) > 1:
                    cols[name] = pd.concat(
                        [pd.Series(chunk, copy=False) for chunk in col],
                        ignore_index=True,
                    ).array
                else:
                    cols[name] = col[0] if col else self._retained[name][0][:0]
            return pd.DataFrame(cols, copy=False)


def _check_index(idx: np.ndarray, n: int) -> None:
    """What numpy's own indexing checks and ``_put``'s take does not."""
    if len(idx) and not (0 <= idx.min() and idx.max() < n):
        raise IndexError(f"index out of bounds for {n} rows")


# rows a take: numpy converts an int32 index to intp before it takes — a
# temporary as large as the column written, once a COLUMN, and memory the
# process touches for the first time costs several times a warm write. A
# block at a time the temporary is half a megabyte that malloc hands back
# warm and the cache keeps.
_TAKE_ROWS = 1 << 16


def _put(out: np.ndarray, src: np.ndarray, by: np.ndarray | None) -> None:
    """``out[:] = src`` — or, with an index, ``out[:] = src[by]`` without
    the intermediate. ``mode="clip"`` because numpy's default buffers
    ``out`` whole (a copy of the chunk a column) to leave it untouched on an
    index error: the caller has checked ``by`` (``_check_index``), once a
    chunk and not once a column."""
    if by is None:
        out[:] = src
        return
    for a in range(0, len(by), _TAKE_ROWS):
        b = a + _TAKE_ROWS
        np.take(src, by[a:b], out=out[a:b], mode="clip")


# rows a chunk has before a pool of host threads fills its columns: under
# it, starting the pool costs more than the threads save (tier-1's frames,
# the streaming API's short chunks stay on the driver)
_POOL_ROWS = 1 << 18
# blocks of _TAKE_ROWS rows in one pooled task of a numpy column (2^21
# rows), so that a frame of few, long columns still gives every worker a share
_SPAN_BLOCKS = 32


def _host_cores() -> int:
    """The cores this process may run on."""
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:  # pragma: no cover (no affinity on this OS)
        return os.cpu_count() or 1


def _row_spans(out: np.ndarray, src: np.ndarray, by: np.ndarray | None,
               step: int | None):
    """``_put``'s arguments for consecutive spans of ``step`` rows of
    ``out`` (one span of all of them without a step)."""
    if step is None:
        yield out, src, by
        return
    for a in range(0, len(out), step):
        b = a + step
        yield (out[a:b], src[a:b], None) if by is None else (out[a:b], src, by[a:b])


def _take_into(chunks: list, values, idx: np.ndarray) -> None:
    """An extension column's take, appended to the column's chunks (one
    take a column a chunk: the chunk order is the writes' order)."""
    chunks.append(values.take(idx))


class _SeenPatterns(NamedTuple):
    """The gamma patterns a job saw, from its histogram pass's counts, and
    the one way from a pair's pattern id to them. Every per-pattern table
    the host builds (EM's input, the telemetry histogram, the score and
    frame tables) has one row an entry of ``ids``, so it is sized by the
    patterns seen and not by the space, which can be millions (5^9 x 3 =
    5,859,375 for Febrl's ten fields); ``remap`` is the one structure of the
    space's size."""

    ids: np.ndarray  # (k,) int64 pattern ids, ascending
    rows: np.ndarray  # (k, C) int8: the gamma vector of each
    counts: np.ndarray | None  # (k,) int64 pairs of each; None: no histogram ran
    # (n_patterns,) int32: pattern id -> its row here, -1 where never seen;
    # None where every pattern of the space is a row (the identity)
    remap: np.ndarray | None

    def row_of(self, pid: np.ndarray) -> np.ndarray:
        """The rows (int32) of the pairs' pattern ids ``pid``, ``_TAKE_ROWS``
        ids at a time (a take converts its index to intp first)."""
        if self.remap is None:
            return pid.astype(np.int32, copy=False)
        out = np.empty(len(pid), np.int32)
        for a in range(0, len(pid), _TAKE_ROWS):
            b = a + _TAKE_ROWS
            np.take(self.remap, pid[a:b], out=out[a:b])
        return out


class Splink:
    @check_types
    def __init__(
        self,
        settings: dict,
        df=None,
        df_l=None,
        df_r=None,
        save_state_fn: Callable = None,
        spark=None,  # accepted and ignored: reference-API compatibility
    ):
        """TPU-native probabilistic data linker.

        Args:
            settings: splink settings dictionary (same schema as the
                reference plus TPU keys; see files/settings_jsonschema.json).
            df: the single input DataFrame when link_type == dedupe_only.
            df_l, df_r: the two inputs for link_only / link_and_dedupe.
            save_state_fn: callable(params, settings) run after every EM
                iteration — the restart hook for very large jobs
                (/root/reference/splink/iterate.py:54-55).
            spark: ignored (the reference's SparkSession slot).
        """
        # Per-run observability scope, opened first so the constructor is
        # itself a span: the run's span table is keyed by this run's id (a
        # later linker neither clears nor pollutes an earlier one's), and
        # the telemetry context is live iff settings["telemetry_dir"] is
        # set — disabled, it adds no host callbacks and compiled programs
        # are unchanged.
        from .obs.runtime import RunContext
        from .utils.profiling import begin_run

        self._obs = RunContext.from_settings(settings)
        begin_run(self._obs.run_id)
        with self._call("init") as call:
            self._init(settings, df, df_l, df_r, save_state_fn)
            call.count(rows=sum(
                len(x) for x in (df, df_l, df_r) if x is not None
            ))

    def _init(self, settings, df, df_l, df_r, save_state_fn):
        self.settings = complete_settings_dict(settings)
        backend = self.settings["backend"]
        if backend != "jax":  # schema enum also rejects; double-checked here
            raise ValueError(
                f"Unsupported backend {backend!r}: this build executes the "
                "compute path with jax/XLA only."
            )
        logger.debug("execution backend: %s", backend)
        self._float_dtype_cache = None
        self.params = Params(self.settings, complete=False)
        self.df = df
        self.df_l = df_l
        self.df_r = df_r
        self._n_left_released: int | None = None
        self.save_state_fn = save_state_fn
        self._check_args()
        enable_compilation_cache(self.settings["compilation_cache_dir"])

        self._table: EncodedTable | None = None
        self._pairs: PairIndex | None = None
        self._G: np.ndarray | None = None
        self._G_dev = None  # device-resident copy (resident regime only)
        self._P: np.ndarray | None = None  # per-pair pattern ids (streamed)
        self._pattern_counts: np.ndarray | None = None
        # (the counts it was built from, _SeenPatterns): _seen_patterns
        self._seen = None
        self._pattern_program = None
        self._virtual = None  # pairgen.VirtualPlan (device pair generation)
        self._virtual_checked = False
        # per-candidate pattern ids from the virtual pass (sentinel kept),
        # where the regime keeps them (``ids_kept``): a pairgen.VirtualIds,
        # the ids with the row pairs the kernel decoded
        self._P_virtual = None
        self._pair_bound: int | None = None  # estimate_pair_upper_bound memo
        self._approx: bool | None = None  # approx_blocking's tier has columns
        # last EMResult replayed into Params (EM diagnostics attach its
        # trimmed trajectory: per-iteration ll lives only device-side)
        self._last_em_result = None
        # memoised TF u-probability fold context (term_frequencies
        # docstring): (spec, token ids, log tables) or False = inactive
        self._tf_fold_cache = None
        # checkpoint/resume state for the current estimate_parameters call
        # (argument overrides; the settings keys are the fallback)
        self._ckpt_dir_arg: str | None = None
        self._ckpt_resume = False

    # ------------------------------------------------------------------

    @property
    def run_id(self) -> str:
        """This linker's telemetry/profiling run id (the key for
        ``utils.profiling.stage_timings(run=...)`` and the suffix of the
        run's telemetry JSONL file name)."""
        return self._obs.run_id

    def _stage(self, name: str) -> StageTimer:
        """A StageTimer bound to this linker's run scope: records the stage
        span under this run id and (when telemetry is enabled) emits it
        with its compile-vs-execute split and a device-memory snapshot."""
        return StageTimer(name, run=self._obs.run_id, telemetry=self._obs)

    def _call(self, name: str) -> StageTimer:
        """The root span of one public call (``kind="call"``, so
        ``stage_timings()`` keeps the stage names): its self time is what
        the facade spends outside every stage and sub-span."""
        return StageTimer(
            name, run=self._obs.run_id, telemetry=self._obs, kind="call"
        )

    def _check_args(self):
        link_type = self.settings["link_type"]
        is_df = lambda x: pd is not None and isinstance(x, pd.DataFrame)  # noqa: E731
        if link_type == "dedupe_only":
            if not (is_df(self.df) and self.df_l is None and self.df_r is None):
                raise ValueError(
                    "For link_type = 'dedupe_only', pass a single DataFrame via "
                    "df=; omit df_l and df_r. e.g. Splink(settings, df=my_df)"
                )
        else:
            if not (is_df(self.df_l) and is_df(self.df_r) and self.df is None):
                raise ValueError(
                    f"For link_type = '{link_type}', pass two DataFrames via "
                    "df_l= and df_r=; omit df. "
                    "e.g. Splink(settings, df_l=first, df_r=second)"
                )

    # ------------------------------------------------------------------
    # Pipeline stages
    # ------------------------------------------------------------------

    @property
    def _float_dtype(self):
        """Resolved compute dtype for EM/scoring, honouring ``float64``.

        Resolved lazily (first compute) because checking the backend
        initialises it. float64 on a non-TPU backend enables jax x64 mode —
        a PROCESS-WIDE, irreversible switch (jax has no per-computation
        dtype mode); without it jax silently downcasts every float64 array
        to float32 and the setting would be a no-op. TPU has no float64, so
        there the setting warns and falls back to float32 as documented in
        the settings schema.
        """
        if self._float_dtype_cache is None:
            resolved = np.float32
            if self.settings["float64"]:
                # resolve fully before caching: an exception here (flaky
                # backend init, interrupt) must not poison the cache with
                # the float32 fallback
                if jax.default_backend() == "tpu":
                    warnings.warn(
                        "float64 requested but the TPU backend has no "
                        "float64 support; running in float32"
                    )
                else:
                    if not jax.config.jax_enable_x64:
                        jax.config.update("jax_enable_x64", True)
                        logger.info(
                            "float64 requested: enabled jax x64 mode "
                            "(process-wide)"
                        )
                    resolved = np.float64
            self._float_dtype_cache = resolved
        return self._float_dtype_cache

    @property
    def _n_left(self) -> int | None:
        if self.settings["link_type"] == "dedupe_only":
            return None
        if self.df_l is not None:
            return len(self.df_l)
        return self._n_left_released

    def release_input(self) -> None:
        """Encode the input dataframe(s), then drop the linker's references to
        them so the raw pandas data can be garbage-collected by the caller.

        Everything downstream (blocking, scoring, retained output columns)
        reads from the columnar :class:`EncodedTable` built here, so the
        original frames are not needed again. Useful before streaming very
        large jobs to halve peak host memory.
        """
        self._ensure_encoded()
        if self.df_l is not None:
            self._n_left_released = len(self.df_l)
        self.df = None
        self.df_l = None
        self.df_r = None

    def _checkpoint_config(self):
        """(checkpoint_dir | None, resume, interval): the argument to
        estimate_parameters wins, else the settings keys."""
        ckpt_dir = self._ckpt_dir_arg or self.settings.get("checkpoint_dir") or None
        return (
            ckpt_dir,
            self._ckpt_resume,
            int(self.settings.get("checkpoint_interval", 5) or 5),
        )

    def _load_validated_checkpoint(self, ckpt_dir, state_hash, resume):
        """Resume's load/validate dance, shared by the fused and streamed
        paths: hash-checked load, cross-process presence agreement, then
        topology validation. Returns the checkpoint or None. Resume with
        no checkpoint on disk yet is the normal FIRST launch of a
        relaunch-loop harness, so it warns and trains fresh rather than
        raising."""
        if not resume:
            return None
        from .parallel.distributed import (
            validate_resume_presence,
            validate_resume_topology,
        )
        from .resilience.checkpoint import load_checkpoint

        ckpt = load_checkpoint(ckpt_dir, expect_hash=state_hash)
        validate_resume_presence(ckpt is not None)
        if ckpt is None:
            logger.warning(
                "resume=True but no checkpoint exists in %s yet; training "
                "from scratch (first launch of a relaunch loop?)",
                ckpt_dir,
            )
            return None
        validate_resume_topology(ckpt.process_count, state_hash, ckpt.iteration)
        return ckpt

    def _em_state_hash(self) -> str:
        from .resilience.checkpoint import settings_state_hash

        # bind the checkpoint to the input data as well as the settings:
        # identical settings over a different dataframe must NOT resume
        # (the histories would describe someone else's trajectory). The
        # encoded row count is a cheap fingerprint that catches the
        # common cases (new extract, different table) without hashing
        # multi-GB column data.
        table = self._ensure_encoded()
        return settings_state_hash(
            self.settings, extra={"n_rows": int(table.n_rows)}
        )

    def _ensure_encoded(self) -> EncodedTable:
        if self._table is None:
            with self._stage("encode") as st:
                if self.settings["link_type"] == "dedupe_only":
                    self._table = encode_table(self.df, self.settings)
                else:
                    self._table = concat_tables(self.df_l, self.df_r, self.settings)
                st.count(
                    rows=int(self._table.n_rows),
                    wide_columns=sum(  # encoded as uint32 code points
                        c.bytes_.dtype != np.uint8
                        for c in self._table.strings.values()
                    ),
                )
        return self._table

    def _ensure_pairs(self) -> PairIndex:
        if self._pairs is None:
            table = self._ensure_encoded()
            spill = self._regime("pairs") == "spill"
            if spill:
                # the durable write path (docs/blocking.md#offline-scale):
                # sharded, manifest-committed, RESUMABLE emission into the
                # caller-owned spill store
                from .blocking_device import spill_block_rules
                from .parallel.distributed import spill_shard_dir

                with self._stage("blocking") as st:
                    self._pairs = spill_block_rules(
                        self.settings, table, self._n_left,
                        spill_shard_dir(self.settings["build_spill_dir"]),
                    )
                    if self._pairs is not None:
                        st.count(pairs=int(self._pairs.n_pairs))
            if self._pairs is None:
                if self.settings.get("build_spill_dir"):
                    from .utils.logging_utils import warn_degraded

                    warn_degraded(
                        "spill_blocking", "host_blocking",
                        "rule shapes unsupported by the device emission plan"
                        if spill else "approx_blocking needs materialised "
                        "blocking (the spill emission driver has no "
                        "approximate tier)",
                    )
                self._block_on_host(table)
            logger.info(
                "blocking produced %d candidate pairs%s",
                self._pairs.n_pairs, " (spill store)" if spill else "",
            )
            from .blocking import clear_key_code_cache

            clear_key_code_cache(table)
        return self._pairs

    def _block_on_host(self, table: EncodedTable) -> None:
        """Host blocking, feeding the overlap's device consumer as it joins."""
        stream = self._overlap_stream(table)
        with self._stage("blocking") as st:
            self._pairs = block_using_rules(
                self.settings, table, self._n_left,
                pair_consumer=stream.feed if stream is not None else None,
            )
            st.count(pairs=int(self._pairs.n_pairs))
        if self._obs.enabled:
            # block-size skew telemetry rides the still-warm key-code cache
            from .blocking import block_size_stats

            self._obs.record(
                "largest_blocks",
                block_size_stats(self.settings, table, self._n_left),
            )
        if self._pairs.spill_tmp is not None:
            # blocking's pair sink wrote every chunk to disk-backed memmaps;
            # the PairIndex owns the directory (weakref finalizer)
            logger.info("pair index spilled to %s", self._pairs.spill_tmp)
        if stream is None:
            return
        # the stream's batches were dispatched while blocking ran; this
        # stage is the tail flush, and carries the whole pass's counts
        patterns = self._regime("stream") == "patterns"
        with self._stage("gammas_patterns" if patterns else "gammas") as st:
            if patterns:
                self._P, self._pattern_counts = stream.finish()
            else:
                self._G, self._G_dev = stream.finish()
            st.count(pairs=stream.total,
                     batches=-(-stream.total // stream.batch_size))
            if patterns:
                st.count(pattern_id_bytes=self._P.nbytes)
                self._count_mesh(st, stream.total)
            st.count(**stream.program.kernel_counts(stream.total))

    def _overlap_stream(self, table: EncodedTable):
        """The device consumer host blocking feeds as it joins, or None (the
        regime's ``overlap`` and ``stream``): jax dispatch is async, so the
        accelerator computes rule k's gammas or pattern ids while the host
        joins rule k+1, as Spark's lazy evaluation overlaps them in the
        reference (``splink/blocking.py:210``)."""
        if not self._regime("overlap"):
            return None
        from .gammas import GammaStream, PatternStream

        program = GammaProgram(
            self.settings, table, float_dtype=self._float_dtype
        )
        stream = self._regime("stream")  # learns the pair bound
        # clamp the device batch to the job bound (like the sequential
        # paths clamp to n) so a small job doesn't pad its single batch up
        # to pair_batch_size
        batch = int(self.settings["pair_batch_size"])
        batch = max(min(batch, -(-max(self._pair_bound, 1) // 8) * 8), 1024)
        if stream == "patterns":
            self._pattern_program = program
            return PatternStream(program, batch, mesh=self._pattern_mesh())
        return GammaStream(program, batch, keep_device_limit=self._regime("keep_limit"))

    def _ensure_gammas(self) -> np.ndarray:
        if self._G is None:
            table = self._ensure_encoded()
            pairs = self._ensure_pairs()  # overlap may set _G or _P here
            if self._facts().multihost_store:
                # this process's store holds ONLY its shard subset: scores
                # or EM over it would silently differ per controller
                raise RuntimeError(
                    "this pair index is a per-process spill shard subset "
                    "(multi-controller emission): scoring APIs need the "
                    "full pair set and are single-controller — train with "
                    "estimate_parameters here, then score in a "
                    "single-process run over the saved model"
                )
            if self._G is not None:
                return self._G
            if self._regime("gammas") == "decode":
                with self._stage("gammas") as st:
                    seen = self._seen_patterns()
                    self._G = seen.rows[seen.row_of(self._P)]
                    st.count(pairs=len(self._G))
                return self._G
            keep = self._regime("keep_device")
            with self._stage("gammas") as st:
                program = GammaProgram(
                    self.settings, table, float_dtype=self._float_dtype
                )
                self._G, self._G_dev = program.compute_with_device(
                    pairs.idx_l,
                    pairs.idx_r,
                    batch_size=self.settings["pair_batch_size"],
                    keep_device=keep,
                )
                st.count(pairs=len(self._G),
                         **program.kernel_counts(len(self._G)))
        return self._G

    def _regime(self, outcome: str, **facts):
        """One outcome of the job's regime (``regime.decide``), learning now the
        facts it waits on; ``facts``: what the caller knows and the linker
        keeps nowhere (a score stream follows; a gamma matrix's rows)."""
        while isinstance(value := decide(self._facts(**facts))[outcome], Wait):
            facts[value.fact] = self._learn(value.fact)
        return value

    def _facts(self, **known) -> Facts:
        """What this linker has learnt of its job, learning nothing new."""
        from .gammas import pattern_ids_fit_uint16, pattern_space_fits, pattern_strides_for

        cols = self.settings["comparison_columns"]
        n_patterns = pattern_strides_for([int(c["num_levels"]) for c in cols])[1]
        custom = any((c.get("comparison") or {}).get("kind") == "custom" for c in cols)
        mesh, plan, pairs = bool(self.settings.get("mesh")), self._virtual, self._pairs
        store = getattr(pairs, "spill_store", None)
        return Facts(**{
            "settings": self.settings,
            "pattern_capable": not custom and pattern_space_fits(n_patterns),
            "mesh": mesh,
            "process_count": jax.process_count() if mesh else 1,
            "approx_columns": self._approx,
            "pair_bound": self._pair_bound,
            "plan": (plan is not None) if self._virtual_checked else None,
            "n_candidates": None if plan is None else plan.n_candidates,
            "ids_fit_uint16": pattern_ids_fit_uint16(n_patterns),
            "n_pairs": None if pairs is None else pairs.n_pairs,
            "spill_store": store is not None,
            # written under multi-controller emission: this process's share
            "multihost_store": store is not None
            and int(store.meta.get("process_count", 1) or 1) > 1,
            **known,
        })

    def _learn(self, fact: str):
        """The fact the regime waits on, learnt now (``_regime``)."""
        if fact == "plan":
            return self._virtual_plan() is not None
        if fact == "n_pairs":
            return self._ensure_pairs().n_pairs
        if fact == "free_ram":  # what is free now
            try:
                return os.sysconf("SC_AVPHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
            except (ValueError, OSError, AttributeError):
                return math.inf  # no probe here; the byte cap still bounds
        table = self._ensure_encoded()
        if fact == "pair_bound":
            from .blocking import estimate_pair_upper_bound

            with span("pair_bound", rows=int(table.n_rows)):
                self._pair_bound = estimate_pair_upper_bound(
                    self.settings, table, self._n_left
                )
            return self._pair_bound
        from .approx.lsh import approx_columns

        self._approx = bool(approx_columns(self.settings, table))
        return self._approx

    @property
    def device_pair_generation_active(self) -> bool:
        """Whether this run used (or will use) the virtual pair index —
        pairs decoded on device with no host materialisation. Public
        accessor for diagnostics/examples; the plan itself is internal."""
        return self._virtual_plan() is not None

    def virtual_kernel_hlo(self) -> list[tuple[int, str]]:
        """Diagnostics: (batch size, optimised HLO) of every virtual-pair-
        index pattern kernel this linker has run so far
        (pairgen.compiled_kernel_texts); empty when the virtual pair index
        is not in use."""
        from .pairgen import compiled_kernel_texts

        plan = self._virtual_plan()
        return [] if plan is None else compiled_kernel_texts(plan)

    def _virtual_plan(self):
        """The device-pair-generation plan, or None (pairgen module
        docstring has the full story). Checked once: the plan build does
        the per-rule key/sort work host blocking would do anyway, so a
        rejected plan costs nothing extra overall."""
        if self._virtual_checked:
            return self._virtual
        self._virtual_checked = True
        if not self._regime("virtual"):
            return None
        from .pairgen import build_virtual_plan

        table = self._ensure_encoded()
        with self._stage("pairgen_plan"):
            self._virtual = build_virtual_plan(
                self.settings, table, self._n_left
            )
        if self._virtual is not None:
            # the int64 key-code cache fed the estimator and the plan;
            # the plan keeps its own int32 copies — don't retain both
            from .blocking import clear_key_code_cache

            clear_key_code_cache(table)
            logger.info(
                "device pair generation: %d candidate positions, %d rules",
                self._virtual.n_candidates,
                len(self._virtual.rules),
            )
        return self._virtual

    def _pattern_mesh(self):
        """The mesh pattern passes shard over (``shard_patterns``), or None."""
        return mesh_from_settings(self.settings) if self._regime("shard_patterns") else None

    def _count_mesh(self, st, positions: int) -> None:
        """On a pattern pass's stage: over how many chips it was sharded
        (``devices``) and each chip's share of its pair positions
        (``pairs_per_device``). Without a mesh the stage carries neither."""
        mesh = self._pattern_mesh()
        if mesh is not None:
            n = mesh.devices.size
            st.count(devices=n, pairs_per_device=-(-positions // n))

    def _ensure_pattern_program(self) -> "GammaProgram":
        """The pattern-capable GammaProgram, built lazily. Scoring-only
        consumers (manual FS weights, the virtual score stream) need just
        the program — NOT the histogram pass _ensure_pattern_ids runs —
        so they must come through here to avoid a redundant device pass
        over every candidate pair."""
        if self._pattern_program is None:
            self._pattern_program = GammaProgram(
                self.settings,
                self._ensure_encoded(),
                float_dtype=self._float_dtype,
            )
        return self._pattern_program

    def _ensure_pattern_ids(self, score_follows: bool = False):
        """(pattern_ids, counts, program): ONE device pass over the pair
        index computing gammas, pattern ids and their histogram. The gamma
        matrix itself never materialises — per-pair state is a uint16/int32
        id, and every later stage (EM, scoring, output columns) derives from
        the ≤ prod(levels+1)-row pattern tables. ``score_follows``: a score
        stream follows the pass (the regime's ``ids_kept`` reads it)."""
        if self._P is None:
            table = self._ensure_encoded()
            if self._virtual_plan() is not None:
                # pairs decode on the device from the plan: nothing per pair
                # is materialised unless the regime keeps the ids
                if self._pattern_counts is not None:
                    return None, self._pattern_counts, self._pattern_program
                from .pairgen import compute_virtual_pattern_ids

                with self._stage("gammas_patterns") as st:
                    self._ensure_pattern_program()
                    want_ids = self._regime("ids_kept", score_follows=score_follows)
                    ids, self._pattern_counts, n_real = (
                        compute_virtual_pattern_ids(
                            self._pattern_program,
                            self._virtual,
                            int(self.settings["pair_batch_size"]),
                            mesh=self._pattern_mesh(),
                            return_ids=want_ids,
                        )
                    )
                    if want_ids:
                        self._P_virtual = ids
                    st.count(pairs=n_real, ids_kept=int(want_ids),
                             pattern_id_bytes=ids.pid.nbytes if want_ids else 0)
                    self._count_mesh(st, self._virtual.n_candidates)
                    st.count(**self._pattern_program.kernel_counts(
                        self._virtual.n_candidates))
                logger.info(
                    "device pair generation scored %d pairs (%d candidate "
                    "positions)", n_real, self._virtual.n_candidates,
                )
                return None, self._pattern_counts, self._pattern_program
            pairs = self._ensure_pairs()
            if self._P is not None:
                # the overlap PatternStream already computed them
                return self._P, self._pattern_counts, self._pattern_program
            with self._stage("gammas_patterns") as st:
                self._pattern_program = GammaProgram(
                    self.settings, table, float_dtype=self._float_dtype
                )
                self._P, self._pattern_counts = (
                    self._pattern_program.compute_pattern_ids(
                        pairs.idx_l,
                        pairs.idx_r,
                        batch_size=self.settings["pair_batch_size"],
                        mesh=self._pattern_mesh(),
                    )
                )
                st.count(pairs=len(self._P), ids_kept=1,
                         pattern_id_bytes=self._P.nbytes)
                self._count_mesh(st, len(self._P))
                st.count(**self._pattern_program.kernel_counts(len(self._P)))
        return self._P, self._pattern_counts, self._pattern_program

    def _tf_fold_ctx(self):
        """The offline TF u-probability fold context, memoised:
        ``(spec, tids, log_tables)`` — term_frequencies.tf_fold_spec
        entries restricted to the encoded string columns, each column's
        (n_rows,) token ids and its float64 log relative-frequency table
        (term_frequencies.tf_log_table, the SAME values the serve index
        gathers from). None when ``serve_tf_adjust`` is off or no flagged
        comparison has a token column — scored frames then carry no
        ``tf_match_probability`` column, exactly as before."""
        if self._tf_fold_cache is None:
            self._tf_fold_cache = False
            if self.settings.get("serve_tf_adjust", True):
                from .term_frequencies import tf_fold_spec, tf_log_table

                table = self._ensure_encoded()
                spec, tids, logs = [], [], []
                for ci, name, top in tf_fold_spec(self.settings):
                    sc = table.strings.get(name)
                    if sc is None or not sc.n_tokens:
                        continue
                    tid = sc.token_ids
                    counts = np.bincount(
                        tid[tid >= 0], minlength=sc.n_tokens
                    )
                    spec.append((ci, name, top))
                    tids.append(tid.astype(np.int32))
                    logs.append(tf_log_table(counts))
                if spec:
                    self._tf_fold_cache = (tuple(spec), tids, logs)
        return self._tf_fold_cache or None

    def _tf_fold_pairs(self, z, il, ir, ctx, out=None) -> np.ndarray:
        """TF-adjusted match probabilities for pairs (il, ir) from their
        match logits ``z`` — the offline half of the serve parity
        contract, evaluated by the SAME jitted fold expression the serve
        megakernel runs (term_frequencies.make_tf_fold_fn). Chunked like
        every other per-pair device pass; written into ``out`` (the scored
        frame's own column) where the caller has one."""
        from .term_frequencies import make_tf_fold_fn

        spec, tids, logs = ctx
        dtype = self._float_dtype
        fold = make_tf_fold_fn(spec)
        lam, m, u, _ = self.params.to_arrays(dtype=dtype)
        u_dev = jnp.asarray(u)
        logs_dev = [jnp.asarray(t.astype(dtype)) for t in logs]
        n = len(z)
        batch = min(int(self.settings["pair_batch_size"]), max(n, 1))
        if out is None:
            out = np.empty(n, dtype)
        for s in range(0, n, batch):
            e = min(s + batch, n)
            host = [z[s:e]]
            host += [tid[il[s:e]] for tid in tids]
            host += [tid[ir[s:e]] for tid in tids]
            with span("h2d_put", bytes=sum(a.nbytes for a in host)):
                args = [jnp.asarray(a) for a in host]
            folded = fold(*args[:1], u_dev, *args[1:], *logs_dev)
            dispatched("tf_fold", folded, rows=e - s)
            out[s:e] = fetch(folded)
        return out

    def _seen_patterns(self) -> _SeenPatterns:
        """The patterns this job's histogram pass saw (``_SeenPatterns``),
        decoded once from its counts: ``np.flatnonzero`` of the counts, their
        gamma rows, the id -> row remap. Where no histogram ran (manual
        weights over the virtual index score without one) every pattern of
        the space is a row. Opens a ``pattern_tables`` span: ``space`` (the
        patterns there are), ``seen``, ``rows_built`` (rows of the tables it
        builds: the gamma rows, and the counts where there are any; the
        remap is not counted)."""
        if self._virtual_plan() is None:
            self._ensure_pattern_ids()  # the materialised pass always counts
        counts = self._pattern_counts
        if self._seen is None or self._seen[0] is not counts:
            program = self._ensure_pattern_program()
            n = program.n_patterns
            with span("pattern_tables", space=n) as sp:
                if counts is None:
                    ids, remap, seen_counts = np.arange(n), None, None
                else:
                    ids = np.flatnonzero(counts)
                    remap = np.full(n, -1, np.int32)
                    remap[ids] = np.arange(len(ids), dtype=np.int32)
                    seen_counts = counts[ids]
                rows = program.pattern_rows(ids)
                sp.count(seen=len(ids), rows_built=len(ids) * (
                    1 if seen_counts is None else 2))
            self._seen = (counts, _SeenPatterns(ids, rows, seen_counts, remap))
        return self._seen[1]

    def _pattern_score_luts(self):
        """Per-pattern lookup tables (host), one row a pattern SEEN
        (``_seen_patterns``; a pair's row is ``row_of`` its pattern id):
        the gamma rows, the match probability and, when intermediates are
        retained, per-column prob_m/prob_u — plus the match-logit LUT when
        the TF fold is active (the per-pair fold adds its delta to the
        pattern's logit; a pattern LUT of folded probabilities is impossible
        because the delta is a property of the PAIR's tokens, not its gamma
        pattern). Reuses the batched scoring path, which bounds HBM at any
        pattern count, in batches of a power of two so that the number of
        patterns a job sees compiles no program of its own."""
        rows = self._seen_patterns().rows
        dtype = self._float_dtype
        lam, m, u, _ = self.params.to_arrays(dtype=dtype)
        params_dev = FSParams(
            lam=jnp.asarray(lam), m=jnp.asarray(m), u=jnp.asarray(u)
        )
        bucket = 1 << max(len(rows) - 1, 1023).bit_length()
        p, pm, pu, z = self._score_batched(
            rows, params_dev, want_z=self._tf_fold_ctx() is not None,
            batch=min(int(self.settings["pair_batch_size"]), bucket),
        )
        return rows, p, pm, pu, z

    def _pattern_frame_tables(self):
        """The per-pattern tables in ``_FrameWriter.write``'s argument
        order, one contiguous row a frame column and one entry a pattern
        seen: the levels as the int64 the frame carries, so a chunk's
        ``gamma_*`` column is one take by the pairs' rows
        (``_SeenPatterns.row_of``) and no ``(pairs, columns)`` matrix is
        made on the way. Under a ``pattern_tables`` span whose
        ``rows_built`` counts the rows of every table built (the scores and
        logits, prob_m and prob_u, the levels)."""
        with span("pattern_tables") as sp:
            PM, p, pm, pu, z = self._pattern_score_luts()
            rows = np.ascontiguousarray
            tables = (
                rows(PM.T, dtype=np.int64),
                p,
                None if pm is None else rows(pm.T),
                None if pu is None else rows(pu.T),
                z,
            )
            sp.count(rows_built=len(p) * sum(t is not None for t in tables))
        return tables

    def _pattern_stream_pairs(self) -> int:
        """How many pairs ``_iter_pattern_triples`` will yield, known before
        it starts: the histogram's total, which leaves the masked positions
        out (pairgen.compute_virtual_pattern_ids). Where no histogram pass
        ran (manual weights over a virtual plan) the plan's candidate
        positions bound it from above."""
        if self._virtual_plan() is None:
            return len(self._ensure_pattern_ids()[0])
        if self._pattern_counts is not None:
            return int(self._pattern_counts.sum())
        return self._virtual.n_candidates

    def _stream_pattern_chunks(self):
        """Yield scored chunks from the pattern-id pipeline: one frame a
        (il, ir, rows) chunk, written at the chunk's length. The
        chunk source (stored virtual ids / virtual recompute / materialised
        pairs) is _iter_pattern_triples — the single definition of the pair
        stream. The stage steps aside while a chunk is with the consumer."""
        tables = self._pattern_frame_tables()
        with self._stage("score_patterns") as st:
            for il, ir, Pk in self._iter_pattern_triples():
                st.count(pairs=len(Pk), batches=1)
                chunk = self._assemble_df_e(il, ir, *tables, by=Pk)
                with st.suspended():
                    yield chunk

    def _score_patterns_frame(self) -> "pd.DataFrame":
        """The whole pattern stream as ONE frame: its columns are allocated
        once at the job's length and every chunk writes its rows at its
        offset, so no byte of the frame is written twice. Zero pairs (no
        candidates, or every position masked) is the typed zero-row frame."""
        tables = self._pattern_frame_tables()
        with self._stage("score_patterns") as st:
            writer = _FrameWriter(self, self._pattern_stream_pairs())
            for il, ir, Pk in self._iter_pattern_triples():
                st.count(pairs=len(Pk), batches=1)
                writer.write(il, ir, *tables, by=Pk)
            return writer.frame()

    def _iter_pattern_triples(self):
        """Yield (idx_l, idx_r, rows) per chunk across the pattern regimes
        — virtual with stored ids (host-only), virtual recompute (device
        pass), materialised pairs — with masked sentinels already filtered;
        ``rows``: each pair's row of the per-pattern tables, its pattern id
        through ``_SeenPatterns.row_of``. The SINGLE definition of the
        pattern pair stream: the score stream assembles frames from it and
        the streaming TF adjustment drives it twice. (The virtual branch
        deliberately avoids _ensure_pattern_ids: scoring needs no histogram
        pass, e.g. under manual FS weights.)

        A virtual position is decoded to its row pair ONCE, by the kernel
        that computed its pattern id (pairgen.unit_decode); both virtual
        branches take the kernel's pairs — kept beside the ids, or home
        with them from the recompute pass — and nothing here decodes a
        position again. What is left under the ``decode_pairs`` span is
        dropping the masked positions.

        The stage that drives the stream gets its regime as counts:
        ``ids_kept`` 1 where it reads ids the pattern pass kept (virtual or
        materialised pairs), 0 where it computes every position's id again,
        which ``recomputed_positions`` then counts."""
        batch = int(self.settings["pair_batch_size"])
        virtual = self._virtual_plan() is not None
        kept = not virtual or self._P_virtual is not None
        count(ids_kept=int(kept), recomputed_positions=0)
        seen = self._seen_patterns()
        if virtual:
            from .pairgen import _virtual_pass_iter

            plan = self._virtual
            program = self._ensure_pattern_program()
            sentinel = program.n_patterns

            def unmasked(Pc, il, ir):
                """One chunk of the stream without its masked positions;
                ``device_decoded`` counts the positions whose row pair the
                kernel decoded, which is all of them."""
                with span(
                    "decode_pairs", rows=len(Pc), device_decoded=len(Pc)
                ) as sp:
                    keep = Pc != sentinel
                    n_kept = int(np.count_nonzero(keep))
                    sp.count(kept=n_kept)
                    if not n_kept:
                        return None
                    return il[keep], ir[keep], seen.row_of(Pc[keep])

            kept = self._P_virtual  # local: immune to concurrent release
            if kept is not None:
                out_base = 0
                for rp in plan.rules:
                    for p0 in range(out_base, out_base + rp.total, batch):
                        rows = slice(p0, min(p0 + batch, out_base + rp.total))
                        t = unmasked(*(a[rows] for a in kept))
                        if t is not None:
                            yield t
                    out_base += rp.total
                return
            for _, _, _, n_valid, *chunk in _virtual_pass_iter(
                program, plan, batch, mesh=self._pattern_mesh()
            ):
                count(recomputed_positions=n_valid)
                t = unmasked(*chunk)
                if t is not None:
                    yield t
            return
        P, _, _ = self._ensure_pattern_ids()
        pairs = self._ensure_pairs()
        for s in range(0, len(P), batch):
            rows = slice(s, min(s + batch, len(P)))
            yield pairs.idx_l[rows], pairs.idx_r[rows], seen.row_of(P[rows])

    def stream_tf_adjusted_comparisons(self, compute_ll: bool = False):
        """Streaming term-frequency adjustment: the scale-free counterpart
        of ``get_scored_comparisons() -> make_term_frequency_adjustments``
        for outputs too large to materialise as one DataFrame.

        Runs EM, then TWO passes over the scored pattern stream: pass 1
        aggregates each flagged column's per-token mean match probability
        (the reference's grouped aggregate + broadcast join,
        /root/reference/splink/term_frequencies.py:49-95 — Spark gave it
        scale-out for free; here it is a chunked host aggregation over
        factorised token ids), pass 2 yields scored chunks with the
        per-column ``<col>_adj`` columns and ``tf_adjusted_match_prob``.
        Under device pair generation both passes are host-only LUT work
        when the EM pass kept its per-candidate ids
        (virtual_materialise_ids)."""
        from .term_frequencies import bayes_combine, term_frequency_columns

        tf_cols = list(term_frequency_columns(self.settings))
        if not self._regime("patterns"):
            # resident regime: the one-frame path already exists
            df_e = self.get_scored_comparisons(compute_ll)
            yield self.make_term_frequency_adjustments(df_e)
            return
        if not tf_cols:
            warnings.warn(
                "No term frequency adjustment columns are specified in "
                "your settings object. Streaming unadjusted comparisons."
            )
            yield from self.stream_scored_comparisons(compute_ll)
            return
        # the try spans EVERYTHING from EM (which materialises the
        # potentially multi-GB per-candidate ids) onward: an exception in
        # the aggregation pass or a consumer abandoning/closing the
        # generator anywhere must not leak the ids
        try:
            self._run_em_patterns(compute_ll, score_follows=True)
            table = self._ensure_encoded()
            cols: dict[str, tuple[np.ndarray, int]] = {}
            for name in tf_cols:
                sc = table.strings.get(name)
                if sc is not None:
                    cols[name] = (sc.token_ids, sc.n_tokens)
                    continue
                nc = table.numerics.get(name)
                if nc is not None:
                    # numeric TF column: factorise values on the fly (token =
                    # distinct value, the same grouping the one-frame host
                    # path applies to raw values); null -> -1
                    codes, uniq = pd.factorize(nc.values_f64)
                    codes = codes.astype(np.int32)
                    codes[nc.null_mask] = -1
                    cols[name] = (codes, len(uniq))
                    continue
                warnings.warn(
                    f"term-frequency column {name!r} is not an encoded "
                    "column; skipped in the streaming TF pass."
                )
            tables = self._pattern_frame_tables()
            p_lut = tables[1]
            base_lambda = float(self.params.params["λ"])
            sums = {n: np.zeros(nt + 1) for n, (_, nt) in cols.items()}
            counts = {n: np.zeros(nt + 1) for n, (_, nt) in cols.items()}
            with self._stage("tf_aggregate_patterns"):
                for il, ir, Pk in self._iter_pattern_triples():
                    p = p_lut[Pk]
                    for name, (tid, _nt) in cols.items():
                        tl = tid[il]
                        agree = (tl == tid[ir]) & (tl >= 0)
                        np.add.at(sums[name], tl[agree], p[agree])
                        np.add.at(counts[name], tl[agree], 1.0)
            adjusted = {}
            for name in cols:
                # token lambda -> Bayes-combined with (1 - base lambda), the
                # same step as compute_token_adjustment
                lam_t = sums[name] / np.maximum(counts[name], 1.0)
                adjusted[name] = bayes_combine(
                    [lam_t, np.full(len(lam_t), 1.0 - base_lambda)]
                )
            with self._stage("score_tf_patterns"):
                for il, ir, Pk in self._iter_pattern_triples():
                    df = self._assemble_df_e(il, ir, *tables, by=Pk)
                    adj_arrays = []
                    for name, (tid, _nt) in cols.items():
                        tl = tid[il]
                        agree = (tl == tid[ir]) & (tl >= 0)
                        adj = np.where(
                            agree, adjusted[name][np.where(agree, tl, 0)], 0.5
                        )
                        df[f"{name}_adj"] = adj
                        adj_arrays.append(adj)
                    df["tf_adjusted_match_prob"] = bayes_combine(
                        [df["match_probability"].to_numpy()] + adj_arrays
                    )
                    lead = ["tf_adjusted_match_prob", "match_probability"]
                    rest = [c for c in df.columns if c not in lead]
                    yield df[lead + rest]
        finally:
            # release on exhaustion AND on an abandoned/closed generator —
            # the ids can be multi-GB
            self._P_virtual = None
            self._obs.finish()

    def _run_em_patterns(self, compute_ll: bool, score_follows: bool = False) -> None:
        """Fused EM on the weighted pattern matrix: one row a pattern seen,
        its pair count as the weight."""
        _, counts, program = self._ensure_pattern_ids(score_follows)
        if int(counts.sum()) == 0:
            warnings.warn(
                "No candidate pairs to estimate from (blocking produced "
                "nothing); parameters are unchanged."
            )
            return
        seen = self._seen_patterns()
        logger.info(
            "pattern-compressed EM: %d pairs -> %d distinct gamma patterns "
            "of %d", int(counts.sum()), len(seen.ids), program.n_patterns,
        )
        self._obs.gauge("gamma_patterns_distinct", len(seen.ids))
        # always cheap here (one row a pattern seen); feeds telemetry AND
        # the EM diagnostics' level-support evidence
        hist = _gamma_histograms(self.settings, seen.rows, weights=seen.counts)
        if self._obs.enabled:
            self._obs.record("gamma_histogram", hist)
        self._run_em_fused(
            jnp.asarray(seen.rows),
            jnp.asarray(seen.counts.astype(self._float_dtype)),
            compute_ll, pairs=int(seen.counts.sum()),
        )
        self._emit_em_diagnostics(hist)

    # ------------------------------------------------------------------
    # Public API (reference parity)
    # ------------------------------------------------------------------

    def manually_apply_fellegi_sunter_weights(self):
        """Score using the m/u values in the settings, without running EM
        (/root/reference/splink/__init__.py:111-119)."""
        with self._call("manually_apply_fellegi_sunter_weights") as call:
            if self._regime("patterns"):
                df_e = self._score_patterns_frame()
            else:
                G = self._ensure_gammas()
                df_e = self._build_df_e(G)
                self._G_dev = None  # release the HBM copy once scoring is done
            call.count(pairs=len(df_e))
        self._obs.finish()
        return df_e

    def estimate_parameters(
        self,
        compute_ll: bool = False,
        *,
        checkpoint_dir: str | os.PathLike | None = None,
        resume: bool = False,
    ) -> Params:
        """Train ONLY: run blocking/gammas/EM and return the fitted
        Params, producing no per-pair output. An extension beyond the
        reference (whose EM runs inside get_scored_comparisons,
        /root/reference/splink/__init__.py:121-145) for jobs where only
        the model is wanted: under device pair generation the whole run
        is the histogram-only pattern pass — zero per-pair bytes cross
        the host<->device link and nothing per-pair lands in host RAM.
        Score later (or in another process via save/load) with
        manually_apply_fellegi_sunter_weights or the streaming APIs.

        Args:
            compute_ll: archive the log likelihood per iteration.
            checkpoint_dir: snapshot EM state here every
                ``checkpoint_interval`` updates (atomic, versioned, bound
                to a settings hash — docs/resilience.md). Overrides the
                ``checkpoint_dir`` settings key.
            resume: continue from the checkpoint in ``checkpoint_dir``
                instead of training from the settings priors. A checkpoint
                written for different settings (hash mismatch) is rejected
                with CheckpointMismatchError; multi-controller runs also
                validate process-count/checkpoint agreement before
                continuing.
        """
        self._ckpt_dir_arg = os.fspath(checkpoint_dir) if checkpoint_dir else None
        self._ckpt_resume = bool(resume)
        if self._ckpt_resume and self._checkpoint_config()[0] is None:
            self._ckpt_resume = False
            raise ValueError(
                "resume=True requires a checkpoint directory: pass "
                "checkpoint_dir= or set the checkpoint_dir settings key."
            )
        try:
            with self._call("estimate_parameters"):
                self._estimate_parameters(compute_ll)
        finally:
            self._ckpt_dir_arg = None
            self._ckpt_resume = False
            self._obs.finish()
        return self.params

    def _estimate_parameters(self, compute_ll: bool) -> None:
        em = self._regime("em")
        if em == "patterns":
            self._run_em_patterns(compute_ll)
        elif em == "spill":
            batches = self._em_batches()
            store = self._pairs.spill_store
            logger.info(
                "spill-fed streamed EM over %d pairs (%d manifest segments)",
                store.total_pairs, len(store.segments),
            )
            self._run_em_streamed_driver(batches, compute_ll, int(store.total_pairs))
            self._emit_em_diagnostics(None)
        else:
            self._run_em(self._ensure_gammas(), compute_ll)
            self._G_dev = None

    def get_scored_comparisons(self, compute_ll: bool = False):
        """Estimate parameters by EM and return scored comparisons
        (/root/reference/splink/__init__.py:121-145).

        Past ``max_resident_pairs`` candidate pairs (or on the virtual pair
        index) EM runs on the histogram of the pairs' gamma patterns and the
        scores are taken from per-pattern tables (splink_tpu/regime.py).
        """
        with self._call("scored_comparisons") as call:
            if self._regime("patterns"):
                self._run_em_patterns(compute_ll, score_follows=True)
                df_e = self._score_patterns_frame()
                # the single-frame output is materialised — release the ids
                # (same convention as _G_dev below); a later re-stream simply
                # recomputes them chunk-wise
                self._P_virtual = None
            else:
                G = self._ensure_gammas()
                self._run_em(G, compute_ll)
                df_e = self._build_df_e(G)
                self._G_dev = None  # release the HBM copy once EM + scoring are done
            call.count(pairs=len(df_e))
        self._obs.finish()
        return df_e

    def _run_em(self, G: np.ndarray, compute_ll: bool) -> None:
        """EM over the gamma matrix G, resident on the device or streamed
        from the host (the regime's ``em_resident``). A device OOM on the
        resident path degrades to the streamed one, same update math,
        instead of crashing the run (docs/resilience.md)."""
        from .resilience import active_plan, is_oom
        from .utils.logging_utils import warn_degraded

        # the gamma histogram doubles as the EM diagnostics' level-support
        # evidence (obs/quality.em_diagnostics) and as the quality
        # profile's raw material. Where the matrix is resident on the
        # device it is counted there, queued ahead of EM and brought home
        # after it; else the host counts it, but for the huge
        # streamed-with-telemetry-off case (diagnostics then omit support
        # counts)
        resident = self._regime("em_resident", n_pairs=len(G))
        hist = counts = None
        if self._G_dev is not None and G is self._G:
            levels = tuple(
                int(c["num_levels"]) for c in self.settings["comparison_columns"]
            )
            with span("gamma_histogram", rows=len(G), device_rows=len(G)):
                counts = level_counts(self._G_dev, levels)
                dispatched("level_counts", counts, rows=len(G))
        elif self._obs.enabled or resident:
            hist = _gamma_histograms(self.settings, G)
        if resident:
            # the resident attempt may replay completed updates into
            # self.params (checkpoint boundaries / save_state_fn) before it
            # OOMs; the fallback must restart from the PRE-attempt state or
            # those updates would be applied twice
            params_snapshot = copy.deepcopy(self.params)
            try:
                active_plan(self.settings).fire("resident_em", pairs=len(G))
                weights = None
                mesh = mesh_from_settings(self.settings)
                if mesh is not None:
                    G_dev, weights = shard_pairs(mesh, G)
                    weights = weights.astype(self._float_dtype)
                elif self._G_dev is not None:
                    G_dev = self._G_dev
                else:
                    with span("h2d_put", bytes=G.nbytes):
                        G_dev = jnp.asarray(G)
                self._run_em_fused(G_dev, weights, compute_ll, pairs=len(G))
            except Exception as e:  # noqa: BLE001 - is_oom() decides
                if not is_oom(e):
                    raise
                self.params = params_snapshot
                warn_degraded(
                    "resident_em", "streamed_em", f"{type(e).__name__}: {e}",
                    pairs=len(G),
                )
                resident = False
        if not resident:
            self._run_em_streamed_driver(self._em_batches(G), compute_ll, len(G))
        if counts is not None:
            hist = _level_dict(self.settings, fetch(counts))
        if self._obs.enabled:
            self._obs.record("gamma_histogram", hist)
        self._emit_em_diagnostics(hist)

    def _run_em_fused(self, G_dev, weights, compute_ll: bool, pairs: int) -> None:
        """Fused on-device EM over ``G_dev`` (the gamma matrix, or the
        pattern matrix with its counts as ``weights``): one compiled
        while_loop; with a checkpoint directory the same loop with a host
        hook writing an atomic checkpoint every ``checkpoint_interval``
        updates; stepped one update at a time when a save_state_fn hook must
        run between them (the reference's ``splink/iterate.py:54-55``)."""
        from .resilience import active_plan

        # a stale result must not attach its trajectory to this run's
        # diagnostics (only _replay_history sets it)
        self._last_em_result = None
        dtype = self._float_dtype
        lam0, m0, u0, _ = self.params.to_arrays(dtype=dtype)
        init = FSParams(lam=jnp.asarray(lam0), m=jnp.asarray(m0), u=jnp.asarray(u0))
        max_iterations = int(self.settings["max_iterations"])
        em_kwargs = dict(
            max_levels=self.params.max_levels,
            em_convergence=self.settings["em_convergence"],
            weights=weights,
            compute_ll=compute_ll,
        )

        ckpt_dir, resume, interval = self._checkpoint_config()
        tel = self._obs if self._obs.enabled else None
        with self._stage("em") as st:
            updates_before = len(self.params.param_history)
            # inside the stage span so em_begin captures it as the parent
            # of every em_iteration span
            if tel is not None:
                tel.em_begin("fused", lam0, m0, u0)
            if ckpt_dir is not None:
                state_hash = self._em_state_hash()
                ckpt = self._load_validated_checkpoint(
                    ckpt_dir, state_hash, resume
                )
                if self.save_state_fn is not None:
                    logger.warning(
                        "checkpoint_dir moves save_state_fn onto the compiled "
                        "loop's host-callback thread (called at checkpoint "
                        "boundaries, mid-program): the hook must stay "
                        "host-side work — dispatching jax computation from "
                        "it can deadlock the running program."
                    )
                replayed = 0

                def on_segment(done, hist, _converged):
                    # history replays into the Params object at each
                    # boundary, then save_state_fn sees it (both on the
                    # callback thread)
                    nonlocal replayed
                    self._replay_em_history(
                        hist["lam"], hist["m"], hist["u"], hist["ll"],
                        replayed, done, compute_ll,
                    )
                    replayed = done
                    if self.save_state_fn is not None:
                        self.save_state_fn(self.params, self.settings)

                result = run_em_checkpointed(
                    G_dev, init, max_iterations=max_iterations,
                    checkpoint_dir=ckpt_dir, state_hash=state_hash,
                    checkpoint_every=interval, resume=resume,
                    resume_checkpoint=ckpt,
                    fault_plan=active_plan(self.settings),
                    on_segment=on_segment, telemetry=tel, **em_kwargs,
                )
                # a resume that was already complete runs zero segments;
                # catch up from the (checkpoint-restored) histories
                n = int(result.n_updates)
                self._replay_em_history(
                    result.lam_history, result.m_history, result.u_history,
                    result.ll_history, replayed, n, compute_ll,
                )
                if compute_ll and not np.isnan(result.ll_history[n]):
                    self.params.params["log_likelihood"] = float(
                        result.ll_history[n]
                    )
                    self.params.log_likelihood_exists = True
                converged = bool(result.converged)
            elif self.save_state_fn is None:
                if tel is not None:
                    # same compiled loop with the host-hook io_callback on:
                    # per-update convergence records stream out through it,
                    # the dataflow (and so the trajectory) is untouched
                    result = run_em_checkpointed(
                        G_dev, init, max_iterations=max_iterations,
                        telemetry=tel, **em_kwargs,
                    )
                else:
                    result = run_em(
                        G_dev, init, max_iterations=max_iterations, **em_kwargs
                    )
                    dispatched("run_em", result.n_updates, rows=G_dev.shape[0])
                self._replay_history(result, compute_ll)
                converged = bool(result.converged)
            else:
                converged = False
                params_dev = init
                for k in range(max_iterations):
                    result = run_em(G_dev, params_dev, max_iterations=1, **em_kwargs)
                    dispatched("run_em", result.n_updates, rows=G_dev.shape[0])
                    params_dev = result.params
                    self._replay_history(result, compute_ll)
                    if tel is not None:
                        tel.em_update(
                            k + 1,
                            float(result.lam_history[1]),
                            np.asarray(result.m_history[1]),
                            np.asarray(result.u_history[1]),
                            float(result.ll_history[0]) if compute_ll else None,
                            bool(result.converged),
                        )
                    self.save_state_fn(self.params, self.settings)
                    if bool(result.converged):
                        converged = True
                        break
            # counted where the stage ends well: an OOM'd resident attempt
            # that falls back to the streamed regime counts its pairs once
            st.count(
                pairs=pairs, patterns=int(G_dev.shape[0]),
                iterations=len(self.params.param_history) - updates_before,
            )
        if converged:
            logger.info("EM algorithm has converged")

    def _em_batches(self, G: np.ndarray | None = None):
        """The batch factory streamed EM calls once a pass:
        ``pair_batch_size`` slices of G or, without G, of the spill store,
        gammas computed on the device a slice at a time
        (``spill.iter_spill_gamma_batches``). Multi-controller hosts each
        stream their ``global_pair_slice`` (which a per-process store
        already is), and each pass's statistics are reduced across them."""
        from .parallel.distributed import global_pair_slice

        batch = int(self.settings["pair_batch_size"])
        sliced = jax.process_count() > 1
        if G is not None:
            G = G[global_pair_slice(len(G))] if sliced else G
            return lambda: (G[s : s + batch] for s in range(0, len(G), batch))
        from .spill import iter_spill_gamma_batches

        store = self._pairs.spill_store
        program = GammaProgram(self.settings, self._ensure_encoded(), float_dtype=self._float_dtype)
        sliced = sliced and not self._facts().multihost_store
        pair_range = global_pair_slice(store.total_pairs) if sliced else None
        return lambda: iter_spill_gamma_batches(
            store, program, batch, pair_range=pair_range
        )

    def _run_em_streamed_driver(self, batches, compute_ll: bool, pairs: int) -> None:
        """Streamed EM, accumulating sufficient statistics a pass (stats
        psum across a mesh's devices): checkpoint/resume plumbing, telemetry
        and the run_em_streamed call over a batch factory — G's slices
        (custom kernels, pattern spaces past their bytes, the resident OOM
        fallback) or the spill manifest's (``_em_batches``)."""
        from .parallel.streaming import run_em_streamed
        from .resilience import RetryPolicy, active_plan
        from .resilience.checkpoint import EMCheckpointer

        self._last_em_result = None  # as in _run_em_fused
        dtype = self._float_dtype
        lam0, m0, u0, _ = self.params.to_arrays(dtype=dtype)
        init = FSParams(lam=jnp.asarray(lam0), m=jnp.asarray(m0), u=jnp.asarray(u0))
        mesh = mesh_from_settings(self.settings)
        stats_reduce = None
        if jax.process_count() > 1:
            from .parallel.distributed import all_sum_stats

            # host-local mesh shardings don't span controllers; the
            # explicit cross-process reduction is what makes each host's
            # partial stats a global aggregate (the caller already
            # restricted its stream to this host's global_pair_slice)
            mesh = None
            stats_reduce = all_sum_stats

        # checkpoint/resume plumbing (docs/resilience.md): the streamed
        # driver exposes progress through on_iteration, so checkpointing
        # is a hook — and resume is (restored init params, start_iteration)
        ckpt_dir, resume, interval = self._checkpoint_config()
        start_iteration = 0
        checkpointer = None
        if ckpt_dir is not None:
            state_hash = self._em_state_hash()
            ckpt = self._load_validated_checkpoint(ckpt_dir, state_hash, resume)
            if ckpt is not None:
                lam_r, m_r, u_r = ckpt.params_arrays()
                init = FSParams(
                    lam=jnp.asarray(lam_r.astype(dtype)),
                    m=jnp.asarray(m_r.astype(dtype)),
                    u=jnp.asarray(u_r.astype(dtype)),
                )
                start_iteration = min(
                    ckpt.iteration, int(self.settings["max_iterations"])
                )
                # replay the pre-interruption history into the Params
                # object so the final state is indistinguishable from an
                # uninterrupted run's
                h = ckpt.history_arrays()
                self._replay_em_history(
                    h["lam"], h["m"], h["u"], h["ll"],
                    0, start_iteration, compute_ll,
                )
            checkpointer = EMCheckpointer(
                ckpt_dir,
                state_hash,
                interval=interval,
                process_count=jax.process_count(),
                write=jax.process_index() == 0,
                dtype=np.dtype(dtype).name,
            ).start(init, from_checkpoint=ckpt)
            if ckpt is not None and ckpt.converged:
                # training already completed before the interruption —
                # resuming would append a spurious extra update
                logger.info(
                    "checkpoint at iteration %d is already converged; "
                    "nothing to resume", ckpt.iteration,
                )
                return

        tel = self._obs if self._obs.enabled else None

        def on_iteration(it, params_dev, ll, converged_now=False):
            if compute_ll and ll is not None:
                self.params.params["log_likelihood"] = float(ll)
                self.params.log_likelihood_exists = True
            lam, m, u = fetch((params_dev.lam, params_dev.m, params_dev.u))
            self.params.update_from_arrays(float(lam), m, u)
            # checkpoint BEFORE save_state_fn and the em_iteration fault
            # site: an injected kill at iteration N must find update N
            # already durable (the kill-and-resume contract)
            if checkpointer is not None:
                checkpointer.on_iteration(
                    it, params_dev, ll, converged=converged_now
                )
            if self.save_state_fn is not None:
                self.save_state_fn(self.params, self.settings)

        with self._stage("em_streamed") as st:
            updates_before = len(self.params.param_history)
            # inside the stage span so em_begin captures it as the parent
            # of every em_iteration span
            if tel is not None:
                tel.em_begin(
                    "streamed",
                    float(np.asarray(init.lam)),
                    np.asarray(init.m),
                    np.asarray(init.u),
                    start_iteration=start_iteration,
                )
            _, _, _, converged = run_em_streamed(
                batches,
                init,
                max_iterations=int(self.settings["max_iterations"]),
                max_levels=self.params.max_levels,
                em_convergence=self.settings["em_convergence"],
                mesh=mesh,
                compute_ll=compute_ll,
                on_iteration=on_iteration,
                stats_reduce=stats_reduce,
                start_iteration=start_iteration,
                retry_policy=RetryPolicy(),
                fault_plan=active_plan(self.settings),
                telemetry=tel,
            )
            st.count(
                pairs=pairs,
                iterations=len(self.params.param_history) - updates_before,
            )
        if checkpointer is not None:
            checkpointer.finish(converged)
        if converged:
            logger.info("EM algorithm has converged")

    def stream_scored_comparisons(self, compute_ll: bool = False):
        """Streaming variant of get_scored_comparisons for outputs too large
        to materialise as one DataFrame: runs (streamed) EM, then yields
        scored-comparison DataFrame chunks of ``pair_batch_size`` pairs.

        The reference returns a lazy Spark DataFrame at any scale
        (/root/reference/splink/__init__.py:121-145); chunked emission is the
        single-host equivalent — each chunk can be appended to parquet etc.

        Its call span ``stream_scored_comparisons`` runs from the first
        ``next()`` to exhaustion or ``close()`` and counts ``pairs`` and
        ``chunks`` handed out and ``suspended_s``, the seconds the chunks
        sat with the consumer: the span steps aside at every yield
        (``StageTimer.suspended``), as ``score_patterns`` does below it.
        """
        call = self._call("stream_scored_comparisons")
        call.count(pairs=0, chunks=0, suspended_s=0.0)
        with call:
            try:
                if self._regime("patterns"):
                    self._run_em_patterns(compute_ll, score_follows=True)
                else:
                    self._run_em(self._ensure_gammas(), compute_ll)
                for chunk in self.stream_scored_comparisons_after_em():
                    call.count(pairs=len(chunk), chunks=1)
                    with call.suspended():
                        yield chunk
            finally:
                # release the (potentially multi-GB) ids on exhaustion AND
                # on an abandoned/closed generator — same convention as the
                # one-frame path; a re-stream simply recomputes chunk-wise
                self._P_virtual = None
                self._obs.finish()

    def stream_scored_comparisons_after_em(self):
        """Yield scored-comparison chunks using the current parameters
        (EM — or a loaded model — already applied); see
        stream_scored_comparisons."""
        if self._regime("patterns"):
            yield from self._stream_pattern_chunks()
            return
        G = self._ensure_gammas()
        batch = int(self.settings["pair_batch_size"])
        for s in range(0, len(G), batch):
            yield self._build_df_e(G, slice(s, min(s + batch, len(G))))

    def _replay_em_history(
        self, lam_h, m_h, u_h, ll_h, from_k: int, to_k: int, compute_ll: bool
    ) -> None:
        """Apply history updates ``from_k+1 .. to_k`` into the Params
        object (per update: archive the pre-update log likelihood at
        index k-1, then update_from_arrays) — the ONE replay loop behind
        plain-result installation, checkpoint-boundary replay and resume
        (history layout: index i = params before update i+1; ll index i =
        log likelihood under params i, NaN = not computed)."""
        for k in range(from_k + 1, to_k + 1):
            if (
                compute_ll
                and ll_h is not None
                and not np.isnan(ll_h[k - 1])
            ):
                self.params.params["log_likelihood"] = float(ll_h[k - 1])
                self.params.log_likelihood_exists = True
            self.params.update_from_arrays(
                float(lam_h[k]), np.asarray(m_h[k]), np.asarray(u_h[k])
            )

    def _emit_em_diagnostics(self, gamma_hist: dict | None) -> None:
        """Offline EM diagnostics (obs/quality.em_diagnostics): final
        m/u/Bayes-factor table with identifiability warnings — levels
        with ~zero training support, levels where m~=u — logged as
        warnings and emitted as one ``em_diagnostics`` telemetry event
        (rendered by ``obs summarize``). Never raises into the run."""
        try:
            from .em import trimmed_trajectory
            from .obs.quality import em_diagnostics

            diag = em_diagnostics(self.params, gamma_hist)
            if self._last_em_result is not None:
                # the device-side trajectory carries the per-iteration
                # log likelihood the Params history cannot reconstruct
                diag["run"] = trimmed_trajectory(self._last_em_result)
            for w in diag["warnings"]:
                logger.warning("EM identifiability: %s", w)
            self._obs.emit_event("em_diagnostics", **diag)
        except Exception as e:  # noqa: BLE001 - diagnostics are best-effort
            logger.warning("EM diagnostics failed: %s", e)

    def _replay_history(self, result, compute_ll: bool) -> None:
        """Install a run_em result's device-side history into the Params
        object so history, convergence logging, charts and save/load match
        the reference's per-iteration bookkeeping."""
        self._last_em_result = result
        # where the driver meets EM: one wait for the loop, then its
        # histories home in one piece (a slice a device array per update
        # would be an eager dispatch each)
        n_updates, lam_h, m_h, u_h, ll_hist = fetch((
            result.n_updates, result.lam_history, result.m_history,
            result.u_history, result.ll_history,
        ))
        n_updates = int(n_updates)
        self._replay_em_history(
            lam_h, m_h, u_h, ll_hist, 0, n_updates, compute_ll
        )
        if compute_ll and not np.isnan(ll_hist[n_updates]):
            self.params.params["log_likelihood"] = float(ll_hist[n_updates])
            self.params.log_likelihood_exists = True

    def make_term_frequency_adjustments(self, df_e):
        """Ex-post term-frequency adjustment of scored comparisons
        (/root/reference/splink/__init__.py:147-163).

        When df_e still corresponds row-for-row to this linker's pair index,
        the per-token aggregation runs on device over the encoded table's
        factorised token ids (segment_sum) instead of a host groupby."""
        from .term_frequencies import (
            make_adjustment_for_term_frequencies,
            term_frequency_columns,
        )

        with self._call("tf") as call:
            pair_token_ids = None
            if self._pairs is not None:
                with span("tf_align_check", rows=len(df_e)):
                    aligned = self._df_e_aligned_with_pairs(df_e)
                if aligned:
                    table = self._ensure_encoded()
                    pair_token_ids = {}
                    with span("tf_token_ids", rows=len(df_e)):
                        for name in term_frequency_columns(self.settings):
                            if name in table.strings:
                                tid = table.strings[name].token_ids
                                pair_token_ids[name] = (
                                    tid[self._pairs.idx_l],
                                    tid[self._pairs.idx_r],
                                    table.strings[name].n_tokens,
                                )
            out = make_adjustment_for_term_frequencies(
                df_e,
                self.params,
                self.settings,
                retain_adjustment_columns=True,
                pair_token_ids=pair_token_ids,
            )
            call.count(rows=len(out))
        return out

    def _df_e_aligned_with_pairs(self, df_e) -> bool:
        """Whether df_e still corresponds row-for-row to the pair index (the
        fast device-side TF path needs this; a user-sorted or filtered frame
        falls back to the host groupby path)."""
        n = self._pairs.n_pairs
        if len(df_e) != n or not df_e.index.equals(pd.RangeIndex(n)):
            return False
        uid = self.settings["unique_id_column_name"]
        cols = (f"{uid}_l", f"{uid}_r")
        if not all(c in df_e.columns for c in cols):
            return False
        table = self._ensure_encoded()
        # Full-column comparison: a sampled check could miss a small
        # permutation and silently misattribute probabilities to token ids.
        # A block at a time, so that the ids the pair index names are never
        # gathered whole, and a frame that differs is known at its first
        # block that does.
        for c, idx in zip(cols, (self._pairs.idx_l, self._pairs.idx_r)):
            got = df_e[c].array
            for a in range(0, n, _TAKE_ROWS):
                rows = slice(a, a + _TAKE_ROWS)
                if not np.array_equal(
                    np.asarray(got[rows]), table.unique_id[idx[rows]]
                ):
                    return False
        return True

    def close_telemetry(self) -> None:
        """End this linker's telemetry record now: closes the JSONL sink
        and unregisters it from the ambient (resilience-event) publisher,
        so a long-lived caller holding many linkers doesn't fan every
        later run's events into earlier records. Happens automatically
        when the linker is garbage-collected; no-op when telemetry is
        disabled or already closed."""
        self._obs.close()

    @check_types
    def save_model_as_json(self, path: str | os.PathLike, overwrite: bool = False):
        self.params.save_params_to_json_file(path, overwrite=overwrite)

    def export_index(self, path: str | os.PathLike | None = None):
        """Freeze this linker into an online-serving artifact
        (:class:`splink_tpu.serve.LinkageIndex`): the encoded input table
        as the packed reference matrix, a per-blocking-rule hash-bucket
        index, the CURRENT parameters (train first — or load a model) and
        the term-frequency tables. With ``path`` the artifact is also
        persisted (atomic, versioned, hash-bound — docs/serving.md);
        either way the built index is returned, ready for
        ``splink_tpu.serve.QueryEngine``."""
        from .serve.index import build_index

        with self._stage("export_index"):
            index = build_index(self)
            if path is not None:
                index.save(path)
        return index

    # ------------------------------------------------------------------
    # Output assembly
    # ------------------------------------------------------------------

    def _score_batched(self, G: np.ndarray, params_dev: FSParams,
                       want_z: bool = False, batch: int | None = None):
        """Score in pair_batch_size device batches (padded to one compiled
        shape), so output assembly never pushes more than a batch of the
        gamma matrix plus its (n, C) float intermediates into HBM.

        The per-column prob_m/prob_u intermediates are only computed and
        transferred when retain_intermediate_calculation_columns is set —
        the default path downloads just the (n,) probabilities; ``want_z``
        additionally downloads the match logits (the TF fold's input;
        sigmoid of the logit is the probability bit for bit). Batches are
        double-buffered: batch k+1 dispatches before batch k's download.
        ``batch``: the compiled batch (else pair_batch_size, clamped to n)."""
        n = len(G)
        if batch is None:
            batch = min(int(self.settings["pair_batch_size"]), max(n, 1))
        n_cols = G.shape[1] if G.ndim == 2 else 0
        want_inter = bool(self.settings["retain_intermediate_calculation_columns"])
        out_dtype = self._float_dtype
        # Device copy is reusable only when scoring the exact same full matrix
        src_dev = self._G_dev if self._G_dev is not None and G is self._G else None
        p = np.empty(n, out_dtype)
        z = np.empty(n, out_dtype) if want_z else None
        if want_inter:
            prob_m = np.empty((n, n_cols), out_dtype)
            prob_u = np.empty((n, n_cols), out_dtype)
        else:
            prob_m = prob_u = None
        pending = None  # (start, stop, device results)
        for s in range(0, n, batch):
            stop = min(s + batch, n)
            count(batches=1)
            if src_dev is not None:
                Gb = src_dev[s:stop]
            else:
                with span("h2d_put", bytes=G[s:stop].nbytes):
                    Gb = jnp.asarray(G[s:stop])
            if stop - s < batch:
                Gb = jnp.concatenate(
                    [Gb, jnp.zeros((batch - (stop - s), n_cols), Gb.dtype)]
                )
            if want_inter and want_z:
                res = score_pairs_with_intermediates_logits(Gb, params_dev)
            elif want_inter:
                res = score_pairs_with_intermediates(Gb, params_dev)
            elif want_z:
                res = score_pairs_with_logits(Gb, params_dev)
            else:
                res = (score_pairs(Gb, params_dev),)
            dispatched("score_pairs", res[0], rows=batch)
            res = tuple(r[: stop - s] for r in res)
            if pending is not None:
                self._drain_score_batch(pending, p, prob_m, prob_u, z)
            pending = (s, stop, res)
        if pending is not None:
            self._drain_score_batch(pending, p, prob_m, prob_u, z)
        return p, prob_m, prob_u, z

    @staticmethod
    def _drain_score_batch(pending, p, prob_m, prob_u, z):
        s, stop, res = pending
        p[s:stop] = fetch(res[0])
        if prob_m is not None:
            prob_m[s:stop] = fetch(res[1])
            prob_u[s:stop] = fetch(res[2])
        if z is not None:
            # the logit rides last in every variant that computes it
            z[s:stop] = fetch(res[-1])

    def _build_df_e(self, G: np.ndarray, rows: slice | None = None):
        """Assemble the scored comparisons DataFrame with the reference's
        column layout (/root/reference/splink/expectation_step.py:128-165).
        ``rows`` restricts output to a slice of the pair set (streaming)."""
        pairs = self._ensure_pairs()

        il, ir = pairs.idx_l, pairs.idx_r
        if rows is not None:
            G, il, ir = G[rows], il[rows], ir[rows]

        dtype = self._float_dtype
        lam, m, u, _ = self.params.to_arrays(dtype=dtype)
        params_dev = FSParams(
            lam=jnp.asarray(lam), m=jnp.asarray(m), u=jnp.asarray(u)
        )
        with self._stage("score") as st:
            p, prob_m, prob_u, z = self._score_batched(
                G, params_dev, want_z=self._tf_fold_ctx() is not None
            )
            st.count(pairs=len(G))
        return self._assemble_df_e(
            il, ir, G.T, p,
            None if prob_m is None else prob_m.T,
            None if prob_u is None else prob_u.T,
            z,
        )

    def _assemble_df_e(self, il, ir, levels, p, prob_m, prob_u, z, by=None):
        """One frame of the pairs (il, ir), written at its own length —
        the resident path's whole job, the streaming API's chunk. The
        arguments after the pairs are ``_FrameWriter.write``'s."""
        writer = _FrameWriter(self, len(il))
        writer.write(il, ir, levels, p, prob_m, prob_u, z, by=by)
        return writer.frame()


@check_types
def load_from_json(
    path: str | os.PathLike,
    df=None,
    df_l=None,
    df_r=None,
    save_state_fn: Callable = None,
    spark=None,
):
    """Load a model saved with save_model_as_json and return a ready linker
    (/root/reference/splink/__init__.py:175-195)."""
    params = load_params_from_json(path)
    linker = Splink(
        params.settings, df=df, df_l=df_l, df_r=df_r, save_state_fn=save_state_fn
    )
    linker.params = params
    return linker
