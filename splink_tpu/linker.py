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
import operator
import os
import warnings
from concurrent.futures import ThreadPoolExecutor
from typing import Callable

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
from .gammas import GammaProgram, register_comparison  # noqa: F401 (re-export)
from .models.fellegi_sunter import FSParams
from .params import Params, load_params_from_json
from .parallel.mesh import mesh_from_settings, shard_pairs
from .settings import comparison_column_name, complete_settings_dict
from .utils.compile_cache import enable_compilation_cache
from .utils.profiling import StageTimer, count, dispatched, fetch, span

logger = logging.getLogger("splink_tpu")

# RAM cap for keeping the virtual pass's per-candidate pattern ids and row
# pairs (pairgen.VirtualIds: 10 bytes a candidate with uint16 ids, 12 with
# int32) for a later score stream: 8.6 GB. Above it the stream recomputes
# them chunk-wise instead (virtual_materialise_ids="on" overrides).
_MAX_RESIDENT_ID_BYTES = 1 << 33

try:  # pandas is required for the linker facade (not for the kernels)
    import pandas as pd
except ImportError:  # pragma: no cover
    pd = None


def _gamma_histograms(settings, G, weights=None, chunk: int = 1 << 22) -> dict:
    """Per-comparison-column gamma-level histogram (telemetry record):
    column name -> [count at level -1 (null), level 0, ..., level L-1].
    ``G`` is either the per-pair gamma matrix or — with ``weights`` (the
    pattern-count vector) — the pattern matrix. Chunked so the int64
    promotion temporaries stay O(chunk): the streamed regime reaches here
    with a G that is huge by definition, and observability must not
    multiply that path's host footprint."""
    cols = settings["comparison_columns"]
    acc = [np.zeros(int(col["num_levels"]) + 1, np.float64) for col in cols]
    with span("gamma_histogram", rows=len(G)):
        for s in range(0, len(G), chunk):
            Gc = G[s : s + chunk]
            w = weights[s : s + chunk] if weights is not None else None
            for c, col in enumerate(cols):
                levels = int(col["num_levels"])
                g = np.asarray(Gc[:, c], np.int64) + 1  # -1 (null) -> bin 0
                acc[c] += np.bincount(
                    np.clip(g, 0, levels), weights=w, minlength=levels + 1
                )[: levels + 1]
    return {
        comparison_column_name(col): [int(v) for v in acc[c]]
        for c, col in enumerate(cols)
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
        self._pattern_program = None
        self._virtual = None  # pairgen.VirtualPlan (device pair generation)
        self._virtual_checked = False
        # per-candidate pattern ids from the virtual pass (sentinel kept),
        # materialised when a score stream is known to follow — one kernel
        # pass instead of two (see _virtual_ids_policy): a pairgen.VirtualIds,
        # the ids with the row pairs the kernel decoded
        self._P_virtual = None
        self._virtual_want_ids = False
        self._pair_bound: int | None = None  # estimate_pair_upper_bound memo
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
                import jax

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
                st.count(rows=int(self._table.n_rows))
        return self._table

    def _ensure_pairs(self) -> PairIndex:
        if self._pairs is None:
            table = self._ensure_encoded()
            build_dir = self.settings.get("build_spill_dir") or None
            if build_dir and self.settings.get("approx_blocking"):
                # the spill driver emits EXACT-rule pairs only; when the
                # approximate LSH tier can actually run, taking it would
                # silently drop every approx pair — the recall feature the
                # setting opts into (the same hazard gate _virtual_plan
                # applies to the virtual pair index)
                from .approx.lsh import approx_columns

                if approx_columns(self.settings, table):
                    from .utils.logging_utils import warn_degraded

                    warn_degraded(
                        "spill_blocking", "host_blocking",
                        "approx_blocking needs materialised blocking (the "
                        "spill emission driver has no approximate tier)",
                    )
                    build_dir = None
            if build_dir:
                # The durable write path (docs/blocking.md#offline-scale):
                # sharded, manifest-committed, RESUMABLE emission into the
                # caller-owned spill store. Overlap scoring is off here by
                # design — a resumed build skips committed segments, so no
                # per-chunk consumer can be fed consistently; the streamed
                # EM consumes the manifest afterwards instead.
                from .blocking_device import spill_block_rules
                from .parallel.distributed import spill_shard_dir

                with self._stage("blocking") as st:
                    pairs = spill_block_rules(
                        self.settings, table, self._n_left,
                        spill_shard_dir(build_dir),
                    )
                    if pairs is not None:
                        st.count(pairs=int(pairs.n_pairs))
                if pairs is not None:
                    self._pairs = pairs
                    logger.info(
                        "blocking produced %d candidate pairs (spill store)",
                        pairs.n_pairs,
                    )
                    from .blocking import clear_key_code_cache

                    clear_key_code_cache(table)
                    return self._pairs
                from .utils.logging_utils import warn_degraded

                warn_degraded(
                    "spill_blocking", "host_blocking",
                    "rule shapes unsupported by the device emission plan",
                )
            stream = self._overlap_stream(table)
            with self._stage("blocking") as st:
                self._pairs = block_using_rules(
                    self.settings,
                    table,
                    self._n_left,
                    pair_consumer=stream.feed if stream is not None else None,
                )
                st.count(pairs=int(self._pairs.n_pairs))
            logger.info("blocking produced %d candidate pairs", self._pairs.n_pairs)
            if self._obs.enabled:
                # block-size skew telemetry rides the still-warm key-code
                # cache; freed with it just below
                from .blocking import block_size_stats

                self._obs.record(
                    "largest_blocks",
                    block_size_stats(self.settings, table, self._n_left),
                )
            self._maybe_spill_pairs()
            if stream is not None:
                self._finish_overlap(stream)
            from .blocking import clear_key_code_cache

            clear_key_code_cache(table)
        return self._pairs

    def _overlap_stream(self, table: EncodedTable):
        """Device-scoring consumer fed DURING blocking (VERDICT round 2 #2:
        end-to-end wall ≈ max(blocking, scoring), not their sum). jax
        dispatch is async, so the accelerator computes rule k's
        gammas/pattern ids while the host joins rule k+1; the second sweep
        over the (possibly disk-spilled) pair index disappears. Spark
        gets the same overlap from lazy evaluation
        (/root/reference/splink/blocking.py:210).

        The regime is chosen BEFORE blocking from a cheap O(n) upper bound
        on the pair count (per-rule key-group histograms): resident-size
        jobs stream the gamma matrix and keep it device-resident for EM
        (no pattern-decode/re-upload penalty); larger jobs stream 3-byte
        pattern ids, which serve both the streamed LUT regime and — decoded
        through the pattern matrix — the resident one if dedup shrank the
        run after all. Custom kernels and pattern-space overflow always
        take GammaStream."""
        if not self.settings.get("overlap_blocking", True):
            return None
        from .gammas import GammaStream, PatternStream

        program = GammaProgram(
            self.settings, table, float_dtype=self._float_dtype
        )
        mesh = mesh_from_settings(self.settings)
        max_resident = int(self.settings["max_resident_pairs"])
        bound = self._estimate_pair_bound(table)
        # clamp the device batch to the job bound (like the sequential
        # paths clamp to n) so a small job doesn't pad its single batch up
        # to pair_batch_size
        batch = int(self.settings["pair_batch_size"])
        batch = max(min(batch, -(-max(bound, 1) // 8) * 8), 1024)
        # _pattern_capable covers the custom-kernel and pattern-space
        # conditions; under a mesh the PatternStream shards its batches
        # over the data axis (gammas.PatternStream mesh support)
        if bound > max_resident and self._pattern_capable():
            self._pattern_program = program
            return PatternStream(program, batch, mesh=self._pattern_mesh())
        keep_limit = max_resident if mesh is None else 0
        return GammaStream(program, batch, keep_device_limit=keep_limit)

    def _finish_overlap(self, stream) -> None:
        from .gammas import PatternStream

        # the stream's batches were dispatched while blocking ran; this
        # stage is the tail flush, and carries the whole pass's counts
        stage = "gammas_patterns" if isinstance(stream, PatternStream) else "gammas"
        with self._stage(stage) as st:
            if isinstance(stream, PatternStream):
                self._P, self._pattern_counts = stream.finish()
            else:
                self._G, self._G_dev = stream.finish()
            st.count(pairs=stream.total,
                     batches=-(-stream.total // stream.batch_size))
            if isinstance(stream, PatternStream):
                self._count_mesh(st, stream.total)
            st.count(**stream.program.kernel_counts(stream.total))

    def _maybe_spill_pairs(self) -> None:
        """Note the blocking-created spill dir (streamed regime): blocking's
        pair sink streams every pair chunk straight to disk-backed memmaps
        when spill_dir is set — rule path and cartesian fallback alike — so
        there is nothing left to copy here. The PairIndex owns the directory
        lifetime via its weakref finalizer; the stale-orphan sweep ran before
        any bytes were written."""
        if self._pairs.spill_tmp is not None:
            self._spill_tmp = self._pairs.spill_tmp
            logger.info("pair index spilled to %s (streamed)", self._spill_tmp)

    def _ensure_gammas(self) -> np.ndarray:
        if self._G is None:
            table = self._ensure_encoded()
            pairs = self._ensure_pairs()  # overlap may set _G or _P here
            if self._multihost_spill_store(pairs) is not None:
                # this process's store holds ONLY its shard subset — a
                # gamma matrix over it would feed scoring/EM paths that
                # assume the FULL pair set, silently producing divergent
                # parameters or subset-only output frames per controller.
                # Training is supported (estimate_parameters routes to the
                # manifest-fed streamed EM with cross-process reduction);
                # scoring output is a single-controller operation.
                raise RuntimeError(
                    "this pair index is a per-process spill shard subset "
                    "(multi-controller emission): scoring APIs need the "
                    "full pair set and are single-controller — train with "
                    "estimate_parameters here, then score in a "
                    "single-process run over the saved model"
                )
            if self._G is not None:
                return self._G
            if self._P is not None:
                # overlap streamed pattern ids but the run ended small
                # enough for the resident regime: decode the gamma matrix
                # from the pattern LUT (bit-identical to recomputation —
                # the pattern id IS the gamma vector in mixed radix)
                with self._stage("gammas") as st:
                    PM = self._pattern_program.patterns_matrix()
                    self._G = PM[self._P]  # fancy-index accepts uint16/int32
                    st.count(pairs=len(self._G))
                return self._G
            # In the resident regime (and without a mesh, which shards its
            # own upload), keep the device-side gamma batches so EM doesn't
            # re-upload the matrix that was just computed there.
            keep = (
                pairs.n_pairs <= int(self.settings["max_resident_pairs"])
                and mesh_from_settings(self.settings) is None
            )
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

    def _pattern_capable(self) -> bool:
        """Static part of the pattern-pipeline test: bounded pattern space
        and no custom comparison kernels — a registered kernel could emit
        gammas outside [-1, num_levels-1], which would alias pattern ids.
        A mesh does NOT disqualify: both the virtual pair index
        (pairgen.make_virtual_pattern_fn) and the materialised pattern
        pass (GammaProgram._pattern_batch_for_mesh, PatternStream) shard
        their batches over the mesh's data axis."""
        from .gammas import MAX_PATTERNS, pattern_strides_for

        for c in self.settings["comparison_columns"]:
            if (c.get("comparison") or {}).get("kind") == "custom":
                return False
        level_counts = [
            int(c["num_levels"]) for c in self.settings["comparison_columns"]
        ]
        _, n_patterns = pattern_strides_for(level_counts)
        return n_patterns <= MAX_PATTERNS

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

    def _estimate_pair_bound(self, table: EncodedTable) -> int:
        if self._pair_bound is None:
            from .blocking import estimate_pair_upper_bound

            with span("pair_bound", rows=int(table.n_rows)):
                self._pair_bound = estimate_pair_upper_bound(
                    self.settings, table, self._n_left
                )
        return self._pair_bound

    def _virtual_plan(self):
        """The device-pair-generation plan, or None (pairgen module
        docstring has the full story). Checked once: the plan build does
        the per-rule key/sort work host blocking would do anyway, so a
        rejected plan costs nothing extra overall."""
        if self._virtual_checked:
            return self._virtual
        self._virtual_checked = True
        mode = self.settings.get("device_pair_generation", "auto")
        if mode == "off" or not self._pattern_capable():
            return None
        if self.settings.get("approx_blocking"):
            # the virtual pair index enumerates EXACT-rule pairs only; the
            # approximate LSH tier emits through materialised blocking, so
            # taking the virtual path here would silently drop every
            # approx pair — the recall feature the setting opts into.
            # With no sketchable string column the tier is a no-op and
            # the virtual path loses nothing (same gate as
            # estimate_pair_upper_bound).
            from .approx.lsh import approx_columns

            if approx_columns(self.settings, self._ensure_encoded()):
                logger.info(
                    "device pair generation disabled: approx_blocking "
                    "needs materialised blocking (the virtual pair index "
                    "has no approximate tier)"
                )
                return None
        from .pairgen import build_virtual_plan

        table = self._ensure_encoded()
        if mode == "auto":
            # small jobs: the resident/overlap paths are already optimal
            bound = self._estimate_pair_bound(table)
            if bound <= int(self.settings["max_resident_pairs"]):
                return None
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

    def _use_pattern_pipeline(self) -> bool:
        """Whether the streamed pattern-id pipeline applies: device pair
        generation active, or a large materialised pair set with
        pattern-capable settings."""
        if self._virtual_plan() is not None:
            return True
        if not self._pattern_capable():
            return False
        pairs = self._ensure_pairs()
        if self._multihost_spill_store(pairs) is not None:
            # a per-process spill store's n_pairs is LOCAL and differs per
            # controller — a count-dependent regime choice here could put
            # controllers on different EM paths (one in a collective, one
            # not: deadlock). The manifest-fed streamed driver is the one
            # multi-controller-correct path for these stores, so the
            # decision is pinned deterministically (process_count is
            # identical in every store's meta).
            return False
        return pairs.n_pairs > int(self.settings["max_resident_pairs"])

    @staticmethod
    def _multihost_spill_store(pairs):
        """The pair index's spill store when it was written under
        MULTI-CONTROLLER emission (and therefore holds only this
        process's shard subset) — None otherwise."""
        store = getattr(pairs, "spill_store", None)
        if store is not None and (
            int(store.meta.get("process_count", 1) or 1) > 1
        ):
            return store
        return None

    def _pattern_mesh(self):
        """The mesh pattern passes shard over: the configured mesh on a
        single controller; None under multi-controller — the sharded
        passes device_put host-local full arrays onto the mesh, which is a
        single-controller layout. Each host then runs the full pattern
        pass on its own default device: duplicated device work, but no
        gamma matrix ever materialises and every host derives the same
        histogram/params (a host-sliced multi-controller pattern pass is
        future work)."""
        mesh = mesh_from_settings(self.settings)
        if mesh is None:
            return None
        import jax

        return mesh if jax.process_count() == 1 else None

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

    def _virtual_ids_policy(self) -> bool:
        """Should the virtual pattern pass ALSO bring home what it knows of
        every candidate — its pattern id and the row pair the kernel decoded
        (pairgen.VirtualIds: 2 or 4 bytes of id and 8 of pair)? One pass
        (ids + pairs + histogram together) beats two (histogram-only EM
        pass, then a recompute inside the score stream) whenever a score
        stream is going to happen and they fit host RAM: the kernels run
        once instead of twice, and the downloads overlap the kernels either
        way. EM-only jobs keep the histogram-only pass — no per-pair bytes
        ever cross the link (what the bytes cost when they do is the
        ``d2h_wait`` / ``mesh_gather`` / ``decode_pairs`` spans of a
        ``chipbench`` run)."""
        mode = self.settings.get("virtual_materialise_ids", "auto")
        if mode == "on":
            return True
        if mode == "off":
            return False
        if not self._virtual_want_ids:
            return False
        from .gammas import pattern_ids_fit_uint16

        small = pattern_ids_fit_uint16(self._ensure_pattern_program().n_patterns)
        need = self._virtual.n_candidates * (10 if small else 12)
        if need > _MAX_RESIDENT_ID_BYTES:
            return False
        # "fits host RAM" means the RAM actually free right now, not just
        # the hard cap: claim at most half of it, else stream chunk-wise
        try:
            avail = os.sysconf("SC_AVPHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
        except (ValueError, OSError, AttributeError):
            return True  # no probe on this platform; the cap still bounds
        return need <= avail // 2

    def _ensure_pattern_ids(self):
        """(pattern_ids, counts, program): ONE device pass over the pair
        index computing gammas, pattern ids and their histogram. The gamma
        matrix itself never materialises — per-pair state is a uint16/int32
        id, and every later stage (EM, scoring, output columns) derives from
        the ≤ prod(levels+1)-row pattern tables. This is also what keeps
        host<->device traffic to a single pass over the pairs."""
        if self._P is None:
            table = self._ensure_encoded()
            if self._virtual_plan() is not None:
                # device pair generation: pairs decode on device from the
                # plan's unit structure; nothing is materialised or
                # transferred per pair. Default is a histogram-ONLY pass
                # (EM needs nothing else); when a score stream is known to
                # follow, _virtual_ids_policy keeps the per-candidate ids
                # and row pairs from this same pass so the stream is
                # LUT-only.
                if self._pattern_counts is not None:
                    return None, self._pattern_counts, self._pattern_program
                from .pairgen import compute_virtual_pattern_ids

                with self._stage("gammas_patterns") as st:
                    self._ensure_pattern_program()
                    want_ids = self._virtual_ids_policy()
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
                    st.count(pairs=n_real, ids_kept=int(want_ids))
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
                st.count(pairs=len(self._P), ids_kept=1)
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

    def _pattern_score_luts(self):
        """Per-pattern lookup tables (host): match probability and, when
        intermediates are retained, per-column prob_m/prob_u — plus the
        match-logit LUT when the TF fold is active (the per-pair fold
        adds its delta to the pattern's logit; a pattern LUT of folded
        probabilities is impossible because the delta is a property of
        the PAIR's tokens, not its gamma pattern). Reuses the batched
        scoring path, which bounds HBM at any pattern count."""
        program = self._ensure_pattern_program()
        PM = program.patterns_matrix()
        dtype = self._float_dtype
        lam, m, u, _ = self.params.to_arrays(dtype=dtype)
        params_dev = FSParams(
            lam=jnp.asarray(lam), m=jnp.asarray(m), u=jnp.asarray(u)
        )
        p, pm, pu, z = self._score_batched(
            PM, params_dev, want_z=self._tf_fold_ctx() is not None
        )
        return PM, p, pm, pu, z

    def _pattern_frame_tables(self):
        """The per-pattern tables in ``_FrameWriter.write``'s argument
        order, one contiguous row a frame column: the levels as the int64
        the frame carries, so a chunk's ``gamma_*`` column is one take by
        pattern id and no ``(pairs, columns)`` matrix is made on the way."""
        PM, p, pm, pu, z = self._pattern_score_luts()
        rows = np.ascontiguousarray
        return (
            rows(PM.T, dtype=np.int64),
            p,
            None if pm is None else rows(pm.T),
            None if pu is None else rows(pu.T),
            z,
        )

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
        (il, ir, pattern-ids) chunk, written at the chunk's length. The
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
        """Yield (idx_l, idx_r, pattern_ids) per chunk across the pattern
        regimes — virtual with stored ids (host-only), virtual recompute
        (device pass), materialised pairs — with masked sentinels already
        filtered. The SINGLE definition of the pattern pair stream: the
        score stream assembles frames from it and the streaming TF
        adjustment drives it twice. (The virtual branch deliberately
        avoids _ensure_pattern_ids: scoring needs no histogram pass, e.g.
        under manual FS weights.)

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
                    return (
                        il[keep],
                        ir[keep],
                        Pc[keep].astype(np.int32, copy=False),
                    )

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
            yield (
                pairs.idx_l[rows],
                pairs.idx_r[rows],
                P[rows].astype(np.int32, copy=False),
            )

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
        if not self._use_pattern_pipeline():
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
        self._virtual_want_ids = True
        # the try spans EVERYTHING from EM (which materialises the
        # potentially multi-GB per-candidate ids) onward: an exception in
        # the aggregation pass or a consumer abandoning/closing the
        # generator anywhere must not leak the ids
        try:
            self._run_em_patterns(compute_ll)
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

    def _run_em_patterns(self, compute_ll: bool) -> None:
        _, counts, program = self._ensure_pattern_ids()
        if int(counts.sum()) == 0:
            warnings.warn(
                "No candidate pairs to estimate from (blocking produced "
                "nothing); parameters are unchanged."
            )
            return
        patterns = program.patterns_matrix()
        seen = counts > 0
        logger.info(
            "pattern-compressed EM: %d pairs -> %d distinct gamma patterns",
            int(counts.sum()),
            int(seen.sum()),
        )
        self._obs.gauge("gamma_patterns_distinct", int(seen.sum()))
        self._last_em_result = None  # same staleness guard as _run_em
        # always cheap here (the pattern matrix is small by construction);
        # feeds telemetry AND the EM diagnostics' level-support evidence
        hist = _gamma_histograms(self.settings, patterns, weights=counts)
        if self._obs.enabled:
            self._obs.record("gamma_histogram", hist)
        self._run_em_resident_weighted(patterns[seen], counts[seen], compute_ll)
        self._emit_em_diagnostics(hist)

    # ------------------------------------------------------------------
    # Public API (reference parity)
    # ------------------------------------------------------------------

    def manually_apply_fellegi_sunter_weights(self):
        """Score using the m/u values in the settings, without running EM
        (/root/reference/splink/__init__.py:111-119)."""
        with self._call("manually_apply_fellegi_sunter_weights") as call:
            if self._use_pattern_pipeline():
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
        if self._use_pattern_pipeline():
            self._run_em_patterns(compute_ll)
            return
        pairs = self._ensure_pairs()
        store = getattr(pairs, "spill_store", None)
        # A store written under multi-controller emission holds only THIS
        # process's shard subset, so the manifest-fed driver (whose
        # cross-process stats reduction forms the global aggregate) is the
        # ONLY correct EM path for it — and the branch must not depend on
        # the LOCAL pair count, which differs per process and would split
        # controllers across collective/non-collective regimes (deadlock)
        # or train each on its own subset without reduction. process_count
        # is identical in every per-process store's meta, so this decision
        # is globally consistent.
        if store is not None and (
            self._multihost_spill_store(pairs) is not None
            or pairs.n_pairs > int(self.settings["max_resident_pairs"])
        ):
            # spill-store-backed pairs past the resident cap: EM consumes
            # the manifest directly — gammas per chunk on device, never
            # rematerialised host-side
            self._run_em_streamed_spill(pairs, compute_ll)
        else:
            G = self._ensure_gammas()
            self._run_em(G, compute_ll)
            self._G_dev = None

    def get_scored_comparisons(self, compute_ll: bool = False):
        """Estimate parameters by EM and return scored comparisons
        (/root/reference/splink/__init__.py:121-145).

        When the candidate-pair count exceeds ``max_resident_pairs`` the
        pipeline switches to the pattern-id regime: one device pass encodes
        each pair's gamma vector as a mixed-radix pattern id and histograms
        them, EM runs on the weighted pattern matrix, and scoring is a host
        LUT gather — pair data crosses the host<->device link exactly once.
        """
        with self._call("scored_comparisons") as call:
            if self._use_pattern_pipeline():
                # scoring follows EM here, so the virtual pass may keep its
                # per-candidate ids and make the stream LUT-only (one kernel
                # pass instead of two)
                self._virtual_want_ids = True
                self._run_em_patterns(compute_ll)
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
        """Dispatch EM to the resident or streamed regime by pair count.

        A device OOM on the resident path (the gamma matrix plus EM
        workspace outgrew HBM) degrades to the streamed regime — same
        update math over host-batched uploads — instead of crashing the
        run (docs/resilience.md degradation ladder)."""
        from .resilience import active_plan, is_oom
        from .utils.logging_utils import warn_degraded

        # a stale result from an earlier call must not attach its
        # trajectory to this run's diagnostics (the streamed/checkpointed
        # paths replay history without going through _replay_history)
        self._last_em_result = None
        # the gamma histogram doubles as the EM diagnostics' level-support
        # evidence (obs/quality.em_diagnostics) and as the quality
        # profile's raw material; in the resident regime it is cheap
        # relative to the gamma computation that just ran, so compute it
        # there unconditionally — the huge streamed-with-telemetry-off
        # case alone skips it (diagnostics then omit support counts)
        hist = None
        if self._obs.enabled or len(G) <= int(
            self.settings["max_resident_pairs"]
        ):
            hist = _gamma_histograms(self.settings, G)
            if self._obs.enabled:
                self._obs.record("gamma_histogram", hist)
        if len(G) > int(self.settings["max_resident_pairs"]):
            self._run_em_streamed(G, compute_ll)
            self._emit_em_diagnostics(hist)
            return
        # the resident attempt may replay completed updates into
        # self.params (checkpoint boundaries / save_state_fn) before it
        # OOMs; the fallback must restart from the PRE-attempt state or
        # those updates would be applied twice
        params_snapshot = copy.deepcopy(self.params)
        try:
            active_plan(self.settings).fire("resident_em", pairs=len(G))
            self._run_em_resident(G, compute_ll)
        except Exception as e:  # noqa: BLE001 - is_oom() decides
            if not is_oom(e):
                raise
            self.params = params_snapshot
            warn_degraded(
                "resident_em", "streamed_em", f"{type(e).__name__}: {e}",
                pairs=len(G),
            )
            self._run_em_streamed(G, compute_ll)
        self._emit_em_diagnostics(hist)

    def _run_em_resident(self, G: np.ndarray, compute_ll: bool) -> None:
        """Fused on-device EM with the gamma matrix resident in HBM."""
        dtype = self._float_dtype
        mesh = mesh_from_settings(self.settings)
        weights = None
        if mesh is not None:
            G_dev, weights = shard_pairs(mesh, G)
            weights = weights.astype(dtype)
        else:
            G_dev = self._G_dev
            if G_dev is None:
                with span("h2d_put", bytes=G.nbytes):
                    G_dev = jnp.asarray(G)
        self._run_em_fused(G_dev, weights, compute_ll, pairs=len(G))

    def _run_em_fused(self, G_dev, weights, compute_ll: bool, pairs: int) -> None:
        """Shared fused-EM driver: whole-run while_loop normally, stepped one
        update at a time when a save_state_fn checkpoint hook must run
        between iterations (the restart semantics of
        /root/reference/splink/iterate.py:54-55)."""
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
                converged = self._run_em_fused_checkpointed(
                    G_dev, init, max_iterations, em_kwargs, ckpt_dir,
                    resume, interval, compute_ll,
                )
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

    def _run_em_fused_checkpointed(
        self, G_dev, init, max_iterations, em_kwargs, ckpt_dir, resume,
        interval, compute_ll,
    ) -> bool:
        """Checkpointed resident EM: em.run_em_checkpointed runs the ONE
        compiled while_loop with an in-loop host hook that writes an
        atomic checkpoint every ``interval`` updates — bit-identical
        trajectory, plus durable resume. History replays into the Params
        object incrementally at each boundary (so save_state_fn sees the
        same per-update cadence as the stepped driver, at boundary
        granularity; both run on the callback thread and must stay
        host-side) and resumed iterations replay from the checkpoint's
        histories."""
        from .resilience import active_plan

        state_hash = self._em_state_hash()
        ckpt = self._load_validated_checkpoint(ckpt_dir, state_hash, resume)
        if self.save_state_fn is not None:
            logger.warning(
                "checkpoint_dir moves save_state_fn onto the compiled "
                "loop's host-callback thread (called at checkpoint "
                "boundaries, mid-program): the hook must stay host-side "
                "work — dispatching jax computation from it can deadlock "
                "the running program."
            )
        replayed = 0

        def replay(done, hist):
            nonlocal replayed
            self._replay_em_history(
                hist["lam"], hist["m"], hist["u"], hist["ll"],
                replayed, done, compute_ll,
            )
            replayed = done

        def on_segment(done, hist, _converged):
            replay(done, hist)
            if self.save_state_fn is not None:
                self.save_state_fn(self.params, self.settings)

        result = run_em_checkpointed(
            G_dev,
            init,
            max_iterations=max_iterations,
            checkpoint_dir=ckpt_dir,
            state_hash=state_hash,
            checkpoint_every=interval,
            resume=resume,
            resume_checkpoint=ckpt,
            fault_plan=active_plan(self.settings),
            on_segment=on_segment,
            telemetry=self._obs if self._obs.enabled else None,
            **em_kwargs,
        )
        # a resume that was already complete runs zero segments; catch up
        # from the result's (checkpoint-restored) histories
        n_updates = int(result.n_updates)
        replay(
            n_updates,
            {
                "lam": result.lam_history,
                "m": result.m_history,
                "u": result.u_history,
                "ll": result.ll_history,
            },
        )
        if compute_ll and not np.isnan(result.ll_history[n_updates]):
            self.params.params["log_likelihood"] = float(
                result.ll_history[n_updates]
            )
            self.params.log_likelihood_exists = True
        return bool(result.converged)

    def _run_em_streamed(self, G: np.ndarray, compute_ll: bool) -> None:
        """Streaming EM over host-resident gamma micro-batches.

        Reached only when the pattern-id pipeline declined the job (mesh set,
        custom kernels, or a pattern space past MAX_PATTERNS) — otherwise
        large pair sets never materialise G at all (_run_em_patterns)."""
        self._run_em_streamed_stats(G, compute_ll)

    def _run_em_resident_weighted(
        self, G_pat: np.ndarray, weights: np.ndarray, compute_ll: bool
    ) -> None:
        """Fused EM on a weighted pattern matrix (counts as weights)."""
        dtype = self._float_dtype
        self._run_em_fused(
            jnp.asarray(G_pat), jnp.asarray(weights.astype(dtype)), compute_ll,
            pairs=int(weights.sum()),
        )

    def _run_em_streamed_stats(self, G: np.ndarray, compute_ll: bool) -> None:
        """Streaming EM accumulating sufficient statistics per pass — the
        fallback when the pattern space is too large for a dense histogram,
        and the mesh path (stats psum across devices).

        Under a multi-controller run (jax.process_count() > 1) each host
        streams only its global_pair_slice of the pair set and the
        per-pass sufficient statistics reduce across processes with
        all_sum_stats (one allgather per pass — the path proven
        bit-compatible with a single process by
        tests/test_multiprocess_em.py), like every host's Spark executor
        reading its own partitions."""
        import jax

        from .parallel.distributed import global_pair_slice

        pairs = len(G)
        if jax.process_count() > 1:
            G = G[global_pair_slice(len(G))]
        batch = int(self.settings["pair_batch_size"])

        def batches():
            for s in range(0, len(G), batch):
                yield G[s : s + batch]

        self._run_em_streamed_driver(batches, compute_ll, pairs)

    def _run_em_streamed_spill(self, pairs: PairIndex, compute_ll: bool) -> None:
        """Manifest-fed streamed EM: the spill store IS the pair stream.

        Each EM pass walks the committed pair range of the store's memmaps
        in ``pair_batch_size`` slices, computes that slice's gamma block on
        device (GammaProgram.iter_gamma_chunks — same batching, padding
        and overflow semantics as the resident paths) and feeds it to
        run_em_streamed. The gamma matrix NEVER materialises on the host:
        at billions of pairs even the int8 G is tens of GB, which is what
        capped the old write path. Multi-controller runs stream only their
        global_pair_slice of the manifest and reduce stats with
        all_sum_stats, exactly like the materialised path. Trajectory is
        bit-identical to a (hypothetical) resident streamed run over the
        same pair order — batch boundaries match by construction."""
        import jax

        from .parallel.distributed import global_pair_slice
        from .spill import iter_spill_gamma_batches

        store = pairs.spill_store
        program = GammaProgram(
            self.settings, self._ensure_encoded(),
            float_dtype=self._float_dtype,
        )
        batch = int(self.settings["pair_batch_size"])
        pair_range = None
        if (
            jax.process_count() > 1
            and int(store.meta.get("process_count", 1) or 1) == 1
        ):
            # a SHARED single-writer store consumed by many controllers
            # slices like a materialised G; a per-process store (written
            # under multi-controller emission) already holds only this
            # host's shard subset — streaming it whole IS the local slice
            pair_range = global_pair_slice(store.total_pairs)

        def batches():
            return iter_spill_gamma_batches(
                store, program, batch, pair_range=pair_range
            )

        self._last_em_result = None
        logger.info(
            "spill-fed streamed EM over %d pairs (%d manifest segments)",
            store.total_pairs, len(store.segments),
        )
        self._run_em_streamed_driver(batches, compute_ll, int(store.total_pairs))
        self._emit_em_diagnostics(None)

    def _run_em_streamed_driver(self, batches, compute_ll: bool, pairs: int) -> None:
        """The shared streamed-EM driver: checkpoint/resume plumbing,
        telemetry and the run_em_streamed call over any re-iterable batch
        factory — the materialised G path and the spill-manifest path
        differ ONLY in where their gamma batches come from."""
        import jax

        from .parallel.streaming import run_em_streamed
        from .resilience import RetryPolicy, active_plan
        from .resilience.checkpoint import EMCheckpointer

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
                if self._use_pattern_pipeline():
                    # scoring follows EM: let the virtual pass keep its ids
                    # (the auto policy still bounds them against free RAM)
                    self._virtual_want_ids = True
                    self._run_em_patterns(compute_ll)
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
        if self._use_pattern_pipeline():
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
                       want_z: bool = False):
        """Score in pair_batch_size device batches (padded to one compiled
        shape), so output assembly never pushes more than a batch of the
        gamma matrix plus its (n, C) float intermediates into HBM.

        The per-column prob_m/prob_u intermediates are only computed and
        transferred when retain_intermediate_calculation_columns is set —
        the default path downloads just the (n,) probabilities; ``want_z``
        additionally downloads the match logits (the TF fold's input;
        sigmoid of the logit is the probability bit for bit). Batches are
        double-buffered: batch k+1 dispatches before batch k's download."""
        n = len(G)
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
