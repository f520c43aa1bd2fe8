"""Shape-bucketed jitted query engine over a :class:`LinkageIndex`.

The hot path is ONE fused jitted program per (query-bucket, candidate-
bucket) shape combination, composed from three kernels (each registered in
the analysis layers — ``serve_encode_query`` / ``serve_candidate_gather`` /
``serve_score_topk`` in :mod:`..analysis.trace_audit`, the scoring kernel
also sharded in :mod:`..analysis.shard_audit`):

  encode_query       padding hygiene on the uploaded (donated) query
                     buffers: rows past the batch's real length are zeroed
                     and their rule buckets forced to -1 on device, so the
                     host can reuse pinned upload buffers without a memset
                     and stale bytes can never alias a candidate.
  candidate_gather   device hash-bucket lookup: each query's per-rule
                     bucket id dereferences the index's CSR
                     (starts/sizes/rows_sorted) into a padded (Q, C)
                     candidate matrix; sequential-rule dedup is an
                     elementwise mask over the per-row bucket ids (a pair
                     produced by an earlier rule is invalid here, the
                     device twin of blocking.py's ``AND NOT
                     ifnull(previous_rule, false)``).
  score_topk         two packed-row reads (query side: a static broadcast;
                     reference side: one gather), the comparison kernels
                     via the shared :func:`gammas._spec_gamma` dispatch
                     (exact bodies — bit-identical to the offline
                     program), log-space Fellegi-Sunter scoring, and a
                     partition-safe row-wise top-k per query
                     (``lax.top_k`` all-gathers under a sharded query
                     axis; see :func:`_top_k_rowwise`).

Inside the fused program no scalar ever syncs to the host (JL011-clean):
the driver dispatches the batch and fetches the packed results once.
Shapes come from :mod:`.bucketing`; after the policy's warmup pass the jit
cache holds every (Q, C) combination and steady-state serving performs
zero recompiles (proven by the ``jax.monitoring`` compile counter in
``obs.metrics``).
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time

from ..analysis import lockwatch

import numpy as np

from ..utils.compile_cache import enable_compilation_cache
from ..utils.logging_utils import warn_degraded

logger = logging.getLogger("splink_tpu")


class IndexSwapError(RuntimeError):
    """A hot-swap candidate index failed to load or validate; the swap was
    rolled back and the previous index is still serving."""


# ---------------------------------------------------------------------------
# Kernel factories (pure jax; traced under jit by the engine and under the
# analysis registries)
# ---------------------------------------------------------------------------


def make_encode_query_fn():
    """(packed_q, qbuckets, valid) -> (packed_q, qbuckets) with padding rows
    zeroed / bucket -1 on device (see module docstring)."""
    import jax.numpy as jnp

    def encode_query(packed_q, qbuckets, valid):
        rows = jnp.arange(packed_q.shape[0], dtype=jnp.int32)
        packed_q = jnp.where(
            (rows < valid)[:, None], packed_q, jnp.uint32(0)
        )
        cols = jnp.arange(qbuckets.shape[1], dtype=jnp.int32)
        qbuckets = jnp.where(
            (cols < valid)[None, :], qbuckets, jnp.int32(-1)
        )
        return packed_q, qbuckets

    return encode_query


def make_candidate_gather_fn(n_rules: int, capacity: int):
    """Device hash-bucket candidate decode for ``n_rules`` rules into a
    padded (Q, ``capacity``) candidate matrix.

    Per query, rule r's bucket contributes its rows at slots
    [offset_r, offset_r + size_r) where offset_r is the running sum of the
    earlier rules' bucket sizes — the same emission order as offline
    blocking. A candidate whose row falls in an EARLIER rule's bucket for
    this query is masked invalid (sequential-rule dedup)."""
    import jax.numpy as jnp

    def candidate_gather(qbuckets, starts, sizes, rows, row_bucket):
        q_n = qbuckets.shape[1]
        slot = jnp.arange(capacity, dtype=jnp.int32)[None, :]  # (1, C)
        cand = jnp.zeros((q_n, capacity), jnp.int32)
        valid = jnp.zeros((q_n, capacity), bool)
        offset = jnp.zeros((q_n, 1), jnp.int32)
        for r in range(n_rules):
            qb = qbuckets[r][:, None]  # (Q, 1)
            has = qb >= 0
            qb0 = jnp.where(has, qb, 0)
            cnt = jnp.where(has, sizes[r][qb0], 0)  # (Q, 1)
            local = slot - offset  # (Q, C)
            in_r = (local >= 0) & (local < cnt)
            limit = jnp.int32(rows[r].shape[0] - 1)
            pos = jnp.clip(starts[r][qb0] + local, 0, jnp.maximum(limit, 0))
            cand_r = rows[r][pos]
            dup = jnp.zeros(in_r.shape, bool)
            for j in range(r):
                qbj = qbuckets[j][:, None]
                dup = dup | ((qbj >= 0) & (row_bucket[j][cand_r] == qbj))
            cand = jnp.where(in_r, cand_r, cand)
            valid = valid | (in_r & ~dup)
            offset = offset + cnt
        n_cand = jnp.sum(valid, axis=1, dtype=jnp.int32)
        return cand, valid, n_cand

    return candidate_gather


def _top_k_rowwise(scores, k: int):
    """(Q, C) -> ((Q, k) values, (Q, k) int32 indices), ``lax.top_k``
    semantics (descending, ties by ascending index) built from k max/mask
    passes. ``lax.top_k`` itself is unpartitionable under GSPMD — it
    all-gathers a query-sharded score matrix onto every device (the
    shard_audit SA-COLL gate caught exactly that) — while per-row max
    reductions along the replicated candidate axis partition trivially.
    k is small (the serving top-k), so k passes beat a gathered sort."""
    import jax.numpy as jnp

    c = scores.shape[1]
    col = jnp.arange(c, dtype=jnp.int32)[None, :]
    masked = scores
    vals, idxs = [], []
    for _ in range(k):
        m = jnp.max(masked, axis=1, keepdims=True)  # (Q, 1)
        # first index attaining the max (top_k's tie order); int32
        # throughout — jnp.argmax would emit int64 under x64
        i = jnp.min(
            jnp.where(masked == m, col, jnp.int32(c)), axis=1
        )
        i = jnp.minimum(i, jnp.int32(c - 1))
        vals.append(m[:, 0])
        idxs.append(i)
        masked = jnp.where(col == i[:, None], jnp.asarray(-2.0, scores.dtype), masked)
    return jnp.stack(vals, axis=1), jnp.stack(idxs, axis=1)


def _take_slots(x, slots):
    """``x[q, slots[q, j]]`` — ``jnp.take_along_axis(x, slots, axis=1)``
    with the int32 slot indices kept int32 (take_along_axis widens them to
    the canonical index dtype, int64 under x64, which the trace audit
    rejects). Slots come from :func:`_top_k_rowwise`, which clamps them
    into range."""
    from jax import lax

    return lax.gather(
        x,
        slots[..., None],
        lax.GatherDimensionNumbers(
            offset_dims=(),
            collapsed_slice_dims=(1,),
            start_index_map=(1,),
            operand_batching_dims=(0,),
            start_indices_batching_dims=(0,),
        ),
        slice_sizes=(1, 1),
        mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS,
    )


def _finish_topk(p, cand, valid, k: int):
    """Shared tail of both scoring paths: mask invalid slots to an
    impossible -1, run the partition-safe row-wise top-k, and map the
    winning slots back to reference rows. Invalid slots can never displace
    a real candidate; ``top_valid`` reports which of the k slots are
    real."""
    import jax.numpy as jnp

    q_n, capacity = cand.shape
    scores = jnp.where(
        valid.reshape(-1), p, jnp.asarray(-1.0, p.dtype)
    ).reshape(q_n, capacity)
    top_p, top_i = _top_k_rowwise(scores, k)
    top_rows = _take_slots(cand, top_i)
    top_valid = _take_slots(valid, top_i)
    # a row with fewer than k valid candidates re-picks slot 0 with the
    # -2 mask sentinel once real entries are exhausted; the score guard
    # keeps such duplicates from reading slot 0's valid flag (real
    # probabilities are >= 0, invalid slots -1, re-picks -2)
    top_valid = top_valid & (top_p > -0.5)
    return top_p, top_rows, top_valid


def make_score_topk_fn(layout: dict, comparison_columns, k: int,
                       tf_spec: tuple = ()):
    """(packed_q, packed_ref, cand, valid, params[, tf_q, tf_tid, tf_log])
    -> (top_p, top_rows, top_valid): gammas via the shared comparison
    dispatch (exact bodies), Fellegi-Sunter match probabilities, masked
    top-k per query. The UNFUSED scoring path — it materialises the full
    (Q*C, n_comparisons) gamma matrix and hands it to
    ``match_probability`` wholesale. Retained as the parity oracle for
    :func:`make_score_fused_fn`, which is the default serving path.

    ``tf_spec`` (term_frequencies.tf_fold_spec entries restricted to the
    index's fold columns) arms the term-frequency u-probability fold:
    per TF column one (Q,) query-token-id vector (``tf_q``), the
    (n_rows,) reference token ids (``tf_tid``) and the log relative-
    frequency table (``tf_log``, term_frequencies.tf_log_table values in
    the compute dtype) — pairs that agree on a token swap the top
    level's average u for the token's own collision probability."""
    import jax
    import jax.numpy as jnp

    from ..gammas import PairContext, _spec_gamma
    from ..models.fellegi_sunter import fold_logit, match_probability
    from ..term_frequencies import tf_fold_delta

    cols = tuple(comparison_columns)
    tf_spec = tuple(tf_spec)

    def score_topk(packed_q, packed_ref, cand, valid, params,
                   tf_q=(), tf_tid=(), tf_log=()):
        q_n, capacity = cand.shape
        # query side: static repeat (broadcast + reshape), NOT an index
        # gather — same row order as packed_q[repeat(arange(Q), C)] but
        # partitions trivially when the query axis is sharded (a computed-
        # index gather of a sharded operand would all-gather it; the
        # shard_audit SA-COLL budget pins this kernel collective-free)
        rows_l = jnp.repeat(packed_q, capacity, axis=0)
        rflat = cand.reshape(-1)
        rows_r = packed_ref[rflat]
        ctx = PairContext(layout, rows_l, rows_r)
        G = jnp.stack([_spec_gamma(c, ctx) for c in cols], axis=1)
        if not tf_spec:
            p = match_probability(G, params)
        else:
            # the TF fold: same delta expression, accumulation order and
            # association as the fused kernel and the offline fold —
            # fold_logit IS the fused kernel's left-to-right log-BF
            # accumulation, the anchor that keeps TF-adjusted parity
            # exact at any column count (its docstring has the ulp story)
            from ..models.fellegi_sunter import _safe_log

            z = fold_logit(G, params)
            log_u = _safe_log(params.u)
            tf_sum = jnp.zeros(z.shape, z.dtype)
            for t, (ci, _name, top) in enumerate(tf_spec):
                tql = jnp.repeat(tf_q[t], capacity)
                trf = tf_tid[t][rflat]
                tf_sum = tf_sum + tf_fold_delta(
                    tql, trf, tf_log[t], log_u[ci, top], z.dtype
                )
            p = jax.nn.sigmoid(z + tf_sum)
        return _finish_topk(p, cand, valid, k)

    return score_topk


def make_score_fused_fn(layout: dict, comparison_columns, k: int,
                        tf_spec: tuple = ()):
    """The fused gamma→score→top-k megakernel: same signature and
    BIT-identical results as :func:`make_score_topk_fn`, without ever
    materialising the (Q*C, n_comparisons) gamma matrix.

    The unfused path stacks every comparison's gamma levels into G, then
    ``match_probability`` walks that matrix twice more (``_select_levels``
    over the m and u tables) — three full (Q*C, C)-shaped intermediates
    round-tripping through HBM per batch. Here each comparison's gamma
    levels fold into a running per-pair log-Bayes-factor the moment they
    are computed: one (Q*C,) accumulator crosses the comparisons, and the
    per-comparison gamma vector dies inside the fusion. Per comparison,
    every arithmetic step mirrors the unfused expression tree exactly —
    the same ``_safe_log`` probability tables, the same per-level
    compare-and-mask lookup in the same level order, the same null
    (gamma = -1) masking. ACROSS comparisons the accumulation order is
    the pinned left-to-right fold of
    :func:`~..models.fellegi_sunter.fold_logit`, which
    ``match_probability`` shares (``log_bayes_factor`` accumulates its
    columns in the same order instead of leaving it to a ``jnp.sum``
    lowering), so fused, unfused and offline scores are the same float on
    every backend. The layer-6 numerics audit (NA-ORD,
    docs/static_analysis.md#layer-6) holds both logits to a host
    left-to-right reference; the parity tests, ``make warmup-smoke`` and
    — on the TPU — ``tests_tpu`` and ``chip_smoke.py`` gate bit-identity.

    With ``tf_spec`` the term-frequency u-probability fold rides the same
    fusion: per TF column ONE extra device gather (the reference token ids
    at the candidate rows; the query side is a static repeat like the
    packed rows) plus a log-table lookup, and the per-pair delta
    accumulates into a separate running sum added to the log-Bayes-factor
    before the sigmoid — the identical expression the unfused oracle and
    the offline fold kernel evaluate (term_frequencies module docstring),
    so TF-adjusted parity stays exact."""
    import jax
    import jax.numpy as jnp

    from ..gammas import PairContext, _spec_gamma
    from ..models.fellegi_sunter import _safe_log
    from ..term_frequencies import tf_fold_delta

    cols = tuple(comparison_columns)
    tf_spec = tuple(tf_spec)

    def score_fused(packed_q, packed_ref, cand, valid, params,
                    tf_q=(), tf_tid=(), tf_log=()):
        # identical row materialisation to the unfused path (static
        # broadcast on the query side, one reference gather) — the fusion
        # target is the scoring chain, not the row reads
        capacity = cand.shape[1]
        rows_l = jnp.repeat(packed_q, capacity, axis=0)
        rflat = cand.reshape(-1)
        rows_r = packed_ref[rflat]
        ctx = PairContext(layout, rows_l, rows_r)
        log_m = _safe_log(params.m)  # (C, L)
        log_u = _safe_log(params.u)
        n_levels = log_m.shape[1]
        log_bf = jnp.zeros(rows_l.shape[0], log_m.dtype)
        for ci, c in enumerate(cols):
            g = _spec_gamma(c, ctx)  # (Q*C,) int8; dies inside the fusion
            # per-column twin of models.fellegi_sunter._select_levels:
            # compare-and-mask accumulation over the static level axis in
            # the same level order, scalar table entries broadcast
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
        lam = params.lam
        prior_logit = _safe_log(lam) - _safe_log(1.0 - lam)
        if not tf_spec:
            p = jax.nn.sigmoid(prior_logit + log_bf)
        else:
            # TF u-probability fold: a separate running delta sum added
            # AFTER the comparison accumulation — `(prior + log_bf) +
            # tf_sum` is the association the offline fold kernel's
            # `z + tf_sum` reproduces (z = prior + log_bf), keeping the
            # adjusted scores bit-identical across every path
            tf_sum = jnp.zeros(log_bf.shape, log_bf.dtype)
            for t, (ci, _name, top) in enumerate(tf_spec):
                tql = jnp.repeat(tf_q[t], capacity)
                trf = tf_tid[t][rflat]
                tf_sum = tf_sum + tf_fold_delta(
                    tql, trf, tf_log[t], log_u[ci, top], log_bf.dtype
                )
            p = jax.nn.sigmoid(prior_logit + log_bf + tf_sum)
        return _finish_topk(p, cand, valid, k)

    return score_fused


def _exec_name(kind: str, q_pad: int, capacity: int) -> str:
    """Canonical sidecar name of one compiled shape combination."""
    return f"{kind}-q{q_pad}-c{capacity}"


@contextlib.contextmanager
def _persistent_cache_disabled():
    """Force a REAL backend compile (no persistent-cache read) — the only
    kind of executable that serializes into a loadable sidecar blob. jax
    decides once per process whether the cache is in use and remembers
    it, so the switch only takes effect with that memo dropped."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _params_structs(mu_shape, dt):
    """ShapeDtypeStruct pytree of the device-resident FSParams."""
    import jax

    from ..models.fellegi_sunter import FSParams

    S = jax.ShapeDtypeStruct
    return FSParams(lam=S((), dt), m=S(mu_shape, dt), u=S(mu_shape, dt))


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


class QueryEngine:
    """Low-latency query interface over a resident :class:`LinkageIndex`.

    One engine owns one index's device residency and one jit cache. Use
    :meth:`warmup` (via a :class:`~.bucketing.BucketPolicy`) before taking
    traffic so steady-state batches never compile.
    """

    def __init__(self, index, *, top_k: int | None = None, policy=None,
                 telemetry=None, brownout_top_k: int | None = None,
                 fused: bool | None = None, aot_dir=None,
                 sketch: bool | None = None, tf_adjust: bool | None = None):
        from .bucketing import BucketPolicy, bucket_for

        self.index = index
        settings = index.settings
        # a serve-only process never builds a linker: enable the persistent
        # compile cache here too (first caller in the process wins)
        enable_compilation_cache(settings.get("compilation_cache_dir"))
        # Fused scoring (make_score_fused_fn) is the default hot path; the
        # unfused program is the retained parity oracle (serve_fused=False
        # or fused=False selects it).
        self.fused = bool(
            settings.get("serve_fused", True) if fused is None else fused
        )
        # Term-frequency u-probability fold (term_frequencies module
        # docstring): default on whenever the index carries fold data
        # (serve_tf_adjust settings gate). ``tf_adjust=`` overrides the
        # gate like ``fused=`` so one index can serve TF-on and TF-off
        # engines side by side (tests/test_serve_tf.py does); it never
        # conjures a fold for an index without the data.
        self._tf_override = tf_adjust  # forwarded across swap_index
        want_tf = bool(
            settings.get("serve_tf_adjust", True)
            if tf_adjust is None
            else tf_adjust
        )
        self.tf_spec = tuple(index.tf_fold_columns()) if want_tf else ()
        if want_tf and not self.tf_spec and index.tf_tables:
            # a TF-flagged model whose artifact predates the fold data
            # (counts only, no per-row token ids): serve exactly as
            # before this build — unadjusted — and say so once
            logger.warning(
                "index carries TF count tables but no per-row token ids "
                "(artifact built before the TF fold); serving UNADJUSTED "
                "scores — re-export the index to enable serve-time TF "
                "adjustment"
            )
        # AOT executable sidecar (serve/aot.py): when set, warmup restores
        # every valid serialized executable instead of compiling, and
        # save_aot() persists the compiled menu for the next process.
        self._aot_dir = os.fspath(aot_dir) if aot_dir else None
        self._aot_store = None  # memoised validated AotStore (or False)
        self.top_k = int(
            top_k
            if top_k is not None
            else settings.get("serve_top_k", 5) or 5
        )
        self.policy = policy or BucketPolicy.from_settings(settings)
        if self.top_k > self.policy.candidate_buckets[-1]:
            raise ValueError(
                f"serve_top_k={self.top_k} exceeds the largest candidate "
                f"bucket ({self.policy.candidate_buckets[-1]}); widen "
                "serve_candidate_buckets — top-k cannot exceed the padded "
                "candidate capacity"
            )
        # Brown-out tier: a second, budgeted program — smaller top-k AND
        # the smallest candidate bucket that covers it, so a degraded
        # dispatch runs the CHEAPEST compiled shape combination instead of
        # shedding outright (admission.py). 0 disables the tier.
        self.brownout_top_k = int(
            brownout_top_k
            if brownout_top_k is not None
            else settings.get("serve_brownout_top_k", 0) or 0
        )
        if self.brownout_top_k < 0 or self.brownout_top_k > self.top_k:
            raise ValueError(
                f"serve_brownout_top_k={self.brownout_top_k} must be in "
                f"[0, serve_top_k={self.top_k}] — the brown-out tier serves "
                "a REDUCED budget"
            )
        self.brownout_capacity = (
            bucket_for(self.brownout_top_k, self.policy.candidate_buckets)
            if self.brownout_top_k
            else None
        )
        self._obs = telemetry
        # Serve-time drift sketch (obs/drift.py): device-side gamma/score
        # histogram accumulation folded onto the fused-kernel outputs of
        # every full-service batch. Requires BOTH the quality_profile
        # setting and a profiled index — a legacy (profile-less) artifact
        # serves unchanged and drift reporting states why it is dark.
        # ``sketch=`` overrides the settings gate (like ``fused=``) so one
        # profiled index can serve sketch-on and sketch-off engines
        # side by side; it never conjures a sketch for a profile-less index.
        self.sketch = None
        self._sketch_override = sketch  # forwarded across swap_index
        want_sketch = (
            bool(settings.get("quality_profile"))
            if sketch is None
            else bool(sketch)
        )
        if want_sketch and index.profile is not None:
            from ..obs.drift import ServeSketch

            self.sketch = ServeSketch(index, index.profile)
        # kind ("full" | "brownout") -> jitted fused program (stable
        # identity; only used through .lower() for AOT-style compilation)
        self._jits: dict = {}
        # (kind, q_pad, capacity) -> jax.stages.Compiled: THE dispatch
        # table. Each entry is an ahead-of-time compiled (or AOT-sidecar
        # restored) executable for one exact shape combination — dispatch
        # never goes through jit's tracing machinery, so a fresh process
        # that restores the menu performs zero backend compiles.
        self._execs: dict = {}
        # key -> "compiled" | "aot": where each executable came from (an
        # AOT-restored menu executes ONE dispatch probe during warmup
        # instead of one per shape — see _warm_one)
        self._exec_source: dict = {}
        self._aot_exec_probed = False
        self._donate = None
        self._warmed: set[tuple[int, int]] = set()
        self._warmed_brownout: set[tuple[int, int]] = set()
        # serializes batch dispatch against index hot-swap: a dispatch in
        # flight finishes on the index it started on (graceful drain), and
        # the swap flip is atomic with respect to the next dispatch
        self._swap_lock = lockwatch.new_rlock("QueryEngine._swap_lock")
        # serializes swap_index against ITSELF (the dispatch lock must stay
        # free during a swap's long validation, so it cannot do this job):
        # without it two concurrent swaps both "commit", one silently lost
        self._swap_mutex = lockwatch.new_lock("QueryEngine._swap_mutex")
        self._probes = None  # (query df, recorded answer arrays)
        self._generation = 0
        # float64 serving needs process-wide x64, same semantics as the
        # linker's float64 setting (jax silently downcasts otherwise)
        if index.dtype == "float64":
            import jax

            if jax.default_backend() == "tpu":
                raise ValueError(
                    "index was built for float64 but the TPU backend has no "
                    "float64 support; rebuild with float64 off"
                )
            if not jax.config.jax_enable_x64:
                jax.config.update("jax_enable_x64", True)
                logger.info(
                    "float64 serving index: enabled jax x64 mode "
                    "(process-wide)"
                )

    # -- kernel ---------------------------------------------------------

    # threadlint: holds=_swap_lock (query/warmup/save_aot enter locked)
    def _build_kernel(self, k: int):
        """One jitted fused program for one top-k. ``capacity`` is a
        static argument; the engine compiles each (capacity, shapes)
        combination explicitly through ``.lower().compile()`` (the AOT
        path — a compiled executable can be serialized into the sidecar
        and restored by a fresh process without the backend compiler)."""
        import functools

        import jax

        index = self.index
        # the gather menu covers the exact rules AND the approx LSH bands
        # (the fallback bucket path rides the same compiled programs, so a
        # fallback batch is recompile-free and brown-out compatible)
        n_rules = len(index.gather_units)
        encode = make_encode_query_fn()
        layout = index.layout
        cols = tuple(index.settings["comparison_columns"])
        make_score = (
            make_score_fused_fn if self.fused else make_score_topk_fn
        )
        score = make_score(layout, cols, k, tf_spec=self.tf_spec)

        def fused(
            capacity, packed_q, qbuckets, valid,
            starts, sizes, rows, row_bucket, packed_ref, params,
            tf_q=(), tf_tid=(), tf_log=(),
        ):
            gather = make_candidate_gather_fn(n_rules, capacity)
            packed_q, qbuckets = encode(packed_q, qbuckets, valid)
            cand, cvalid, n_cand = gather(
                qbuckets, starts, sizes, rows, row_bucket
            )
            top_p, top_rows, top_valid = score(
                packed_q, packed_ref, cand, cvalid, params,
                tf_q, tf_tid, tf_log,
            )
            return top_p, top_rows, top_valid, n_cand

        # donate the per-request buffers (query rows + buckets); the
        # CPU backend ignores donation with a warning, so gate it — and
        # the drift sketch re-reads the query upload AFTER the scoring
        # kernel consumed it, so sketching keeps the buffers live
        donate = ()
        if jax.default_backend() not in ("cpu",) and self.sketch is None:
            donate = (1, 2)
        self._donate = donate
        return functools.partial(
            jax.jit, static_argnums=(0,), donate_argnums=donate
        )(fused)

    # threadlint: holds=_swap_lock (query/warmup/save_aot enter locked)
    def _jit_kernel(self, kind: str):
        """The jitted program for one tier (stable identity; lowered per
        shape by :meth:`_ensure_exec`, never called directly)."""
        if kind == "brownout" and not self.brownout_top_k:
            raise RuntimeError(
                "brown-out tier is disabled (serve_brownout_top_k=0)"
            )
        jfn = self._jits.get(kind)
        if jfn is None:
            k = self.top_k if kind == "full" else self.brownout_top_k
            jfn = self._jits[kind] = self._build_kernel(k)
        return jfn

    # threadlint: holds=_swap_lock (query/warmup/save_aot enter locked)
    def _arg_structs(self, q_pad: int):
        """ShapeDtypeStruct pytree of one dispatch's dynamic arguments at
        query bucket ``q_pad`` — what ``.lower()`` needs instead of real
        (allocated) example batches."""
        import jax

        index = self.index
        S = jax.ShapeDtypeStruct
        dt = index.float_dtype
        i32, u32 = np.int32, np.uint32
        units = index.gather_units
        structs = (
            S((q_pad, index.n_lanes), u32),
            S((len(units), q_pad), i32),
            S((), i32),
            tuple(S(r.starts.shape, i32) for r in units),
            tuple(S(r.sizes.shape, i32) for r in units),
            tuple(S(r.rows_sorted.shape, i32) for r in units),
            tuple(S(r.row_bucket.shape, i32) for r in units),
            S(index.packed.shape, u32),
            _params_structs(index.m.shape, dt),
        )
        if not self.tf_spec:
            # legacy / TF-off: the exact argument tree of today's
            # executables (byte-identical serving, unchanged sidecars
            # modulo the binding's tf flag)
            return structs
        tf_dev = index.tf_device_state()
        return structs + (
            tuple(S((q_pad,), i32) for _ in self.tf_spec),
            tuple(S(a.shape, i32) for a in tf_dev["tid"]),
            tuple(S(a.shape, dt) for a in tf_dev["log"]),
        )

    # threadlint: holds=_swap_lock (query/warmup/save_aot enter locked)
    def _ensure_exec(self, kind: str, q_pad: int, capacity: int):
        """The compiled executable for one exact shape combination:
        dispatch-table hit, else AOT-sidecar restore (zero backend
        compiles), else a fresh ``.lower().compile()``."""
        key = (kind, q_pad, capacity)
        ex = self._execs.get(key)
        if ex is not None:
            return ex
        store = self._aot_ready_store()
        if store is not None:
            ex = store.restore(_exec_name(kind, q_pad, capacity))
            if ex is not None:
                from ..obs.metrics import note_aot_restore

                note_aot_restore()
                self._execs[key] = ex
                self._exec_source[key] = "aot"
                return ex
        from ..obs.metrics import compile_stats, install_compile_monitor

        install_compile_monitor()
        h0 = compile_stats()["cache_hits"]
        lowered = self._jit_kernel(kind).lower(
            capacity, *self._arg_structs(q_pad)
        )
        ex = self._execs[key] = lowered.compile()
        # an executable the PERSISTENT cache served was itself
        # deserialized — like an AOT restore, re-serializing it yields a
        # blob that cannot be loaded ("Symbols not found"); save_aot must
        # know to re-compile it cache-bypassed for the sidecar
        self._exec_source[key] = (
            "cache" if compile_stats()["cache_hits"] > h0 else "compiled"
        )
        return ex

    # -- AOT executable sidecar -----------------------------------------

    # threadlint: holds=_swap_lock (query/warmup/save_aot enter locked)
    def _aot_binding(self) -> dict:
        """The strict-invalidation identity every sidecar executable is
        bound to (serve/aot.py adds the environment half: jax/jaxlib
        version, backend, target-feature fingerprint)."""
        index = self.index
        return {
            "index_state_hash": index.state_hash,
            "index_fingerprint": index.content_fingerprint(),
            "dtype": index.dtype,
            "n_rules": len(index.rules),
            "n_approx_bands": (
                0 if index.approx is None else index.approx.bands
            ),
            "top_k": self.top_k,
            "brownout_top_k": self.brownout_top_k,
            "query_buckets": list(self.policy.query_buckets),
            "candidate_buckets": list(self.policy.candidate_buckets),
            "fused": self.fused,
            # sketching flips buffer donation off, which changes the
            # compiled executable — a sidecar saved either way must not
            # serve the other configuration
            "sketch": self.sketch is not None,
            # the TF fold changes the compiled scoring tail (extra gather
            # + delta accumulation), so a sidecar saved either way must
            # not serve the other configuration
            "tf": bool(self.tf_spec),
        }

    # threadlint: holds=_swap_lock (query/warmup/save_aot enter locked)
    def _aot_ready_store(self):
        """The validated sidecar store, memoised; None when no sidecar is
        configured, present, or valid (every invalidation reason emits one
        ``serve_aot`` degradation event and serving falls back to fresh
        compiles — never a wrong or foreign executable)."""
        if self._aot_store is None:
            if self._aot_dir is None:
                self._aot_store = False
            else:
                from .aot import AotStore

                store = AotStore(self._aot_dir)
                self._aot_store = (
                    store if store.validate(self._aot_binding()) else False
                )
        return self._aot_store or None

    def save_aot(self, directory=None) -> str:
        """Serialize every compiled executable currently in the dispatch
        table into the AOT sidecar at ``directory`` (default: the engine's
        ``aot_dir``), bound to the index fingerprint, settings hash, shape
        menu and environment. Call after :meth:`warmup` so the sidecar
        holds the full bucket menu. Returns the sidecar meta path."""
        # the whole save runs under the swap lock (reentrant): a swap
        # committing mid-iteration would mix two menus into one sidecar
        with self._swap_lock:
            return self._save_aot_locked(directory or self._aot_dir)

    # threadlint: holds=_swap_lock
    def _save_aot_locked(self, directory) -> str:
        from .aot import AotStore

        if not directory:
            raise ValueError(
                "no sidecar directory: pass save_aot(directory) or "
                "construct the engine with aot_dir="
            )
        if not self._execs:
            raise RuntimeError(
                "nothing to save: run warmup() first so the dispatch table "
                "holds the compiled bucket menu"
            )
        executables = {}
        recompiled = 0
        fresh_jits: dict = {}
        for (kind, q_pad, capacity), ex in self._execs.items():
            if self._exec_source.get((kind, q_pad, capacity)) != "compiled":
                # only an executable ACTUALLY backend-compiled in this
                # process serializes into a loadable blob; one restored
                # from the sidecar OR served by the persistent compile
                # cache was itself deserialized, and re-serializing it
                # succeeds silently but fails deserialize_and_load with
                # "Symbols not found" — writing it would overwrite a
                # valid sidecar with a poisoned one. Re-compile a fresh
                # twin with the persistent cache bypassed; the existing
                # executable keeps serving. The twin comes from a NEW jit
                # wrapper: jax memoises trace -> lowering -> executable per
                # function object, so re-lowering the engine's own wrapper
                # hands back the very executable being replaced.
                if kind not in fresh_jits:
                    fresh_jits[kind] = self._build_kernel(
                        self.top_k if kind == "full" else self.brownout_top_k
                    )
                with _persistent_cache_disabled():
                    ex = fresh_jits[kind].lower(
                        capacity, *self._arg_structs(q_pad)
                    ).compile()
                recompiled += 1
            executables[_exec_name(kind, q_pad, capacity)] = ex
        path = AotStore.write(directory, self._aot_binding(), executables)
        logger.info(
            "AOT executable sidecar saved: %s (%d executables, %d "
            "re-lowered from restored entries)",
            directory, len(executables), recompiled,
        )
        return path

    # -- query paths ----------------------------------------------------

    def encode(self, df):
        """Host-side query encode (see LinkageIndex.encode_queries)."""
        with self._swap_lock:  # reentrant: the batch path enters locked
            return self.index.encode_queries(df)

    def query_arrays(self, df, *, degraded: bool = False, profile=None,
                     approx_out: list | None = None):
        """Score a query DataFrame; returns
        ``(top_p, top_rows, top_valid, n_candidates)`` numpy arrays of
        shape (n, k) / (n,). ``top_rows`` are reference ROW indices; map
        through ``index.unique_id`` for ids (``query`` does).

        ``approx_out``, when a list, receives one (n,) bool array marking
        the queries served through the approx LSH FALLBACK bucket path
        (their exact keys hit no bucket; candidates come from minhash band
        buckets and results should surface as ``approx=True``). The scores
        themselves are bit-identical to offline scoring of the same
        (query, candidate) pairs — the fallback changes WHICH candidates
        are gathered, never how a pair is scored.

        ``degraded=True`` runs the brown-out program: top-k
        ``brownout_top_k`` over candidates truncated to the cheapest
        bucket (``brownout_capacity``) — the budgeted answer the service
        serves under pressure instead of shedding.

        ``profile`` (an :class:`~..obs.reqtrace.PhaseProfile`) accumulates
        the batch's compile/execute/transfer split for request tracing.
        Profiling splits the EXISTING single result rendezvous into a
        compute wait plus the D2H fetch — it adds no new host sync and
        leaves the compiled programs untouched."""
        with self._swap_lock:
            k = self.brownout_top_k if degraded else self.top_k
            if degraded and not k:
                raise RuntimeError(
                    "brown-out tier is disabled (serve_brownout_top_k=0)"
                )
            batch = self.encode(df)
            if self.sketch is not None:
                # host-side sketch counters from the already-encoded
                # batch (OOV / null-key / approx-fallback rates) — no
                # device work; brown-out batches only count as degraded
                # (their reduced top-k would skew the histograms)
                if degraded:
                    self.sketch.note_degraded(batch.n)
                else:
                    self.sketch.note_batch(df, batch, len(self.index.rules))
            if approx_out is not None:
                approx_out.append(
                    batch.approx_used
                    if batch.approx_used is not None
                    else np.zeros(batch.n, bool)
                )
            out_p = np.full((batch.n, k), -1.0, self.index.float_dtype)
            out_rows = np.zeros((batch.n, k), np.int32)
            out_valid = np.zeros((batch.n, k), bool)
            out_ncand = np.zeros(batch.n, np.int64)
            pos = 0
            for q_pad, start, stop in self.policy.iter_query_chunks(batch.n):
                p, r, v, nc = self._run_chunk(
                    batch, start, stop, q_pad, degraded=degraded,
                    profile=profile,
                )
                out_p[start:stop] = p[: stop - start]
                out_rows[start:stop] = r[: stop - start]
                out_valid[start:stop] = v[: stop - start]
                out_ncand[start:stop] = nc[: stop - start]
                pos = stop
            assert pos == batch.n
            return out_p, out_rows, out_valid, out_ncand

    # threadlint: holds=_swap_lock (only query_arrays calls this, locked)
    def _run_chunk(self, batch, start: int, stop: int, q_pad: int, *,
                   degraded: bool = False, profile=None):
        """One bucketed device dispatch: pad the chunk to ``q_pad`` queries
        and its candidate axis to a policy bucket, run the fused kernel,
        fetch once."""
        import jax.numpy as jnp

        index = self.index
        n = stop - start
        qb = batch.qbuckets[:, start:stop]
        if degraded:
            # brown-out: the candidate budget IS the truncation — always
            # the cheapest compiled shape, no per-batch warning spam (the
            # service tags every result degraded and emits the episode
            # events)
            capacity = self.brownout_capacity
            kind = "brownout"
        else:
            counts = index.candidate_counts(qb)
            need = max(int(counts.max(initial=0)), self.top_k, 1)
            capacity = self.policy.candidate_bucket(need)
            if capacity is None:
                capacity = self.policy.candidate_buckets[-1]
                warn_degraded(
                    "serve_candidates",
                    "truncated",
                    f"largest candidate block needs {need} slots but the "
                    f"largest candidate bucket is {capacity}; blocks are "
                    "truncated to the bucket (top-k over the truncated set)",
                    queries=n,
                )
            kind = "full"
        if profile is not None:
            from ..obs.metrics import compile_totals

            # snapshot BEFORE the dispatch-table lookup: a cold shape
            # compiles inside _ensure_exec, not inside the call
            c0 = compile_totals()[1]
        kernel = self._ensure_exec(kind, q_pad, capacity)
        # pinned upload buffers are reused without a host memset: the
        # encode_query kernel zeroes padding rows on device
        packed_pad = np.empty((q_pad, index.n_lanes), np.uint32)
        packed_pad[:n] = batch.packed[start:stop]
        qb_pad = np.empty((len(index.gather_units), q_pad), np.int32)
        qb_pad[:, :n] = qb
        dev = index.device_state()
        packed_dev = jnp.asarray(packed_pad)
        tf_args = ()
        if self.tf_spec:
            # padding rows carry token id -1 (never agrees), so the fold
            # is inert on them like the encode kernel's zeroed rows
            tf_q = []
            for t in range(len(self.tf_spec)):
                buf = np.full(q_pad, -1, np.int32)
                buf[:n] = batch.tf_tids[t, start:stop]
                tf_q.append(jnp.asarray(buf))
            tf_dev = index.tf_device_state()
            tf_args = (tuple(tf_q), tf_dev["tid"], tf_dev["log"])
        top_p, top_rows, top_valid, n_cand = kernel(
            packed_dev,
            jnp.asarray(qb_pad),
            np.int32(n),
            dev["starts"],
            dev["sizes"],
            dev["rows"],
            dev["row_bucket"],
            dev["packed"],
            dev["params"],
            *tf_args,
        )
        if self.sketch is not None and not degraded:
            # fold the batch into the device drift accumulator: an async
            # dispatch over the already-device-resident outputs — nothing
            # is fetched, the hot path gains no host sync (padding rows
            # carry top_valid=False and drop inside the scatter)
            self.sketch.update(
                packed_dev, dev["packed"], top_rows, top_valid, top_p
            )
        (self._warmed_brownout if degraded else self._warmed).add(
            (q_pad, capacity)
        )
        if profile is None:
            # the single host fetch for this batch
            return (
                np.asarray(top_p),
                np.asarray(top_rows),
                np.asarray(top_valid),
                np.asarray(n_cand),
            )
        # traced batch: split the SAME single rendezvous into its parts —
        # compile (monitor delta; zero in steady state), device compute
        # (block_until_ready on the already-dispatched outputs) and the
        # D2H fetch. No additional sync point: the untraced path blocks at
        # exactly this line inside np.asarray instead.
        import jax

        profile.compile_s += max(compile_totals()[1] - c0, 0.0)
        t0 = time.perf_counter()
        jax.block_until_ready((top_p, top_rows, top_valid, n_cand))
        t1 = time.perf_counter()
        profile.execute_s += t1 - t0
        out = (
            np.asarray(top_p),
            np.asarray(top_rows),
            np.asarray(top_valid),
            np.asarray(n_cand),
        )
        profile.transfer_s += time.perf_counter() - t1
        return out

    def query(self, df):
        """Score a query DataFrame; returns a tidy DataFrame with one row
        per (query, match): query id, matched reference id, rank, match
        probability, the query's candidate count and — when the index
        carries the approx tier — an ``approx`` flag marking matches found
        through the LSH fallback bucket path (the query's exact keys hit
        no bucket)."""
        import pandas as pd

        approx_out: list = []
        # one lock span across scoring AND the uid mapping: a hot-swap
        # committing between them would map row indices scored on the old
        # index through the new index's unique_id column
        with self._swap_lock:
            top_p, top_rows, top_valid, n_cand = self.query_arrays(
                df, approx_out=approx_out
            )
            approx_used = approx_out[0]
            ref_uid = self.index.unique_id
            q_idx, rank = np.nonzero(top_valid)
            uid_col = self.index.settings["unique_id_column_name"]
            query_uid = self._query_uids(df)
            out = {
                f"{uid_col}_q": query_uid[q_idx],
                f"{uid_col}_m": ref_uid[top_rows[q_idx, rank]],
                "rank": rank.astype(np.int64),
                "match_probability": top_p[q_idx, rank],
                "n_candidates": n_cand[q_idx],
            }
            if self.index.approx is not None:
                out["approx"] = approx_used[q_idx]
        return pd.DataFrame(out)

    # threadlint: holds=_swap_lock (only query() calls this, locked)
    def _query_uids(self, df) -> np.ndarray:
        uid_col = self.index.settings["unique_id_column_name"]
        if uid_col in df.columns:
            return df[uid_col].to_numpy()
        return np.arange(len(df))

    # -- warmup / compile accounting ------------------------------------

    def warmup(self) -> dict:
        """Ready every (query-bucket, candidate-bucket) combination so
        steady-state serving never compiles — the brown-out tier's
        (query-bucket, ``brownout_capacity``) shapes included when enabled,
        so a brown-out EPISODE is also recompile-free. Each combination is
        AOT-restored from the sidecar when one is configured and valid
        (zero backend compiles), else compiled fresh. Freshly compiled
        programs each execute one dummy batch; a restored menu executes
        only the FIRST and the LARGEST full-service shape
        (deserialization already validated the artifacts, the first probe
        proves dispatch on this machine, the largest proves the biggest
        buffer allocation — per-shape dummy batches made restored warmup
        scale with menu compute for nothing).

        Returns the jax.monitoring-measured accounting split:
        ``combinations``, ``compiles`` (REAL backend compiles),
        ``cache_hits`` (persistent-compilation-cache restores) and
        ``aot_restored`` (sidecar-deserialized executables) — a cold
        replica shows combinations == compiles, a persistent-cache-warm
        one combinations == cache_hits, an AOT-restored one
        combinations == aot_restored with compiles == 0."""
        from ..obs.metrics import compile_stats, install_compile_monitor

        install_compile_monitor()
        s0 = compile_stats()
        combos = self.policy.warmup_combinations()
        for q_pad, capacity in combos:
            self._warm_one(
                q_pad, capacity,
                force_execute=(q_pad, capacity) == combos[-1],
            )
        brownout_combos = []
        if self.brownout_top_k:
            brownout_combos = [
                (qb, self.brownout_capacity)
                for qb in self.policy.query_buckets
            ]
            for q_pad, capacity in brownout_combos:
                self._warm_one(q_pad, capacity, degraded=True)
        # pre-compile the drift-sketch program for every query bucket
        # (one dummy all-invalid dispatch per shape), so sketching
        # adds zero steady-state recompiles. These compiles are ON
        # TOP of the scoring combinations — sketch-on replicas show
        # compiles > combinations here, never in steady state.
        with self._swap_lock:
            if self.sketch is not None:
                for q_pad in self.policy.query_buckets:
                    self.sketch.warm(q_pad, self.top_k)
        s1 = compile_stats()
        stats = {
            "combinations": len(combos) + len(brownout_combos),
            "compiles": s1["compiles"] - s0["compiles"],
            "cache_hits": s1["cache_hits"] - s0["cache_hits"],
            "aot_restored": s1["aot_restores"] - s0["aot_restores"],
        }
        if self._obs is not None:
            self._obs.count("serve_warmup_compiles", stats["compiles"])
            self._obs.count("serve_warmup_cache_hits", stats["cache_hits"])
            self._obs.count(
                "serve_warmup_aot_restores", stats["aot_restored"]
            )
        return stats

    def _warm_one(self, q_pad: int, capacity: int,
                  degraded: bool = False, force_execute: bool = False) -> None:
        import jax.numpy as jnp

        with self._swap_lock:
            index = self.index
            dev = index.device_state()
            kind = "brownout" if degraded else "full"
            kernel = self._ensure_exec(kind, q_pad, capacity)
            if not force_execute and (
                self._exec_source.get((kind, q_pad, capacity)) == "aot"
            ):
                # a restored executable was already validated by its
                # deserialization; executing a dummy batch per shape is
                # what made CPU-tier warmup scale with the menu (the big
                # combos score millions of padded pairs for nothing). ONE
                # dispatch probe per restored menu proves execution on
                # this machine; the rest skip straight to ready.
                if self._aot_exec_probed:
                    (self._warmed_brownout if degraded else self._warmed).add(
                        (q_pad, capacity)
                    )
                    return
                self._aot_exec_probed = True
            packed = np.zeros((q_pad, index.n_lanes), np.uint32)
            qb = np.full((len(index.gather_units), q_pad), -1, np.int32)
            tf_args = ()
            if self.tf_spec:
                tf_dev = index.tf_device_state()
                tf_args = (
                    tuple(
                        jnp.asarray(np.full(q_pad, -1, np.int32))
                        for _ in self.tf_spec
                    ),
                    tf_dev["tid"],
                    tf_dev["log"],
                )
            out = kernel(
                jnp.asarray(packed),
                jnp.asarray(qb),
                np.int32(0),
                dev["starts"],
                dev["sizes"],
                dev["rows"],
                dev["row_bucket"],
                dev["packed"],
                dev["params"],
                *tf_args,
            )
            np.asarray(out[0])  # execute fully
            (self._warmed_brownout if degraded else self._warmed).add(
                (q_pad, capacity)
            )

    @property
    def warmed_shapes(self) -> set:
        """The (query_bucket, candidate_bucket) combinations compiled so
        far (full-service program; the brown-out program's shapes are in
        ``warmed_brownout_shapes``)."""
        with self._swap_lock:
            return set(self._warmed)

    @property
    def warmed_brownout_shapes(self) -> set:
        with self._swap_lock:
            return set(self._warmed_brownout)

    def probe(self) -> None:
        """Execute the smallest warmed shape end to end (kernel + device +
        result fetch, no compile after warmup). The watchdog's circuit-
        breaker recovery probe: success proves the engine can dispatch."""
        self._warm_one(
            self.policy.query_buckets[0], self.policy.candidate_buckets[0],
            force_execute=True,
        )

    @property
    def generation(self) -> int:
        """How many hot-swaps this engine has committed."""
        with self._swap_lock:
            return self._generation

    @property
    def tf_active(self) -> bool:
        """Whether this engine folds the term-frequency u-probability
        adjustment into its served scores (settings gate on AND the index
        carries the fold data)."""
        with self._swap_lock:
            return bool(self.tf_spec)

    # -- drift sketch drain ---------------------------------------------

    def drift_drain_due(self, cadence_s: float) -> bool:
        """Whether the drift accumulator is due a drain (no lock, no
        device work — a cheap poll for the service worker/watchdog).

        Deliberately lock-free: the swap lock is held for entire batch
        dispatches, and the watchdog must never stall its tick budget on
        a serving batch. ``sketch`` only flips on a hot-swap; racing one
        at worst answers the poll for the outgoing sketch (off-by-one
        tick, self-correcting next poll)."""
        # threadlint: disable=TL001 (atomic reference read, see docstring)
        return self.sketch is not None and self.sketch.drain_due(cadence_s)

    def drain_drift(self):
        """Fetch + reset the drift accumulator into one window sketch
        (:class:`~..obs.drift.WindowSketch`), or None when sketching is
        off. The sketch's ONLY device fetch — called between batches by
        the service worker or from the watchdog when idle, never inside a
        dispatch."""
        with self._swap_lock:
            if self.sketch is None:
                return None
            return self.sketch.drain()

    # -- parity probes & index hot-swap ---------------------------------

    def capture_probes(self, df) -> int:
        """Record ``df`` and this engine's CURRENT answers for it as the
        parity probe set: :meth:`swap_index` replays these queries on a
        candidate index and requires bit-identical answers before
        committing. Returns the number of probes stored."""
        df = df.reset_index(drop=True).copy()
        # one lock span across compute AND store: a swap committing in
        # between would attach answers recorded on the OLD index to the
        # NEW one, failing the next (valid) swap's parity replay
        with self._swap_lock:
            answers = self.query_arrays(df)
            self._probes = (df, answers)
        return len(df)

    @property
    def probe_count(self) -> int:
        """Stat-only accessor, deliberately lock-free: ``_probes`` is an
        atomically-assigned tuple reference and the swap lock can be held
        for a whole batch dispatch — a health poll must not stall on it.
        A read racing capture/swap returns the count of either the old or
        the new probe set, both truthful answers."""
        probes = self._probes  # threadlint: disable=TL001 (see docstring)
        return 0 if probes is None else len(probes[0])

    def swap_index(self, source, *, refresh_probes: bool = False) -> dict:
        """Hot-swap to a new :class:`LinkageIndex` with validation and
        rollback (ISSUE tentpole 4):

        1. load the candidate (a directory path or an in-memory index) —
           ``load_index`` verifies format version, settings-hash binding
           and the array fingerprint;
        2. build + pre-warm a pending engine over it (every bucket
           combination compiles BEFORE the flip, so post-swap steady
           state stays recompile-free);
        3. replay the stored parity probes against the recorded answers —
           any drift (``refresh_probes=False``) fails the swap;
        4. atomically flip index/kernels/warm-state under the swap lock —
           an in-flight dispatch finishes on the old index first
           (graceful drain), the next one runs on the new.

        ANY failure before the flip emits a ``serve_index_swap``
        degradation event and raises :class:`IndexSwapError` with the old
        index untouched and still serving. ``refresh_probes=True`` skips
        the parity comparison and re-records the probe answers on the new
        index (an intentional content change). Concurrent ``swap_index``
        calls serialize on the swap mutex — without it both would
        "commit" and one new index would be silently lost."""
        with self._swap_mutex:
            return self._swap_index_serialized(source, refresh_probes)

    def _swap_index_serialized(self, source, refresh_probes: bool) -> dict:
        from ..obs.events import publish
        from ..resilience.faults import active_plan
        from .index import LinkageIndex, load_index

        t0 = time.perf_counter()
        with self._swap_lock:
            plan = active_plan(self.index.settings)
            generation = self._generation + 1
        try:
            plan.fire("swap_load", generation=generation)
            if isinstance(source, LinkageIndex):
                new_index = source
            else:
                new_index = load_index(source)
        except Exception as e:  # noqa: BLE001 - every load failure rolls back
            warn_degraded(
                "serve_index_swap",
                "rolled_back",
                f"candidate index failed to load: {e}",
                generation=generation,
            )
            raise IndexSwapError(
                f"index swap rolled back (old index still serving): "
                f"candidate failed to load: {e}"
            ) from e
        probes_checked = 0
        new_probes = None
        with self._swap_lock:
            probes = self._probes  # snapshot: validation runs on THIS set
        try:
            # a candidate loaded from disk may ship its own AOT sidecar
            # (<dir>/aot) — the pending engine's pre-warm restores from it
            # when its binding matches, cutting the swap's compile window;
            # a stale/foreign sidecar degrades to fresh compiles as usual
            pending_aot = None
            if not isinstance(source, LinkageIndex):
                cand_aot = os.path.join(os.fspath(source), "aot")
                if os.path.isdir(cand_aot):
                    pending_aot = cand_aot
            pending = QueryEngine(
                new_index,
                top_k=self.top_k,
                policy=self.policy,
                telemetry=self._obs,
                brownout_top_k=self.brownout_top_k,
                fused=self.fused,
                sketch=self._sketch_override,
                tf_adjust=self._tf_override,
                aot_dir=pending_aot,
            )
            warm = pending.warmup()
            plan.fire("swap_validate", generation=generation)
            if probes is not None:
                probe_df, expected = probes
                got = pending.query_arrays(probe_df)
                if refresh_probes:
                    new_probes = (probe_df, got)
                else:
                    _check_probe_parity(expected, got)
                    probes_checked = len(probe_df)
                    new_probes = (probe_df, got)
        except Exception as e:  # noqa: BLE001 - every validation failure rolls back
            warn_degraded(
                "serve_index_swap",
                "rolled_back",
                f"candidate index failed validation: {e}",
                generation=generation,
            )
            raise IndexSwapError(
                f"index swap rolled back (old index still serving): {e}"
            ) from e
        with self._swap_lock:
            self.index = pending.index
            self.tf_spec = pending.tf_spec
            self._jits = pending._jits
            self._execs = pending._execs
            self._exec_source = pending._exec_source
            self._aot_exec_probed = pending._aot_exec_probed
            self._donate = pending._donate
            self._aot_dir = pending._aot_dir
            self._aot_store = pending._aot_store
            self._warmed = pending._warmed
            self._warmed_brownout = pending._warmed_brownout
            # the drift sketch binds to the index's profile and device
            # residency; the pending engine built (and warmed) its own
            self.sketch = pending.sketch
            if new_probes is not None:
                self._probes = new_probes
            elif self._probes is not probes:
                # a concurrent capture landed DURING validation: its
                # answers describe the outgoing index and must not gate
                # the next swap — drop them so the service re-seeds its
                # probe set from post-swap traffic
                self._probes = None
            self._generation = generation
            n_rows = self.index.n_rows
        stats = {
            "generation": generation,
            "n_rows": n_rows,
            "warmup_combinations": warm["combinations"],
            "warmup_compiles": warm["compiles"],
            "probes_checked": probes_checked,
            "elapsed_s": round(time.perf_counter() - t0, 3),
        }
        publish("index_swap", **stats)
        logger.info(
            "serving index hot-swapped: generation %d, %d rows, "
            "%d probe(s) parity-checked, %.3fs",
            generation, n_rows, probes_checked, stats["elapsed_s"],
        )
        return stats


def _check_probe_parity(expected, got) -> None:
    """Raise with a precise diff summary unless the candidate engine's
    probe answers are BIT-identical to the recorded ones (same dtypes,
    same shapes, same values — the serve<->offline parity contract carried
    across the swap)."""
    names = ("top_p", "top_rows", "top_valid", "n_candidates")
    for name, e, g in zip(names, expected, got):
        if e.dtype != g.dtype or e.shape != g.shape:
            raise ValueError(
                f"probe parity failed on {name}: recorded "
                f"{e.shape}/{e.dtype} vs candidate {g.shape}/{g.dtype}"
            )
        if not np.array_equal(e, g):
            bad = int(np.sum(e != g))
            raise ValueError(
                f"probe parity failed on {name}: {bad}/{e.size} entries "
                "differ from the recorded answers (bit-identity required; "
                "pass refresh_probes=True for an intentional content change)"
            )
