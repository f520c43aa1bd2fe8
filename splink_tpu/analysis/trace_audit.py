"""trace_audit: jaxpr-level audit of the declared kernel registry (layer 2).

The AST linter sees what the source *says*; this layer checks what the
compiler will actually *run* on one device (:mod:`shard_audit` — layer 3 —
re-checks the sharded kernels under a multi-device mesh). Every kernel in the registry — the EM step,
the gamma batch, the string kernels, the TF adjustment, the streamed pass —
is traced with abstract-shaped example inputs and its jaxpr is asserted
against four invariants:

  TA-CONST     no embedded constant above a size budget. A closed-over
               numpy/device array becomes a jaxpr constant serialised into
               every compiled program (gammas.py keeps the packed table
               an explicit argument for exactly this reason; the audit
               pins that design).
  TA-DTYPE     no strong dtype wider than float32/int32 (weak-typed Python
               scalars are exempt — they adapt to their operand's dtype).
               Kernels are traced with x64 FORCED ON (enable_x64), which is
               what makes the check a leak detector: any internal f64/i64
               means a constructor derives its dtype from ambient config
               instead of from inputs, and would behave differently across
               backends. The CLI therefore catches the same leaks the x64
               test tier does.
  TA-CALLBACK  no host callback other than the declared ones (the EM
               host-hook's ordered io_callback — shared by the checkpoint
               writer and the telemetry convergence stream — is the single
               sanctioned host round-trip in the hot loop).
  TA-HASH      identical jaxpr across two independent traces — a trace that
               differs run-to-run (dict-order iteration, fresh closures)
               defeats jit caching and reproducibility.

Registering a kernel::

    @register_kernel("my_kernel", allow_callbacks=("io_callback",))
    def _build_my_kernel():
        fn = ...            # callable to trace
        args = (...)        # example inputs (small shapes; dtypes matter)
        return fn, args, {}

The builder runs lazily inside :func:`run_audit` so importing this module
stays cheap and the registry can reference heavyweight modules.
"""

from __future__ import annotations

import functools
import hashlib
import re
from dataclasses import dataclass, field
from typing import Callable

from .findings import Finding

# dtypes a production (TPU-regime) kernel may hold internally
DEFAULT_ALLOWED_DTYPES = frozenset(
    {"float32", "int32", "int8", "int16", "uint8", "uint16", "uint32", "bool"}
)

# primitives that cross to the host
_CALLBACK_PRIMS = {
    "io_callback",
    "pure_callback",
    "callback",
    "debug_callback",
    "debug_print",
}

DEFAULT_CONST_BUDGET = 1 << 16  # 64 KiB per embedded constant


@dataclass
class KernelSpec:
    name: str
    build: Callable  # () -> (fn, args, kwargs)
    allow_dtypes: frozenset = DEFAULT_ALLOWED_DTYPES
    allow_callbacks: tuple = ()
    const_budget_bytes: int = DEFAULT_CONST_BUDGET
    # per-spec memo of the build result and the first trace. Audits are
    # idempotent reads, so re-running one (the tier-1 gate plus the CLI in
    # a single process) must not re-pay builder or trace cost — this is
    # what keeps `make lint` wall-clock flat as the registry grows. A
    # single slot suffices: audit_kernel always builds/traces under the
    # forced-x64 tier, and the x64-off shard tier has its own specs
    # (sharing only the module-level shared_* input builders below).
    cache: dict = field(default_factory=dict)

    def built(self):
        """Builder output, memoised."""
        if "build" not in self.cache:
            self.cache["build"] = self.build()
        return self.cache["build"]


REGISTRY: dict[str, KernelSpec] = {}


def register_kernel(
    name: str,
    *,
    allow_dtypes=None,
    allow_callbacks=(),
    const_budget_bytes: int = DEFAULT_CONST_BUDGET,
):
    """Declare one kernel for auditing; the decorated builder returns
    ``(fn, example_args, example_kwargs)`` and runs lazily."""

    def deco(build: Callable) -> Callable:
        if name in REGISTRY:
            raise ValueError(f"duplicate kernel name {name!r}")
        REGISTRY[name] = KernelSpec(
            name=name,
            build=build,
            allow_dtypes=(
                DEFAULT_ALLOWED_DTYPES
                if allow_dtypes is None
                else frozenset(allow_dtypes)
            ),
            allow_callbacks=tuple(allow_callbacks),
            const_budget_bytes=const_budget_bytes,
        )
        return build

    return deco


def _iter_jaxprs(jaxpr):
    """The jaxpr and every sub-jaxpr reachable through eqn params."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for value in eqn.params.values():
            for sub in _as_jaxprs(value):
                yield from _iter_jaxprs(sub)


def _as_jaxprs(value):
    import jax.extend.core as jex_core

    if isinstance(value, jex_core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jex_core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _as_jaxprs(v)


def _iter_closed_consts(closed):
    """(const, owner) pairs for the closed jaxpr and nested closed jaxprs."""
    import jax.extend.core as jex_core

    for c in closed.consts:
        yield c
    for jaxpr in _iter_jaxprs(closed.jaxpr):
        for eqn in jaxpr.eqns:
            for value in eqn.params.values():
                stack = [value]
                while stack:
                    v = stack.pop()
                    if isinstance(v, jex_core.ClosedJaxpr):
                        for c in v.consts:
                            yield c
                    elif isinstance(v, (tuple, list)):
                        stack.extend(v)


def audit_kernel(spec: KernelSpec) -> list[Finding]:
    """Trace one registered kernel and check the four invariants."""
    import jax
    import numpy as np

    findings: list[Finding] = []

    def fail(check: str, message: str, hint: str = "") -> None:
        findings.append(
            Finding(rule=check, path=spec.name, line=0, message=message, hint=hint)
        )

    try:
        # Trace under x64 REGARDLESS of ambient config: unpinned
        # constructors only reveal themselves as int64/float64 when x64 is
        # on, so without this the CLI (`make lint`, x64 off) would pass a
        # kernel that the x64 test tier rejects.
        with jax.enable_x64(True):
            fn, args, kwargs = spec.built()
            # Each trace goes through a FRESH wrapper object AND the jit
            # trace caches are dropped in between: jax caches traces on
            # function identity (for jit-wrapped kernels even a fresh outer
            # lambda still hits pjit's cached inner jaxpr), so without both
            # steps the determinism check would compare a value with
            # itself. The FIRST trace is memoised on the spec (repeated
            # audits in one process — the tier-1 gate plus the CLI tests —
            # reuse it); the second is always fresh, so TA-HASH keeps
            # comparing two independently produced jaxprs.
            closed = spec.cache.get("trace")
            if closed is None:
                closed = spec.cache["trace"] = jax.make_jaxpr(
                    lambda *a, **k: fn(*a, **k)
                )(*args, **kwargs)
            jax.clear_caches()
            closed2 = jax.make_jaxpr(lambda *a, **k: fn(*a, **k))(
                *args, **kwargs
            )
    except Exception as e:  # noqa: BLE001 - any trace failure is a finding
        fail("TA-ERROR", f"kernel failed to trace: {type(e).__name__}: {e}")
        return findings

    # (a) embedded-constant budget
    for const in _iter_closed_consts(closed):
        arr = np.asarray(const) if hasattr(const, "shape") else None
        if arr is None:
            continue
        if arr.nbytes > spec.const_budget_bytes:
            fail(
                "TA-CONST",
                f"embedded constant {arr.shape} {arr.dtype} "
                f"({arr.nbytes} bytes) exceeds the "
                f"{spec.const_budget_bytes}-byte budget",
                "pass the array as an explicit argument instead of closing "
                "over it (it is serialised into every compile request)",
            )

    # (b) dtype-width audit and (c) callback allowlist, one jaxpr walk
    bad_dtypes: dict[str, set[str]] = {}
    for jaxpr in _iter_jaxprs(closed.jaxpr):
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim in _CALLBACK_PRIMS and prim not in spec.allow_callbacks:
                fail(
                    "TA-CALLBACK",
                    f"undeclared host callback primitive '{prim}' in the "
                    "compiled program",
                    "declare it in the kernel's allow_callbacks, or remove "
                    "the host round-trip",
                )
            for var in (*eqn.invars, *eqn.outvars):
                aval = getattr(var, "aval", None)
                dtype = getattr(aval, "dtype", None)
                if dtype is None:
                    continue  # tokens etc.
                if getattr(aval, "weak_type", False):
                    continue  # Python scalars adapt to their operands
                name = dtype.name
                if name not in spec.allow_dtypes:
                    bad_dtypes.setdefault(name, set()).add(prim)
    for name, prims in sorted(bad_dtypes.items()):
        shown = ", ".join(sorted(prims)[:6])
        fail(
            "TA-DTYPE",
            f"dtype {name} appears in the traced program (primitives: "
            f"{shown}) but is not in the kernel's allowed set "
            f"{sorted(spec.allow_dtypes)}",
            "pin the constructor/accumulator dtype (dtype=jnp.int32 / "
            "float32) or allowlist it for this kernel",
        )

    # (d) trace determinism. Callback primitives print their wrapper
    # object's repr (a fresh address per trace); normalise addresses away
    # so only STRUCTURAL differences — changed constants, reordered eqns —
    # fail the check.
    def jaxpr_hash(c):
        text = re.sub(r"0x[0-9a-f]+", "0x", str(c.jaxpr))
        return hashlib.sha256(text.encode()).hexdigest()

    h1 = jaxpr_hash(closed)
    h2 = jaxpr_hash(closed2)
    if h1 != h2:
        fail(
            "TA-HASH",
            f"two traces produced different jaxprs ({h1[:12]} vs {h2[:12]})",
            "remove trace-order nondeterminism (unordered dict/set "
            "iteration, per-call closures) from the kernel",
        )
    return findings


def run_audit(names=None) -> tuple[list[Finding], int]:
    """Audit the given kernels (default: all). Returns (findings, count)."""
    _ensure_default_registry()
    if names:
        unknown = [n for n in names if n not in REGISTRY]
        if unknown:
            raise KeyError(f"unknown kernel(s): {', '.join(unknown)}")
        specs = [REGISTRY[n] for n in names]
    else:
        specs = [REGISTRY[n] for n in sorted(REGISTRY)]
    findings: list[Finding] = []
    for spec in specs:
        findings.extend(audit_kernel(spec))
    return findings, len(specs)


# ---------------------------------------------------------------------------
# Shared example-input builders. Module level (not buried in the registry
# closure) and memoised, so the x64-on jaxpr tier here and the x64-off
# shard-audit tier (shard_audit.py) build the FS inputs and the gamma
# program ONCE per process: every dtype is pinned, so the abstract avals
# are identical across tiers and safe to share.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def shared_fs_inputs():
    """(G, params) example inputs for the EM-family kernels (pinned
    int8/float32 — x64-independent)."""
    import jax.numpy as jnp
    import numpy as np

    from ..models.fellegi_sunter import FSParams

    rng = np.random.default_rng(0)
    G = jnp.asarray(rng.integers(-1, 3, size=(128, 3)).astype(np.int8))
    params = FSParams(
        lam=jnp.float32(0.3),
        m=jnp.asarray(np.full((3, 3), 1.0 / 3, np.float32)),
        u=jnp.asarray(np.full((3, 3), 1.0 / 3, np.float32)),
    )
    return G, params


@functools.lru_cache(maxsize=1)
def shared_gamma_program():
    """One GammaProgram for the gamma-family specs across BOTH audit tiers
    (builders use it read-only; rebuilding costs encode_table + program
    construction each time)."""
    import jax.numpy as jnp
    import pandas as pd

    from ..data import encode_table
    from ..gammas import GammaProgram
    from ..settings import complete_settings_dict

    df = pd.DataFrame(
        {
            "unique_id": range(6),
            "name": ["martha", "marhta", "mx", None, "anna", "bob"],
            "city": ["x", "y", "x", "y", None, "x"],
            "amount": [1.0, 1.01, 5.0, None, 2.0, 3.0],
        }
    )
    settings = complete_settings_dict(
        {
            "link_type": "dedupe_only",
            "comparison_columns": [
                {"col_name": "name", "num_levels": 3},
                {
                    "col_name": "city",
                    "num_levels": 2,
                    "comparison": {"kind": "exact"},
                },
                {
                    "col_name": "amount",
                    "data_type": "numeric",
                    "num_levels": 3,
                    "comparison": {
                        "kind": "numeric_perc",
                        "thresholds": [0.01, 0.2],
                    },
                },
            ],
            "blocking_rules": ["l.unique_id = r.unique_id"],
        }
    )
    table = encode_table(df, settings)
    return GammaProgram(settings, table, float_dtype=jnp.float32)


# ---------------------------------------------------------------------------
# Default registry: the pipeline's hot kernels.
# ---------------------------------------------------------------------------

_defaults_registered = False


def _ensure_default_registry() -> None:
    global _defaults_registered
    if _defaults_registered:
        return
    _defaults_registered = True

    _fs_inputs = shared_fs_inputs

    # make_jaxpr would trace every argument, including the jit wrapper's
    # static ones — each builder therefore closes the statics into a lambda
    # and exposes ONLY the traced arguments

    @register_kernel("em_step")
    def _build_em_step():
        import jax.numpy as jnp

        from ..em import run_em

        G, params = _fs_inputs()
        fn = lambda G, p, tol: run_em(  # noqa: E731
            G,
            p,
            max_iterations=4,
            max_levels=3,
            em_convergence=tol,
            compute_ll=True,
        )
        return fn, (G, params, jnp.float32(1e-4)), {}

    # host_hook=True is the checkpoint path: exactly one declared
    # io_callback may cross to the host per update, nothing else
    @register_kernel("em_step_checkpointed", allow_callbacks=("io_callback",))
    def _build_em_step_hooked():
        import jax.numpy as jnp

        from ..em import run_em

        G, params = _fs_inputs()
        fn = lambda G, p, tol: run_em(  # noqa: E731
            G,
            p,
            max_iterations=4,
            max_levels=3,
            em_convergence=tol,
            compute_ll=True,
            host_hook=True,
        )
        return fn, (G, params, jnp.float32(1e-4)), {}

    # telemetry-enabled EM: when a sink is configured the linker routes the
    # fused loop through run_em_checkpointed(telemetry=...), which turns on
    # the SAME single sanctioned io_callback the checkpoint hook uses (the
    # EM convergence stream rides it; obs/runtime.py). This spec pins that
    # telemetry-ON adds exactly that callback and nothing else — and the
    # plain `em_step` spec above (empty allowlist) pins that telemetry-OFF
    # programs carry NO callback at all, i.e. telemetry is jaxpr-invisible
    # when disabled. compute_ll=False here (telemetry does not require it),
    # so both ll variants of the hooked program stay audited.
    @register_kernel("em_step_telemetry", allow_callbacks=("io_callback",))
    def _build_em_step_telemetry():
        import jax.numpy as jnp

        from ..em import run_em

        G, params = _fs_inputs()
        fn = lambda G, p, tol: run_em(  # noqa: E731
            G,
            p,
            max_iterations=4,
            max_levels=3,
            em_convergence=tol,
            compute_ll=False,
            host_hook=True,
        )
        return fn, (G, params, jnp.float32(1e-4)), {}

    @register_kernel("streamed_pass")
    def _build_streamed_pass():
        from ..parallel.streaming import _batch_stats

        G, params = _fs_inputs()
        fn = lambda G, p: _batch_stats(  # noqa: E731
            G, p, 3, None, True
        )
        return fn, (G, params), {}

    @register_kernel("score_pairs")
    def _build_score_pairs():
        from ..em import score_pairs

        G, params = _fs_inputs()
        return score_pairs, (G, params), {}

    _gamma_program = shared_gamma_program

    @register_kernel("gamma_batch")
    def _build_gamma_batch():
        import jax.numpy as jnp
        import numpy as np

        program = _gamma_program()
        il = jnp.asarray(np.zeros(256, np.int32))
        ir = jnp.asarray(np.ones(256, np.int32))
        # packed table as an explicit argument — the no-embedded-constant
        # design TA-CONST pins (a closure capture here would blow the budget
        # at real row counts)
        return program._gamma_batch_fn, (program._packed, il, ir), {}

    @register_kernel("pattern_kernel")
    def _build_pattern_kernel():
        import jax.numpy as jnp
        import numpy as np

        program = _gamma_program()
        il = jnp.asarray(np.zeros(256, np.int32))
        ir = jnp.asarray(np.ones(256, np.int32))
        acc = jnp.zeros(program.n_patterns + 1, jnp.int32)
        valid = jnp.int32(200)
        return program._pattern_kernel, (program._packed, il, ir, valid, acc), {}

    @register_kernel("virtual_pattern_kernel")
    def _build_virtual_pattern():
        import jax.numpy as jnp
        import numpy as np

        from ..pairgen import make_virtual_pattern_fn

        program = _gamma_program()
        bs = 128
        fn = make_virtual_pattern_fn(
            program, bs, n_prev=0, has_uid_mask=False
        )
        imax = np.int32(np.iinfo(np.int32).max)
        pos = jnp.arange(bs, dtype=jnp.int32)
        order = jnp.asarray(np.arange(6, dtype=np.int32))
        units = jnp.asarray(np.zeros(4, np.int32))
        lens = jnp.asarray(np.full(4, 3, np.int32))
        # meta row layout: [u0, valid, pc_rel... (power-of-two padded with
        # int32 max)] — values are irrelevant to the trace, shapes/dtypes
        # are what the audit checks
        meta = jnp.asarray(
            np.array([0, bs, 0, imax, imax, imax], np.int32)
        )
        acc = jnp.asarray(np.zeros(program.n_patterns + 1, np.int32))
        prev_codes = jnp.asarray(np.zeros((1, 6), np.int32))
        uid_codes = jnp.asarray(np.zeros(6, np.int32))
        return (
            fn,
            (
                pos,
                program._packed,
                order,
                units,
                lens,
                units,
                lens,
                prev_codes,
                uid_codes,
                (),
                meta,
                acc,
            ),
            {},
        )

    @register_kernel("jaro_winkler")
    def _build_jw():
        import jax.numpy as jnp
        import numpy as np

        from ..ops import strings

        rng = np.random.default_rng(0)
        s = jnp.asarray(rng.integers(97, 123, size=(64, 24)).astype(np.uint8))
        ln = jnp.asarray(np.full(64, 8, np.int32))
        return (
            strings.jaro_winkler_vmapped,
            (s, s, ln, ln, jnp.float32(0.1), jnp.float32(0.7)),
            {},
        )

    @register_kernel("levenshtein")
    def _build_lev():
        import jax.numpy as jnp
        import numpy as np

        from ..ops import strings

        rng = np.random.default_rng(0)
        s = jnp.asarray(rng.integers(97, 123, size=(64, 24)).astype(np.uint8))
        ln = jnp.asarray(np.full(64, 8, np.int32))
        return strings.levenshtein_ratio_vmapped, (s, s, ln, ln), {}

    @register_kernel("tf_adjustment")
    def _build_tf():
        import jax.numpy as jnp
        import numpy as np

        from ..term_frequencies import _device_token_stats_fn

        n_seg = 256
        tid = jnp.asarray(np.zeros(512, np.int32))
        p = jnp.zeros(512, jnp.float32)
        sums = jnp.zeros(n_seg, jnp.float32)
        counts = jnp.zeros(n_seg, jnp.float32)
        return _device_token_stats_fn(n_seg), (tid, tid, p, sums, counts), {}

    @register_kernel("tf_gather")
    def _build_tf_gather():
        import jax.numpy as jnp
        import numpy as np

        from ..term_frequencies import _device_token_gather_fn

        n_seg = 256
        tid = jnp.asarray(np.zeros(512, np.int32))
        adjusted = jnp.zeros(n_seg, jnp.float32)
        return _device_token_gather_fn(n_seg), (tid, tid, adjusted), {}

    # ----- online-serving hot path (splink_tpu/serve/engine.py) -----
    # The serving kernels run per REQUEST, so the x64 tier doubles as the
    # latency-hygiene gate: a dtype leak or embedded constant here costs
    # every query, not just one batch.

    @register_kernel("serve_encode_query")
    def _build_serve_encode():
        import jax.numpy as jnp
        import numpy as np

        from ..serve.engine import make_encode_query_fn

        packed = jnp.asarray(np.zeros((32, 8), np.uint32))
        qb = jnp.asarray(np.zeros((2, 32), np.int32))
        return make_encode_query_fn(), (packed, qb, jnp.int32(20)), {}

    @register_kernel("serve_candidate_gather")
    def _build_serve_gather():
        import jax.numpy as jnp
        import numpy as np

        from ..serve.engine import make_candidate_gather_fn

        fn = make_candidate_gather_fn(n_rules=2, capacity=16)
        qb = jnp.asarray(np.zeros((2, 32), np.int32))
        starts = tuple(jnp.asarray(np.zeros(4, np.int32)) for _ in range(2))
        sizes = tuple(jnp.asarray(np.ones(4, np.int32)) for _ in range(2))
        rows = tuple(jnp.asarray(np.zeros(8, np.int32)) for _ in range(2))
        row_bucket = tuple(
            jnp.asarray(np.zeros(6, np.int32)) for _ in range(2)
        )
        return fn, (qb, starts, sizes, rows, row_bucket), {}

    @register_kernel("serve_score_topk")
    def _build_serve_score():
        import jax.numpy as jnp
        import numpy as np

        from ..serve.engine import make_score_topk_fn

        program = _gamma_program()
        _, params = _fs_inputs()
        fn = make_score_topk_fn(
            program._layout, program.settings["comparison_columns"], k=4
        )
        packed_q = jnp.asarray(np.zeros((16, program._packed.shape[1]),
                                        np.uint32))
        cand = jnp.asarray(np.zeros((16, 8), np.int32))
        valid = jnp.asarray(np.zeros((16, 8), bool))
        # the packed reference table as an explicit argument — the same
        # no-embedded-constant design TA-CONST pins for gamma_batch
        return fn, (packed_q, program._packed, cand, valid, params), {}

    # the fused gamma→score→top-k megakernel (engine default): same
    # contract as serve_score_topk — per-comparison gammas fold into the
    # running log-Bayes-factor instead of stacking the full gamma matrix,
    # bit-identical outputs (parity-gated) with fewer HBM round-trips
    # (SA-COST pins the bytes reduction in the shard tier)
    @register_kernel("serve_score_fused")
    def _build_serve_score_fused():
        import jax.numpy as jnp
        import numpy as np

        from ..serve.engine import make_score_fused_fn

        program = _gamma_program()
        _, params = _fs_inputs()
        fn = make_score_fused_fn(
            program._layout, program.settings["comparison_columns"], k=4
        )
        packed_q = jnp.asarray(np.zeros((16, program._packed.shape[1]),
                                        np.uint32))
        cand = jnp.asarray(np.zeros((16, 8), np.int32))
        valid = jnp.asarray(np.zeros((16, 8), bool))
        return fn, (packed_q, program._packed, cand, valid, params), {}

    # the TF-fold variant of the fused megakernel (serve_tf_adjust): the
    # default serving path for TF-flagged models — one extra reference-
    # token-id gather + log-table lookup per TF column folds the
    # u-probability adjustment into the running log-Bayes-factor. Gated
    # exactly like the base fused kernel (it runs per request) — the
    # forced-x64 tier catches any unpinned dtype in the fold arithmetic.
    @register_kernel("serve_score_fused_tf")
    def _build_serve_score_fused_tf():
        import jax.numpy as jnp
        import numpy as np

        from ..serve.engine import make_score_fused_fn

        program = _gamma_program()
        _, params = _fs_inputs()
        # fold the exact "city" comparison (index 1, 2 levels -> top 1)
        fn = make_score_fused_fn(
            program._layout, program.settings["comparison_columns"], k=4,
            tf_spec=((1, "city", 1),),
        )
        packed_q = jnp.asarray(np.zeros((16, program._packed.shape[1]),
                                        np.uint32))
        cand = jnp.asarray(np.zeros((16, 8), np.int32))
        valid = jnp.asarray(np.zeros((16, 8), bool))
        n_ref = program._packed.shape[0]
        tf_q = (jnp.asarray(np.zeros(16, np.int32)),)
        tf_tid = (jnp.asarray(np.zeros(n_ref, np.int32)),)
        tf_log = (jnp.asarray(np.full(4, -1.0, np.float32)),)
        return (
            fn,
            (packed_q, program._packed, cand, valid, params,
             tf_q, tf_tid, tf_log),
            {},
        )

    # ----- device-native blocking (splink_tpu/blocking_device.py) -----
    # These kernels sit on the TRAINING-time hot path (candidate
    # generation for every materialised-pair run), so they are gated like
    # the gamma kernels: pinned int32 widths (the x64 tier catches any
    # constructor deriving width from ambient config), no embedded plan
    # arrays, no host callbacks, deterministic traces.

    @register_kernel("block_segment_sort")
    def _build_block_segment_sort():
        import jax.numpy as jnp
        import numpy as np

        from ..blocking_device import make_segment_sort_fn

        fn = make_segment_sort_fn()
        rng = np.random.default_rng(0)
        codes = jnp.asarray(
            rng.integers(-1, 5, size=32).astype(np.int32)
        )
        side = jnp.asarray((np.arange(32) % 2).astype(np.int32))
        rank = jnp.asarray(np.arange(32, dtype=np.int32))
        row = jnp.asarray(np.arange(32, dtype=np.int32))
        return fn, (codes, side, rank, row), {}

    @register_kernel("block_bucket_csr")
    def _build_block_bucket_csr():
        import jax.numpy as jnp
        import numpy as np

        from ..blocking_device import make_bucket_csr_fn

        fn = make_bucket_csr_fn()
        rng = np.random.default_rng(0)
        codes = jnp.asarray(
            rng.integers(-1, 5, size=32).astype(np.int32)
        )
        return fn, (codes,), {}

    @register_kernel("block_pair_emit")
    def _build_block_pair_emit():
        import jax.numpy as jnp
        import numpy as np

        from ..blocking_device import make_pair_emit_fn

        bs = 64
        fn = make_pair_emit_fn(
            bs, n_prev=1, has_uid_mask=True, rank_filter=True
        )
        imax = np.int32(np.iinfo(np.int32).max)
        pos = jnp.arange(bs, dtype=jnp.int32)
        order = jnp.asarray(np.arange(8, dtype=np.int32))
        units = jnp.asarray(np.zeros(4, np.int32))
        lens = jnp.asarray(np.full(4, 3, np.int32))
        ranks = jnp.asarray(np.arange(8, dtype=np.int32))
        prev_l = jnp.asarray(np.zeros((1, 8), np.int32))
        prev_r = jnp.asarray(np.zeros((1, 8), np.int32))
        uid = jnp.asarray(np.zeros(8, np.int32))
        # meta row layout: [u0, valid, pc_rel... (power-of-two padded with
        # int32 max)] — values are irrelevant to the trace, shapes/dtypes
        # are what the audit checks
        meta = jnp.asarray(
            np.array([0, bs, 0, imax, imax, imax], np.int32)
        )
        return (
            fn,
            (pos, order, units, lens, units, lens, ranks, prev_l, prev_r,
             uid, (), meta),
            {},
        )

    @register_kernel("spill_chunk_digest")
    def _build_spill_chunk_digest():
        import jax.numpy as jnp
        import numpy as np

        from ..blocking_device import make_chunk_digest_fn

        fn = make_chunk_digest_fn()
        rng = np.random.default_rng(0)
        i = jnp.asarray(rng.integers(0, 64, size=64).astype(np.int32))
        j = jnp.asarray(rng.integers(0, 64, size=64).astype(np.int32))
        keep = jnp.asarray(rng.integers(0, 2, size=64).astype(bool))
        return fn, (i, j, keep), {}

    @register_kernel("spill_chunk_digest_compact")
    def _build_spill_chunk_digest_compact():
        import jax.numpy as jnp
        import numpy as np

        from ..blocking_device import make_chunk_digest_compact_fn

        fn = make_chunk_digest_compact_fn()
        rng = np.random.default_rng(0)
        i_ext = jnp.asarray(
            np.concatenate(
                [rng.integers(0, 64, size=64), [37]]
            ).astype(np.int32)
        )
        j = jnp.asarray(rng.integers(0, 64, size=64).astype(np.int32))
        pos = jnp.arange(64, dtype=jnp.int32)
        return fn, (i_ext, j, pos), {}

    # ----- approximate blocking (splink_tpu/approx/) -----
    # The minhash-signature and LSH-verification kernels run over every
    # record / every candidate pair of an approx-tier run (and the minhash
    # kernel again per serve fallback batch), so they are gated like the
    # blocking kernels: pinned uint32/int32 widths under the forced-x64
    # trace, no embedded hash-parameter constants, no callbacks,
    # deterministic traces.

    @register_kernel("approx_minhash")
    def _build_approx_minhash():
        import jax.numpy as jnp
        import numpy as np

        from ..approx.minhash import (
            column_salts,
            hash_params,
            make_minhash_fn,
        )

        fn = make_minhash_fn(2, 4, 2, ((12, "ascii"),))
        rng = np.random.default_rng(0)
        bytes_ = jnp.asarray(
            rng.integers(97, 123, size=(16, 12)).astype(np.uint8)
        )
        lens = jnp.asarray(np.full(16, 8, np.int32))
        a, b = hash_params(8)
        salts = column_salts(1)
        return (
            fn,
            (bytes_, lens, jnp.asarray(a), jnp.asarray(b),
             jnp.asarray(salts)),
            {},
        )

    @register_kernel("approx_verify")
    def _build_approx_verify():
        import jax.numpy as jnp
        import numpy as np

        from ..approx.lsh import make_verify_fn

        fn = make_verify_fn(2, 4, ((12, "ascii"),), True)
        rng = np.random.default_rng(0)
        i = jnp.asarray(np.zeros(32, np.int32))
        j = jnp.asarray(np.ones(32, np.int32))
        band_codes = jnp.asarray(
            rng.integers(-1, 4, size=(4, 16)).astype(np.int32)
        )
        bytes_ = jnp.asarray(
            rng.integers(97, 123, size=(16, 12)).astype(np.uint8)
        )
        lens = jnp.asarray(np.full(16, 8, np.int32))
        mask = jnp.asarray(np.zeros((16, 1), np.uint32))
        count = jnp.asarray(np.full(16, 7, np.int32))
        return fn, (i, j, band_codes, bytes_, lens, mask, count), {}

    # the TF-WEIGHTED minhash sampler (approx_tf_weighting): exponential-
    # race weighted sampling — one IDF gather per gram, f32 race values,
    # winning-gram identity as the signature lane. Same gating as the
    # unweighted kernel (it runs over every record and per serve
    # fallback batch).
    @register_kernel("approx_minhash_weighted")
    def _build_approx_minhash_weighted():
        import jax.numpy as jnp
        import numpy as np

        from ..approx.minhash import (
            DF_TABLE_SIZE,
            column_salts,
            hash_params,
            make_minhash_fn,
        )

        fn = make_minhash_fn(2, 4, 2, ((12, "ascii"),), weighted=True)
        rng = np.random.default_rng(0)
        bytes_ = jnp.asarray(
            rng.integers(97, 123, size=(16, 12)).astype(np.uint8)
        )
        lens = jnp.asarray(np.full(16, 8, np.int32))
        a, b = hash_params(8)
        salts = column_salts(1)
        idf = jnp.asarray(np.ones(DF_TABLE_SIZE, np.float32))
        return (
            fn,
            (bytes_, lens, jnp.asarray(a), jnp.asarray(b),
             jnp.asarray(salts), idf),
            {},
        )

    # the TF-WEIGHTED verify kernel (approx_tf_weighting + threshold):
    # IDF-weighted q-gram Jaccard — sum of gram weights over the
    # intersection / union of the distinct-gram sets, weights gathered at
    # the shared gram hash. Ranks the progressive emission, so it runs
    # over every surviving candidate pair.
    @register_kernel("approx_verify_weighted")
    def _build_approx_verify_weighted():
        import jax.numpy as jnp
        import numpy as np

        from ..approx.lsh import make_verify_fn
        from ..approx.minhash import DF_TABLE_SIZE

        fn = make_verify_fn(2, 4, ((12, "ascii"),), True, weighted=True)
        rng = np.random.default_rng(0)
        i = jnp.asarray(np.zeros(32, np.int32))
        j = jnp.asarray(np.ones(32, np.int32))
        band_codes = jnp.asarray(
            rng.integers(-1, 4, size=(4, 16)).astype(np.int32)
        )
        bytes_ = jnp.asarray(
            rng.integers(97, 123, size=(16, 12)).astype(np.uint8)
        )
        lens = jnp.asarray(np.full(16, 8, np.int32))
        mask = jnp.asarray(np.zeros((16, 1), np.uint32))
        count = jnp.asarray(np.full(16, 7, np.int32))
        idf = jnp.asarray(np.ones(DF_TABLE_SIZE, np.float32))
        return (
            fn, (i, j, band_codes, bytes_, lens, mask, count, idf), {}
        )

    # the brown-out tier's budgeted twin (engine kind="brownout"): same
    # factory, reduced top-k over a small candidate capacity — the shape
    # the service dispatches under pressure, so it is gated like the
    # full-service program (it runs per degraded request). Not registered
    # in the shard tier: brown-out batches are single-device by design
    # (the cheapest shape combination, not a sharded one).
    @register_kernel("serve_score_topk_brownout")
    def _build_serve_score_brownout():
        import jax.numpy as jnp
        import numpy as np

        from ..serve.engine import make_score_topk_fn

        program = _gamma_program()
        _, params = _fs_inputs()
        fn = make_score_topk_fn(
            program._layout, program.settings["comparison_columns"], k=1
        )
        packed_q = jnp.asarray(np.zeros((16, program._packed.shape[1]),
                                        np.uint32))
        cand = jnp.asarray(np.zeros((16, 4), np.int32))
        valid = jnp.asarray(np.zeros((16, 4), bool))
        return fn, (packed_q, program._packed, cand, valid, params), {}

    # ----- linkage quality observatory (splink_tpu/obs/quality.py,
    #       obs/drift.py) -----
    # The profile kernel runs once per build_index over every training
    # gamma chunk; the sketch kernel runs per SERVED BATCH, folded onto
    # the fused megakernel's outputs — a dtype leak or embedded constant
    # there costs every request, and any host callback would break the
    # zero-extra-sync contract the drift-smoke gates. Both follow the
    # pattern-kernel int32 scatter-add histogram protocol.

    @register_kernel("quality_profile")
    def _build_quality_profile():
        from ..obs.quality import make_profile_fn

        G, params = _fs_inputs()
        fn = make_profile_fn((3, 3, 3), bins=8)
        return fn, (G, params), {}

    @register_kernel("serve_drift_sketch")
    def _build_serve_drift_sketch():
        import jax.numpy as jnp
        import numpy as np

        from ..obs.drift import make_sketch_fn

        program = _gamma_program()
        _, params = _fs_inputs()
        cols = program.settings["comparison_columns"]
        bins = 8
        width = max(int(c["num_levels"]) for c in cols) + 1
        size = len(cols) * width + 2 * bins
        fn = make_sketch_fn(program._layout, cols, bins)
        acc = jnp.asarray(np.zeros(size, np.int32))
        packed_q = jnp.asarray(np.zeros((16, program._packed.shape[1]),
                                        np.uint32))
        top_rows = jnp.asarray(np.zeros((16, 4), np.int32))
        top_valid = jnp.asarray(np.zeros((16, 4), bool))
        top_p = jnp.asarray(np.zeros((16, 4), np.float32))
        return (
            fn,
            (acc, packed_q, program._packed, top_rows, top_valid, top_p),
            {},
        )
