"""perf_audit: measured per-kernel runtime/memory baselines (layer 4).

Layers 1-3 are STATIC: they pin what the source says (jaxlint), what the
compiler will run on one device (trace_audit) and what GSPMD will run on a
mesh (shard_audit) — structure and *analytical* cost, never a measured
clock. A change that doubles a kernel's execute time without touching its
jaxpr (a fusion the compiler stopped doing, a layout change, an
accidentally-serialised scatter) ships silently through all three. This
layer closes that hole: every kernel in the layer-2 registry is **compiled
and executed** with its fixed-seed example inputs at one to three
registered shapes, and three measured metrics are compared against
committed per-``(tier, kernel, shape)`` baselines
(``perf_baselines.json``):

  PA-TIME   compile wall (one fresh ``lower().compile()``, trace + lower +
            backend compile) and execute wall (best-of-N
            ``block_until_ready`` over the compiled executable) must not
            regress past the per-metric tolerance band. Runtime is noisy —
            especially on a shared 2-core CI container — so the gate is
            ONE-SIDED (only slower fires; faster is an improvement to
            fold in with ``make perf-baselines``) and protected by a
            noise-floor guard: a kernel must still exceed its band on the
            MEDIAN of K interleaved re-measurements before the finding
            fires, so a single scheduler hiccup cannot flap CI.
  PA-MEM    deterministic per-executable memory from XLA's
            ``memory_analysis()`` (argument/output/temp bytes — the same
            client query SA-COST uses, here at the perf shapes) plus, on
            backends that report ``memory_stats`` (TPU/GPU — the PR 3
            machinery), the measured peak-device-bytes delta across the
            execute. Deterministic bytes gate tightly; the measured peak
            gates loosely and only when both sides recorded it (CPU
            records null).
  PA-BASE   the kernel/shape has no committed baseline for this tier —
            generate one with ``make perf-baselines`` and review the JSON
            diff like a bench result.
  PA-ERROR  the kernel failed to compile or execute at a perf shape.

Baselines are keyed by **tier** (``jax.default_backend()``), because CPU
numbers predict nothing about the accelerator regime (HyperBlocker's
point: rule-based blocking is accelerator-native); hardware bring-up adds
a ``tpu``/``gpu`` block beside ``cpu`` rather than overwriting it, and the
audit only ever gates against the tier it is running on.

Shapes: every registered kernel is measured at its layer-2 registered
shape (label ``reg``); kernels in :data:`PERF_SCALES` additionally run at
tiled batch sizes (labels ``x4``/``x16``...) — the batch-axis arrays of
the example inputs are tiled, lookup tables and parameters are untouched —
so a regression that only appears past the tiny audit shapes (a serialised
scatter, an O(n^2) fallback) is still caught. Measurement forces x64 OFF
(the production program width, mirroring shard_audit) so the x64 test tier
and the CLI measure the identical executable.

Refreshing baselines intentionally (new kernel, accepted perf change)::

    make perf-baselines     # python -m splink_tpu.analysis --perf-audit
                            #        --update-perf-baselines

The runtime half of the performance observatory — serve-time regression
alerting over the SAME execute signal — lives in
:mod:`splink_tpu.obs.kernelwatch` (docs/observability.md#perf).
"""

from __future__ import annotations

import json
import os
import statistics
import time
from dataclasses import dataclass

from .findings import Finding

BASELINES_PATH = os.path.join(os.path.dirname(__file__), "perf_baselines.json")

#: best-of-N execute repeats per measurement (min = the least-noise sample)
DEFAULT_BEST_OF = 5

#: noise-floor guard: a metric over its band is re-measured this many times
#: and must regress on the MEDIAN before PA-TIME fires
DEFAULT_REMEASURE = 5

#: one-sided tolerance bands (relative) + absolute floors. The floors keep
#: micro-kernels honest: a 0.1ms kernel jittering to 0.25ms on a loaded
#: container is scheduler noise, not a regression — but a 10ms kernel
#: drifting to 25ms fires long before the floor matters.
EXECUTE_RTOL = 1.0  # fire past 2x the committed execute wall
EXECUTE_ATOL_MS = 1.0
COMPILE_RTOL = 1.0  # compile time: trace+lower+backend, equally noisy
COMPILE_ATOL_MS = 500.0
MEM_RTOL = 0.25  # deterministic memory_analysis bytes (the SA-COST band)
DEVICE_MEM_RTOL = 0.5  # measured peak device delta (runtime, loose)

#: metrics measured per (tier, kernel, shape). ``*_ms`` are runtime
#: (one-sided + noise guard); ``*_bytes`` are deterministic per-executable
#: estimates; ``peak_device_bytes`` is the measured peak delta (null on
#: backends without memory_stats — the CPU tier).
TIME_KEYS = ("compile_ms", "execute_ms")
MEM_KEYS = ("argument_bytes", "output_bytes", "temp_bytes")

#: kernels measured at scaled batch shapes beyond the registered one:
#: name -> (base batch length of the registered example inputs, scale
#: factors). The batch axis is tiled; every other array (packed tables,
#: parameters, histograms, hash constants) keeps its registered shape.
#: Only arrays whose LEADING axis equals the base length tile — the
#: builders keep batch lengths distinct from table lengths exactly so
#: this stays unambiguous.
PERF_SCALES: dict[str, tuple[int, tuple[int, ...]]] = {
    "em_step": (128, (8, 32)),
    "streamed_pass": (128, (8, 32)),
    "score_pairs": (128, (8, 32)),
    "gamma_batch": (256, (4, 16)),
    "pattern_kernel": (256, (4, 16)),
    "jaro_winkler": (64, (4, 16)),
    "levenshtein": (64, (4,)),
    "tf_adjustment": (512, (4,)),
    "tf_gather": (512, (4,)),
    "serve_score_topk": (16, (4,)),
    "serve_score_fused": (16, (4, 16)),
    "approx_minhash": (16, (4,)),
    "approx_verify": (32, (4,)),
    "quality_profile": (128, (8,)),
    "serve_drift_sketch": (16, (4,)),
}

#: layer-2 kernels excluded from the perf tier, with the reason rendered
#: by ``--list-perf-kernels``. The audit EXECUTES kernels; the host-hook
#: EM twins carry an io_callback wired to the linker's checkpoint/telemetry
#: plumbing, which does not exist in the audit process — their compiled
#: loop bodies are the `em_step` program plus the callback, so the plain
#: twin carries the perf signal.
PERF_EXCLUDED: dict[str, str] = {
    "em_step_checkpointed": "io_callback host hook needs linker plumbing; "
    "em_step measures the same loop",
    "em_step_telemetry": "io_callback host hook needs linker plumbing; "
    "em_step measures the same loop",
}


@dataclass
class PerfShape:
    """One measured (kernel, shape) cell."""

    kernel: str
    label: str  # "reg" or "x<factor>"
    factor: int  # 1 for the registered shape


def perf_plan(names=None) -> list[PerfShape]:
    """The measurement plan over the layer-2 registry: every non-excluded
    kernel at its registered shape, plus the :data:`PERF_SCALES` tilings.
    Importing the plan builds no inputs and touches no backend — the
    ``--list-perf-kernels`` path `make lint` runs."""
    from .trace_audit import REGISTRY, _ensure_default_registry

    _ensure_default_registry()
    if names:
        unknown = [n for n in names if n not in REGISTRY]
        if unknown:
            raise KeyError(f"unknown kernel(s): {', '.join(unknown)}")
        kernels = list(names)
    else:
        kernels = [n for n in sorted(REGISTRY) if n not in PERF_EXCLUDED]
    plan: list[PerfShape] = []
    for name in kernels:
        plan.append(PerfShape(name, "reg", 1))
        base_scales = PERF_SCALES.get(name)
        if base_scales:
            for f in base_scales[1]:
                plan.append(PerfShape(name, f"x{f}", f))
    return plan


def format_plan(plan: list[PerfShape]) -> str:
    """The ``--list-perf-kernels`` listing: kernels, shapes, exclusions."""
    by_kernel: dict[str, list[str]] = {}
    for cell in plan:
        by_kernel.setdefault(cell.kernel, []).append(cell.label)
    lines = [
        f"{len(by_kernel)} kernel(s), {len(plan)} measured shape(s) "
        f"[tier-keyed baselines: {os.path.basename(BASELINES_PATH)}]"
    ]
    for name, labels in by_kernel.items():
        lines.append(f"  {name:<28}{' '.join(labels)}")
    for name, reason in sorted(PERF_EXCLUDED.items()):
        lines.append(f"  {name:<28}(excluded: {reason})")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Input scaling
# ---------------------------------------------------------------------------


def _tile_leaf(leaf, factor: int, base_n: int):
    import numpy as np

    if not hasattr(leaf, "shape") or not getattr(leaf, "ndim", 0):
        return leaf
    if leaf.shape[0] != base_n:
        return leaf
    import jax.numpy as jnp

    arr = np.asarray(leaf)
    reps = (factor,) + (1,) * (arr.ndim - 1)
    return jnp.asarray(np.tile(arr, reps))


def _scaled_args(name: str, args, kwargs, factor: int):
    """Tile the batch-axis arrays of one kernel's example inputs."""
    import jax

    if factor == 1:
        return args, kwargs
    base_n = PERF_SCALES[name][0]
    return jax.tree.map(
        lambda leaf: _tile_leaf(leaf, factor, base_n), (args, kwargs)
    )


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------


def _peak_device_bytes() -> int | None:
    """Max ``peak_bytes_in_use`` across local devices, or None where the
    backend reports no memory_stats (CPU) — the PR 3 snapshot machinery."""
    from ..obs.metrics import device_memory_snapshot

    devices = device_memory_snapshot()
    peaks = [d.get("peak_bytes_in_use") or 0 for d in devices]
    return max(peaks) if peaks else None


def _compile_cell(name: str, factor: int):
    """(compiled, args, kwargs, compile_ms) for one plan cell — a FRESH
    trace+lower+compile (jit caches cleared first, so repeated audits in
    one process still measure a real compile, not a cache lookup)."""
    import jax

    from .trace_audit import REGISTRY

    spec = REGISTRY[name]
    fn, args, kwargs = spec.built()
    args, kwargs = _scaled_args(name, args, kwargs, factor)
    jax.clear_caches()
    jfn = jax.jit(lambda *a, **k: fn(*a, **k))
    t0 = time.perf_counter()
    compiled = jfn.lower(*args, **kwargs).compile()
    compile_ms = (time.perf_counter() - t0) * 1e3
    return compiled, args, kwargs, compile_ms


def _execute_best_of(compiled, args, kwargs, best_of: int) -> float:
    """Best-of-N execute wall (ms) over the compiled executable; one
    unmeasured warm-up dispatch first so allocator/first-touch costs never
    land in the timed window."""
    import jax

    jax.block_until_ready(compiled(*args, **kwargs))
    best = float("inf")
    for _ in range(max(best_of, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args, **kwargs))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def measure_cell(
    cell: PerfShape, best_of: int = DEFAULT_BEST_OF
) -> dict:
    """The committed-baseline record for one (kernel, shape): measured
    compile/execute wall, deterministic memory_analysis bytes, peak device
    delta (null without memory_stats). Forces x64 OFF — the production
    program width — regardless of ambient config."""
    import jax

    with jax.enable_x64(False):
        peak0 = _peak_device_bytes()
        compiled, args, kwargs, compile_ms = _compile_cell(
            cell.kernel, cell.factor
        )
        record: dict = {"compile_ms": round(compile_ms, 3)}
        try:
            ma = compiled.memory_analysis()
        except Exception:  # noqa: BLE001 - optional per backend
            ma = None
        for key, attr in (
            ("argument_bytes", "argument_size_in_bytes"),
            ("output_bytes", "output_size_in_bytes"),
            ("temp_bytes", "temp_size_in_bytes"),
        ):
            val = getattr(ma, attr, None) if ma is not None else None
            if val is not None:
                record[key] = float(val)
        record["execute_ms"] = round(
            _execute_best_of(compiled, args, kwargs, best_of), 4
        )
        peak1 = _peak_device_bytes()
        record["peak_device_bytes"] = (
            max(peak1 - (peak0 or 0), 0)
            if peak1 is not None
            else None
        )
    return record


def _remeasure_execute(cell: PerfShape, k: int, best_of: int) -> float:
    """Median of K fresh best-of-N execute measurements (the PA-TIME noise
    guard). Re-uses one compile; the K re-runs interleave real time so a
    transient CPU spike cannot dominate every sample."""
    import jax

    with jax.enable_x64(False):
        compiled, args, kwargs, _ = _compile_cell(cell.kernel, cell.factor)
        samples = [
            _execute_best_of(compiled, args, kwargs, best_of)
            for _ in range(max(k, 1))
        ]
    return statistics.median(samples)


def _remeasure_compile(cell: PerfShape, k: int) -> float:
    """Median of K fresh compile measurements (the PA-TIME noise guard on
    the compile metric)."""
    import jax

    samples = []
    with jax.enable_x64(False):
        for _ in range(max(k, 1)):
            *_rest, compile_ms = _compile_cell(cell.kernel, cell.factor)
            samples.append(compile_ms)
    return statistics.median(samples)


# ---------------------------------------------------------------------------
# Audit
# ---------------------------------------------------------------------------


def _over_band(want: float, got: float, rtol: float, atol: float) -> bool:
    """One-sided: fires only when the measurement regressed past BOTH the
    relative band and the absolute floor."""
    return got > want * (1.0 + rtol) and got - want > atol


def _drift_msg(metric: str, want: float, got: float, rtol: float) -> str:
    rel = (got - want) / max(abs(want), 1e-12)
    return (
        f"{metric}: baseline {want:.3f}, measured {got:.3f} "
        f"(+{rel * 100:.0f}% > +{rtol * 100:.0f}% tolerance)"
    )


def audit_cell(
    cell: PerfShape,
    baseline: dict | None,
    *,
    best_of: int = DEFAULT_BEST_OF,
    remeasure: int = DEFAULT_REMEASURE,
) -> list[Finding]:
    """Measure one (kernel, shape) and compare against its committed
    baseline with the PA-* bands (module docstring)."""
    findings: list[Finding] = []
    where = f"{cell.kernel}@{cell.label}"

    def fail(check: str, message: str, hint: str = "") -> None:
        findings.append(
            Finding(rule=check, path=where, line=0, message=message,
                    hint=hint)
        )

    try:
        measured = measure_cell(cell, best_of=best_of)
    except Exception as e:  # noqa: BLE001 - any perf-shape failure is a finding
        fail(
            "PA-ERROR",
            f"kernel failed to compile/execute at the perf shape: "
            f"{type(e).__name__}: {e}",
        )
        return findings
    if baseline is None:
        fail(
            "PA-BASE",
            "no committed perf baseline for this (tier, kernel, shape)",
            "generate one with `make perf-baselines` and commit "
            "perf_baselines.json",
        )
        return findings

    refresh = "if the change is intended, refresh with `make perf-baselines`"
    # PA-TIME: runtime metrics, one-sided + median-of-K noise guard
    for metric, rtol, atol, remeasure_fn in (
        ("execute_ms", EXECUTE_RTOL, EXECUTE_ATOL_MS,
         lambda: _remeasure_execute(cell, remeasure, best_of)),
        ("compile_ms", COMPILE_RTOL, COMPILE_ATOL_MS,
         lambda: _remeasure_compile(cell, remeasure)),
    ):
        want = baseline.get(metric)
        got = measured.get(metric)
        if want is None or got is None:
            continue
        if _over_band(float(want), float(got), rtol, atol):
            median = remeasure_fn()
            if _over_band(float(want), float(median), rtol, atol):
                fail(
                    "PA-TIME",
                    _drift_msg(metric, float(want), float(median), rtol)
                    + f" [median of {remeasure} re-runs; first "
                    f"measurement {float(got):.3f}]",
                    "a measured runtime regression on this kernel; " + refresh,
                )
    # PA-MEM: deterministic per-executable bytes, tight band, no re-measure
    for metric in MEM_KEYS:
        want = baseline.get(metric)
        got = measured.get(metric)
        if want is None or got is None:
            continue
        if _over_band(float(want), float(got), MEM_RTOL, 0.0):
            fail(
                "PA-MEM",
                _drift_msg(metric, float(want), float(got), MEM_RTOL),
                "the executable's memory footprint grew; " + refresh,
            )
    # PA-MEM: measured peak device delta — only when BOTH sides recorded
    # it (backends without memory_stats record null)
    want = baseline.get("peak_device_bytes")
    got = measured.get("peak_device_bytes")
    if want is not None and got is not None and float(want) > 0:
        if _over_band(float(want), float(got), DEVICE_MEM_RTOL, 0.0):
            fail(
                "PA-MEM",
                _drift_msg(
                    "peak_device_bytes", float(want), float(got),
                    DEVICE_MEM_RTOL,
                ),
                "the measured device high-water mark grew; " + refresh,
            )
    return findings


# ---------------------------------------------------------------------------
# Driver + baselines
# ---------------------------------------------------------------------------


def current_tier() -> str:
    """The baseline tier key: the backend the measurement runs on."""
    import jax

    return jax.default_backend()


def load_baselines(path: str = BASELINES_PATH) -> dict:
    if not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def run_perf_audit(
    names=None,
    baselines: dict | None = None,
    *,
    best_of: int = DEFAULT_BEST_OF,
    remeasure: int = DEFAULT_REMEASURE,
) -> tuple[list[Finding], int]:
    """Audit the given kernels (default: the full perf plan) against the
    committed baselines for the CURRENT tier. Returns (findings, number of
    measured shapes)."""
    plan = perf_plan(names)
    if baselines is None:
        baselines = load_baselines()
    tier = current_tier()
    per_kernel = (
        baselines.get("tiers", {}).get(tier, {}).get("kernels", {})
    )
    findings: list[Finding] = []
    for cell in plan:
        base = per_kernel.get(cell.kernel, {}).get(cell.label)
        findings.extend(
            audit_cell(cell, base, best_of=best_of, remeasure=remeasure)
        )
    return findings, len(plan)


def update_baselines(
    names=None,
    path: str = BASELINES_PATH,
    *,
    best_of: int = DEFAULT_BEST_OF,
) -> dict:
    """Re-measure the perf plan and write the committed baseline file for
    the CURRENT tier (other tiers' blocks are preserved — hardware
    bring-up adds a tpu/gpu block beside cpu). A full refresh (no names)
    rebuilds this tier's block from the plan alone, pruning dead entries;
    a named refresh merges. Returns the new baselines dict."""
    import jax

    plan = perf_plan(names)
    existing = load_baselines(path)
    tiers = dict(existing.get("tiers", {}))
    tier = current_tier()
    kernels: dict[str, dict] = (
        {k: dict(v) for k, v in tiers.get(tier, {}).get("kernels", {}).items()}
        if names
        else {}
    )
    for cell in plan:
        kernels.setdefault(cell.kernel, {})[cell.label] = measure_cell(
            cell, best_of=best_of
        )
    tiers[tier] = {
        "device": str(jax.devices()[0]),
        "kernels": {
            k: {s: kernels[k][s] for s in sorted(kernels[k])}
            for k in sorted(kernels)
        },
    }
    new = {
        "_meta": {
            "jax": jax.__version__,
            "best_of": best_of,
            "refresh": "make perf-baselines",
            "bands": {
                "execute_ms": f"+{EXECUTE_RTOL * 100:.0f}% "
                f"(floor {EXECUTE_ATOL_MS}ms, median-of-"
                f"{DEFAULT_REMEASURE} guard)",
                "compile_ms": f"+{COMPILE_RTOL * 100:.0f}% "
                f"(floor {COMPILE_ATOL_MS}ms)",
                "memory_bytes": f"+{MEM_RTOL * 100:.0f}%",
            },
        },
        "tiers": {t: tiers[t] for t in sorted(tiers)},
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(new, fh, indent=2, sort_keys=True)
        fh.write("\n")
    return new
