"""shard_audit: SPMD partition-safety audit of the sharded kernels (layer 3).

Layers 1 and 2 check what the source says and what the compiler will run on
ONE device. This layer checks what GSPMD will run on a MESH: every kernel in
the shard registry is lowered under a forced multi-device host mesh (the
same 8 virtual CPU devices the test tier pins via
``--xla_force_host_platform_device_count=8``) with the production shardings
from :mod:`splink_tpu.parallel.mesh`, and four invariants are asserted
against the compiled SPMD program:

  SA-SPEC   every input/output leaf whose leading axis is the pair axis
            carries the pair sharding (PartitionSpec over ``mesh.DATA_AXIS``)
            and everything else is replicated — no accidental full
            replication of an ``(n_pairs, ...)`` array, which at scale turns
            a sharded run into eight copies of the single-device one.
  SA-COLL   an exact per-kernel collective budget, measured from the
            optimised HLO: the EM stats reductions contain their known psums
            (``all-reduce``) and nothing else, the scoring/gamma kernels
            contain ZERO collectives, and ``all-gather`` / ``all-to-all``
            are forbidden everywhere (a width-changing bitcast used to
            silently all-gather the whole gamma batch — this check pins the
            fix). Budgets live in the committed baseline file and are
            compared exactly; a deleted or duplicated psum fails the gate.
  SA-PAD    kernels that consume ``shard_pairs`` outputs thread the
            padding-weight array: the weights input must reach every kernel
            output in the jaxpr dataflow, so padded rows cannot contribute
            to M-step sums (a kernel that drops the weights argument has an
            unused invar and fails).
  SA-COST   per-kernel FLOPs / bytes-accessed / per-device memory-footprint
            estimates from XLA ``cost_analysis()`` / ``memory_analysis()``,
            checked against committed JSON baselines
            (``shard_baselines.json``) within a tolerance — cost regressions
            fail ``make lint`` the same way a lint finding does.

The audit forces x64 OFF while lowering (mirroring trace_audit forcing it
ON): baselines are recorded for the production-width program, so the gate
measures the same executable whether it runs from the CLI (x64 off) or the
x64 test tier.

Refreshing baselines intentionally (new kernel, accepted cost change)::

    make shard-baselines        # python -m splink_tpu.analysis --shard-audit
                                #        --update-baselines

Registering a kernel::

    @register_shard_kernel(
        "my_kernel_sharded",
        n_pairs=1024,                    # pair-axis length in example args
        allow_collectives=("all-reduce",),
        pad_weights_argnum=2,            # or None when not a stats kernel
    )
    def _build():
        mesh = audit_mesh()
        ...device_put args with pair_sharding(mesh) / replicated(mesh)...
        return fn, args, {}
"""

from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass, field
from typing import Callable

from .findings import Finding

DEFAULT_MESH_SIZE = 8
DEFAULT_COST_RTOL = 0.25

BASELINES_PATH = os.path.join(os.path.dirname(__file__), "shard_baselines.json")

# collective HLO ops, counted at their definition sites in the optimised
# module ("-start" covers async variants)
_COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter)"
    r"(?:-start)?\("
)

_COST_KEYS = (
    "flops",
    "transcendentals",
    "bytes_accessed",
    "argument_size_in_bytes",
    "output_size_in_bytes",
    "temp_size_in_bytes",
    "total_bytes_per_device",
)


@dataclass
class ShardKernelSpec:
    name: str
    build: Callable  # () -> (fn, args, kwargs), args device_put on the mesh
    n_pairs: int  # pair-axis length of the example inputs (SA-SPEC key)
    allow_collectives: tuple = ()
    pad_weights_argnum: int | None = None  # positional arg carrying weights
    cost_rtol: float = DEFAULT_COST_RTOL
    mesh_size: int = DEFAULT_MESH_SIZE
    origin: str = ""  # file:line of the registering builder
    cache: dict = field(default_factory=dict)

    @property
    def location(self) -> str:
        """``file:kernel`` anchor findings render with."""
        return f"{self.origin}:{self.name}" if self.origin else self.name


SHARD_REGISTRY: dict[str, ShardKernelSpec] = {}


def register_shard_kernel(
    name: str,
    *,
    n_pairs: int,
    allow_collectives=(),
    pad_weights_argnum: int | None = None,
    cost_rtol: float = DEFAULT_COST_RTOL,
    mesh_size: int = DEFAULT_MESH_SIZE,
    registry: dict | None = None,
):
    """Declare one sharded kernel for auditing; the decorated builder runs
    lazily and returns ``(fn, example_args, example_kwargs)`` with the
    arguments already placed on the audit mesh. ``registry`` overrides the
    global one (fixture corpora register into their own dict)."""

    reg = SHARD_REGISTRY if registry is None else registry

    def deco(build: Callable) -> Callable:
        if name in reg:
            raise ValueError(f"duplicate shard kernel name {name!r}")
        code = getattr(build, "__code__", None)
        origin = ""
        if code is not None:
            path = code.co_filename
            for root in (os.getcwd(), os.path.dirname(os.path.dirname(
                    os.path.dirname(os.path.abspath(__file__))))):
                try:
                    rel = os.path.relpath(path, root)
                except ValueError:  # different drive (windows)
                    continue
                if not rel.startswith(".."):
                    path = rel
                    break
            origin = path
        reg[name] = ShardKernelSpec(
            name=name,
            build=build,
            n_pairs=n_pairs,
            allow_collectives=tuple(allow_collectives),
            pad_weights_argnum=pad_weights_argnum,
            cost_rtol=cost_rtol,
            mesh_size=mesh_size,
            origin=origin,
        )
        return build

    return deco


def audit_mesh(size: int = DEFAULT_MESH_SIZE):
    """The mesh shard builders place their example arguments on."""
    from ..parallel.mesh import make_mesh

    return make_mesh(size)


# ---------------------------------------------------------------------------
# Lowering + measurement
# ---------------------------------------------------------------------------


def _collective_counts(hlo_text: str) -> dict[str, int]:
    counts: dict[str, int] = {}
    for kind in _COLLECTIVE_RE.findall(hlo_text):
        counts[kind] = counts.get(kind, 0) + 1
    return counts


def _measure_costs(compiled) -> dict[str, float]:
    """flops / bytes / per-device memory estimates from the XLA client.
    Backends that cannot answer a query simply omit the key (the baseline
    comparison only checks keys both sides have)."""
    out: dict[str, float] = {}
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 - optional per backend
        ca = None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if isinstance(ca, dict):
        for src, dst in (
            ("flops", "flops"),
            ("transcendentals", "transcendentals"),
            ("bytes accessed", "bytes_accessed"),
        ):
            if src in ca:
                out[dst] = float(ca[src])
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 - optional per backend
        ma = None
    if ma is not None:
        total = 0.0
        ok = False
        for key in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
        ):
            val = getattr(ma, key, None)
            if val is not None:
                out[key] = float(val)
                total += float(val)
                ok = True
        gen = getattr(ma, "generated_code_size_in_bytes", None)
        if gen is not None:
            total += float(gen)
        if ok:
            # summed footprint (args + outputs + temps + code), NOT a
            # liveness-aware high-water mark — XLA does not expose one
            # here; the per-component keys above carry the real signal
            out["total_bytes_per_device"] = total
    return out


def _lowered(spec: ShardKernelSpec):
    """(fn, args, kwargs, compiled) for one spec, memoised on the spec.

    Builds and compiles with x64 forced OFF — the production program width —
    regardless of ambient config, so the x64 test tier and the CLI measure
    the identical executable (the mirror image of trace_audit forcing x64
    ON to catch dtype leaks)."""
    import jax
    if "lowered" not in spec.cache:
        with jax.enable_x64(False):
            fn, args, kwargs = spec.build()
            jfn = fn if hasattr(fn, "lower") else jax.jit(fn)
            compiled = jfn.lower(*args, **kwargs).compile()
        spec.cache["lowered"] = (fn, args, kwargs, compiled)
    return spec.cache["lowered"]


def measure_shard_kernel(spec: ShardKernelSpec) -> dict:
    """The committed-baseline record for one kernel: exact collective
    counts plus cost/memory estimates."""
    _, _, _, compiled = _lowered(spec)
    record = {"collectives": _collective_counts(compiled.as_text())}
    record.update(_measure_costs(compiled))
    return record


# ---------------------------------------------------------------------------
# Invariants
# ---------------------------------------------------------------------------


def _partition_spec(sharding):
    """Normalised PartitionSpec tuple (trailing None stripped), or None when
    the sharding object exposes no spec."""
    pspec = getattr(sharding, "spec", None)
    if pspec is None:
        return None
    parts = tuple(pspec)
    while parts and parts[-1] is None:
        parts = parts[:-1]
    return parts


def _leading_axis_names(parts) -> tuple:
    if not parts:
        return ()
    head = parts[0]
    return tuple(head) if isinstance(head, tuple) else (head,)


def _check_leaf_sharding(spec, fail, role, index, aval_shape, sharding):
    from ..parallel.mesh import DATA_AXIS

    parts = _partition_spec(sharding)
    if parts is None:
        # non-NamedSharding (e.g. GSPMD) — fall back to the replication flag
        if aval_shape and aval_shape[0] == spec.n_pairs and getattr(
            sharding, "is_fully_replicated", False
        ):
            fail(
                "SA-SPEC",
                f"{role} {index} {aval_shape} is a pair-axis array but is "
                "fully replicated on the mesh",
                "give it the pair sharding (mesh.pair_sharding)",
            )
        return
    is_pair_leaf = bool(aval_shape) and aval_shape[0] == spec.n_pairs
    if is_pair_leaf:
        if DATA_AXIS not in _leading_axis_names(parts):
            fail(
                "SA-SPEC",
                f"{role} {index} {aval_shape} has the pair axis leading "
                f"but PartitionSpec{parts} does not shard it over "
                f"'{DATA_AXIS}' — the array is replicated onto every "
                "device",
                "device_put it with mesh.pair_sharding (shard_pairs does "
                "this for you)",
            )
    elif parts:
        fail(
            "SA-SPEC",
            f"{role} {index} {aval_shape} is not a pair-axis array but "
            f"carries PartitionSpec{parts} — parameters/tables/accumulators "
            "replicate in this design",
            "device_put it with mesh.replicated",
        )


def _flat_input_leaves(args, kwargs, shardings_pytree):
    """Zip the flattened example inputs with the flattened shardings the
    executable committed to (jit preserves the argument pytree, so the two
    flatten in the same order)."""
    import jax

    leaves = jax.tree.leaves((args, kwargs))
    shard_leaves = jax.tree.leaves(
        shardings_pytree, is_leaf=lambda x: hasattr(x, "is_fully_replicated")
    )
    return list(zip(leaves, shard_leaves))


def _weights_leaf_index(args, argnum: int) -> int:
    """Flat-leaf index of positional arg ``argnum`` (the weights array is a
    single flat leaf)."""
    import jax

    offset = 0
    for arg in args[:argnum]:
        offset += len(jax.tree.leaves(arg))
    return offset


def _pad_reaches_all_outputs(closed, weights_leaf: int):
    """Taint-propagate from the weights invar; return the (possibly empty)
    list of output positions it does NOT reach.

    pjit sub-jaxprs are descended precisely (position-mapped); other
    higher-order eqns (while/scan/cond) are conservative — any tainted
    input taints every output — which is exact enough to catch the real
    failure mode: a weights argument that never enters the dataflow."""
    import jax.extend.core as jex_core

    def hit(v, tainted):  # Literal atoms are unhashable and never tainted
        return not isinstance(v, jex_core.Literal) and v in tainted

    def walk(jaxpr, tainted: set):
        for eqn in jaxpr.eqns:
            sub = None
            if eqn.primitive.name == "pjit":
                sub = eqn.params.get("jaxpr")
            if sub is not None and isinstance(sub, jex_core.ClosedJaxpr):
                inner_taint = {
                    sub.jaxpr.invars[i]
                    for i, v in enumerate(eqn.invars)
                    if i < len(sub.jaxpr.invars) and hit(v, tainted)
                }
                inner_out = walk(sub.jaxpr, inner_taint)
                for i, v in enumerate(sub.jaxpr.outvars):
                    if hit(v, inner_out) and i < len(eqn.outvars):
                        tainted.add(eqn.outvars[i])
            elif any(hit(v, tainted) for v in eqn.invars):
                tainted.update(eqn.outvars)
        return tainted

    invars = closed.jaxpr.invars
    if weights_leaf >= len(invars):
        return list(range(len(closed.jaxpr.outvars)))
    tainted = walk(closed.jaxpr, {invars[weights_leaf]})
    return [
        i
        for i, v in enumerate(closed.jaxpr.outvars)
        if not hit(v, tainted)
    ]


def audit_shard_kernel(
    spec: ShardKernelSpec, baseline: dict | None
) -> list[Finding]:
    """Lower one registered kernel on the audit mesh and check the four
    SA-* invariants against its committed baseline."""
    import jax

    findings: list[Finding] = []

    def fail(check: str, message: str, hint: str = "") -> None:
        findings.append(
            Finding(
                rule=check, path=spec.location, line=0, message=message,
                hint=hint,
            )
        )

    if len(jax.devices()) < spec.mesh_size:
        fail(
            "SA-ENV",
            f"audit mesh needs {spec.mesh_size} devices but only "
            f"{len(jax.devices())} are visible",
            "run under XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{spec.mesh_size} (make lint sets this)",
        )
        return findings

    try:
        fn, args, kwargs, compiled = _lowered(spec)
    except Exception as e:  # noqa: BLE001 - any lowering failure is a finding
        fail(
            "SA-ERROR",
            f"kernel failed to lower/compile on the mesh: "
            f"{type(e).__name__}: {e}",
        )
        return findings

    # SA-SPEC: committed input shardings + inferred output shardings
    in_shardings = compiled.input_shardings
    if isinstance(in_shardings, tuple) and len(in_shardings) == 2:
        in_tree = in_shardings
    else:  # defensive: some versions return the args tuple only
        in_tree = (in_shardings, {})
    for idx, (leaf, sharding) in enumerate(
        _flat_input_leaves(args, kwargs, in_tree)
    ):
        _check_leaf_sharding(
            spec, fail, "input", idx, tuple(leaf.shape), sharding
        )
    with jax.enable_x64(False):
        out_struct = jax.eval_shape(
            fn if not hasattr(fn, "lower") else (lambda *a, **k: fn(*a, **k)),
            *args,
            **kwargs,
        )
    out_leaves = jax.tree.leaves(out_struct)
    out_shardings = jax.tree.leaves(
        compiled.output_shardings,
        is_leaf=lambda x: hasattr(x, "is_fully_replicated"),
    )
    for idx, (leaf, sharding) in enumerate(zip(out_leaves, out_shardings)):
        _check_leaf_sharding(
            spec, fail, "output", idx, tuple(leaf.shape), sharding
        )

    # SA-COLL: forbidden kinds always fail; allowed kinds must match the
    # committed budget exactly
    counts = _collective_counts(compiled.as_text())
    for kind, n in sorted(counts.items()):
        if kind not in spec.allow_collectives:
            fail(
                "SA-COLL",
                f"{n}x {kind} in the SPMD program but the kernel's "
                f"collective allowlist is {list(spec.allow_collectives)}",
                "an unpartitionable op forced cross-device data movement; "
                "rewrite it shard-local (see gammas._u32_bytes_le) or "
                "declare the collective deliberately",
            )
    if baseline is not None:
        budget = baseline.get("collectives", {})
        for kind in sorted(set(budget) | set(counts)):
            if kind not in spec.allow_collectives:
                continue  # unallowed kinds already reported above
            want, got = int(budget.get(kind, 0)), int(counts.get(kind, 0))
            if want != got:
                fail(
                    "SA-COLL",
                    f"collective budget drift: expected {want}x {kind} "
                    f"(committed baseline), found {got}x",
                    "a psum was deleted/duplicated; if intentional, "
                    "refresh with `make shard-baselines`",
                )

    # SA-PAD: padding weights must reach every output
    if spec.pad_weights_argnum is not None:
        try:
            with jax.enable_x64(False):
                closed = jax.make_jaxpr(lambda *a, **k: fn(*a, **k))(
                    *args, **kwargs
                )
            unreached = _pad_reaches_all_outputs(
                closed, _weights_leaf_index(args, spec.pad_weights_argnum)
            )
        except Exception as e:  # noqa: BLE001
            fail("SA-ERROR", f"SA-PAD trace failed: {type(e).__name__}: {e}")
            unreached = []
        if unreached:
            fail(
                "SA-PAD",
                "padding-weight array (arg "
                f"{spec.pad_weights_argnum}) does not reach output(s) "
                f"{unreached} — padded rows from shard_pairs would "
                "contribute to the M-step sums",
                "thread the weights through every reduction "
                "(sufficient_stats(..., weights=w))",
            )

    # SA-COST: measured estimates vs committed baseline, within tolerance
    measured = _measure_costs(compiled)
    if baseline is None:
        fail(
            "SA-COST",
            "no committed cost baseline for this kernel",
            "generate one with `make shard-baselines` and commit "
            "shard_baselines.json",
        )
    else:
        for key in _COST_KEYS:
            if key not in baseline or key not in measured:
                continue
            want, got = float(baseline[key]), float(measured[key])
            if want == 0.0 and got == 0.0:
                continue
            rel = abs(got - want) / max(abs(want), 1.0)
            if rel > spec.cost_rtol:
                sign = "+" if got >= want else "-"
                fail(
                    "SA-COST",
                    f"{key}: baseline {want:.0f}, measured {got:.0f} "
                    f"({sign}{rel * 100:.1f}% > ±{spec.cost_rtol * 100:.0f}%"
                    " tolerance)",
                    "a perf/memory regression on the sharded path; if the "
                    "change is intended, refresh with `make "
                    "shard-baselines`",
                )
    return findings


# ---------------------------------------------------------------------------
# Driver + baselines
# ---------------------------------------------------------------------------


def load_baselines(path: str = BASELINES_PATH) -> dict:
    if not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def run_shard_audit(
    names=None, baselines: dict | None = None, registry: dict | None = None
) -> tuple[list[Finding], int]:
    """Audit the given shard kernels (default: all registered). Returns
    (findings, kernel count)."""
    reg = SHARD_REGISTRY if registry is None else registry
    if registry is None:
        _ensure_default_registry()
    if baselines is None:
        baselines = load_baselines()
    per_kernel = baselines.get("kernels", baselines)
    if names:
        unknown = [n for n in names if n not in reg]
        if unknown:
            raise KeyError(f"unknown shard kernel(s): {', '.join(unknown)}")
        specs = [reg[n] for n in names]
    else:
        specs = [reg[n] for n in sorted(reg)]
    findings: list[Finding] = []
    for spec in specs:
        findings.extend(audit_shard_kernel(spec, per_kernel.get(spec.name)))
    return findings, len(specs)


def update_baselines(names=None, path: str = BASELINES_PATH) -> dict:
    """Re-measure every (or the named) registered kernel and write the
    committed baseline file. A full refresh (no names) rebuilds the file
    from the registry alone, so budgets for renamed/removed kernels are
    PRUNED rather than lingering as dead entries nothing audits; a named
    refresh merges into the existing file. Returns the new baselines
    dict."""
    import jax

    _ensure_default_registry()
    if names:
        unknown = [n for n in names if n not in SHARD_REGISTRY]
        if unknown:
            raise KeyError(f"unknown shard kernel(s): {', '.join(unknown)}")
        specs = [SHARD_REGISTRY[n] for n in names]
        kernels = dict(load_baselines(path).get("kernels", {}))
    else:
        specs = [SHARD_REGISTRY[n] for n in sorted(SHARD_REGISTRY)]
        kernels = {}
    for spec in specs:
        kernels[spec.name] = measure_shard_kernel(spec)
    new = {
        "_meta": {
            "jax": jax.__version__,
            "mesh_devices": DEFAULT_MESH_SIZE,
            "refresh": "make shard-baselines",
        },
        "kernels": {k: kernels[k] for k in sorted(kernels)},
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(new, fh, indent=2, sort_keys=True)
        fh.write("\n")
    return new


# ---------------------------------------------------------------------------
# Default registry: the sharded hot path.
# ---------------------------------------------------------------------------

_defaults_registered = False


def _ensure_default_registry() -> None:
    global _defaults_registered
    if _defaults_registered:
        return
    _defaults_registered = True

    from .trace_audit import shared_fs_inputs, shared_gamma_program

    def _sharded_fs(n_pairs: int):
        """(mesh, G, params, weights): the shared FS example inputs tiled to
        ``n_pairs`` and placed with production shardings (reusing the layer-2
        builder cache, so the two tiers build inputs once)."""
        import jax
        import numpy as np

        from ..parallel.mesh import pair_sharding, replicated

        mesh = audit_mesh()
        G_small, params = shared_fs_inputs()
        reps = -(-n_pairs // G_small.shape[0])
        G_np = np.tile(np.asarray(G_small), (reps, 1))[:n_pairs]
        G = jax.device_put(G_np, pair_sharding(mesh))
        w = jax.device_put(
            np.ones(n_pairs, np.float32), pair_sharding(mesh)
        )
        params = jax.device_put(params, replicated(mesh))
        return mesh, G, params, w

    # The full fused EM loop: pair-sharded gammas + weights, replicated
    # params; every reduction lowers to per-device partials + psum.
    @register_shard_kernel(
        "em_step_sharded",
        n_pairs=1024,
        allow_collectives=("all-reduce",),
        pad_weights_argnum=2,
    )
    def _build_em_step_sharded():
        import jax
        import jax.numpy as jnp

        from ..em import run_em
        from ..parallel.mesh import replicated

        mesh, G, params, w = _sharded_fs(1024)
        fn = lambda G, p, w, tol: run_em(  # noqa: E731
            G,
            p,
            max_iterations=4,
            max_levels=3,
            em_convergence=tol,
            weights=w,
            compute_ll=True,
        )
        tol = jax.device_put(jnp.float32(1e-4), replicated(mesh))
        return fn, (G, params, w, tol), {}

    # One E+M sufficient-stats pass — THE stats reduction whose psums the
    # collective budget pins.
    @register_shard_kernel(
        "em_stats_sharded",
        n_pairs=1024,
        allow_collectives=("all-reduce",),
        pad_weights_argnum=2,
    )
    def _build_em_stats_sharded():
        from ..models.fellegi_sunter import (
            match_probability,
            sufficient_stats,
        )

        mesh, G, params, w = _sharded_fs(1024)

        def fn(G, p, w):
            return sufficient_stats(G, match_probability(G, p), 3, w)

        return fn, (G, params, w), {}

    # The streamed micro-batch kernel (stats + ll): same psum class.
    @register_shard_kernel(
        "streamed_pass_sharded",
        n_pairs=1024,
        allow_collectives=("all-reduce",),
        pad_weights_argnum=2,
    )
    def _build_streamed_pass_sharded():
        from ..parallel.streaming import _batch_stats

        mesh, G, params, w = _sharded_fs(1024)
        fn = lambda G, p, w: _batch_stats(G, p, 3, w, True)  # noqa: E731
        return fn, (G, params, w), {}

    # Scoring is embarrassingly parallel over pairs: zero collectives, and
    # the scores come back pair-sharded (padded rows are sliced host-side).
    @register_shard_kernel("score_pairs_sharded", n_pairs=1024)
    def _build_score_pairs_sharded():
        from ..em import score_pairs

        _, G, params, _ = _sharded_fs(1024)
        fn = lambda G, p: score_pairs(G, p)  # noqa: E731
        return fn, (G, params), {}

    # Gamma batch (the one body every kernel composes): packed
    # table replicated, pair indices sharded, ZERO collectives. This is the
    # kernel whose width-changing bitcast used to all-gather the batch.
    @register_shard_kernel("gamma_batch_sharded", n_pairs=256)
    def _build_gamma_batch_sharded():
        import jax
        import numpy as np

        from ..parallel.mesh import pair_sharding, replicated

        mesh = audit_mesh()
        program = shared_gamma_program()
        packed = jax.device_put(program._packed, replicated(mesh))
        il = jax.device_put(np.zeros(256, np.int32), pair_sharding(mesh))
        ir = jax.device_put(np.ones(256, np.int32), pair_sharding(mesh))
        return program._gamma_batch_fn, (packed, il, ir), {}

    # Materialised pattern-histogram kernel on the mesh: exactly ONE psum
    # (the replicated histogram accumulator), nothing else.
    @register_shard_kernel(
        "pattern_kernel_sharded",
        n_pairs=256,
        allow_collectives=("all-reduce",),
    )
    def _build_pattern_kernel_sharded():
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ..parallel.mesh import pair_sharding, replicated

        mesh = audit_mesh()
        program = shared_gamma_program()
        fn = program._pattern_batch_for_mesh(mesh)
        packed = jax.device_put(program._packed, replicated(mesh))
        il = jax.device_put(np.zeros(256, np.int32), pair_sharding(mesh))
        ir = jax.device_put(np.ones(256, np.int32), pair_sharding(mesh))
        valid = jax.device_put(jnp.int32(200), replicated(mesh))
        acc = jax.device_put(
            np.zeros(program.n_patterns + 1, np.int32), replicated(mesh)
        )
        return fn, (packed, il, ir, valid, acc), {}

    # Virtual pair index decode+score twin: sharded position iota, one
    # histogram psum — how device pair generation composes with multi-chip
    # EM.
    @register_shard_kernel(
        "virtual_pattern_kernel_sharded",
        n_pairs=128,
        allow_collectives=("all-reduce",),
    )
    def _build_virtual_pattern_sharded():
        import jax
        import numpy as np

        from ..pairgen import make_virtual_pattern_fn
        from ..parallel.mesh import pair_sharding, replicated

        mesh = audit_mesh()
        program = shared_gamma_program()
        bs = 128
        fn = make_virtual_pattern_fn(
            program, bs, n_prev=0, has_uid_mask=False, mesh=mesh
        )
        shard, rep = pair_sharding(mesh), replicated(mesh)
        imax = np.int32(np.iinfo(np.int32).max)
        pos = jax.device_put(np.arange(bs, dtype=np.int32), shard)
        packed = jax.device_put(program._packed, rep)
        order = jax.device_put(np.arange(6, dtype=np.int32), rep)
        units = jax.device_put(np.zeros(4, np.int32), rep)
        lens = jax.device_put(np.full(4, 3, np.int32), rep)
        meta = jax.device_put(
            np.array([0, bs, 0, imax, imax, imax], np.int32), rep
        )
        acc = jax.device_put(
            np.zeros(program.n_patterns + 1, np.int32), rep
        )
        prev_codes = jax.device_put(np.zeros((1, 6), np.int32), rep)
        uid_codes = jax.device_put(np.zeros(6, np.int32), rep)
        return (
            fn,
            (
                pos,
                packed,
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

    # Online-serving scoring kernel (serve/engine.make_score_topk_fn)
    # sharded over the QUERY axis — the serving analogue of the pair axis:
    # the query-side row expansion is a static broadcast (deliberately NOT
    # an index gather, which GSPMD would all-gather under a sharded query
    # axis), candidate gathers read the replicated reference table with
    # sharded indices, and top-k runs along the replicated candidate axis.
    # ZERO collectives — multi-chip serving divides query batches cleanly.
    @register_shard_kernel("serve_score_topk_sharded", n_pairs=64)
    def _build_serve_score_sharded():
        import jax
        import numpy as np

        from ..parallel.mesh import pair_sharding, replicated
        from ..serve.engine import make_score_topk_fn

        mesh = audit_mesh()
        program = shared_gamma_program()
        _, params_small = shared_fs_inputs()
        fn = make_score_topk_fn(
            program._layout, program.settings["comparison_columns"], k=4
        )
        shard, rep = pair_sharding(mesh), replicated(mesh)
        packed_q = jax.device_put(
            np.zeros((64, program._packed.shape[1]), np.uint32), shard
        )
        packed_ref = jax.device_put(program._packed, rep)
        cand = jax.device_put(np.zeros((64, 8), np.int32), shard)
        valid = jax.device_put(np.zeros((64, 8), bool), shard)
        params = jax.device_put(params_small, rep)
        return fn, (packed_q, packed_ref, cand, valid, params), {}

    # The fused megakernel twin of serve_score_topk_sharded: identical
    # sharding story (query axis sharded, reference/params replicated,
    # static query-side broadcast, top-k along the replicated candidate
    # axis), ZERO collectives — and a committed SA-COST baseline BELOW the
    # unfused kernel's (no stacked gamma matrix, no full-matrix m/u
    # probability lookups), which is the measured per-device-bytes proof
    # of the fusion.
    @register_shard_kernel("serve_score_fused_sharded", n_pairs=64)
    def _build_serve_score_fused_sharded():
        import jax
        import numpy as np

        from ..parallel.mesh import pair_sharding, replicated
        from ..serve.engine import make_score_fused_fn

        mesh = audit_mesh()
        program = shared_gamma_program()
        _, params_small = shared_fs_inputs()
        fn = make_score_fused_fn(
            program._layout, program.settings["comparison_columns"], k=4
        )
        shard, rep = pair_sharding(mesh), replicated(mesh)
        packed_q = jax.device_put(
            np.zeros((64, program._packed.shape[1]), np.uint32), shard
        )
        packed_ref = jax.device_put(program._packed, rep)
        cand = jax.device_put(np.zeros((64, 8), np.int32), shard)
        valid = jax.device_put(np.zeros((64, 8), bool), shard)
        params = jax.device_put(params_small, rep)
        return fn, (packed_q, packed_ref, cand, valid, params), {}

    # The TF-fold variant of the fused megakernel: query-side token ids
    # shard with the query axis (they are per-query data like packed_q),
    # the reference token ids and log-frequency tables replicate with the
    # reference table, and the fold's gathers read replicated operands
    # with sharded indices — ZERO collectives, the serving contract
    # unchanged by the adjustment.
    @register_shard_kernel("serve_score_fused_tf_sharded", n_pairs=64)
    def _build_serve_score_fused_tf_sharded():
        import jax
        import numpy as np

        from ..parallel.mesh import pair_sharding, replicated
        from ..serve.engine import make_score_fused_fn

        mesh = audit_mesh()
        program = shared_gamma_program()
        _, params_small = shared_fs_inputs()
        fn = make_score_fused_fn(
            program._layout, program.settings["comparison_columns"], k=4,
            tf_spec=((1, "city", 1),),
        )
        shard, rep = pair_sharding(mesh), replicated(mesh)
        packed_q = jax.device_put(
            np.zeros((64, program._packed.shape[1]), np.uint32), shard
        )
        packed_ref = jax.device_put(program._packed, rep)
        cand = jax.device_put(np.zeros((64, 8), np.int32), shard)
        valid = jax.device_put(np.zeros((64, 8), bool), shard)
        params = jax.device_put(params_small, rep)
        n_ref = program._packed.shape[0]
        tf_q = (jax.device_put(np.zeros(64, np.int32), shard),)
        tf_tid = (jax.device_put(np.zeros(n_ref, np.int32), rep),)
        tf_log = (jax.device_put(np.full(4, -1.0, np.float32), rep),)
        return (
            fn,
            (packed_q, packed_ref, cand, valid, params,
             tf_q, tf_tid, tf_log),
            {},
        )

    # Device-blocking emission decode+mask body sharded over the pair-
    # POSITION axis (the blocking analogue of the pair axis): the unit
    # tables, ranks, codes and meta replicate, each shard decodes and
    # masks its own slice of every chunk, outputs come back position-
    # sharded. ZERO collectives — the compaction prefix-sum is
    # deliberately single-device (the host compacts per shard in the
    # mesh driver), so nothing here may force cross-device movement.
    @register_shard_kernel("block_pair_decode_sharded", n_pairs=64)
    def _build_block_pair_decode_sharded():
        import jax
        import numpy as np

        from ..blocking_device import make_pair_emit_fn
        from ..parallel.mesh import pair_sharding, replicated

        mesh = audit_mesh()
        bs = 64
        fn = make_pair_emit_fn(
            bs, n_prev=1, has_uid_mask=True, rank_filter=True, mesh=mesh
        )
        shard, rep = pair_sharding(mesh), replicated(mesh)
        imax = np.int32(np.iinfo(np.int32).max)
        pos = jax.device_put(np.arange(bs, dtype=np.int32), shard)
        order = jax.device_put(np.arange(8, dtype=np.int32), rep)
        units = jax.device_put(np.zeros(4, np.int32), rep)
        lens = jax.device_put(np.full(4, 3, np.int32), rep)
        ranks = jax.device_put(np.arange(8, dtype=np.int32), rep)
        prev_l = jax.device_put(np.zeros((1, 8), np.int32), rep)
        prev_r = jax.device_put(np.zeros((1, 8), np.int32), rep)
        uid = jax.device_put(np.zeros(8, np.int32), rep)
        meta = jax.device_put(
            np.array([0, bs, 0, imax, imax, imax], np.int32), rep
        )
        return (
            fn,
            (pos, order, units, lens, units, lens, ranks, prev_l, prev_r,
             uid, (), meta),
            {},
        )

    # Spill-emission transfer digest sharded over the pair-position axis:
    # each shard mixes its own (i, j) lanes against replicated constants
    # and the wraparound uint32 sum lowers to exactly ONE declared psum —
    # the only cross-device traffic the sharded write path performs (the
    # emission decode itself is collective-free, block_pair_decode_sharded
    # above).
    @register_shard_kernel(
        "spill_chunk_digest_sharded",
        n_pairs=64,
        allow_collectives=("all-reduce",),
    )
    def _build_spill_chunk_digest_sharded():
        import jax
        import numpy as np

        from ..blocking_device import make_chunk_digest_fn
        from ..parallel.mesh import pair_sharding

        mesh = audit_mesh()
        fn = make_chunk_digest_fn(mesh)
        shard = pair_sharding(mesh)
        rng = np.random.default_rng(0)
        i = jax.device_put(
            rng.integers(0, 64, size=64).astype(np.int32), shard
        )
        j = jax.device_put(
            rng.integers(0, 64, size=64).astype(np.int32), shard
        )
        keep = jax.device_put(
            rng.integers(0, 2, size=64).astype(bool), shard
        )
        return fn, (i, j, keep), {}

    # Approximate-blocking minhash signatures sharded over the RECORD
    # axis: each shard sketches its own rows against the replicated hash
    # parameters — embarrassingly parallel, zero collectives, outputs
    # record-sharded. This is the index-build / signature-refresh shape on
    # a mesh.
    @register_shard_kernel("approx_minhash_sharded", n_pairs=64)
    def _build_approx_minhash_sharded():
        import jax
        import numpy as np

        from ..approx.minhash import (
            column_salts,
            hash_params,
            make_minhash_fn,
        )
        from ..parallel.mesh import pair_sharding, replicated

        mesh = audit_mesh()
        shard, rep = pair_sharding(mesh), replicated(mesh)
        fn = make_minhash_fn(2, 4, 2, ((12, "ascii"),))
        rng = np.random.default_rng(0)
        bytes_ = jax.device_put(
            rng.integers(97, 123, size=(64, 12)).astype(np.uint8), shard
        )
        lens = jax.device_put(np.full(64, 8, np.int32), shard)
        a, b = hash_params(8)
        salts = column_salts(1)
        return (
            fn,
            (bytes_, lens, jax.device_put(a, rep), jax.device_put(b, rep),
             jax.device_put(salts, rep)),
            {},
        )

    # Approximate-blocking verification sharded over the candidate-PAIR
    # axis: i/j shard, the band-code matrix and the per-column byte/aux
    # tables replicate, each shard gathers and verifies its own pairs —
    # zero collectives, outputs pair-sharded (the blocking-emission
    # pattern block_pair_decode_sharded pins, applied to the verify pass).
    @register_shard_kernel("approx_verify_sharded", n_pairs=64)
    def _build_approx_verify_sharded():
        import jax
        import numpy as np

        from ..approx.lsh import make_verify_fn
        from ..parallel.mesh import pair_sharding, replicated

        mesh = audit_mesh()
        shard, rep = pair_sharding(mesh), replicated(mesh)
        fn = make_verify_fn(2, 4, ((12, "ascii"),), True)
        rng = np.random.default_rng(0)
        i = jax.device_put(np.zeros(64, np.int32), shard)
        j = jax.device_put(np.ones(64, np.int32), shard)
        band_codes = jax.device_put(
            rng.integers(-1, 4, size=(4, 16)).astype(np.int32), rep
        )
        bytes_ = jax.device_put(
            rng.integers(97, 123, size=(16, 12)).astype(np.uint8), rep
        )
        lens = jax.device_put(np.full(16, 8, np.int32), rep)
        mask = jax.device_put(np.zeros((16, 1), np.uint32), rep)
        count = jax.device_put(np.full(16, 7, np.int32), rep)
        return fn, (i, j, band_codes, bytes_, lens, mask, count), {}

    # The TF-WEIGHTED minhash sampler: record-sharded like the unweighted
    # kernel, with the IDF table replicated beside the hash parameters —
    # the per-gram IDF gather reads a replicated operand with sharded
    # indices, so the weighted tier stays embarrassingly parallel (zero
    # collectives).
    @register_shard_kernel("approx_minhash_weighted_sharded", n_pairs=64)
    def _build_approx_minhash_weighted_sharded():
        import jax
        import numpy as np

        from ..approx.minhash import (
            DF_TABLE_SIZE,
            column_salts,
            hash_params,
            make_minhash_fn,
        )
        from ..parallel.mesh import pair_sharding, replicated

        mesh = audit_mesh()
        shard, rep = pair_sharding(mesh), replicated(mesh)
        fn = make_minhash_fn(2, 4, 2, ((12, "ascii"),), weighted=True)
        rng = np.random.default_rng(0)
        bytes_ = jax.device_put(
            rng.integers(97, 123, size=(64, 12)).astype(np.uint8), shard
        )
        lens = jax.device_put(np.full(64, 8, np.int32), shard)
        a, b = hash_params(8)
        salts = column_salts(1)
        idf = jax.device_put(np.ones(DF_TABLE_SIZE, np.float32), rep)
        return (
            fn,
            (bytes_, lens, jax.device_put(a, rep), jax.device_put(b, rep),
             jax.device_put(salts, rep), idf),
            {},
        )

    # The TF-WEIGHTED verify kernel: pair-sharded like the unweighted
    # verifier, IDF table replicated with the byte/aux tables — each
    # shard weighs its own pairs, zero collectives.
    @register_shard_kernel("approx_verify_weighted_sharded", n_pairs=64)
    def _build_approx_verify_weighted_sharded():
        import jax
        import numpy as np

        from ..approx.lsh import make_verify_fn
        from ..approx.minhash import DF_TABLE_SIZE
        from ..parallel.mesh import pair_sharding, replicated

        mesh = audit_mesh()
        shard, rep = pair_sharding(mesh), replicated(mesh)
        fn = make_verify_fn(2, 4, ((12, "ascii"),), True, weighted=True)
        rng = np.random.default_rng(0)
        i = jax.device_put(np.zeros(64, np.int32), shard)
        j = jax.device_put(np.ones(64, np.int32), shard)
        band_codes = jax.device_put(
            rng.integers(-1, 4, size=(4, 16)).astype(np.int32), rep
        )
        bytes_ = jax.device_put(
            rng.integers(97, 123, size=(16, 12)).astype(np.uint8), rep
        )
        lens = jax.device_put(np.full(16, 8, np.int32), rep)
        mask = jax.device_put(np.zeros((16, 1), np.uint32), rep)
        count = jax.device_put(np.full(16, 7, np.int32), rep)
        idf = jax.device_put(np.ones(DF_TABLE_SIZE, np.float32), rep)
        return (
            fn,
            (i, j, band_codes, bytes_, lens, mask, count, idf),
            {},
        )

    # String similarity is per-pair elementwise: zero collectives, output
    # sharded.
    @register_shard_kernel("jaro_winkler_sharded", n_pairs=64)
    def _build_jw_sharded():
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ..ops import strings
        from ..parallel.mesh import pair_sharding, replicated

        mesh = audit_mesh()
        rng = np.random.default_rng(0)
        s = jax.device_put(
            rng.integers(97, 123, size=(64, 24)).astype(np.uint8),
            pair_sharding(mesh),
        )
        ln = jax.device_put(np.full(64, 8, np.int32), pair_sharding(mesh))
        p = jax.device_put(jnp.float32(0.1), replicated(mesh))
        bt = jax.device_put(jnp.float32(0.7), replicated(mesh))
        fn = lambda s1, s2, l1, l2, p, bt: (  # noqa: E731
            strings.jaro_winkler_vmapped(s1, s2, l1, l2, p, bt)
        )
        return fn, (s, s, ln, ln, p, bt), {}

    # Quality-profile capture on the mesh: the training gammas arrive
    # pair-sharded (the index build reuses whatever sharding the EM run
    # left them in), params replicate, and the flat histogram reduces into
    # the replicated output through exactly the scatter-add psums the
    # committed baseline pins — the pattern-kernel collective class.
    @register_shard_kernel(
        "quality_profile_sharded",
        n_pairs=1024,
        allow_collectives=("all-reduce",),
    )
    def _build_quality_profile_sharded():
        from ..obs.quality import make_profile_fn

        mesh, G, params, _ = _sharded_fs(1024)
        fn = make_profile_fn((3, 3, 3), bins=8)
        return fn, (G, params), {}

    # Serve-time drift sketch on the mesh: the accumulator and reference
    # table replicate, the per-batch top-k outputs arrive query-sharded
    # (the serving axis serve_score_fused_sharded pins), and the updated
    # accumulator reduces back replicated via the same scatter-add psum
    # class — sketching composes with multi-chip serving without adding a
    # collective beyond its own histogram reduction.
    @register_shard_kernel(
        "serve_drift_sketch_sharded",
        n_pairs=64,
        allow_collectives=("all-reduce",),
    )
    def _build_serve_drift_sketch_sharded():
        import jax
        import numpy as np

        from ..obs.drift import make_sketch_fn
        from ..parallel.mesh import pair_sharding, replicated

        mesh = audit_mesh()
        program = shared_gamma_program()
        cols = program.settings["comparison_columns"]
        bins = 8
        width = max(int(c["num_levels"]) for c in cols) + 1
        size = len(cols) * width + 2 * bins
        fn = make_sketch_fn(program._layout, cols, bins)
        shard, rep = pair_sharding(mesh), replicated(mesh)
        acc = jax.device_put(np.zeros(size, np.int32), rep)
        packed_q = jax.device_put(
            np.zeros((64, program._packed.shape[1]), np.uint32), shard
        )
        packed_ref = jax.device_put(program._packed, rep)
        top_rows = jax.device_put(np.zeros((64, 4), np.int32), shard)
        top_valid = jax.device_put(np.zeros((64, 4), bool), shard)
        top_p = jax.device_put(np.zeros((64, 4), np.float32), shard)
        return (
            fn,
            (acc, packed_q, packed_ref, top_rows, top_valid, top_p),
            {},
        )
