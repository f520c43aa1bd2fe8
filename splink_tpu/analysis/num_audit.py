"""num_audit: MEASURED numerical-safety audit over the kernel registry.

numlint (layer 6's static half) reasons about source text; this module is
the measured half: it EXECUTES every kernel in the trace-audit registry
on its registered fixed-seed inputs plus a library of adversarial corner
batches, and checks invariants no AST rule can see:

    check    what it asserts
    -------  ----------------------------------------------------------
    NA-FIN   no NaN/Inf escapes: every float output leaf is finite for
             the registered inputs AND for every applicable corner batch
             (all-null rows, exact-0/1 probabilities, empty candidate
             buckets, max-count TF tables, denormal-adjacent parameters).
    NA-ULP   f32-vs-f64 divergence stays within the committed per-kernel
             ulp budget: the kernel is run once at f32 and once with its
             float inputs upcast to f64 under enable_x64; the largest
             elementwise divergence, measured in f32 ulps at the f64
             result's magnitude, must not exceed ``ulp_budget`` for this
             tier in analysis/num_baselines.json.
    NA-MONO  match_probability is monotone in each comparison column's
             log-Bayes-factor direction: sweeping one column through its
             levels sorted by log(m/u) (null slotted at 0) while the
             other columns stay null must produce a non-decreasing
             probability, for match_probability and for
             sigmoid(fold_logit).
    NA-ORD   the fold order is pinned: fold_logit and match_logit (the
             offline score's logit) must be BIT-IDENTICAL to a host-side
             numpy f32 reference that accumulates the per-column masked
             level lookups strictly left to right, using the device's
             own log tables as data.
    NA-BASE  bookkeeping: a registered kernel has no ulp budget for this
             tier (the committed baselines are stale).
    NA-ERROR a kernel or corner failed to execute at all.

Corner batches are declared PER KERNEL SHAPE, not applied blindly:
transforms inspect the registered input pytree and only apply where the
leaf they target exists (int8 gamma matrices for ``all_null``, FSParams
for ``prob_extremes``/``denormal``, bool validity masks for ``empty``),
plus a few kernel-specific corners for the TF tables. Blind leaf
mutation would violate documented preconditions (e.g. the minhash IDF
floor) and report noise, not findings.

Like the perf baselines, ulp budgets are keyed by accelerator tier
(``jax.default_backend()``): reduction strategies and libm choices
differ per backend, so one tier's divergence says nothing about
another's. Budgets are refreshed with

    python -m splink_tpu.analysis --update-num-baselines   # make num-baselines

which re-measures on the current tier and rewrites ONLY that tier's
block (other tiers' committed budgets survive). The measurement is
deterministic (fixed-seed inputs, no timing), so budgets store the
ceiling of the measured divergence verbatim — there are no noise bands.
"""

from __future__ import annotations

import functools
import json
import math
import os

from .findings import Finding

BASELINES_PATH = os.path.join(os.path.dirname(__file__), "num_baselines.json")

# Model-level plan entries (NA-MONO / NA-ORD) that audit the shared
# Fellegi-Sunter surface rather than one registered kernel.
MODEL_CHECKS = ("match_probability", "fold_logit")

# Registered kernels excluded from a specific check, with the reason
# surfaced in --list output and docs. Empty today; the mechanism exists
# so a future kernel that legitimately cannot run at f64 (e.g. one
# pinned to a u32 hash domain wider than f64's integer range) documents
# itself instead of silently dropping out of the plan.
NUM_EXCLUDED: dict[str, str] = {}


def current_tier() -> str:
    import jax

    return jax.default_backend()


def load_baselines(path: str = BASELINES_PATH) -> dict:
    if not os.path.exists(path):
        return {}
    with open(path) as fh:
        return json.load(fh)


# ---------------------------------------------------------------------------
# corner library
# ---------------------------------------------------------------------------


def _map_args(args, leaf_fn, params_fn=None):
    """Rebuild an args tuple, mapping array leaves through ``leaf_fn`` and
    FSParams nodes through ``params_fn`` (FSParams is a tuple subclass, so
    it must be intercepted before tuple recursion)."""
    from ..models.fellegi_sunter import FSParams

    def rec(x):
        if isinstance(x, FSParams):
            return params_fn(x) if params_fn is not None else x
        if isinstance(x, tuple):
            return tuple(rec(e) for e in x)
        return leaf_fn(x) if hasattr(x, "dtype") else x

    return tuple(rec(a) for a in args)


def _corner_all_null(args):
    """Every comparison null: int8 gamma matrices become all -1."""
    import jax.numpy as jnp

    hit = False

    def leaf(x):
        nonlocal hit
        if x.ndim and x.dtype == jnp.int8:
            hit = True
            return jnp.full_like(x, -1)
        return x

    new = _map_args(args, leaf)
    return new if hit else None


def _corner_prob_extremes(args):
    """Exact-0/1 probabilities: lambda = 0, m mass all on level 0, u mass
    all on the top level — every _safe_log sees a hard zero somewhere."""
    import jax.numpy as jnp

    seen = False

    def params(p):
        nonlocal seen
        seen = True
        from ..models.fellegi_sunter import FSParams

        m = jnp.zeros_like(p.m).at[:, 0].set(1.0)
        u = jnp.zeros_like(p.u).at[:, -1].set(1.0)
        return FSParams(lam=jnp.zeros_like(p.lam), m=m, u=u)

    new = _map_args(args, lambda x: x, params)
    return new if seen else None


def _corner_denormal(args):
    """Denormal-adjacent parameters: every probability cell sits below the
    f32 normal range, forcing _safe_log's tiny floor to do real work."""
    import jax.numpy as jnp

    seen = False

    def params(p):
        nonlocal seen
        seen = True
        from ..models.fellegi_sunter import FSParams

        sub = jnp.asarray(1e-39, p.m.dtype)
        return FSParams(
            lam=jnp.full_like(p.lam, sub),
            m=jnp.full_like(p.m, sub),
            u=jnp.full_like(p.u, sub),
        )

    new = _map_args(args, lambda x: x, params)
    return new if seen else None


def _corner_empty(args):
    """Empty buckets: every bool validity/keep mask goes all-False."""
    import jax.numpy as jnp

    hit = False

    def leaf(x):
        nonlocal hit
        if x.ndim and x.dtype == jnp.bool_:
            hit = True
            return jnp.zeros_like(x)
        return x

    new = _map_args(args, leaf)
    return new if hit else None


# f32 holds integers exactly up to 2**24; a count table at that ceiling is
# the largest TF table the f32 pipeline can represent without rounding.
_F32_MAX_COUNT = 16777216.0


def _corner_tf_max_counts(args):
    """tf_adjustment at saturation: every pair matches, every token's
    count sits at f32's exact-integer ceiling with sums == counts."""
    import jax.numpy as jnp

    tid_a, tid_b, p, sums, counts = args
    return (
        tid_a,
        tid_b,
        jnp.ones_like(p),
        jnp.full_like(sums, _F32_MAX_COUNT),
        jnp.full_like(counts, _F32_MAX_COUNT),
    )


def _corner_tf_max_adjust(args):
    """tf_gather with the adjustment table pinned at 1.0 everywhere."""
    import jax.numpy as jnp

    tid_a, tid_b, adjusted = args
    return (tid_a, tid_b, jnp.ones_like(adjusted))


def _corner_tf_zero_log(args):
    """serve_score_fused_tf with max-count log tables: log(count/total)=0
    for every token, the table a degenerate single-token column builds."""
    import jax.numpy as jnp

    new = list(args)
    new[-1] = tuple(jnp.zeros_like(t) for t in args[-1])
    return tuple(new)


# generic corners: (name, transform) tried against every kernel's args;
# a transform returns None when the leaf it targets is absent.
GENERIC_CORNERS = (
    ("all_null", _corner_all_null),
    ("prob_extremes", _corner_prob_extremes),
    ("denormal", _corner_denormal),
    ("empty", _corner_empty),
)

# kernel-specific corners keyed by registry name.
SPECIAL_CORNERS = {
    "tf_adjustment": (("max_counts", _corner_tf_max_counts),),
    "tf_gather": (("max_adjust", _corner_tf_max_adjust),),
    "serve_score_fused_tf": (("max_count_table", _corner_tf_zero_log),),
}


# ---------------------------------------------------------------------------
# finite checks
# ---------------------------------------------------------------------------


def _finite_leaves(out) -> list[str]:
    """Names of non-finite float leaves in an output pytree."""
    import jax
    import numpy as np

    bad = []
    leaves = jax.tree_util.tree_leaves(out)
    for i, leaf in enumerate(leaves):
        arr = np.asarray(leaf)
        if np.issubdtype(arr.dtype, np.floating) and not np.isfinite(arr).all():
            bad.append(f"leaf[{i}]:{arr.dtype}")
    return bad


def _finite_em(out, expect_ll: bool = True) -> list[str]:
    """EMResult checker: histories are NaN-padded BEYOND n_updates by
    contract (em.EMResult docstring), so only the populated prefix is
    required to be finite — and ll_history only when the kernel ran with
    compute_ll (otherwise the whole vector is NaN by contract)."""
    import numpy as np

    n = int(out.n_updates) + 1
    bad = []
    named = [
        ("params", out.params),
        ("lam_history", out.lam_history[:n]),
        ("m_history", out.m_history[:n]),
        ("u_history", out.u_history[:n]),
    ]
    if expect_ll:
        named.append(("ll_history", out.ll_history[:n]))
    for name, part in named:
        for frag in _finite_leaves(part):
            bad.append(f"{name}.{frag}")
    # the padding itself must stay padding: anything after the populated
    # prefix that is finite would mean the loop wrote past its counter
    if np.isfinite(np.asarray(out.lam_history[n:])).any():
        bad.append("lam_history: finite values past n_updates")
    return bad


_FIN_CHECKERS = {
    "em_step": _finite_em,
    "em_step_checkpointed": _finite_em,
    # the telemetry kernel registers with compute_ll=False: its ll_history
    # is all-NaN by contract, not a numerics escape
    "em_step_telemetry": functools.partial(_finite_em, expect_ll=False),
}


# ---------------------------------------------------------------------------
# ulp divergence
# ---------------------------------------------------------------------------


def _upcast_args(args):
    """Float leaves -> f64 (under enable_x64); everything else verbatim."""
    import jax
    import jax.numpy as jnp

    def leaf(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            # the deliberate f64 oracle arm of the ulp measurement —
            # only ever reached under enable_x64 (see _measure_ulp)
            return jnp.asarray(
                x, jnp.float64 if jax.config.jax_enable_x64 else x.dtype
            )
        return x

    return _map_args(
        args,
        leaf,
        lambda p: type(p)(*(leaf(v) for v in p)),
    )


def _ulp_divergence(out32, out64) -> float:
    """Largest f32-vs-f64 output divergence, in f32 ulps at the f64
    result's magnitude. Positions that are NaN in BOTH runs (the EM
    history padding) are contract, not divergence; a NaN on one side
    only is infinite divergence."""
    import jax
    import numpy as np

    worst = 0.0
    l32 = jax.tree_util.tree_leaves(out32)
    l64 = jax.tree_util.tree_leaves(out64)
    for a, b in zip(l32, l64):
        a = np.asarray(a)
        if not np.issubdtype(a.dtype, np.floating):
            continue
        a = a.astype(np.float64)
        b = np.asarray(b).astype(np.float64)
        nan_a, nan_b = np.isnan(a), np.isnan(b)
        if (nan_a != nan_b).any():
            return math.inf
        keep = ~nan_a
        a, b = a[keep], b[keep]
        if a.size == 0:
            continue
        # one f32 ulp at |b|, floored at the smallest normal's spacing so
        # divergence near 0 is measured on an absolute scale; equal values
        # (same-signed infinities included — NA-FIN owns those) diverge by
        # 0, while a mismatched infinity is infinite divergence
        with np.errstate(invalid="ignore", over="ignore"):
            ref = np.minimum(np.abs(b), float(np.finfo(np.float32).max))
            ref = np.maximum(ref, float(np.finfo(np.float32).tiny))
            ulp = np.spacing(ref.astype(np.float32)).astype(np.float64)
            diff = np.where(a == b, 0.0, np.abs(a - b))
            worst = max(worst, float(np.max(diff / ulp)))
    return worst


def _measure_ulp(spec) -> float:
    """Run a kernel at f32 and at f64 (inputs upcast, x64 on) and return
    the divergence. Deterministic: same seed inputs, no timing."""
    import jax
    fn, args, kwargs = spec.built()
    with jax.enable_x64(False):
        out32 = jax.block_until_ready(fn(*args, **kwargs))
    with jax.enable_x64(True):
        out64 = jax.block_until_ready(fn(*_upcast_args(args), **kwargs))
    return _ulp_divergence(out32, out64)


# ---------------------------------------------------------------------------
# model-level invariants: NA-MONO / NA-ORD
# ---------------------------------------------------------------------------


def _mono_params():
    """Asymmetric FSParams for the monotonicity/order checks: the shared
    audit params are uniform (every log-BF is 0), which would make both
    checks vacuous."""
    import jax.numpy as jnp

    from ..models.fellegi_sunter import FSParams

    return FSParams(
        lam=jnp.float32(0.23),
        m=jnp.asarray(
            [[0.85, 0.10, 0.05], [0.70, 0.20, 0.10], [0.55, 0.30, 0.15]],
            jnp.float32,
        ),
        u=jnp.asarray(
            [[0.05, 0.25, 0.70], [0.10, 0.30, 0.60], [0.20, 0.30, 0.50]],
            jnp.float32,
        ),
    )


def _check_monotone() -> list[Finding]:
    """NA-MONO: sweeping one column through its levels sorted by log(m/u)
    (null slotted at 0) must give non-decreasing match probability."""
    import jax
    import numpy as np

    from ..models.fellegi_sunter import fold_logit, match_probability

    findings = []
    params = _mono_params()
    m = np.asarray(params.m, np.float64)
    u = np.asarray(params.u, np.float64)
    C, L = m.shape
    for ci in range(C):
        bf = {lv: math.log(m[ci, lv]) - math.log(u[ci, lv]) for lv in range(L)}
        bf[-1] = 0.0  # null contributes no evidence
        order = sorted(bf, key=bf.get)
        G = np.full((len(order), C), -1, np.int8)
        G[:, ci] = order
        G = jax.numpy.asarray(G)
        for label, fn in (
            ("match_probability", lambda G: match_probability(G, params)),
            ("sigmoid(fold_logit)", lambda G: jax.nn.sigmoid(fold_logit(G, params))),
        ):
            p = np.asarray(fn(G), np.float64)
            if not (np.diff(p) >= 0).all():
                findings.append(
                    Finding(
                        rule="NA-MONO",
                        path="match_probability",
                        line=0,
                        message=(
                            f"{label} not monotone in column {ci}'s log-BF "
                            f"order {order}: probabilities "
                            + ", ".join(f"{v:.6g}" for v in p)
                        ),
                        hint="a probability that drops as evidence strengthens "
                        "means a fold or guard reordered the evidence",
                    )
                )
    return findings


def _check_fold_order() -> list[Finding]:
    """NA-ORD: fold_logit AND match_logit (the offline score's logit) must
    match a host numpy f32 reference that accumulates the per-column
    masked level lookups strictly left to right, bit for bit. The
    reference consumes the DEVICE log tables as data, so it pins only the
    association order, not libm log."""
    import numpy as np

    from ..models.fellegi_sunter import _safe_log, fold_logit, match_logit
    from .trace_audit import shared_fs_inputs

    G, _ = shared_fs_inputs()
    params = _mono_params()

    Gn = np.asarray(G)
    log_m = np.asarray(_safe_log(params.m))
    log_u = np.asarray(_safe_log(params.u))
    prior = np.asarray(_safe_log(params.lam) - _safe_log(1.0 - params.lam))
    zero = np.float32(0.0)
    log_bf = np.zeros(Gn.shape[0], np.float32)
    for ci in range(Gn.shape[1]):
        g = Gn[:, ci]
        lp_m = np.zeros(g.shape, np.float32)
        lp_u = np.zeros(g.shape, np.float32)
        for lv in range(log_m.shape[1]):
            hit = g == lv
            lp_m = lp_m + np.where(hit, log_m[ci, lv], zero)
            lp_u = lp_u + np.where(hit, log_u[ci, lv], zero)
        null = g >= 0
        log_bf = log_bf + (
            np.where(null, lp_m, zero) - np.where(null, lp_u, zero)
        )
    reference = (prior + log_bf).astype(np.float32)

    findings = []
    for name, fn in (("fold_logit", fold_logit), ("match_logit", match_logit)):
        device = np.asarray(fn(G, params))
        if np.array_equal(device, reference):
            continue
        n_diff = int((device != reference).sum())
        worst = float(np.max(np.abs(device.astype(np.float64) - reference)))
        findings.append(
            Finding(
                rule="NA-ORD",
                path=name,
                line=0,
                message=(
                    f"{name} differs from the left-to-right reference "
                    f"fold at {n_diff}/{device.size} rows (max abs diff "
                    f"{worst:.3e}) — the contracted fold order moved"
                ),
                hint="serve/offline score parity and every TF-anchored path "
                "assume this column order; see docs/numerics notes before "
                "changing it",
            )
        )
    return findings


# ---------------------------------------------------------------------------
# plan / audit / refresh
# ---------------------------------------------------------------------------


def num_plan(names=None) -> list[str]:
    """Audit plan: every registered kernel plus the model-level checks.
    Unknown names raise KeyError (same contract as the other audits)."""
    from .trace_audit import REGISTRY, _ensure_default_registry

    _ensure_default_registry()
    known = list(REGISTRY) + list(MODEL_CHECKS)
    if names is None:
        return known
    for name in names:
        if name not in known:
            raise KeyError(name)
    return [n for n in known if n in set(names)]


def _kernel_corners(name, args):
    corners = []
    for cname, fn in GENERIC_CORNERS:
        mutated = fn(args)
        if mutated is not None:
            corners.append((cname, mutated))
    for cname, fn in SPECIAL_CORNERS.get(name, ()):
        corners.append((cname, fn(args)))
    return corners


def audit_kernel_numerics(spec, base: dict | None) -> list[Finding]:
    """All numeric checks for one registered kernel: NA-FIN over the
    registered inputs and every applicable corner, NA-ULP against the
    committed budget (NA-BASE when the budget is missing)."""
    import jax
    findings: list[Finding] = []
    fn, args, kwargs = spec.built()
    check_fin = _FIN_CHECKERS.get(spec.name, _finite_leaves)

    batches = [("registered", args)] + _kernel_corners(spec.name, args)
    for cname, batch in batches:
        try:
            with jax.enable_x64(False):
                out = jax.block_until_ready(fn(*batch, **kwargs))
        except Exception as exc:  # noqa: BLE001 - surfaced as a finding
            findings.append(
                Finding(
                    rule="NA-ERROR",
                    path=spec.name,
                    line=0,
                    message=f"corner '{cname}' failed to execute: {exc!r}",
                    hint="corner batches stay inside documented input "
                    "contracts; an execution failure is a kernel bug",
                )
            )
            continue
        bad = check_fin(out)
        if bad:
            findings.append(
                Finding(
                    rule="NA-FIN",
                    path=spec.name,
                    line=0,
                    message=(
                        f"non-finite output for corner '{cname}': "
                        + ", ".join(bad)
                    ),
                    hint="finite inputs must give finite outputs; guard the "
                    "log/division the corner exposed (_safe_log idiom)",
                )
            )

    if base is None or "ulp_budget" not in (base or {}):
        findings.append(
            Finding(
                rule="NA-BASE",
                path=spec.name,
                line=0,
                message=(
                    f"no ulp budget for kernel '{spec.name}' on tier "
                    f"'{current_tier()}'"
                ),
                hint="run `make num-baselines` and commit "
                "analysis/num_baselines.json",
            )
        )
        return findings

    budget = float(base["ulp_budget"])
    try:
        measured = _measure_ulp(spec)
    except Exception as exc:  # noqa: BLE001 - surfaced as a finding
        findings.append(
            Finding(
                rule="NA-ERROR",
                path=spec.name,
                line=0,
                message=f"f64 shadow run failed: {exc!r}",
                hint="kernels must execute under enable_x64 with upcast "
                "inputs; pin or gate the offending dtype",
            )
        )
        return findings
    if measured > budget:
        findings.append(
            Finding(
                rule="NA-ULP",
                path=spec.name,
                line=0,
                message=(
                    f"f32/f64 divergence grew: ulp: budget {budget:g}, "
                    f"measured {measured:g}"
                ),
                hint="a wider f32 error bar usually means a guard or "
                "reduction moved; if intended, `make num-baselines`",
            )
        )
    return findings


def run_num_audit(names=None, baselines: dict | None = None) -> tuple[list[Finding], int]:
    """Audit the given kernels (default: the full plan, model checks
    included) against the committed ulp budgets for the CURRENT tier.
    Returns (findings, number of kernels/model surfaces audited)."""
    from .trace_audit import REGISTRY

    plan = num_plan(names)
    if baselines is None:
        baselines = load_baselines()
    per_kernel = baselines.get("tiers", {}).get(current_tier(), {}).get("kernels", {})

    findings: list[Finding] = []
    audited = 0
    for name in plan:
        if name == "match_probability":
            findings.extend(_check_monotone())
            audited += 1
        elif name == "fold_logit":
            findings.extend(_check_fold_order())
            audited += 1
        else:
            findings.extend(
                audit_kernel_numerics(REGISTRY[name], per_kernel.get(name))
            )
            audited += 1
    return findings, audited


def update_baselines(names=None, path: str = BASELINES_PATH) -> dict:
    """Re-measure ulp budgets for the current tier and rewrite its block
    (other tiers' committed budgets survive verbatim). A full refresh
    replaces the tier's kernel map; a named refresh merges into it."""
    import jax

    from .trace_audit import REGISTRY

    plan = [n for n in num_plan(names) if n not in MODEL_CHECKS]
    tier = current_tier()
    existing = load_baselines(path)
    tiers = dict(existing.get("tiers", {}))
    kernels = {} if names is None else dict(tiers.get(tier, {}).get("kernels", {}))

    for name in plan:
        spec = REGISTRY[name]
        _, args, _ = spec.built()
        measured = _measure_ulp(spec)
        # deterministic measurement; ceil gives integral budgets and a
        # whisker of slack for libm differences within a tier
        kernels[name] = {
            "ulp_budget": float(math.ceil(measured)),
            "corners": ["registered"]
            + [c for c, _ in _kernel_corners(name, args)],
        }

    tiers[tier] = {
        "device": str(jax.devices()[0]),
        "kernels": kernels,
    }
    payload = {
        "_meta": {
            "jax": jax.__version__,
            "refresh": "python -m splink_tpu.analysis --update-num-baselines",
            "semantics": (
                "ulp_budget = ceil(max f32-vs-f64 output divergence in f32 "
                "ulps) on this tier's registered inputs; exceeded -> NA-ULP"
            ),
        },
        "tiers": {t: tiers[t] for t in sorted(tiers)},
    }
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2, sort_keys=True)
        fh.write("\n")
    return payload
