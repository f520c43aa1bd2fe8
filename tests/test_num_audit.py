"""num_audit layer (layer 6, measured half): plan coverage, corner
transforms, the NA-* gates and their falsifiability, and the tier-keyed
ulp-baseline file discipline.

The full-registry clean gate lives in tests/test_codebase_clean.py (same
pattern as the other audit layers); here we exercise the machinery on
cheap kernels so the mechanics are covered without re-running the whole
fleet twice per tier-1 pass."""

import json
import math
import os

import pytest

from splink_tpu.analysis import num_plan, run_num_audit
from splink_tpu.analysis import num_audit as na
from splink_tpu.analysis.num_audit import (
    MODEL_CHECKS,
    audit_kernel_numerics,
    current_tier,
    load_baselines,
    update_baselines,
)
from splink_tpu.analysis.trace_audit import (
    REGISTRY,
    _ensure_default_registry,
)

_ensure_default_registry()


def test_plan_covers_registry_and_model_checks():
    plan = num_plan()
    assert set(plan) == set(REGISTRY) | set(MODEL_CHECKS)
    # model-level surfaces ride in the same plan: the CLI's --num-kernels
    # can name them exactly like registered kernels
    assert "match_probability" in plan and "fold_logit" in plan


def test_unknown_kernel_rejected():
    with pytest.raises(KeyError):
        num_plan(["does_not_exist"])


def test_committed_baselines_cover_every_registered_kernel():
    # the acceptance contract: no registered kernel without a budget
    budgets = (
        load_baselines().get("tiers", {}).get(current_tier(), {}).get("kernels", {})
    )
    assert set(budgets) == set(REGISTRY)
    for name, cell in budgets.items():
        assert cell["ulp_budget"] >= 0, name
        assert cell["corners"][0] == "registered", name


def test_subset_audit_clean_including_model_checks():
    findings, audited = run_num_audit(
        ["tf_gather", "tf_adjustment", "match_probability", "fold_logit"]
    )
    assert audited == 4
    assert not findings, "\n" + "\n".join(f.format() for f in findings)


def test_missing_baseline_is_na_base():
    findings = audit_kernel_numerics(REGISTRY["tf_gather"], None)
    assert [f.rule for f in findings] == ["NA-BASE"]
    assert "num-baselines" in findings[0].hint


def test_ulp_drift_fails_with_a_diff_style_message():
    # the NA-ULP gate must render budget-vs-measured, not just "failed":
    # a doctored budget below any possible measurement trips it
    findings = audit_kernel_numerics(
        REGISTRY["tf_gather"], {"ulp_budget": -1.0}
    )
    rendered = "\n".join(f.format() for f in findings)
    assert "NA-ULP" in rendered
    assert "ulp: budget" in rendered and "measured" in rendered
    assert "tf_gather" in rendered


class _NaNSpec:
    """Minimal stand-in for a registry spec whose kernel leaks a NaN."""

    name = "nan_leaker"

    def built(self):
        import jax.numpy as jnp

        fn = lambda x: jnp.log(x - 1.0)  # noqa: E731 - log(0) at x=1
        return fn, (jnp.ones((4,), jnp.float32),), {}


def test_nan_escape_is_na_fin():
    findings = audit_kernel_numerics(_NaNSpec(), {"ulp_budget": 1e9})
    assert "NA-FIN" in {f.rule for f in findings}
    fin = next(f for f in findings if f.rule == "NA-FIN")
    assert "registered" in fin.message


def test_mono_gate_is_falsifiable(monkeypatch):
    # inverting the probability makes evidence strengthen downward — the
    # monotonicity gate must notice
    import splink_tpu.models.fellegi_sunter as fs

    orig = fs.match_probability
    monkeypatch.setattr(
        fs, "match_probability", lambda G, p: 1.0 - orig(G, p)
    )
    findings = na._check_monotone()
    assert "NA-MONO" in {f.rule for f in findings}


def test_ord_gate_is_falsifiable(monkeypatch):
    # any deviation from the pinned fold — here a uniform nudge — must
    # break bit-identity with the left-to-right reference
    import splink_tpu.models.fellegi_sunter as fs

    orig = fs.fold_logit
    monkeypatch.setattr(
        fs, "fold_logit", lambda G, p: orig(G, p) + 1e-4
    )
    findings = na._check_fold_order()
    assert [f.rule for f in findings] == ["NA-ORD"]
    assert "left-to-right" in findings[0].message


def test_ord_gate_covers_the_offline_logit(monkeypatch):
    # match_logit (the offline score) is held to the same reference: a
    # right-to-left accumulation — what a backend's reduce tree may do —
    # rounds differently and must fire, naming the function
    import jax.numpy as jnp

    import splink_tpu.models.fellegi_sunter as fs

    def right_to_left(G, p):
        ev = fs.gamma_log_probs(G, p.m) - fs.gamma_log_probs(G, p.u)
        acc = jnp.zeros(ev.shape[:-1], ev.dtype)
        for ci in reversed(range(ev.shape[-1])):
            acc = acc + ev[..., ci]
        return fs._safe_log(p.lam) - fs._safe_log(1.0 - p.lam) + acc

    assert na._check_fold_order() == []
    monkeypatch.setattr(fs, "match_logit", right_to_left)
    findings = na._check_fold_order()
    assert [(f.rule, f.path) for f in findings] == [("NA-ORD", "match_logit")]


def test_corner_transforms_only_touch_their_leaves():
    import jax.numpy as jnp

    # no int8 leaf -> all_null does not apply
    assert na._corner_all_null((jnp.ones((3,), jnp.float32),)) is None
    # int8 leaf -> every entry null, other leaves untouched
    args = (
        jnp.zeros((2, 3), jnp.int8),
        jnp.ones((3,), jnp.float32),
    )
    mutated = na._corner_all_null(args)
    assert (jnp.asarray(mutated[0]) == -1).all()
    assert (jnp.asarray(mutated[1]) == 1.0).all()
    # bool mask -> emptied; nothing else applies on float-only args
    assert na._corner_empty((jnp.ones((3,), jnp.float32),)) is None
    emptied = na._corner_empty((jnp.ones((4,), bool),))
    assert not jnp.asarray(emptied[0]).any()


def test_prob_extremes_hits_exact_zero_and_one():
    from splink_tpu.analysis.trace_audit import shared_fs_inputs

    _, params = shared_fs_inputs()
    (new_params,) = na._corner_prob_extremes((params,))
    import numpy as np

    assert float(new_params.lam) == 0.0
    m = np.asarray(new_params.m)
    assert (m[:, 0] == 1.0).all() and (m[:, 1:] == 0.0).all()


def test_update_baselines_preserves_other_tiers(tmp_path):
    path = os.path.join(str(tmp_path), "num_baselines.json")
    foreign = {
        "tiers": {
            "tpu": {"device": "TPU v9", "kernels": {"k": {"ulp_budget": 5.0}}}
        }
    }
    with open(path, "w") as fh:
        json.dump(foreign, fh)

    payload = update_baselines(names=["tf_gather"], path=path)
    with open(path) as fh:
        on_disk = json.load(fh)
    assert on_disk == payload
    # the foreign tier's committed budgets survive verbatim
    assert on_disk["tiers"]["tpu"] == foreign["tiers"]["tpu"]
    tier = current_tier()
    cell = on_disk["tiers"][tier]["kernels"]["tf_gather"]
    assert cell["ulp_budget"] == math.ceil(cell["ulp_budget"])


def test_em_history_padding_is_contract_not_finding():
    # EMResult NaN-pads histories beyond n_updates; the finite checker
    # must accept the padding and still reject a NaN INSIDE the prefix
    import jax.numpy as jnp

    from splink_tpu.em import run_em
    from splink_tpu.analysis.trace_audit import shared_fs_inputs

    G, params = shared_fs_inputs()
    out = run_em(
        G,
        params,
        max_iterations=2,
        max_levels=3,
        em_convergence=1e-4,
        compute_ll=True,
    )
    assert na._finite_em(out) == []

    poisoned = out._replace(
        ll_history=out.ll_history.at[0].set(jnp.nan)
    )
    assert na._finite_em(poisoned)
