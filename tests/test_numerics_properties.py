"""Numerics properties the log-space model contract promises, the EM
trajectory guard (poisoned batch -> structured halt), and regression
tests for every unguarded log/division site the layer-6 sweep fixed.

Property style: corner inputs (exact 0/1 probabilities, all-null gamma
rows, empty buckets, zero-sum denominators) drive the PUBLIC surfaces —
the corners come from the num_audit corner library so the tests and the
audit agree on what "adversarial but in-contract" means."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from splink_tpu.models.fellegi_sunter import (
    FSParams,
    _safe_log,
    fold_logit,
    log_likelihood,
    match_logit,
    match_probability,
)

# ---------------------------------------------------------------------------
# _safe_log / match_probability corner properties (satellite: property tests)
# ---------------------------------------------------------------------------


def test_safe_log_zero_one_and_tiny():
    x = jnp.asarray([0.0, 1.0, np.finfo(np.float32).tiny], jnp.float32)
    out = np.asarray(_safe_log(x))
    assert np.isfinite(out).all()
    assert out[1] == 0.0
    # log(0) is floored at log(tiny), not -inf
    assert out[0] == out[2] == np.float32(np.log(np.finfo(np.float32).tiny))


def _params(C=3, L=3, lam=0.3, seed=7):
    rng = np.random.default_rng(seed)
    m = rng.dirichlet(np.ones(L), size=C).astype(np.float32)
    u = rng.dirichlet(np.ones(L), size=C).astype(np.float32)
    return FSParams(
        lam=jnp.float32(lam), m=jnp.asarray(m), u=jnp.asarray(u)
    )


def test_all_null_rows_score_the_prior_exactly():
    # a row with every comparison null carries no evidence: both fold
    # orders must return sigmoid(logit(lambda)) bit-exactly
    params = _params()
    G = jnp.full((5, 3), -1, jnp.int8)
    prior = jax.nn.sigmoid(
        _safe_log(params.lam) - _safe_log(1.0 - params.lam)
    )
    p_sum = np.asarray(match_probability(G, params))
    p_fold = np.asarray(jax.nn.sigmoid(fold_logit(G, params)))
    assert (p_sum == float(prior)).all()
    assert (p_fold == float(prior)).all()


def test_exact_zero_one_probabilities_stay_finite():
    # the prob_extremes corner: lambda = 0, hard 0/1 cells in m and u
    m = jnp.zeros((3, 3), jnp.float32).at[:, 0].set(1.0)
    u = jnp.zeros((3, 3), jnp.float32).at[:, -1].set(1.0)
    params = FSParams(lam=jnp.float32(0.0), m=m, u=u)
    rng = np.random.default_rng(0)
    G = jnp.asarray(rng.integers(-1, 3, size=(64, 3)), jnp.int8)
    for fn in (match_probability, match_logit, fold_logit):
        assert np.isfinite(np.asarray(fn(G, params))).all(), fn.__name__
    assert np.isfinite(float(log_likelihood(G, params)))


@pytest.mark.parametrize("x64", [False, True])
def test_fold_parity_one_column(x64):
    # with a single comparison there is only one association order:
    # fold_logit and match_logit must agree bit for bit, f32 and f64
    ctx = jax.enable_x64(x64)
    with ctx:
        params = _params(C=1, L=3)
        if x64:
            params = FSParams(
                lam=jnp.float64(params.lam),
                m=jnp.asarray(params.m, jnp.float64),
                u=jnp.asarray(params.u, jnp.float64),
            )
        G = jnp.asarray([[-1], [0], [1], [2]], jnp.int8)
        a = np.asarray(fold_logit(G, params))
        b = np.asarray(match_logit(G, params))
        assert a.dtype == b.dtype
        assert np.array_equal(a, b)


@pytest.mark.parametrize("x64", [False, True])
def test_fold_parity_eight_columns_bit_identical(x64):
    # match_logit pins fold_logit's left-to-right column order
    # (log_bayes_factor), so the offline score's logit IS the fused serve
    # kernel's at any column count — by construction, not by how a
    # backend happens to lower a reduce (on the TPU a jnp.sum over the
    # column axis left offline and serve a few ulps apart, PR 21)
    ctx = jax.enable_x64(x64)
    with ctx:
        dt = jnp.float64 if x64 else jnp.float32
        params = _params(C=8, L=3, seed=11)
        params = FSParams(
            lam=jnp.asarray(0.3, dt),
            m=jnp.asarray(params.m, dt),
            u=jnp.asarray(params.u, dt),
        )
        rng = np.random.default_rng(3)
        G = jnp.asarray(rng.integers(-1, 3, size=(256, 8)), jnp.int8)
        for wrap in (lambda f: f, jax.jit):
            a = np.asarray(wrap(fold_logit)(G, params))
            b = np.asarray(wrap(match_logit)(G, params))
            assert a.dtype == b.dtype == np.dtype(dt)
            assert np.array_equal(a, b)
        p_fold = np.asarray(jax.nn.sigmoid(fold_logit(G, params)))
        assert np.array_equal(p_fold, np.asarray(match_probability(G, params)))


def test_empty_candidate_bucket_through_fused_serve_kernel():
    # the registered fused-serve inputs ARE an empty bucket (every
    # validity flag False): the kernel must produce fully finite scores
    from splink_tpu.analysis.trace_audit import (
        REGISTRY,
        _ensure_default_registry,
    )

    _ensure_default_registry()
    fn, args, kwargs = REGISTRY["serve_score_fused"].built()
    out = jax.block_until_ready(fn(*args, **kwargs))
    for leaf in jax.tree_util.tree_leaves(out):
        arr = np.asarray(leaf)
        if np.issubdtype(arr.dtype, np.floating):
            assert np.isfinite(arr).all()


# ---------------------------------------------------------------------------
# EM numerics guard (satellite: poisoned batch halts the trajectory)
# ---------------------------------------------------------------------------


class _CaptureSink:
    def __init__(self):
        self.events = []

    def emit(self, type, **fields):
        self.events.append((type, fields))


def test_poisoned_batch_halts_em_with_structured_event():
    from splink_tpu.em import EMNumericsError, run_em_checkpointed
    from splink_tpu.obs.events import register_ambient, unregister_ambient

    rng = np.random.default_rng(5)
    G = jnp.asarray(rng.integers(-1, 3, size=(64, 3)), jnp.int8)
    params = _params()
    # a poisoned batch: one NaN row weight is enough to poison the
    # weighted sufficient statistics and, with them, every new parameter
    weights = jnp.ones((64,), jnp.float32).at[7].set(jnp.nan)

    sink = _CaptureSink()
    register_ambient(sink)
    try:
        with pytest.raises(EMNumericsError) as exc_info:
            run_em_checkpointed(
                G,
                params,
                max_iterations=4,
                max_levels=3,
                em_convergence=1e-4,
                weights=weights,
                compute_ll=True,
                on_segment=lambda *a: None,  # host hook active
            )
    finally:
        unregister_ambient(sink)

    err = exc_info.value
    assert err.iteration == 1
    assert err.last_good_iteration == 0
    assert set(err.fields) >= {"lam", "m", "u"}
    assert err.checkpoint_dir is None

    events = [f for t, f in sink.events if t == "em_numerics"]
    assert len(events) == 1
    assert events[0]["iteration"] == 1
    assert events[0]["fields"] == err.fields
    assert events[0]["last_good_iteration"] == 0


def test_poisoned_batch_leaves_checkpoint_reference(tmp_path):
    # with checkpointing on, the event and the exception point at the
    # directory a restart would resume from
    from splink_tpu.em import EMNumericsError, run_em_checkpointed

    rng = np.random.default_rng(5)
    G = jnp.asarray(rng.integers(-1, 3, size=(64, 3)), jnp.int8)
    weights = jnp.ones((64,), jnp.float32).at[0].set(jnp.inf)

    with pytest.raises(EMNumericsError) as exc_info:
        run_em_checkpointed(
            G,
            _params(),
            max_iterations=4,
            max_levels=3,
            em_convergence=1e-4,
            weights=weights,
            checkpoint_dir=str(tmp_path),
        )
    err = exc_info.value
    assert err.checkpoint_dir == str(tmp_path)
    # the poison hits the very first update, so nothing was persisted
    # yet — the reference must say so rather than invent a boundary
    assert err.last_checkpoint_iteration is None


def test_clean_em_run_unaffected_by_guard():
    from splink_tpu.em import run_em_checkpointed

    rng = np.random.default_rng(5)
    G = jnp.asarray(rng.integers(-1, 3, size=(64, 3)), jnp.int8)
    result = run_em_checkpointed(
        G,
        _params(),
        max_iterations=3,
        max_levels=3,
        em_convergence=1e-6,
        compute_ll=True,
        on_segment=lambda *a: None,
    )
    n = int(result.n_updates)
    assert n >= 1
    assert np.isfinite(np.asarray(result.lam_history[: n + 1])).all()


# ---------------------------------------------------------------------------
# regression tests for the layer-6 sweep's fixed sites
# ---------------------------------------------------------------------------


def test_bayes_combine_contradictory_evidence_is_neutral():
    from splink_tpu.term_frequencies import bayes_combine

    # p=1 and p=0 together: prod(p) = prod(1-p) = 0 — formerly 0/0=NaN,
    # now the no-information posterior
    out = bayes_combine([np.asarray([1.0]), np.asarray([0.0])])
    assert out[0] == 0.5
    # ordinary inputs keep the exact unguarded value
    a, b = 0.9, 0.8
    out = bayes_combine([np.asarray([a]), np.asarray([b])])
    assert out[0] == a * b / (a * b + (1 - a) * (1 - b))


def test_token_adjustment_device_zero_zero_corner():
    from splink_tpu.term_frequencies import compute_token_adjustment_device

    # an agreeing token with match probability 0 under base_lambda 0:
    # num = den = 0 — formerly NaN through the whole adjustment table
    adj, tok_lambda, counts = compute_token_adjustment_device(
        np.asarray([0]), np.asarray([0]), np.asarray([0.0]), 0.0, n_tokens=2
    )
    assert adj[0] == 0.5
    assert np.isfinite(np.asarray(tok_lambda)).all()
    assert np.isfinite(np.asarray(adj)).all()


def test_normalised_all_zero_distribution_is_uniform():
    from splink_tpu.params import _normalised

    assert _normalised([0.0, 0.0, 0.0]) == [1 / 3] * 3
    assert _normalised([2.0, 2.0]) == [0.5, 0.5]


def test_normalise_prob_list_rejects_zero_sum():
    from splink_tpu.settings import normalise_prob_list

    with pytest.raises(ValueError, match="positive sum"):
        normalise_prob_list([0.0, 0.0])
    assert normalise_prob_list([1.0, 3.0]) == [0.25, 0.75]


def test_intuition_zero_filled_level_stays_neutral():
    from types import SimpleNamespace

    from splink_tpu.intuition import _get_adjustment_factors, intuition_report

    params = SimpleNamespace(
        params={
            "π": {
                "gamma_name": {
                    "column_name": "name",
                    "num_levels": 2,
                    "custom_comparison": False,
                }
            },
            "λ": 0.3,
        }
    )
    # EM never observed this gamma value: both probabilities zero-filled
    row = {
        "gamma_name": 0,
        "name_l": "ann",
        "name_r": "bob",
        "prob_gamma_name_match": 0.0,
        "prob_gamma_name_non_match": 0.0,
    }
    factors = _get_adjustment_factors(row, params)
    assert factors[0]["value"] == 0.5  # formerly ZeroDivisionError
    assert factors[0]["normalised"] == 0.0
    report = intuition_report(row, params)
    # the prior must come through unchanged: no evidence either way
    assert "0.3" in report


def test_psi_and_js_finite_on_vanished_bins():
    from splink_tpu.obs.drift import js_divergence, psi

    expected = [100.0, 0.0, 5.0]
    observed = [0.0, 80.0, 5.0]
    # eps=0 leaves hard zeros in both proportion vectors — formerly
    # inf/nan through the unguarded log ratios
    with np.errstate(divide="raise", invalid="raise"):
        p = psi(expected, observed, eps=0.0)
        j = js_divergence(expected, observed, eps=0.0)
    assert np.isfinite(p)
    assert j is not None and 0.0 <= j <= 1.0
    # identical distributions: exactly zero either way
    assert psi(expected, expected, eps=0.0) == 0.0
    assert js_divergence(expected, expected, eps=0.0) == 0.0
    # smoothed path keeps its old values (guard floors below eps)
    assert psi(expected, observed) == pytest.approx(
        psi(expected, observed, eps=1e-4)
    )
