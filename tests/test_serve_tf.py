"""TF-fused serving (ISSUE 14 tentpole a): the term-frequency
u-probability fold in the serve megakernel and its offline twin.

The contract under test (docs/serving.md#term-frequency-adjustment):

  * serve<->offline TF-adjusted parity is BIT-identity (f32 and f64): the
    engine's top_p equals the offline frame's ``tf_match_probability``
    for the same pair exactly;
  * fused<->unfused TF parity is exact (the unfused program stays the
    oracle);
  * the fold has teeth: a pair agreeing on a RARE token outscores an
    otherwise-identical pair agreeing on a COMMON token;
  * legacy artifacts — TF-less indexes, and TF indexes built before the
    fold (counts but no per-row token ids) — serve exactly as before;
  * the AOT sidecar binding carries the tf flag (a sidecar saved either
    way never serves the other configuration) and steady-state serving
    with TF on performs zero compile requests;
  * the quality observatory re-anchors: a TF-serving engine over a
    profile captured from UNADJUSTED scores goes dark on the score drift
    channel instead of firing a spurious alert;
  * the new kernel registrations are falsifiable (broken twins trip
    TA-DTYPE / SA-COLL).
"""

import numpy as np
import pandas as pd
import pytest

from splink_tpu import Splink
from splink_tpu.serve import BucketPolicy, QueryEngine, load_index

N = 100


def people_df(n=N, seed=11):
    rng = np.random.default_rng(seed)
    firsts = ["amelia", "oliver", "isla", "george", "ava", "noah", "emily"]
    # "smith" dominates; "zorn" is rare — the fold's motivating skew
    lasts = ["smith"] * 8 + ["jones", "taylor", "zorn"]
    return pd.DataFrame(
        {
            "unique_id": range(n),
            "first_name": [str(rng.choice(firsts)) for _ in range(n)],
            "surname": [str(rng.choice(lasts)) for _ in range(n)],
            "dob": [f"19{rng.integers(40, 99)}" for _ in range(n)],
        }
    )


def tf_settings(**over):
    s = {
        "link_type": "dedupe_only",
        "comparison_columns": [
            {
                "col_name": "first_name",
                "num_levels": 3,
                "term_frequency_adjustments": True,
            },
            {
                "col_name": "surname",
                "num_levels": 2,
                "comparison": {"kind": "exact"},
                "term_frequency_adjustments": True,
            },
        ],
        "blocking_rules": ["l.dob = r.dob", "l.surname = r.surname"],
        "max_iterations": 6,
    }
    s.update(over)
    return s


@pytest.fixture(scope="module")
def trained():
    df = people_df()
    linker = Splink(tf_settings(), df=df)
    df_e = linker.get_scored_comparisons()
    index = linker.export_index()
    return df, linker, df_e, index


@pytest.fixture(scope="module")
def engine(trained):
    _, _, _, index = trained
    eng = QueryEngine(
        index, top_k=64, policy=BucketPolicy((16, 128), (64, 256))
    )
    eng.warmup()
    return eng


def _score_map(df_e, col):
    return {
        (r["unique_id_l"], r["unique_id_r"]): r[col]
        for _, r in df_e.iterrows()
    }


def _assert_parity(df, df_e, index, top_p, top_rows, top_valid, col,
                   cast=np.float32):
    offline = _score_map(df_e, col)
    checked = 0
    for q in range(len(df)):
        for r in range(top_p.shape[1]):
            if not top_valid[q, r]:
                continue
            m = int(index.unique_id[top_rows[q, r]])
            if m == q:
                continue
            key = (min(q, m), max(q, m))
            assert key in offline, f"served pair {key} missing offline"
            assert cast(offline[key]) == top_p[q, r], key
            checked += 1
    assert checked > 100
    return checked


def test_offline_frame_carries_tf_match_probability(trained):
    _, _, df_e, _ = trained
    assert "tf_match_probability" in df_e.columns
    # the fold moves scores: agreeing pairs shift, disagreeing are exact
    assert not np.array_equal(
        df_e["tf_match_probability"].to_numpy(),
        df_e["match_probability"].to_numpy(),
    )


def test_tf_serve_offline_parity_bit_identical(trained, engine):
    """Every served score equals the offline TF-adjusted score for the
    same pair bitwise — the fold is one expression, not two."""
    df, _, df_e, index = trained
    assert engine.tf_active
    top_p, top_rows, top_valid, _ = engine.query_arrays(df)
    assert top_p.dtype == np.float32
    _assert_parity(df, df_e, index, top_p, top_rows, top_valid,
                   "tf_match_probability")


def test_tf_parity_float64_tier():
    df = people_df(60, seed=3)
    linker = Splink(tf_settings(float64=True, max_iterations=3), df=df)
    df_e = linker.get_scored_comparisons()
    index = linker.export_index()
    eng = QueryEngine(index, top_k=64, policy=BucketPolicy((64,), (128,)))
    assert eng.tf_active
    top_p, top_rows, top_valid, _ = eng.query_arrays(df)
    assert top_p.dtype == np.float64
    offline = _score_map(df_e, "tf_match_probability")
    checked = 0
    for q in range(len(df)):
        for r in range(top_p.shape[1]):
            if not top_valid[q, r]:
                continue
            m = int(index.unique_id[top_rows[q, r]])
            if m == q:
                continue
            assert offline[(min(q, m), max(q, m))] == top_p[q, r]
            checked += 1
    assert checked > 50


def test_fused_unfused_tf_parity_exact(trained, engine):
    df, _, _, index = trained
    top_p, top_rows, top_valid, n_cand = engine.query_arrays(df)
    oracle = QueryEngine(
        index, top_k=64, policy=BucketPolicy((16, 128), (64, 256)),
        fused=False,
    )
    p2, r2, v2, nc2 = oracle.query_arrays(df)
    assert np.array_equal(p2, top_p)
    assert np.array_equal(r2, top_rows)
    assert np.array_equal(v2, top_valid)
    assert np.array_equal(nc2, n_cand)


def test_tf_off_engine_serves_unadjusted(trained):
    """tf_adjust=False over the same index reproduces the UNADJUSTED
    scores — the legacy behaviour, selectable per engine."""
    df, _, df_e, index = trained
    eng = QueryEngine(
        index, top_k=64, policy=BucketPolicy((16, 128), (64, 256)),
        tf_adjust=False,
    )
    assert not eng.tf_active
    top_p, top_rows, top_valid, _ = eng.query_arrays(df)
    _assert_parity(df, df_e, index, top_p, top_rows, top_valid,
                   "match_probability")


def test_rare_token_agreement_outscores_common(trained, engine):
    """The motivating claim: with identical gamma vectors, agreeing on
    the rare surname is stronger evidence than agreeing on the dominant
    one — TF-adjusted scores order them; unadjusted scores cannot."""
    _, _, df_e, _ = trained
    agree = df_e[df_e["surname_l"] == df_e["surname_r"]]
    # restrict to rows with the same gamma vector so the ONLY difference
    # is the agreed token's frequency
    gcols = [c for c in df_e.columns if c.startswith("gamma_")]
    key = agree[gcols].astype(str).agg("|".join, axis=1)
    counts = df_e["surname_l"].value_counts()
    found = False
    for _, grp in agree.groupby(key):
        toks = grp["surname_l"].unique()
        if len(toks) < 2:
            continue
        rare = min(toks, key=lambda t: counts.get(t, 0))
        common = max(toks, key=lambda t: counts.get(t, 0))
        if counts.get(rare, 0) == counts.get(common, 0):
            continue
        p_rare = grp[grp["surname_l"] == rare]["tf_match_probability"]
        p_common = grp[grp["surname_l"] == common]["tf_match_probability"]
        p_un = grp["match_probability"]
        assert p_un.nunique() == 1  # unadjusted: identical by construction
        assert float(p_rare.iloc[0]) > float(p_common.iloc[0])
        found = True
        break
    assert found, "corpus held no same-gamma rare/common agreement pair"


def test_streamed_offline_path_matches_one_frame(trained):
    """The streamed/pattern offline path carries the SAME fold column,
    bit-identical to the one-frame path (offline<->offline parity across
    regimes)."""
    import copy

    df, linker0, df_e, _ = trained
    linker = Splink(
        tf_settings(max_resident_pairs=1024, device_pair_generation="off"),
        df=df,
    )
    # same fitted params as the fixture (a fresh EM would drift in FP);
    # scoring-only through the pattern-LUT regime
    linker.params = copy.deepcopy(linker0.params)
    streamed = linker.manually_apply_fellegi_sunter_weights()
    assert linker._regime("patterns")
    assert "tf_match_probability" in streamed.columns
    for col in ("match_probability", "tf_match_probability"):
        one = _score_map(df_e, col)
        two = _score_map(streamed, col)
        assert set(one) == set(two)
        for k in one:
            assert np.float32(one[k]) == np.float32(two[k]), (col, k)


def test_legacy_tf_index_without_tids_serves_unadjusted(trained, caplog):
    """An artifact with count tables but NO per-row token ids (built
    before the fold) serves unadjusted with a one-time warning — never a
    crash, never a silently wrong fold."""
    import logging

    df, _, df_e, index = trained
    import copy

    stripped = copy.copy(index)
    stripped.tf_tids = {}
    stripped._tf_device = None
    stripped._device = None
    stripped._content_fp = None
    with caplog.at_level(logging.WARNING, logger="splink_tpu"):
        eng = QueryEngine(
            stripped, top_k=64, policy=BucketPolicy((128,), (256,))
        )
    assert not eng.tf_active
    assert any("UNADJUSTED" in r.message for r in caplog.records)
    top_p, top_rows, top_valid, _ = eng.query_arrays(df)
    _assert_parity(df, df_e, index, top_p, top_rows, top_valid,
                   "match_probability")


def test_tf_index_save_load_roundtrip(tmp_path, trained, engine):
    df, _, _, index = trained
    index.save(tmp_path)
    loaded = load_index(tmp_path)
    assert sorted(loaded.tf_tids) == sorted(index.tf_tids)
    for name in index.tf_tids:
        assert np.array_equal(loaded.tf_tids[name], index.tf_tids[name])
    assert loaded.content_fingerprint() == index.content_fingerprint()
    eng = QueryEngine(
        loaded, top_k=64, policy=BucketPolicy((16, 128), (64, 256))
    )
    assert eng.tf_active
    p1, r1, v1, _ = engine.query_arrays(df)
    p2, r2, v2, _ = eng.query_arrays(df)
    assert np.array_equal(p1, p2)
    assert np.array_equal(r1, r2)
    assert np.array_equal(v1, v2)


def test_aot_binding_carries_tf_flag(tmp_path, trained):
    """The sidecar binding's tf flag: a menu saved TF-on restores only
    into a TF-on engine; a TF-off engine over the same sidecar falls back
    to fresh compiles (wrong executables are never served)."""
    _, _, _, index = trained
    policy = BucketPolicy((16,), (64,))
    aot = tmp_path / "aot"
    eng = QueryEngine(index, top_k=8, policy=policy, aot_dir=aot)
    assert eng._aot_binding()["tf"] is True
    eng.warmup()
    eng.save_aot()
    restored = QueryEngine(index, top_k=8, policy=policy, aot_dir=aot)
    stats = restored.warmup()
    assert stats["aot_restored"] == stats["combinations"]
    assert stats["compiles"] == 0
    off = QueryEngine(
        index, top_k=8, policy=policy, aot_dir=aot, tf_adjust=False
    )
    assert off._aot_binding()["tf"] is False
    stats_off = off.warmup()
    assert stats_off["aot_restored"] == 0  # binding mismatch -> no restore


def test_zero_steady_state_compile_requests_with_tf(trained, engine):
    from splink_tpu.obs.metrics import (
        compile_requests,
        install_compile_monitor,
    )

    df, _, _, _ = trained
    install_compile_monitor()
    engine.query_arrays(df)  # warm any residual shape
    c0 = compile_requests()
    for start in (0, 20, 40):
        engine.query_arrays(df.iloc[start : start + 15])
    assert compile_requests() == c0


def test_profile_tf_adjusted_flag_and_drift_reanchor(trained):
    """Quality-observatory compat: a TF model's profile records
    tf_adjusted; a LEGACY profile (unadjusted scores) under a TF-serving
    engine makes the drift monitor's score channel report psi None with
    a reason — no spurious drift_alert on swap — while gamma channels
    stay live."""
    from splink_tpu.obs.drift import DriftMonitor, WindowSketch
    from splink_tpu.obs.quality import capture_profile

    df, linker, _, _ = trained
    profile = capture_profile(linker)
    assert profile is not None and profile.tf_adjusted
    assert profile.to_meta()["tf_adjusted"] is True
    # simulate the pre-PR artifact: same histograms, unadjusted flag
    profile.tf_adjusted = False
    monitor = DriftMonitor(
        profile, window_s=1.0, alert_psi=0.01, score_reference=False
    )
    bins = profile.bins
    n_cols = len(profile.columns)
    width = max(profile.num_levels) + 1
    # a wildly skewed served-score window that WOULD alert on the score
    # channel if it were live
    score = np.zeros(bins, np.int64)
    score[-1] = 10_000
    gamma = np.asarray(profile.gamma_hist_matched[:, :width], np.int64)
    for _ in range(12):
        monitor.observe(
            WindowSketch(
                0.0, gamma.copy(), score.copy(),
                {"queries": 1000, "oov": 0, "exact_miss": 0,
                 "approx_served": 0, "degraded": 0,
                 "nulls": np.zeros(n_cols, np.int64)},
                score_all=score.copy(),
            )
        )
    drift = monitor.window_drift(1.0)
    assert drift["channels"]["score"]["psi"] is None
    assert drift["channels"]["score"]["reason"] == (
        "reference_scores_unadjusted"
    )
    assert not any(
        a["channel"] == "score" for a in monitor.alerts()
    )


def test_service_dark_score_channel_for_legacy_profile():
    """End to end through LinkageService._make_drift_monitor: a TF-active
    engine over a profile with tf_adjusted=False gets score_reference
    False."""
    from splink_tpu.serve import LinkageService

    df = people_df(60, seed=5)
    linker = Splink(
        tf_settings(max_iterations=3, quality_profile=True), df=df
    )
    linker.get_scored_comparisons()
    index = linker.export_index()
    assert index.profile is not None and index.profile.tf_adjusted
    index.profile.tf_adjusted = False  # simulate pre-PR artifact
    eng = QueryEngine(
        index, top_k=8, policy=BucketPolicy((16,), (64,)), sketch=True
    )
    eng.warmup()
    svc = LinkageService(eng)
    try:
        assert svc._drift is not None
        assert svc._drift.score_reference is False
        drift = svc._drift.window_drift(svc._drift.window_s)
        assert drift is None or (
            drift["channels"]["score"]["psi"] is None
        )
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# Audit falsifiability twins
# ---------------------------------------------------------------------------


def test_tf_kernels_registered_and_clean():
    from splink_tpu.analysis.trace_audit import run_audit

    findings, audited = run_audit(["serve_score_fused_tf"])
    assert audited == 1
    assert not findings, "\n".join(f.format() for f in findings)


def test_tf_shard_kernel_registered_and_clean():
    from splink_tpu.analysis.shard_audit import run_shard_audit

    findings, audited = run_shard_audit(["serve_score_fused_tf_sharded"])
    assert audited == 1
    assert not findings, "\n".join(f.format() for f in findings)


def test_bad_tf_fold_trips_ta_dtype():
    """A doctored fold whose log-frequency table is float64 leaks the
    wide dtype through the delta arithmetic under the forced-x64 trace —
    TA-DTYPE fires."""
    from splink_tpu.analysis.trace_audit import KernelSpec, audit_kernel

    def build():
        import jax.numpy as jnp

        from splink_tpu.term_frequencies import tf_fold_delta

        def bad(tid_l, tid_r, log_u_top):
            table = jnp.asarray(
                np.linspace(-5.0, -1.0, 8)  # float64 under x64
            )
            return tf_fold_delta(
                tid_l, tid_r, table, log_u_top, table.dtype
            )

        tid = jnp.zeros(32, jnp.int32)
        return bad, (tid, tid, jnp.float32(-0.5)), {}

    spec = KernelSpec(name="bad_tf_fold_dtype", build=build)
    findings = audit_kernel(spec)
    assert any(f.rule == "TA-DTYPE" for f in findings), [
        f.format() for f in findings
    ]


def test_bad_tf_gather_trips_sa_coll():
    """A twin that shards the reference token-id table over the pair axis
    forces GSPMD to all-gather it for the candidate gather — SA-COLL
    fires (the production kernel replicates the table)."""
    from splink_tpu.analysis.shard_audit import (
        audit_shard_kernel,
        register_shard_kernel,
    )

    registry: dict = {}

    @register_shard_kernel(
        "bad_tf_gather_sharded", n_pairs=64, registry=registry
    )
    def _build():
        import jax

        from splink_tpu.analysis.shard_audit import audit_mesh
        from splink_tpu.parallel.mesh import pair_sharding

        mesh = audit_mesh()
        shard = pair_sharding(mesh)
        tid_ref = jax.device_put(np.zeros(64, np.int32), shard)  # WRONG
        cand = jax.device_put(np.zeros(64, np.int32), shard)

        def bad(tid_ref, cand):
            return tid_ref[cand]

        return bad, (tid_ref, cand), {}

    findings = audit_shard_kernel(registry["bad_tf_gather_sharded"], None)
    assert any(f.rule == "SA-COLL" for f in findings), [
        f.format() for f in findings
    ]
