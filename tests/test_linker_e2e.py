"""End-to-end linker tests: the analogue of the reference's test_main_api
(/root/reference/tests/test_spark.py:613-638) — init -> block -> gammas -> EM
-> scores -> save -> load -> rescore -> explain — plus link types and output
column layout."""

import numpy as np
import pandas as pd
import pytest

from splink_tpu import Splink, load_from_json
from splink_tpu.intuition import adjustment_factor_chart, intuition_report


def synth_people(n_base=120, seed=11):
    """Synthetic dataset with planted duplicates (FEBRL-style)."""
    rng = np.random.default_rng(seed)
    firsts = ["amelia", "oliver", "isla", "george", "ava", "noah", "emily", "jack"]
    lasts = ["smith", "jones", "taylor", "brown", "wilson", "evans"]
    rows = []
    uid = 0
    truth = []
    for k in range(n_base):
        f = rng.choice(firsts)
        l = rng.choice(lasts)
        dob = f"19{rng.integers(40, 99)}"
        rows.append({"unique_id": uid, "first_name": f, "surname": l, "dob": dob, "group": k})
        uid += 1
        if rng.random() < 0.35:  # duplicate with a possible typo
            f2 = f
            if rng.random() < 0.4:
                i = rng.integers(0, len(f))
                f2 = f[:i] + chr(97 + rng.integers(26)) + f[i + 1 :]
            rows.append({"unique_id": uid, "first_name": f2, "surname": l, "dob": dob, "group": k})
            truth.append(k)
            uid += 1
    return pd.DataFrame(rows)


def dedupe_settings(**over):
    s = {
        "link_type": "dedupe_only",
        "comparison_columns": [
            {"col_name": "first_name", "num_levels": 3},
            {"col_name": "surname", "num_levels": 2, "comparison": {"kind": "exact"}},
        ],
        "blocking_rules": ["l.dob = r.dob"],
        "max_iterations": 20,
        "additional_columns_to_retain": ["group"],
    }
    s.update(over)
    return s


def test_main_api_roundtrip(tmp_path):
    df = synth_people()
    linker = Splink(dedupe_settings(), df=df)
    df_e = linker.get_scored_comparisons()

    # planted duplicates (same group id) should outscore non-duplicates
    dup = df_e[df_e.group_l == df_e.group_r]
    nondup = df_e[df_e.group_l != df_e.group_r]
    assert len(dup) and len(nondup)
    assert dup.match_probability.mean() > 0.8
    assert nondup.match_probability.mean() < 0.2

    # save -> load -> rescore must reproduce identical probabilities
    path = str(tmp_path / "model.json")
    linker.save_model_as_json(path)
    linker2 = load_from_json(path, df=df)
    df_e2 = linker2.manually_apply_fellegi_sunter_weights()
    np.testing.assert_allclose(
        df_e2.match_probability.to_numpy(),
        df_e.match_probability.to_numpy(),
        rtol=1e-6,
    )

    # intuition report runs on a scored row and ends at its probability
    row = df_e.iloc[0]
    report = intuition_report(row, linker.params)
    assert "Initial probability of match" in report
    assert f"{row.match_probability:.4f}"[:6] in report or "Final probability" in report
    chart = adjustment_factor_chart(row, linker.params)
    assert chart["data"]["values"]


def test_output_column_layout():
    df = synth_people(40)
    linker = Splink(dedupe_settings(), df=df)
    df_e = linker.get_scored_comparisons()
    cols = df_e.columns.tolist()
    assert cols[0] == "match_probability"
    assert cols[1:3] == ["unique_id_l", "unique_id_r"]
    # per-column block: values, gamma, then intermediate probabilities
    i = cols.index("first_name_l")
    assert cols[i : i + 5] == [
        "first_name_l",
        "first_name_r",
        "gamma_first_name",
        "prob_gamma_first_name_non_match",
        "prob_gamma_first_name_match",
    ]
    assert "group_l" in cols and "group_r" in cols


def test_retain_flags_off():
    df = synth_people(40)
    s = dedupe_settings(
        retain_matching_columns=False,
        retain_intermediate_calculation_columns=False,
        additional_columns_to_retain=[],
    )
    linker = Splink(s, df=df)
    df_e = linker.get_scored_comparisons()
    assert "first_name_l" not in df_e.columns
    assert "prob_gamma_first_name_match" not in df_e.columns
    assert "gamma_first_name" in df_e.columns


def test_max_iterations_zero_scores_priors():
    df = synth_people(40)
    s = dedupe_settings(max_iterations=0)
    s["comparison_columns"][0]["m_probabilities"] = [0.1, 0.2, 0.7]
    s["comparison_columns"][0]["u_probabilities"] = [0.7, 0.2, 0.1]
    linker = Splink(s, df=df)
    df_e = linker.get_scored_comparisons()
    assert len(linker.params.param_history) == 0
    assert linker.params.iteration == 1
    # scoring still happened
    assert df_e.match_probability.between(0, 1).all()


def test_link_only_end_to_end():
    df = synth_people(60, seed=3)
    # split base vs duplicate rows into two "datasets"
    df_l = df.drop_duplicates("group", keep="first").reset_index(drop=True)
    df_r = df[~df.index.isin(df.drop_duplicates("group", keep="first").index)].reset_index(drop=True)
    s = dedupe_settings(link_type="link_only")
    linker = Splink(s, df_l=df_l, df_r=df_r)
    df_e = linker.get_scored_comparisons()
    assert len(df_e)
    same = df_e[df_e.group_l == df_e.group_r]
    assert same.match_probability.mean() > 0.5


def test_link_and_dedupe_source_table_columns():
    df = synth_people(40, seed=5)
    half = len(df) // 2
    df_l, df_r = df.iloc[:half].copy(), df.iloc[half:].copy()
    s = dedupe_settings(link_type="link_and_dedupe")
    linker = Splink(s, df_l=df_l, df_r=df_r)
    df_e = linker.get_scored_comparisons()
    assert "_source_table_l" in df_e.columns
    assert set(df_e._source_table_l.unique()) <= {"left", "right"}
    # ordering: never (right, left)
    assert not ((df_e._source_table_l == "right") & (df_e._source_table_r == "left")).any()


def test_wrong_input_combination_raises():
    df = synth_people(10)
    with pytest.raises(ValueError, match="dedupe_only"):
        Splink(dedupe_settings(), df_l=df, df_r=df)
    with pytest.raises(ValueError, match="link_only"):
        Splink(dedupe_settings(link_type="link_only"), df=df)


def test_save_state_fn_called_each_iteration():
    df = synth_people(40)
    calls = []
    linker = Splink(
        dedupe_settings(max_iterations=5, em_convergence=1e-12),
        df=df,
        save_state_fn=lambda p, s: calls.append(p.iteration),
    )
    linker.get_scored_comparisons()
    assert len(calls) == len(linker.params.param_history)


def test_custom_comparison_registered():
    import splink_tpu

    def initials_match(ctx, col_settings):
        import jax.numpy as jnp

        fn = ctx.col("first_name")
        sn = ctx.col("surname")
        eq = (fn.chars_l[:, 0] == fn.chars_r[:, 0]) & (
            sn.chars_l[:, 0] == sn.chars_r[:, 0]
        )
        gamma = eq.astype(jnp.int8)
        return jnp.where(fn.null | sn.null, jnp.int8(-1), gamma)

    splink_tpu.register_comparison("initials_match", initials_match)
    df = synth_people(40)
    s = dedupe_settings()
    s["comparison_columns"].append(
        {
            "custom_name": "initials",
            "custom_columns_used": ["first_name", "surname"],
            "num_levels": 2,
            "comparison": {"kind": "custom", "fn": "initials_match"},
        }
    )
    linker = Splink(s, df=df)
    df_e = linker.get_scored_comparisons()
    assert "gamma_initials" in df_e.columns
    assert set(df_e.gamma_initials.unique()) <= {-1, 0, 1}


def test_release_input_dedupe_scores_identically():
    df = synth_people()
    a = Splink(dedupe_settings(), df=df)
    sa = a.get_scored_comparisons()
    b = Splink(dedupe_settings(), df=df)
    b.release_input()
    assert b.df is None
    sb = b.get_scored_comparisons()
    cols = ["unique_id_l", "unique_id_r", "match_probability"]
    pd.testing.assert_frame_equal(
        sa[cols].sort_values(cols[:2]).reset_index(drop=True),
        sb[cols].sort_values(cols[:2]).reset_index(drop=True),
    )


def test_release_input_link_only_keeps_n_left():
    df = synth_people()
    df_l, df_r = df.iloc[:70].copy(), df.iloc[70:].copy()
    s = dedupe_settings(link_type="link_only")
    a = Splink(s, df_l=df_l, df_r=df_r)
    sa = a.get_scored_comparisons()
    b = Splink(s, df_l=df_l, df_r=df_r)
    b.release_input()
    assert b.df_l is None and b._n_left == 70
    sb = b.get_scored_comparisons()
    cols = ["unique_id_l", "unique_id_r", "match_probability"]
    pd.testing.assert_frame_equal(
        sa[cols].sort_values(cols[:2]).reset_index(drop=True),
        sb[cols].sort_values(cols[:2]).reset_index(drop=True),
    )


def test_float64_setting_enables_x64_in_fresh_process():
    """Outside the test suite (whose conftest enables x64 globally),
    settings float64=True must itself enable jax x64 mode — otherwise jax
    silently downcasts every float64 array to float32 and the setting is a
    no-op."""
    import os
    import subprocess
    import sys

    code = (
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import pandas as pd\n"
        "from splink_tpu import Splink\n"
        "df = pd.DataFrame({'unique_id': [0, 1, 2], 'a': ['x', 'x', 'y']})\n"
        "s = {'link_type': 'dedupe_only',\n"
        "     'comparison_columns': [{'col_name': 'a',\n"
        "                             'comparison': {'kind': 'exact'}}],\n"
        "     'blocking_rules': ['l.a = r.a'], 'float64': True,\n"
        "     'max_iterations': 2}\n"
        "l = Splink(s, df=df)\n"
        "out = l.get_scored_comparisons()\n"
        "assert jax.config.jax_enable_x64, 'x64 not enabled'\n"
        "assert out.match_probability.dtype == 'float64', out.match_probability.dtype\n"
        "print('OK')\n"
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    )
    res = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=300,
    )
    assert res.returncode == 0 and "OK" in res.stdout, res.stdout + res.stderr
