"""Observability layer: intuition report, charts, block diagnostics.

Parity targets: intuition narrative (/root/reference/splink/intuition.py:32-92),
chart methods + combined HTML (/root/reference/splink/params.py:358-484,
chart_definitions.py:248-277), get_largest_blocks
(/root/reference/splink/comparison_evaluation.py:12-34).
"""

import json

import numpy as np
import pandas as pd
import pytest

from splink_tpu import Splink
from splink_tpu.comparison_evaluation import get_largest_blocks
from splink_tpu.intuition import adjustment_factor_chart, intuition_report


@pytest.fixture
def trained_linker():
    rng = np.random.default_rng(11)
    firsts = np.array(["amelia", "oliver", "isla", "george"])
    n = 120
    df = pd.DataFrame(
        {
            "unique_id": np.arange(n),
            "first_name": firsts[rng.integers(0, 4, n)],
            "surname": np.array(["smith", "jones", "taylor"])[rng.integers(0, 3, n)],
            "city": [f"c{i % 3}" for i in range(n)],
        }
    )
    settings = {
        "link_type": "dedupe_only",
        "blocking_rules": ["l.city = r.city"],
        "comparison_columns": [
            {"col_name": "first_name", "num_levels": 2, "comparison": {"kind": "exact"}},
            {"col_name": "surname", "num_levels": 2, "comparison": {"kind": "exact"}},
        ],
        "retain_intermediate_calculation_columns": True,
        "retain_matching_columns": True,
        "max_iterations": 5,
    }
    linker = Splink(settings, df=df)
    df_e = linker.get_scored_comparisons(compute_ll=True)
    return linker, df_e


def test_intuition_report_narrative(trained_linker):
    linker, df_e = trained_linker
    row = df_e.iloc[0]
    text = intuition_report(row, linker.params)
    assert "Initial probability of match (prior)" in text
    assert "Comparison of first_name" in text
    assert "Comparison of surname" in text
    assert "Adjustment factor = m/(m + u)" in text
    # the narrative's final probability equals the scored probability
    final = float(text.strip().rsplit("=", 1)[1])
    assert final == pytest.approx(float(row["match_probability"]), abs=1e-4)


def test_intuition_report_requires_intermediates(trained_linker):
    linker, df_e = trained_linker
    row = df_e.iloc[0].drop(labels=["prob_gamma_first_name_match"])
    with pytest.raises(KeyError, match="retain_intermediate_calculation_columns"):
        intuition_report(row, linker.params)


def test_adjustment_factor_chart(trained_linker):
    linker, df_e = trained_linker
    spec = adjustment_factor_chart(df_e.iloc[0], linker.params)
    rows = spec["data"]["values"]
    assert {r["col_name"] for r in rows} == {"first_name", "surname"}
    for r in rows:
        assert abs(r["normalised"]) <= 0.5
        assert r["value"] == pytest.approx(r["normalised"] + 0.5)


def test_params_charts_and_html(tmp_path, trained_linker):
    linker, _ = trained_linker
    p = linker.params
    for method in (
        "pi_iteration_chart",
        "lambda_iteration_chart",
        "ll_iteration_chart",
        "probability_distribution_chart",
        "adjustment_factor_chart",
    ):
        spec = getattr(p, method)()
        assert isinstance(spec, dict) and "data" in spec
        json.dumps(spec)  # must be JSON-serialisable

    out = tmp_path / "charts.html"
    p.all_charts_write_html_file(str(out))
    html = out.read_text()
    assert "vega" in html.lower()
    with pytest.raises(ValueError):  # overwrite guard
        p.all_charts_write_html_file(str(out))
    p.all_charts_write_html_file(str(out), overwrite=True)


def test_get_largest_blocks():
    df = pd.DataFrame(
        {
            "first_name": ["a", "a", "a", "b", "b", None, "c"],
            "surname": ["x"] * 7,
        }
    )
    top = get_largest_blocks("l.first_name = r.first_name", df, limit=2)
    assert top.iloc[0]["first_name"] == "a"
    assert top.iloc[0]["count"] == 3
    assert len(top) == 2

    two_col = get_largest_blocks(
        "l.first_name = r.first_name and l.surname = r.surname", df
    )
    assert list(two_col.columns) == ["first_name", "surname", "count"]

    with pytest.raises(ValueError):
        get_largest_blocks("something invalid", df)


def test_intuition_report_with_case_sql_column():
    """The per-row intuition narrative and waterfall work when a comparison
    is a compiled hand-written CASE expression (kind case_sql)."""
    import numpy as np
    import pandas as pd

    from splink_tpu import Splink
    from splink_tpu.intuition import intuition_report

    rng = np.random.default_rng(2)
    n = 120
    df = pd.DataFrame(
        {
            "unique_id": np.arange(n),
            "name": rng.choice(["ann", "bob", "cat", "dan"], n),
            "city": rng.choice(["x", "y"], n),
        }
    )
    s = {
        "link_type": "dedupe_only",
        "blocking_rules": ["l.city = r.city"],
        "comparison_columns": [
            {
                "col_name": "name",
                "num_levels": 3,
                "case_expression": """case
                    when name_l is null or name_r is null then -1
                    when name_l = name_r then 2
                    when jaro_winkler_sim(name_l, name_r) > 0.7 then 1
                    else 0 end""",
            }
        ],
        "retain_intermediate_calculation_columns": True,
        "max_iterations": 4,
    }
    linker = Splink(s, df=df)
    df_e = linker.get_scored_comparisons()
    row = df_e.iloc[0]
    report = intuition_report(row, linker.params)
    assert "Initial probability of match" in report
    assert "gamma_name" in report


def test_stage_timings_recorded_through_pipeline():
    """StageTimer records encode/blocking/gammas/em wall times during a
    linker run — the structured-profiling analogue of the reference logging
    each stage's generated SQL."""
    import numpy as np
    import pandas as pd

    from splink_tpu import Splink
    from splink_tpu.utils.profiling import reset_timings, stage_timings

    rng = np.random.default_rng(4)
    n = 100
    df = pd.DataFrame(
        {
            "unique_id": np.arange(n),
            "name": rng.choice(["a", "b", "c"], n),
            "city": rng.choice(["x", "y"], n),
        }
    )
    s = {
        "link_type": "dedupe_only",
        "blocking_rules": ["l.city = r.city"],
        "comparison_columns": [
            {"col_name": "name", "comparison": {"kind": "exact"}}
        ],
        "max_iterations": 3,
    }
    reset_timings()
    Splink(s, df=df).get_scored_comparisons()
    t = stage_timings()
    for stage in ("encode", "blocking", "gammas", "em"):
        assert stage in t and t[stage][0] >= 0, (stage, t.keys())


def test_spill_sweep_reclaims_recycled_pid_dirs(tmp_path):
    """A stale splink_pairs_* dir whose recorded pid was recycled by an
    unrelated live process is reclaimed (the start-time token detects the
    reuse); a dir owned by a genuinely live process is kept."""
    import os

    from splink_tpu.blocking import (
        _owner_token,
        _proc_start_time,
        _sweep_stale_spill_dirs,
    )

    spill = tmp_path / "spill"
    spill.mkdir()

    # pid 1 is always alive; recording a WRONG start time simulates a dir
    # written by a dead process whose pid was later recycled
    recycled = spill / "splink_pairs_recycled"
    recycled.mkdir()
    live_start = _proc_start_time(1)
    assert live_start is not None  # linux /proc available in CI
    (recycled / "owner.pid").write_text(f"1 {live_start + 12345}")

    # same pid with the CORRECT start time: a live owner, must be kept
    kept = spill / "splink_pairs_live"
    kept.mkdir()
    (kept / "owner.pid").write_text(_owner_token(1))

    # dead pid: reclaimed regardless of token format (legacy single-field)
    dead = spill / "splink_pairs_dead"
    dead.mkdir()
    dead_pid = 1
    for cand in range(300000, 400000):
        if not os.path.exists(f"/proc/{cand}"):
            dead_pid = cand
            break
    (dead / "owner.pid").write_text(str(dead_pid))

    _sweep_stale_spill_dirs(str(spill))
    assert not recycled.exists(), "recycled-pid orphan not reclaimed"
    assert kept.exists(), "live owner's dir must not be touched"
    assert not dead.exists(), "dead-pid orphan not reclaimed"


@pytest.fixture
def fresh_cache_state(monkeypatch):
    """The compile-cache module as a fresh process sees it (no directory
    applied yet), with jax's own cache config restored afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as jcc

    from splink_tpu.utils import compile_cache

    saved = {
        name: getattr(jax.config, name)
        for name in (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
        )
    }
    monkeypatch.setattr(compile_cache, "_applied", None)
    yield compile_cache
    for name, value in saved.items():
        jax.config.update(name, value)
    jcc.reset_cache()


def _expected_default_dir():
    import os

    import splink_tpu
    from splink_tpu.utils.envfp import cpu_target_fingerprint

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(splink_tpu.__file__)))
    return os.path.join(
        checkout, ".jax_cache", f"cpu-{cpu_target_fingerprint()[:16]}"
    )


def test_cache_dir_env_var_wins_and_nothing_else_is_set(
    fresh_cache_state, monkeypatch, tmp_path
):
    """JAX_COMPILATION_CACHE_DIR set: that directory and no other — the
    code sets nothing but the two thresholds that make small programs
    cacheable."""
    import jax

    cc = fresh_cache_state
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 9.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 99)
    got = cc.enable_compilation_cache(str(tmp_path / "from_settings"))
    assert got == str(tmp_path / "outside")
    assert jax.config.jax_compilation_cache_dir == before
    assert cc._applied is None
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.5
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0


def test_cache_dir_unset_is_the_fixed_in_checkout_path(
    fresh_cache_state, monkeypatch, tmp_path
):
    """Variable unset: one fixed directory inside the checkout, resolved
    from the package's location (CPU entries under the target-fingerprint
    subdirectory — XLA:CPU executables pin exact machine features); the
    thresholds apply this way too; the first caller wins for the process;
    the linker and a serve-only engine land in the same place."""
    import jax
    import pandas as pd

    from splink_tpu import Splink
    from splink_tpu.serve import QueryEngine

    cc = fresh_cache_state
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    expect = _expected_default_dir()
    assert ".cache" not in expect and "tmp" not in expect
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 9.0)
    assert cc.enable_compilation_cache() == expect
    assert jax.config.jax_compilation_cache_dir == expect
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.5
    # first caller wins: a later explicit setting never moves the cache
    assert cc.enable_compilation_cache(str(tmp_path / "late")) == expect

    df = pd.DataFrame(
        {"unique_id": range(40), "name": ["ann", "bob"] * 20,
         "dob": [f"d{k % 5}" for k in range(40)]}
    )
    s = {
        "link_type": "dedupe_only",
        "comparison_columns": [{"col_name": "name", "num_levels": 2}],
        "blocking_rules": ["l.dob = r.dob"],
        "max_iterations": 1,
    }
    for build in (
        lambda: Splink(dict(s), df=df),
        lambda: QueryEngine(Splink(dict(s), df=df).export_index()),
    ):
        monkeypatch.setattr(cc, "_applied", None)
        jax.config.update("jax_compilation_cache_dir", None)
        build()
        assert jax.config.jax_compilation_cache_dir == expect


def test_cache_dir_setting_applies_when_variable_unset(
    fresh_cache_state, monkeypatch, tmp_path
):
    """settings["compilation_cache_dir"] places the cache when the
    variable is unset, and entries actually land there."""
    import os

    import jax
    import pandas as pd
    from jax.experimental.compilation_cache import compilation_cache as jcc

    from splink_tpu import Splink
    from splink_tpu.utils.envfp import cpu_target_fingerprint

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cache = tmp_path / "xla"
    expect = os.path.join(str(cache), f"cpu-{cpu_target_fingerprint()[:16]}")
    df = pd.DataFrame(
        {"unique_id": range(100), "name": ["ann", "bob"] * 50,
         "dob": [f"d{k % 7}" for k in range(100)]}
    )
    s = {
        "link_type": "dedupe_only",
        "comparison_columns": [{"col_name": "name", "num_levels": 2}],
        "blocking_rules": ["l.dob = r.dob"],
        "max_iterations": 1,
        "compilation_cache_dir": str(cache),
    }
    linker = Splink(s, df=df)
    assert jax.config.jax_compilation_cache_dir == expect
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # drop in-process executable caches (earlier tests may have compiled
    # these shapes; only a real compile persists) and jax's cache object,
    # bound to the first directory this process initialised with
    jcc.reset_cache()
    jax.clear_caches()
    linker.get_scored_comparisons()
    entries = [f for _root, _dirs, files in os.walk(cache) for f in files]
    assert entries, "no compiled executables persisted"


# ----------------------------------------------------------------------
# Run-scoped profiling (utils/profiling.py): span tables are keyed by run
# id — two linkers in one process do not interleave their timings
# (tests/test_spans.py covers the span table itself).
# ----------------------------------------------------------------------


def _tiny_df(n=60, seed=0):
    import numpy as np
    import pandas as pd

    rng = np.random.default_rng(seed)
    return pd.DataFrame(
        {
            "unique_id": np.arange(n),
            "name": rng.choice(["a", "b", "c"], n),
            "city": rng.choice(["x", "y"], n),
        }
    )


def _tiny_settings(**over):
    s = {
        "link_type": "dedupe_only",
        "comparison_columns": [
            {"col_name": "name", "comparison": {"kind": "exact"}}
        ],
        "blocking_rules": ["l.city = r.city"],
        "max_iterations": 2,
    }
    s.update(over)
    return s


def test_timings_scoped_per_linker_run():
    """Two linkers record into separate run scopes; stage_timings() reads
    the CURRENT run and stage_timings(run=...) a specific linker's."""
    from splink_tpu import Splink
    from splink_tpu.utils.profiling import stage_timings

    a = Splink(_tiny_settings(), df=_tiny_df(seed=1))
    a.get_scored_comparisons()
    t_a = stage_timings(run=a.run_id)
    assert "em" in t_a and len(t_a["em"]) == 1

    # constructing linker B opens (and makes current) a FRESH scope
    b = Splink(_tiny_settings(), df=_tiny_df(seed=2))
    assert stage_timings() == {}
    b.get_scored_comparisons()
    assert len(stage_timings(run=b.run_id)["em"]) == 1
    # A's record is untouched by B's run (the old process-global _TIMINGS
    # would have interleaved them)
    assert stage_timings(run=a.run_id) == t_a

    # interleaved construction: A2 built BEFORE B2 runs still records into
    # its own scope when driven afterwards
    a2 = Splink(_tiny_settings(), df=_tiny_df(seed=3))
    b2 = Splink(_tiny_settings(), df=_tiny_df(seed=4))
    b2.get_scored_comparisons()
    a2.get_scored_comparisons()
    assert len(stage_timings(run=a2.run_id)["em"]) == 1
    assert len(stage_timings(run=b2.run_id)["em"]) == 1


