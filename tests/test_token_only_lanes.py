"""A column every comparison reads through its token alone is packed as that
token lane alone.

``gammas.token_only_columns`` names the string columns whose every reader is
an ``exact`` comparison on its own input column; ``pack_table`` gives each of
them one lane (its token, -1 for null) instead of characters, a length and a
token. Held here: the set for every configuration the benchmark runs and for
settings where an exact column is read some other way too; the narrowed
program gives the gamma matrix, the pattern ids, the virtual pass and the
scored frame of the program packed with every lane, bit for bit, nulls
present; the ``pack_table`` span's ``token_only_columns`` and ``lanes``
counts. The packed width of each configuration:
``tests/test_gamma_one_body.py``.
"""

import copy
import json
import os
import sys
import warnings

import numpy as np
import pandas as pd
import pytest

import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import datagen  # noqa: E402
from splink_tpu import Splink, gammas, register_comparison  # noqa: E402
from splink_tpu.gammas import GammaProgram, token_only_columns  # noqa: E402
from splink_tpu.pairgen import build_virtual_plan, make_virtual_pattern_fn  # noqa: E402
from splink_tpu.settings import complete_settings_dict  # noqa: E402
from splink_tpu.utils.profiling import spans  # noqa: E402

CONFIGS = os.path.join(ROOT, "chipbench", "configs")
ROWS = 3000


def _config(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def _complete(settings):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # an empty rule list warns
        return complete_settings_dict(copy.deepcopy(settings))


# ----------------------------------------------------------------------
# The set, from the settings alone
# ----------------------------------------------------------------------

TOKEN_ONLY = {
    "baseline_c4": {"dob", "city"},
    "baseline_c4_v5e4": {"dob", "city"},
    "baseline_c5": {"dob", "city"},
    "c4_case_library": {"city"},
    "baseline_c3": {"city"},
    "baseline_c2": set(),
    "febrl_ten_fields": set(),
    "musicbrainz_songs": set(),
}


def test_every_configuration_has_its_expected_set():
    on_disk = {f[:-5] for f in os.listdir(CONFIGS) if f.endswith(".json")}
    assert on_disk == set(TOKEN_ONLY)


@pytest.mark.parametrize("name", sorted(TOKEN_ONLY))
def test_token_only_columns_of_a_configuration(name):
    settings = _complete(_config(name)["settings"])
    assert token_only_columns(settings) == frozenset(TOKEN_ONLY[name])


def _exact(col, **extra):
    return {"col_name": col, "comparison": {"kind": "exact"}, **extra}


def _initials(ctx, col_settings):
    pc = ctx.col("city")
    return (pc.chars_l[:, 0] == pc.chars_r[:, 0]).astype(np.int8)


register_comparison("test_token_only_city_initial", _initials)

# each: comparison columns beside ``dob`` and ``city`` compared exact, and
# the columns the set then holds
SHARED_READERS = {
    "jaro_winkler": (
        [{"custom_name": "city_jw", "custom_columns_used": ["city"], "num_levels": 3,
          "comparison": {"kind": "jaro_winkler", "column": "city",
                         "thresholds": [0.94, 0.88]}}],
        {"dob"},
    ),
    "qgram": (
        [{"custom_name": "city_qgram", "custom_columns_used": ["city"], "num_levels": 2,
          "comparison": {"kind": "qgram_jaccard", "column": "city", "thresholds": [0.6]}}],
        {"dob"},
    ),
    "name_inversion_other_column": (
        [{"col_name": "surname", "num_levels": 4,
          "comparison": {"kind": "name_inversion", "other_columns": ["city"],
                         "thresholds": [0.94, 0.88]}}],
        {"dob"},
    ),
    "case_sql": (
        [{"custom_name": "city_case", "custom_columns_used": ["city"], "num_levels": 2,
          "case_expression": "case when substr(city_l, 1, 3) = substr(city_r, 1, 3) "
                             "then 1 else 0 end"}],
        {"dob"},
    ),
    "dmetaphone": (
        [{"custom_name": "city_sound", "custom_columns_used": ["city"], "num_levels": 3,
          "comparison": {"kind": "dmetaphone", "column": "city"}}],
        {"dob"},
    ),
    "second_exact_on_the_same_input": (
        [{"custom_name": "city_again", "custom_columns_used": ["city"], "num_levels": 2,
          "comparison": {"kind": "exact", "column": "city"}}],
        {"dob", "city"},
    ),
    "custom_anywhere": (
        [{"custom_name": "city_initial", "custom_columns_used": ["city"], "num_levels": 2,
          "comparison": {"kind": "custom", "fn": "test_token_only_city_initial"}}],
        set(),
    ),
}


@pytest.mark.parametrize("case", sorted(SHARED_READERS))
def test_a_column_read_another_way_keeps_its_characters(case):
    others, want = SHARED_READERS[case]
    settings = _complete({
        "link_type": "dedupe_only",
        "blocking_rules": ["l.dob = r.dob"],
        "comparison_columns": [_exact("dob"), _exact("city"), *others],
    })
    assert token_only_columns(settings) == frozenset(want)


# ----------------------------------------------------------------------
# The narrowed program against the program packed with every lane
# ----------------------------------------------------------------------


def _frames(config):
    gen = {k: v for k, v in config["generator"].items()
           if k not in ("kind", "population_seed", "rows")}
    people = datagen.make_people(rows=ROWS, seed=config["generator"]["population_seed"], **gen)
    # the cells' width 16 (3,000 of their people may hold no longer name)
    people.loc[0, ["first_name", "surname"]] = "bartholomewmaxim", "featherstonehaug"
    # nulls in every exact column beside the generator's own
    for col, step in (("dob", 97), ("city", 89)):
        people.loc[people.index[::step], col] = None
    if config["settings"]["link_type"] == "link_only":
        return dict(zip(("df_l", "df_r"), datagen.split_for_linking(people)))
    return {"df": people}


def _run(config, frames):
    """The job's linker, its spans, its scored frame and a program on its
    table."""
    linker = Splink(copy.deepcopy(config["settings"]), **copy.deepcopy(frames))
    frame = linker.get_scored_comparisons()
    job_spans = spans(run=linker.run_id)
    program = GammaProgram(linker.settings, linker._ensure_encoded())
    return linker, job_spans, frame, program


@pytest.fixture(scope="module")
def narrowed_and_wide():
    """Per configuration, once: the job run as the program packs it, and the
    same job with every string column packed whole."""
    memo = {}

    def made(name):
        if name not in memo:
            config = _config(name)
            frames = _frames(config)
            narrow = _run(config, frames)
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(gammas, "token_only_columns", lambda settings: frozenset())
                wide = _run(config, frames)
            memo[name] = narrow, wide
        return memo[name]

    return made


CELLS = {"baseline_c4": (2, 20, 30), "baseline_c3": (1, 13, 18)}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_scored_frame_is_the_wide_programs(name, narrowed_and_wide):
    (_, _, frame_n, program_n), (_, _, frame_w, program_w) = narrowed_and_wide(name)
    _, narrow_lanes, wide_lanes = CELLS[name]
    assert program_n._packed.shape[1] == narrow_lanes
    assert program_w._packed.shape[1] == wide_lanes
    assert len(frame_n) > 1000
    pd.testing.assert_frame_equal(frame_n, frame_w, check_exact=True)
    for col in ("dob", "city"):
        if f"gamma_{col}" in frame_n:
            assert (frame_n[f"gamma_{col}"] == -1).any()  # nulls were compared


@pytest.mark.parametrize("name", sorted(CELLS))
def test_gamma_matrix_and_pattern_ids_are_the_wide_programs(name, narrowed_and_wide):
    (_, _, _, program_n), (_, _, _, program_w) = narrowed_and_wide(name)
    n = program_n._packed.shape[0]
    rng = np.random.default_rng(45)
    il = rng.integers(0, n, 5000).astype(np.int32)
    ir = rng.integers(0, n, 5000).astype(np.int32)
    # equal halves, so the exact columns' level 1 is common too
    ir[::2] = il[::2]
    G = program_n.compute(il, ir, batch_size=2048)
    np.testing.assert_array_equal(G, program_w.compute(il, ir, batch_size=2048))
    assert (G == -1).any() and (G == 1).any()
    pid_n, counts_n = program_n.compute_pattern_ids(il, ir, batch_size=2048)
    pid_w, counts_w = program_w.compute_pattern_ids(il, ir, batch_size=2048)
    np.testing.assert_array_equal(pid_n, pid_w)
    np.testing.assert_array_equal(counts_n, counts_w)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_virtual_pass_is_the_wide_programs(name, narrowed_and_wide):
    (linker, _, _, program_n), (_, _, _, program_w) = narrowed_and_wide(name)
    table = linker._ensure_encoded()
    n_left = linker._n_left if name == "baseline_c3" else None
    plan = build_virtual_plan(program_n.settings, table, n_left=n_left, chunk=64)
    rp = plan.rules[0]
    imax = np.iinfo(np.int32).max
    outs = []
    for program in (program_n, program_w):
        fn = make_virtual_pattern_fn(program, 4096, n_prev=0, has_uid_mask=False)
        outs.append(fn(
            jnp.arange(4096, dtype=jnp.int32), program._packed, jnp.asarray(rp.order),
            jnp.asarray(rp.ua), jnp.asarray(rp.la), jnp.asarray(rp.ub), jnp.asarray(rp.lb),
            jnp.asarray(plan.codes), jnp.zeros(1, jnp.int32), (),
            jnp.asarray(np.array([0, 4096, 0, imax], np.int32)),
            jnp.zeros(program.n_patterns + 1, jnp.int32),
        ))
    for a, b in zip(*outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    pid, acc = np.asarray(outs[0][0]), np.asarray(outs[0][3])
    assert int(acc[:-1].sum()) > 1000 and len(np.unique(pid)) > 10


# ----------------------------------------------------------------------
# The count that says the narrowing engaged
# ----------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(CELLS))
def test_pack_table_span_counts_token_only_columns(name, narrowed_and_wide):
    (_, narrow_spans, _, _), (_, wide_spans, _, _) = narrowed_and_wide(name)
    token_only, narrow_lanes, wide_lanes = CELLS[name]
    for job_spans, count, lanes in ((narrow_spans, token_only, narrow_lanes),
                                    (wide_spans, 0, wide_lanes)):
        # one job packs its table once
        [pack] = [s for s in job_spans if s["name"] == "pack_table"]
        assert pack["counts"]["token_only_columns"] == count
        assert pack["counts"]["lanes"] == lanes
        assert pack["counts"]["rows"] == ROWS
