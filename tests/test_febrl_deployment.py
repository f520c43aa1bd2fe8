"""Febrl's ten-field person schema on config 4's dedupe, against the plain reference.

The deployment ``chipbench/configs/febrl_ten_fields.json`` (the cell
``febrl10_dedupe_virtual``): ten comparison columns, nine at 4 levels and
``state`` at 2, so the pattern space is 5^9 x 3 = 5,859,375 — past the 2^22
the pattern-id pipeline was held to until PR 41. At 2,000 of the cell's own
people on the CPU, through the facade's normal path (the virtual pair index,
the histogram pass, pattern EM, the score stream), the job has to give the
pair set and every gamma level of ``chipbench.reference`` exactly and λ/m/u and
every score within the configuration's limits; the bfloat16 control has to
fail them. Held beside it: every per-pattern table the host builds has one row
a pattern SEEN (``linker._SeenPatterns``) and is bit-equal to the full-space
table it replaces, on config 4's 1,296-pattern model and on a small space with
the intermediates and the TF fold's logits; the regime's ``pattern_capable`` fact on both sides
of the byte-derived bound; ``gammas.MAX_PATTERNS = 1`` still forcing the
streamed-stats regime; the ``pattern_tables`` span's counts.
"""

import contextlib
import copy
import json
import os
import sys

import numpy as np
import pandas as pd
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import correct, datagen, reference  # noqa: E402
from chipbench.datagen_febrl import FIELDS, make_febrl  # noqa: E402
from splink_tpu import Splink, gammas  # noqa: E402
from splink_tpu.utils.profiling import spans  # noqa: E402

ROWS = 2000
SPACE = 5 ** 9 * 3


def _config(name):
    with open(os.path.join(ROOT, "chipbench", "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _config("febrl_ten_fields")


@pytest.fixture(scope="module")
def people(config):
    gen = {k: v for k, v in config["generator"].items()
           if k not in ("kind", "population_seed", "rows")}
    df = make_febrl(rows=ROWS, seed=config["generator"]["population_seed"], **gen)
    # as the runner hands them over: another order, the ids renumbered
    df = df.iloc[np.random.default_rng(5).permutation(ROWS)].reset_index(drop=True)
    return df.assign(unique_id=np.arange(ROWS))


def _small(settings, **over):
    """The configuration's settings at a tier-1 size: the least batch the
    settings take, the virtual index forced as the cell forces it."""
    settings = copy.deepcopy(settings)
    settings.update(pair_batch_size=1024, max_resident_pairs=1024, **over)
    return settings


@pytest.fixture(scope="module")
def job(config, people):
    """(linker, frame, span table) of one whole job."""
    linker = Splink(_small(config["settings"]), df=people)
    frame = linker.get_scored_comparisons()
    return linker, frame, spans(run=linker.run_id)


@pytest.fixture(scope="module")
def prep(config, people):
    # the reference's device blocks at a tier-1 size (its numbers do not
    # depend on the block)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(reference, "BLOCK", 1 << 12)
        yield reference.prepare(config["settings"], {"df": people})


def test_the_generator_gives_febrls_ten_fields_at_an_exact_size(people):
    assert list(people.columns) == ["unique_id", *FIELDS, "cluster"]
    assert len(people) == ROWS and people.cluster.nunique() == round(ROWS / 1.3)
    assert people.date_of_birth.str.fullmatch(r"\d{8}").mean() > 0.95
    assert people.date_of_birth.notna().all() and people.address_2.isna().mean() > 0.5
    # every value within the program's default string width (data.py: 24):
    # the reference compares whole strings
    assert max(people[f].str.len().max() for f in FIELDS) <= 24


def test_a_space_past_2_to_the_22_runs_on_the_normal_path(job):
    linker, frame, table = job
    program = linker._pattern_program
    assert program.n_patterns == SPACE > 1 << 22
    assert linker._facts().pattern_capable and linker._virtual_plan() is not None
    assert linker._G is None  # no per-pair gamma matrix on the host
    names = [s["name"] for s in table]
    assert "pairgen_plan" in names and "em" in names and "gammas" not in names
    [stage] = [s for s in table if s["name"] == "gammas_patterns"]
    assert stage["counts"]["ids_kept"] == 1
    # int32 ids home: the sentinel 5,859,375 does not fit uint16
    assert stage["counts"]["pattern_id_bytes"] == 4 * linker._virtual.n_candidates
    assert len(frame) == stage["counts"]["pairs"]


def test_the_job_meets_the_reference(config, job, prep):
    linker, frame, _ = job
    produced = {"frame": frame, "tf_frame": None, "params": linker.params.params,
                "digests": [(0,)], "uid": "unique_id"}
    numbers = correct.compare(produced, prep)
    ok, rows = correct.verdict(numbers, config["limits"])
    assert ok, rows
    assert numbers["pairs_wrong"] == 0 and numbers["gamma_wrong"] == 0


def test_the_bfloat16_control_fails(config, people, prep):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(reference, "BLOCK", 1 << 12)
        control = reference.run(config["settings"], {"df": people}, precision="bfloat16")
    ok, rows = correct.verdict(correct.compare(correct.stand_in(control), prep),
                               config["limits"])
    assert not ok, rows


def test_the_pattern_tables_span_counts_the_patterns_seen(job):
    linker, frame, table = job
    seen = linker._seen_patterns()
    gamma = np.stack([frame[f"gamma_{f}"].to_numpy() for f in FIELDS], axis=1)
    assert len(seen.ids) == len(np.unique(gamma, axis=0))  # the frame's patterns
    tables = [s["counts"] for s in table if s["name"] == "pattern_tables"]
    assert len(tables) == 2  # EM's input, then the score and frame tables
    assert sum(c.get("space", 0) for c in tables) == SPACE
    assert sum(c.get("seen", 0) for c in tables) == len(seen.ids)
    # gamma rows + counts, then scores + levels: sized by the patterns seen
    assert sum(c["rows_built"] for c in tables) == 4 * len(seen.ids) < SPACE // 1000
    assert seen.remap.shape == (SPACE,) and seen.remap.dtype == np.int32


def _small_space_settings():
    """Three columns, 4 x 3 x 3 = 36 patterns: the intermediates retained and
    a TF-flagged column, so every per-pattern table exists."""
    return {
        "link_type": "dedupe_only",
        "comparison_columns": [
            {"col_name": "first_name", "num_levels": 3, "term_frequency_adjustments": True},
            {"col_name": "surname", "num_levels": 2},
            {"col_name": "dob", "comparison": {"kind": "exact"}},
        ],
        "blocking_rules": ["l.dob = r.dob", "l.surname = r.surname"],
        "retain_intermediate_calculation_columns": True,
        "device_pair_generation": "on",
        "max_resident_pairs": 1024,
        "pair_batch_size": 1024,
    }


@pytest.mark.parametrize("model", ["baseline_c4", "small_space"])
def test_seen_pattern_tables_are_the_full_space_tables_bit_for_bit(model):
    """Every per-pattern table is the full-space table's rows at the patterns
    seen, bit for bit, and the frame written from them is the frame the
    full-space tables give the same pairs."""
    import jax.numpy as jnp

    from splink_tpu.gammas import patterns_matrix_for
    from splink_tpu.linker import _FrameWriter
    from splink_tpu.models.fellegi_sunter import FSParams

    settings = (_small(_config(model)["settings"]) if model == "baseline_c4"
                else _small_space_settings())
    people = datagen.make_people(rows=3000, seed=4)
    linker = Splink(settings, df=people)
    frame = linker.get_scored_comparisons()
    seen = linker._seen_patterns()
    program = linker._pattern_program
    assert len(seen.ids) < program.n_patterns  # some patterns never occur
    full = patterns_matrix_for(program.level_counts)
    lam, m, u, _ = linker.params.to_arrays(dtype=linker._float_dtype)
    params = FSParams(lam=jnp.asarray(lam), m=jnp.asarray(m), u=jnp.asarray(u))
    want_z = linker._tf_fold_ctx() is not None
    assert want_z == (model == "small_space")
    p, pm, pu, z = linker._score_batched(full, params, want_z=want_z)
    full_tables = (np.ascontiguousarray(full.T, dtype=np.int64), p,
                   None if pm is None else np.ascontiguousarray(pm.T),
                   None if pu is None else np.ascontiguousarray(pu.T), z)
    tables = linker._pattern_frame_tables()
    np.testing.assert_array_equal(seen.rows, full[seen.ids])
    for got, whole in zip(tables, full_tables):
        assert (got is None) == (whole is None)
        if got is not None:
            assert got.dtype == whole.dtype
            assert np.array_equal(got, whole[..., seen.ids]), model  # bit for bit
    writer = _FrameWriter(linker, len(frame))
    for il, ir, rows in linker._iter_pattern_triples():
        writer.write(il, ir, *full_tables, by=seen.ids[rows])
    pd.testing.assert_frame_equal(writer.frame(), frame, check_exact=True)


def test_pattern_capable_on_both_sides_of_the_byte_bound(config, people, monkeypatch):
    assert gammas.MAX_PATTERNS == gammas.MAX_PATTERN_SPACE_BYTES // gammas.PATTERN_SPACE_BYTES
    assert gammas.pattern_space_fits(SPACE) and gammas.pattern_space_fits(5 ** 10)
    assert not gammas.pattern_space_fits(gammas.MAX_PATTERNS + 1)
    assert not gammas.pattern_space_fits(5 ** 12)  # 12 columns at 4 levels: 2.9 GB of accumulators
    for bound, capable in ((SPACE, True), (SPACE - 1, False)):
        monkeypatch.setattr(gammas, "MAX_PATTERNS", bound)
        linker = Splink(_small(config["settings"]), df=people)
        assert linker._facts().pattern_capable is capable
        # past the bound even "on" gives no virtual plan: the gamma-matrix paths
        assert (linker._virtual_plan() is not None) is capable
        refused = pytest.raises(ValueError, match="dense device accumulators")
        with contextlib.nullcontext() if capable else refused:
            gammas.PatternStream(linker._ensure_pattern_program(), 1024)


def test_max_patterns_one_still_forces_the_streamed_stats_regime(monkeypatch):
    """What tests/test_multiprocess_em.py and tests/dist_linker_worker.py lean
    on: ``gammas.MAX_PATTERNS = 1`` takes every model off the pattern path, so
    EM streams sufficient statistics over the gamma matrix."""
    from splink_tpu.linker import Splink as Linker

    calls = []
    streamed = Linker._run_em_streamed_driver
    monkeypatch.setattr(
        Linker, "_run_em_streamed_driver",
        lambda self, batches, ll, pairs: calls.append(pairs)
        or streamed(self, batches, ll, pairs),
    )
    monkeypatch.setattr(gammas, "MAX_PATTERNS", 1)
    settings = _small(_config("baseline_c4")["settings"])
    linker = Splink(settings, df=datagen.make_people(rows=3000, seed=4))
    assert not linker._facts().pattern_capable and not linker._regime("patterns")
    linker.estimate_parameters()
    assert calls == [linker._ensure_pairs().n_pairs]
    assert not [s for s in spans(run=linker.run_id) if s["name"] == "pattern_tables"]


@pytest.mark.parametrize("capable", [True, False])
def test_the_cells_runner_stops_a_program_that_declines_the_virtual_index(
        config, people, monkeypatch, capable):
    """``runners/job_febrl.py`` runs the cell only on a program that takes the
    virtual pair index its configuration turns on; one that declines it (a
    space past its cap, as the parent commit's 2^22) exits non-zero at once."""
    from chipbench.runners import job_febrl

    if not capable:
        monkeypatch.setattr(gammas, "MAX_PATTERNS", SPACE - 1)
    stops = pytest.raises(SystemExit, match="declines the virtual pair index")
    with contextlib.nullcontext() if capable else stops:
        job_febrl.require_virtual_index(_small(config["settings"]), people)
