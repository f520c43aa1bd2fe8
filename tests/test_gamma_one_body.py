"""One exact Jaro-Winkler body and a packed row without a prefilter's lanes.

Every gamma kernel — one chip or a mesh, virtual or materialised pairs, ids
kept or not — composes ``gammas._make_gamma_body``: ``string_ops.jaro_winkler``
on every pair of a Jaro-Winkler column, ``bucket_similarity`` on the result.
Held here: the levels against the thresholds on adversarial data (shared
prefixes, nulls, token-equal names, repeats), against the vector-form kernel
and the Python oracle; the packed row's width for every configuration the
benchmark runs, as the ``pack_table`` span's ``lanes`` says it; the G and
pattern outputs are exactly ``batch`` rows; the registry's signature and the
settings schema know no prefilter. The one-chip and mesh virtual kernels
against each other, and the histogram-only pass's waits:
``tests/test_virtual_pairs.py``.
"""

import copy
import json
import os
import pathlib
import sys

import numpy as np
import pandas as pd
import pytest

import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import splink_tpu  # noqa: E402
from chipbench import datagen, datagen_febrl  # noqa: E402
from splink_tpu import Splink  # noqa: E402
from splink_tpu.data import encode_table  # noqa: E402
from splink_tpu.gammas import GammaProgram, _Parts  # noqa: E402
from splink_tpu.ops import strings  # noqa: E402
from splink_tpu.settings import complete_settings_dict  # noqa: E402
from splink_tpu.utils import kernel_registry  # noqa: E402
from splink_tpu.utils.profiling import spans  # noqa: E402
from splink_tpu.validate import ValidationError, validate_settings  # noqa: E402

from conftest import py_jaro_winkler  # noqa: E402

THRESHOLDS = (0.94, 0.88)


# ----------------------------------------------------------------------
# Levels against thresholds
# ----------------------------------------------------------------------


def _fuzz_words(rng, n):
    """Adversarial mix: random words, heavy repeats, a three-letter alphabet,
    shared 4-char prefixes, near-misses, empties."""
    alphabet = list("abcdefghijklmnopqrstuvwxyz")
    tight = list("abc")
    words = []
    for _ in range(n):
        r = rng.random()
        if r < 0.15:
            words.append("a" * rng.integers(0, 13))
        elif r < 0.35:
            words.append("".join(rng.choice(tight, rng.integers(0, 12))))
        elif r < 0.55:
            words.append("pref" + "".join(rng.choice(alphabet, rng.integers(0, 8))))
        elif r < 0.6:
            words.append("")
        else:
            words.append("".join(rng.choice(alphabet, rng.integers(1, 12))))
    return np.array(words, dtype=object)


def _names(kind, n=400, seed=5):
    rng = np.random.default_rng(seed)
    if kind == "shared_prefix":
        # shared 4- and 6-char prefixes, distinct suffixes: the pairs sit on
        # and around the thresholds and none is token-equal
        return np.array([f"prefix{i:04d}" if i % 2 else f"pref{i * 7919 % 10**6:06d}"
                         for i in range(n)], dtype=object)
    if kind == "fuzz_words":
        return _fuzz_words(rng, n)
    base = np.array(
        ["amelia", "amelie", "oliver", "olivia", "isla", "george",
         "georgia", "ava", "eva", "noah", "nora", "", None],
        dtype=object,
    )
    return base[rng.integers(0, len(base), n)]


def _jw_settings(**overrides):
    s = {
        "link_type": "dedupe_only",
        "blocking_rules": ["l.city = r.city"],
        "comparison_columns": [
            {
                "col_name": "name",
                "num_levels": 3,
                "comparison": {"kind": "jaro_winkler", "thresholds": list(THRESHOLDS)},
            },
        ],
    }
    s.update(overrides)
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return complete_settings_dict(s)


def _program(kind):
    names = _names(kind)
    rng = np.random.default_rng(11)
    df = pd.DataFrame({
        "unique_id": np.arange(len(names)),
        "name": names,
        "city": np.array(["x", "y"], dtype=object)[rng.integers(0, 2, len(names))],
    })
    s = _jw_settings()
    table = encode_table(df, s)
    return GammaProgram(s, table), table, names


@pytest.mark.parametrize("kind", ["names_null_and_token_equal", "shared_prefix", "fuzz_words"])
def test_jaro_winkler_levels_match_thresholds(kind):
    """Level = number of thresholds strictly below the pair's similarity; a
    null on either side is -1 (an empty string is a VALUE). Equal to the
    vector-form kernel bucketed by hand, in the G and the pattern regime, and
    to the Python oracle, wherever float32 cannot land on the other side."""
    program, table, names = _program(kind)
    rng = np.random.default_rng(9)
    il = rng.integers(0, len(names), 2048).astype(np.int32)
    ir = rng.integers(0, len(names), 2048).astype(np.int32)
    G = program.compute(il, ir, batch_size=512)
    assert G.shape == (2048, 1)

    sc = table.strings["name"]
    sim = np.asarray(strings.jaro_winkler_vmapped(
        sc.bytes_[il], sc.bytes_[ir], sc.lengths[il], sc.lengths[ir], 0.1, 0.7))
    null = (sc.token_ids[il] < 0) | (sc.token_ids[ir] < 0)
    want = np.where(null, -1, sum((sim > t).astype(np.int8) for t in THRESHOLDS))
    # a similarity that EQUALS a threshold ("preffuiluy" / "pref" is 0.88,
    # "prefix0001" / "prefix0002" 0.94) may round to either side inside
    # another fusion: the adjacent level stands
    tie = ~null & (np.min([np.abs(sim - np.float32(t)) for t in THRESHOLDS], axis=0) <= 1e-6)
    np.testing.assert_array_equal(G[~tie, 0], want[~tie])
    assert (np.abs(G[tie, 0] - want[tie]) <= 1).all() and tie.sum() < len(tie) // 2
    assert len(np.unique(want[~tie])) >= 2
    assert null.any() == (kind == "names_null_and_token_equal")

    pids, counts = program.compute_pattern_ids(il, ir, batch_size=512)
    np.testing.assert_array_equal(pids, G[:, 0].astype(np.int32) + 1)
    np.testing.assert_array_equal(counts, np.bincount(pids, minlength=program.n_patterns))

    for k in range(0, 2048, 8):
        a, b = names[il[k]], names[ir[k]]
        if pd.isna(a) or pd.isna(b):  # pandas 3: a missing string is NaN
            assert G[k, 0] == -1
            continue
        exact = py_jaro_winkler(a, b)
        if min(abs(exact - t) for t in THRESHOLDS) > 1e-6:
            assert G[k, 0] == sum(exact > t for t in THRESHOLDS), (a, b, exact)


# ----------------------------------------------------------------------
# Outputs carry no flag
# ----------------------------------------------------------------------


def test_g_and_pattern_outputs_have_exactly_batch_rows():
    from splink_tpu.pairgen import build_virtual_plan, make_virtual_pattern_fn

    program, table, _ = _program("shared_prefix")
    il = jnp.zeros(256, jnp.int32)
    ir = jnp.arange(256, dtype=jnp.int32)
    assert program._gamma_batch(il, ir).shape == (256, program.n_cols)
    acc = jnp.zeros(program.n_patterns + 1, jnp.int32)
    pid, acc = program._pattern_batch(il, ir, 200, acc)
    assert pid.shape == (256,) and acc.shape == (program.n_patterns + 1,)
    assert int(acc.sum()) == 256 and int(acc[-1]) == 56  # the padding, in the sentinel
    # the virtual kernel: ids and row pairs, one a position
    plan = build_virtual_plan(program.settings, table, chunk=64)
    rp = plan.rules[0]
    fn = make_virtual_pattern_fn(program, 128, n_prev=0, has_uid_mask=False)
    imax = np.iinfo(np.int32).max
    pid, i, j, acc = fn(
        jnp.arange(128, dtype=jnp.int32), program._packed, jnp.asarray(rp.order),
        jnp.asarray(rp.ua), jnp.asarray(rp.la), jnp.asarray(rp.ub), jnp.asarray(rp.lb),
        jnp.asarray(plan.codes), jnp.zeros(1, jnp.int32), (),
        jnp.asarray(np.array([0, 128, 0, imax], np.int32)),
        jnp.zeros(program.n_patterns + 1, jnp.int32),
    )
    assert pid.shape == i.shape == j.shape == (128,)
    assert acc.shape == (program.n_patterns + 1,) and int(acc.sum()) == 128


# ----------------------------------------------------------------------
# The packed row of every configuration the benchmark runs
# ----------------------------------------------------------------------

# words of a packed row: string columns' chars / 4 + a length and a token
# lane each, the bigram Jaccard's mask and count lanes; a column only ever
# compared exact (config 4's dob and city, the case library's and c3's city)
# its token lane alone; nothing for a bound
PACKED_LANES = {
    "baseline_c4": 20,
    "baseline_c4_v5e4": 20,
    "baseline_c5": 20,
    "baseline_c3": 13,
    "c4_case_library": 23,
    "baseline_c2": 12,
    "febrl_ten_fields": 54,
}


@pytest.mark.parametrize("name", sorted(PACKED_LANES))
def test_packed_row_width_of_a_configuration(name):
    with open(os.path.join(ROOT, "chipbench", "configs", f"{name}.json")) as f:
        config = json.load(f)
    gen = {k: v for k, v in config["generator"].items()
           if k not in ("kind", "population_seed", "rows")}
    febrl = config["generator"]["kind"] == "febrl"
    make = datagen_febrl.make_febrl if febrl else datagen.make_people
    people = make(rows=3000, seed=config["generator"]["population_seed"], **gen)
    # a column's width follows its longest value: the cells' populations hold
    # names of 9 to 16 characters (width 16), 3,000 of their people may not
    names = ["given_name" if febrl else "first_name", "surname"]
    people.loc[0, names] = "bartholomewmaxim", "featherstonehaug"
    link = config["settings"]["link_type"] == "link_only"
    frames = (dict(zip(("df_l", "df_r"), datagen.split_for_linking(people)))
              if link else {"df": people})
    linker = Splink(copy.deepcopy(config["settings"]), **frames)
    before = len(spans(run=linker.run_id))
    program = GammaProgram(linker.settings, linker._ensure_encoded())
    [pack] = [s for s in spans(run=linker.run_id)[before:] if s["name"] == "pack_table"]
    assert program._packed.shape[1] == PACKED_LANES[name]
    assert pack["counts"]["lanes"] == PACKED_LANES[name]
    assert pack["counts"]["rows"] == program._packed.shape[0] == len(people)
    assert not [k for k in program._layout if "jw" in k]


# ----------------------------------------------------------------------
# No divisor in a signature, no prefilter key in the schema
# ----------------------------------------------------------------------


def test_signature_and_parts_know_no_divisor():
    a, _, _ = _program("shared_prefix")
    b, _, _ = _program("shared_prefix")
    assert _Parts._fields == ("cols", "layout", "strides", "n_patterns")
    assert len(a._sig) == 3 and a._sig == b._sig  # columns, layout, float dtype
    il = jnp.zeros(8, jnp.int32)
    a._gamma_batch(il, il)
    keys = set(kernel_registry.keys())
    b._gamma_batch(il, il)
    assert set(kernel_registry.keys()) == keys  # the same program, by signature
    assert a._gamma_batch_fn is b._gamma_batch_fn


@pytest.mark.parametrize("key,value", [("two_phase_jw", "off"), ("jw_survivor_divisor", 8)])
def test_prefilter_keys_are_rejected_by_the_schema(key, value):
    s = {
        "link_type": "dedupe_only",
        "comparison_columns": [{"col_name": "name"}],
        "blocking_rules": ["l.city = r.city"],
        key: value,
    }
    with pytest.raises(ValidationError, match=key):
        validate_settings(s)


def test_schema_has_67_keys():
    root = pathlib.Path(splink_tpu.__file__).parent
    with open(root / "files" / "settings_jsonschema.json") as f:
        assert len(json.load(f)["properties"]) == 67


def test_no_prefilter_is_left_in_the_program():
    root = pathlib.Path(splink_tpu.__file__).parent
    assert not (root / "ops" / "jw_bound.py").exists()
    left = [str(p.relative_to(root)) for p in sorted(root.rglob("*"))
            if p.suffix in (".py", ".json")
            and any(w in p.read_text() for w in ("two_phase", "jw_bound", "jw_survivor"))]
    assert left == []
