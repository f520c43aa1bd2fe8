"""The job's regime (splink_tpu/regime.py): one case a row of the table in
its docstring, the rule that a fact arriving later never changes an outcome
already decided, the four settings keys read in one function, and each
public entry of the linker taking the path the table names."""

import itertools
import math
import os
import re

import numpy as np
import pandas as pd
import pytest

from splink_tpu import Splink
from splink_tpu.regime import MAX_RESIDENT_ID_BYTES, Facts, Wait, decide
from splink_tpu.utils.profiling import spans

R = 1000


def _facts(settings=None, **facts):
    s = {"max_resident_pairs": R}
    s.update(settings or {})
    facts.setdefault("pattern_capable", True)
    return Facts(settings=s, **facts)


# (outcome, settings, facts, expected): one row of the table each
_ROWS = {
    # virtual: build the virtual pair index's plan
    "virtual/off": ("virtual", {"device_pair_generation": "off"}, {}, False),
    "virtual/not_pattern_capable": (
        "virtual", {"device_pair_generation": "on"}, {"pattern_capable": False}, False),
    "virtual/approx_with_columns": (
        "virtual", {"device_pair_generation": "on", "approx_blocking": True},
        {"approx_columns": True}, False),
    "virtual/approx_without_columns": (
        "virtual", {"device_pair_generation": "on", "approx_blocking": True},
        {"approx_columns": False}, True),
    "virtual/approx_not_looked_at": (
        "virtual", {"approx_blocking": True}, {}, Wait("approx_columns")),
    "virtual/on_below_the_bound": (
        "virtual", {"device_pair_generation": "on"}, {"pair_bound": R}, True),
    "virtual/on_bound_unknown": ("virtual", {"device_pair_generation": "on"}, {}, True),
    "virtual/auto_above_the_bound": ("virtual", {}, {"pair_bound": R + 1}, True),
    "virtual/auto_at_the_bound": ("virtual", {}, {"pair_bound": R}, False),
    "virtual/auto_bound_unknown": ("virtual", {}, {}, Wait("pair_bound")),
    # pairs: spill emission or host blocking
    "pairs/host": ("pairs", {}, {}, "host"),
    "pairs/spill_emission": ("pairs", {"build_spill_dir": "d"}, {}, "spill"),
    "pairs/spill_with_approx_columns": (
        "pairs", {"build_spill_dir": "d", "approx_blocking": True},
        {"approx_columns": True}, "host"),
    "pairs/spill_with_approx_no_columns": (
        "pairs", {"build_spill_dir": "d", "approx_blocking": True},
        {"approx_columns": False}, "spill"),
    # overlap and its consumer
    "overlap/on": ("overlap", {}, {}, True),
    "overlap/off": ("overlap", {"overlap_blocking": False}, {}, False),
    "stream/pattern_stream": ("stream", {}, {"pair_bound": R + 1}, "patterns"),
    "stream/gamma_stream": ("stream", {}, {"pair_bound": R}, "gammas"),
    "stream/gamma_stream_not_capable": (
        "stream", {}, {"pair_bound": R + 1, "pattern_capable": False}, "gammas"),
    "stream/bound_unknown": ("stream", {}, {}, Wait("pair_bound")),
    "keep_limit/no_mesh": ("keep_limit", {}, {}, R),
    "keep_limit/mesh": ("keep_limit", {}, {"mesh": True}, 0),
    "shard_patterns/no_mesh": ("shard_patterns", {}, {}, False),
    "shard_patterns/mesh": ("shard_patterns", {}, {"mesh": True}, True),
    "shard_patterns/mesh_multi_controller": (
        "shard_patterns", {}, {"mesh": True, "process_count": 2}, False),
    # patterns: the pattern-id pipeline
    "patterns/virtual_plan": ("patterns", {}, {"plan": True, "n_candidates": 5}, True),
    "patterns/not_capable": ("patterns", {}, {"plan": False, "pattern_capable": False}, False),
    "patterns/multihost_store": (
        "patterns", {}, {"plan": False, "n_pairs": R + 1, "spill_store": True,
                         "multihost_store": True}, False),
    "patterns/above": ("patterns", {}, {"plan": False, "n_pairs": R + 1}, True),
    "patterns/at": ("patterns", {}, {"plan": False, "n_pairs": R}, False),
    "patterns/plan_unknown": ("patterns", {}, {}, Wait("plan")),
    "patterns/pairs_unknown": ("patterns", {}, {"plan": False}, Wait("n_pairs")),
    # gammas: P decoded to G, or computed
    "gammas/p_decoded_to_g": (
        "gammas", {}, {"pair_bound": R + 1, "n_pairs": R}, "decode"),
    "gammas/gamma_stream": ("gammas", {}, {"pair_bound": R, "n_pairs": R}, "compute"),
    "gammas/spill_store": (
        "gammas", {}, {"pair_bound": R + 1, "n_pairs": R, "spill_store": True}, "compute"),
    "gammas/overlap_off": (
        "gammas", {"overlap_blocking": False}, {"n_pairs": R}, "compute"),
    "gammas/pairs_unknown": ("gammas", {}, {"pair_bound": R + 1}, Wait("n_pairs")),
    "keep_device/resident": ("keep_device", {}, {"n_pairs": R}, True),
    "keep_device/mesh": ("keep_device", {}, {"n_pairs": R, "mesh": True}, False),
    "keep_device/above": ("keep_device", {}, {"n_pairs": R + 1}, False),
    # em: how estimate_parameters trains
    "em/patterns": ("em", {}, {"plan": True, "n_candidates": 5}, "patterns"),
    "em/spill_store_above": (
        "em", {}, {"plan": False, "pattern_capable": False, "n_pairs": R + 1,
                   "spill_store": True}, "spill"),
    "em/multihost_store": (
        "em", {}, {"plan": False, "n_pairs": 1, "spill_store": True,
                   "multihost_store": True}, "spill"),
    "em/spill_store_resident": (
        "em", {}, {"plan": False, "n_pairs": R, "spill_store": True}, "gammas"),
    "em/g_streamed": (
        "em", {}, {"plan": False, "pattern_capable": False, "n_pairs": R + 1}, "gammas"),
    "em/pairs_unknown": ("em", {}, {"plan": False, "pattern_capable": False},
                         Wait("n_pairs")),
    "em_resident/g_resident": ("em_resident", {}, {"n_pairs": R}, True),
    "em_resident/g_streamed": ("em_resident", {}, {"n_pairs": R + 1}, False),
    # ids_kept: the virtual pass keeps its ids for the score stream
    "ids_kept/on": ("ids_kept", {"virtual_materialise_ids": "on"}, {}, True),
    "ids_kept/off": (
        "ids_kept", {"virtual_materialise_ids": "off"},
        {"score_follows": True, "plan": True, "n_candidates": 5,
         "free_ram": 1 << 40}, False),
    "ids_kept/auto_no_score_stream": (
        "ids_kept", {}, {"plan": True, "n_candidates": 5, "free_ram": 1 << 40}, False),
    "ids_kept/auto_score_stream": (
        "ids_kept", {}, {"score_follows": True, "plan": True, "n_candidates": 5,
                         "free_ram": 1 << 40}, True),
    "ids_kept/auto_past_the_byte_cap": (
        "ids_kept", {}, {"score_follows": True, "plan": True,
                         "n_candidates": MAX_RESIDENT_ID_BYTES // 12 + 1,
                         "free_ram": math.inf}, False),
    "ids_kept/auto_uint16_under_the_byte_cap": (
        "ids_kept", {}, {"score_follows": True, "plan": True, "ids_fit_uint16": True,
                         "n_candidates": MAX_RESIDENT_ID_BYTES // 12 + 1,
                         "free_ram": math.inf}, True),
    "ids_kept/auto_past_half_free_ram": (
        "ids_kept", {}, {"score_follows": True, "plan": True, "n_candidates": 100,
                         "free_ram": 2 * 1200 - 1}, False),
    "ids_kept/auto_half_free_ram": (
        "ids_kept", {}, {"score_follows": True, "plan": True, "n_candidates": 100,
                         "free_ram": 2 * 1200}, True),
    "ids_kept/auto_ram_unprobed": (
        "ids_kept", {}, {"score_follows": True, "plan": True, "n_candidates": 5},
        Wait("free_ram")),
    "ids_kept/auto_plan_unknown": (
        "ids_kept", {}, {"score_follows": True}, Wait("plan")),
    "ids_kept/auto_no_plan": (
        "ids_kept", {}, {"score_follows": True, "plan": False}, False),
}


@pytest.mark.parametrize("row", sorted(_ROWS))
def test_each_row_of_the_regime_table(row):
    outcome, settings, facts, expected = _ROWS[row]
    got = decide(_facts(settings, **facts))[outcome]
    assert got == expected and type(got) is type(expected), (got, expected)


# the facts the linker learns late, each with what arrives with it
_LATE = {
    "approx_columns": {"approx_columns": True},
    "pair_bound": {"pair_bound": R + 1},
    "plan": {"plan": True, "n_candidates": 5},
    "no_plan": {"plan": False},
    "n_pairs": {"n_pairs": R + 1, "spill_store": True},
    "free_ram": {"free_ram": 1 << 40},
}


@pytest.mark.parametrize("mode,ids", [("auto", "auto"), ("on", "off"), ("off", "on")])
def test_a_fact_that_arrives_later_never_changes_a_decided_outcome(mode, ids):
    settings = {"device_pair_generation": mode, "virtual_materialise_ids": ids,
                "approx_blocking": True, "build_spill_dir": "d"}

    def facts(names):
        out = {"score_follows": True}
        for name in names:
            out.update(_LATE[name])
        return _facts(settings, **out)

    for k in range(len(_LATE) + 1):
        for known in itertools.combinations(_LATE, k):
            if {"plan", "no_plan"} <= set(known):
                continue
            before = decide(facts(known))
            for extra in set(_LATE) - set(known):
                if {"plan", "no_plan"} <= {*known, extra}:
                    continue
                after = decide(facts((*known, extra)))
                for outcome, value in before.items():
                    if not isinstance(value, Wait):
                        assert after[outcome] == value, (known, extra, outcome)


def test_the_four_regime_keys_are_read_in_one_function():
    root = os.path.join(os.path.dirname(os.path.dirname(__file__)), "splink_tpu")
    read = re.compile(
        r"""(\[|\.get\()\s*["'](max_resident_pairs|device_pair_generation|"""
        r"""virtual_materialise_ids|overlap_blocking)["']"""
    )
    hits = []
    for folder, _dirs, files in os.walk(root):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(folder, name)
                with open(path, encoding="utf-8") as f:
                    hits += [(os.path.relpath(path, root), m.group(2))
                             for m in read.finditer(f.read())]
    assert hits and {path for path, _ in hits} == {"regime.py"}, hits
    assert {key for _, key in hits} == {
        "max_resident_pairs", "device_pair_generation",
        "virtual_materialise_ids", "overlap_blocking"}
    import splink_tpu.linker as linker_module

    assert not hasattr(linker_module.Splink, "_virtual_want_ids")
    em_methods = [n for n in vars(linker_module.Splink) if n.startswith("_run_em")]
    assert len(em_methods) <= 5, em_methods


# ---------------------------------------------------------------------------
# each public entry takes the table's path
# ---------------------------------------------------------------------------


def _people(n, seed):
    rng = np.random.default_rng(seed)
    return pd.DataFrame({
        "unique_id": np.arange(n),
        "first_name": rng.choice(["ann", "bob", "cat", "dan", "eve"], n),
        "surname": rng.choice(["smith", "jones", "taylor", "brown"], n),
        "city": rng.choice(["x", "y", "z"], n),
    })


def _c4_shaped():
    """A small dedupe on the virtual pair index, as config 4 runs."""
    settings = {
        "link_type": "dedupe_only",
        "comparison_columns": [{"col_name": "first_name"}, {"col_name": "surname"}],
        "blocking_rules": ["l.city = r.city", "l.surname = r.surname"],
        "max_iterations": 3,
        "device_pair_generation": "on",
        "max_resident_pairs": 1024,
    }
    return Splink(settings, df=_people(400, 1))


def _c3_shaped():
    """A small two-frame link with TF, resident, as c3 runs."""
    settings = {
        "link_type": "link_only",
        "comparison_columns": [
            {"col_name": "first_name", "term_frequency_adjustments": True},
            {"col_name": "surname"},
        ],
        "blocking_rules": ["l.city = r.city"],
        "max_iterations": 3,
    }
    df = _people(400, 2)
    return Splink(settings, df_l=df.iloc[:250], df_r=df.iloc[250:])


_ENTRIES = {
    "get_scored_comparisons": lambda lk: lk.get_scored_comparisons(),
    "estimate_parameters": lambda lk: lk.estimate_parameters(),
    "stream_scored_comparisons": lambda lk: list(lk.stream_scored_comparisons()),
    "stream_tf_adjusted_comparisons":
        lambda lk: list(lk.stream_tf_adjusted_comparisons()),
    "manually_apply_fellegi_sunter_weights":
        lambda lk: lk.manually_apply_fellegi_sunter_weights(),
}
# a score stream follows the pattern pass in these entries
_SCORE_FOLLOWS = {"get_scored_comparisons", "stream_scored_comparisons",
                  "stream_tf_adjusted_comparisons"}


@pytest.mark.parametrize("job", ["c4_shaped", "c3_shaped"])
@pytest.mark.parametrize("entry", sorted(_ENTRIES))
def test_each_public_entry_takes_the_tables_path(job, entry):
    linker = {"c4_shaped": _c4_shaped, "c3_shaped": _c3_shaped}[job]()
    _ENTRIES[entry](linker)
    table = spans(run=linker.run_id)
    stages = {s["name"]: s["counts"] for s in table if s["kind"] == "stage"}
    follows = entry in _SCORE_FOLLOWS

    def regime(outcome):
        return linker._regime(outcome, score_follows=follows)

    assert regime("patterns") is (job == "c4_shaped")
    assert regime("virtual") is (job == "c4_shaped")
    trains = entry != "manually_apply_fellegi_sunter_weights"
    if regime("patterns"):
        assert "pairgen_plan" in stages and "blocking" not in stages
        assert ("em" in stages) is trains
        assert ("score_patterns" in stages) is (entry != "estimate_parameters")
        if trains:
            assert regime("em") == "patterns"
            kept = stages["gammas_patterns"]["ids_kept"]
            assert kept == int(regime("ids_kept")) == int(follows)
    else:
        assert "blocking" in stages and "pairgen_plan" not in stages
        assert regime("em") == "gammas" and regime("em_resident")
        assert regime("gammas") == "compute" and regime("keep_device")
        assert "gammas" in stages and "em_streamed" not in stages
        assert ("em" in stages) is trains
        assert "pair_bound" in {s["name"] for s in table}
