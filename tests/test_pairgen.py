"""Device-side pair generation (the virtual pair index): the decoded pair
stream must contain EXACTLY the pairs host blocking materialises — same
(i, j) multiset after masking, same orientation, same sequential-rule dedup
— across group sizes that force unit splitting, duplicate uids, nulls, and
both supported link types; and the linker's virtual pattern pipeline must
score identically to the materialised pipelines."""

import numpy as np
import pandas as pd
import pytest

import splink_tpu.pairgen as pairgen
from splink_tpu import Splink
from splink_tpu.blocking import block_using_rules
from splink_tpu.data import concat_tables, encode_table
from splink_tpu.gammas import GammaProgram
from splink_tpu.pairgen import (
    build_virtual_plan,
    compute_virtual_pattern_ids,
    decode_positions,
)
from splink_tpu.settings import complete_settings_dict


def _pairs_from_plan(plan):
    """Decode the ENTIRE virtual stream host-side, drop masked."""
    out = []
    for r, rp in enumerate(plan.rules):
        if rp.total == 0:
            continue
        q = np.arange(rp.total, dtype=np.int64)
        i, j, masked = decode_positions(plan, r, q)
        out.append((i[~masked], j[~masked]))
    if not out:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    return (
        np.concatenate([a for a, _ in out]),
        np.concatenate([b for _, b in out]),
    )


def _pair_set(i, j):
    return set(zip(np.asarray(i).tolist(), np.asarray(j).tolist()))


def _settings(rules, link_type="dedupe_only", cols=None):
    return complete_settings_dict(
        {
            "link_type": link_type,
            "comparison_columns": cols
            or [{"col_name": "name", "num_levels": 2}],
            "blocking_rules": rules,
        }
    )


def _df(n, seed, uid=None):
    rng = np.random.default_rng(seed)
    return pd.DataFrame(
        {
            "unique_id": uid if uid is not None else np.arange(n),
            "name": rng.choice(["ann", "bob", "cat", None], n),
            "city": rng.choice([f"c{k}" for k in range(max(n // 30, 2))], n),
            "dob": rng.choice([f"d{k}" for k in range(max(n // 8, 2))], n),
        }
    )


@pytest.mark.parametrize("chunk", [4, 16, 2048])
@pytest.mark.parametrize(
    "rules",
    [
        ["l.city = r.city"],
        ["l.dob = r.dob", "l.city = r.city"],
        ["l.city = r.city", "l.dob = r.dob", "l.name = r.name"],
    ],
)
def test_virtual_pairs_equal_host_blocking_dedupe(chunk, rules):
    df = _df(240, seed=7)
    s = _settings(rules)
    table = encode_table(df, s)
    want = block_using_rules(s, table)
    plan = build_virtual_plan(s, table, chunk=chunk)
    assert plan is not None
    i, j = _pairs_from_plan(plan)
    assert len(i) == want.n_pairs
    assert _pair_set(i, j) == _pair_set(want.idx_l, want.idx_r)
    # orientation: every decoded pair has rank_i < rank_j == i < j here
    assert (i < j).all()


def test_virtual_pairs_with_duplicate_uids(monkeypatch):
    # duplicate uids: the strict l.uid < r.uid ordering drops equal-uid
    # pairs — the device mask must reproduce that
    uid = np.array([0, 1, 1, 2, 3, 3, 3, 4, 5, 6] * 8)
    df = _df(80, seed=9, uid=uid)
    s = _settings(["l.city = r.city", "l.dob = r.dob"])
    table = encode_table(df, s)
    want = block_using_rules(s, table)
    plan = build_virtual_plan(s, table, chunk=8)
    assert plan is not None and plan.uid_codes is not None
    i, j = _pairs_from_plan(plan)
    uidv = df["unique_id"].to_numpy()

    def keyed(ii, jj):
        return set(zip(uidv[np.asarray(ii)], uidv[np.asarray(jj)]))

    assert len(i) == want.n_pairs
    assert _pair_set(i, j) == _pair_set(want.idx_l, want.idx_r)


@pytest.mark.parametrize("chunk", [4, 2048])
def test_virtual_pairs_equal_host_blocking_link_only(chunk):
    df = _df(200, seed=11)
    df_l, df_r = df.iloc[:120].copy(), df.iloc[120:].copy()
    s = _settings(
        ["l.city = r.city", "l.dob = r.dob"], link_type="link_only"
    )
    table = concat_tables(df_l, df_r, s)
    want = block_using_rules(s, table, n_left=len(df_l))
    plan = build_virtual_plan(s, table, n_left=len(df_l), chunk=chunk)
    assert plan is not None
    i, j = _pairs_from_plan(plan)
    assert len(i) == want.n_pairs
    assert _pair_set(i, j) == _pair_set(want.idx_l, want.idx_r)
    assert (i < 120).all() and (j >= 120).all()  # left rows on the l side


def test_unsupported_shapes_fall_back():
    df = _df(40, seed=1)
    # no rule at all is one keyless group of every row, not a fallback
    s = _settings([])
    plan = build_virtual_plan(s, encode_table(df, s))
    assert plan.n_candidates == 40 * 39 // 2 and len(plan.rules) == 1
    # rule with no equality conjunction at all
    s = _settings(["l.dob != r.dob"])
    assert build_virtual_plan(s, encode_table(df, s)) is None


def test_device_kernel_matches_host_decode():
    """The jitted int32/f32 decode must agree with the f64 host oracle at
    every position, including multi-chunk groups and batch boundaries that
    split units."""
    df = _df(300, seed=13)
    s = _settings(["l.dob = r.dob", "l.city = r.city"])
    table = encode_table(df, s)
    plan = build_virtual_plan(s, table, chunk=8)  # force many units
    program = GammaProgram(s, table)
    ids, counts, n_real = compute_virtual_pattern_ids(
        program, plan, batch_size=128
    )
    pids = ids.pid
    # oracle: decode on host, score the unmasked pairs through the
    # materialised pattern pipeline
    i, j = _pairs_from_plan(plan)
    want_p, want_c = program.compute_pattern_ids(i, j, batch_size=128)
    np.testing.assert_array_equal(counts, want_c)
    assert n_real == len(i)
    # pids: positions that aren't masked must carry the same pattern id,
    # in the same relative order
    sentinel = program.n_patterns
    got_real = pids[pids != sentinel]
    np.testing.assert_array_equal(
        got_real.astype(np.int32), want_p.astype(np.int32)
    )


def _linker_settings(**over):
    s = {
        "link_type": "dedupe_only",
        "comparison_columns": [
            {"col_name": "name", "num_levels": 2},
            {"col_name": "dob", "num_levels": 2},
        ],
        "blocking_rules": ["l.city = r.city", "l.dob = r.dob"],
        "max_iterations": 4,
    }
    s.update(over)
    return s


def test_linker_virtual_pipeline_matches_materialised():
    # max_resident_pairs forces BOTH sides into the pattern regime, so the
    # only difference is virtual vs materialised pairs — must be bitwise
    df = _df(260, seed=17)
    on = Splink(
        _linker_settings(
            device_pair_generation="on", max_resident_pairs=1024
        ),
        df=df,
    ).get_scored_comparisons()
    off = Splink(
        _linker_settings(
            device_pair_generation="off", max_resident_pairs=1024
        ),
        df=df,
    ).get_scored_comparisons()
    key = ["unique_id_l", "unique_id_r"]
    on = on.sort_values(key).reset_index(drop=True)
    off = off.sort_values(key).reset_index(drop=True)
    assert len(on) == len(off)
    np.testing.assert_array_equal(on[key].to_numpy(), off[key].to_numpy())
    np.testing.assert_allclose(
        on["match_probability"], off["match_probability"], rtol=1e-12
    )
    np.testing.assert_array_equal(on["gamma_name"], off["gamma_name"])


def test_linker_virtual_stream_and_inference():
    df = _df(200, seed=19)
    s = _linker_settings(device_pair_generation="on", max_iterations=0)
    a = Splink(s, df=df).manually_apply_fellegi_sunter_weights()
    b = Splink(
        _linker_settings(device_pair_generation="off", max_iterations=0),
        df=df,
    ).manually_apply_fellegi_sunter_weights()
    key = ["unique_id_l", "unique_id_r"]
    a = a.sort_values(key).reset_index(drop=True)
    b = b.sort_values(key).reset_index(drop=True)
    np.testing.assert_allclose(
        a["match_probability"], b["match_probability"], rtol=1e-12
    )
    # streamed chunks concatenate to the same frame
    lk = Splink(s, df=df)
    chunks = list(lk.stream_scored_comparisons())
    c = pd.concat(chunks, ignore_index=True).sort_values(key)
    np.testing.assert_allclose(
        c["match_probability"].to_numpy(),
        a["match_probability"].to_numpy(),
        rtol=1e-12,
    )


def test_virtual_materialised_ids_stream_matches_recompute():
    """virtual_materialise_ids: the LUT-only stream from stored ids must
    be bitwise identical to the recompute stream, and the auto policy
    must engage exactly on the scoring path."""
    df = _df(240, seed=29)
    kw = dict(device_pair_generation="on", max_resident_pairs=1024)
    kept = Splink(_linker_settings(**kw), df=df)
    gen = kept.stream_scored_comparisons()
    chunks = [next(gen)]
    # policy engaged: ids kept from the EM pass (checked mid-stream —
    # exhausting the generator releases them)
    assert kept._P_virtual is not None
    assert kept._P_virtual.pid.dtype == np.uint16
    assert kept._P_virtual.il.dtype == kept._P_virtual.ir.dtype == np.int32
    chunks.extend(gen)
    assert kept._P_virtual is None  # released once the stream is exhausted
    out_kept = pd.concat(chunks, ignore_index=True)
    # the one-frame API releases the ids once the frame is materialised
    released = Splink(_linker_settings(**kw), df=df)
    out_frame = released.get_scored_comparisons()
    assert released._P_virtual is None
    off = Splink(
        _linker_settings(virtual_materialise_ids="off", **kw), df=df
    )
    out_off = off.get_scored_comparisons()
    assert off._P_virtual is None  # forced two-pass
    key = ["unique_id_l", "unique_id_r"]
    a = out_kept.sort_values(key).reset_index(drop=True)
    b = out_off.sort_values(key).reset_index(drop=True)
    c = out_frame.sort_values(key).reset_index(drop=True)
    np.testing.assert_array_equal(a[key].to_numpy(), b[key].to_numpy())
    np.testing.assert_array_equal(
        a["match_probability"].to_numpy(), b["match_probability"].to_numpy()
    )
    np.testing.assert_array_equal(a[key].to_numpy(), c[key].to_numpy())
    np.testing.assert_array_equal(
        a["match_probability"].to_numpy(), c["match_probability"].to_numpy()
    )
    # EM-only entry points keep the histogram-only pass under auto
    em_only = Splink(_linker_settings(**kw), df=df)
    assert em_only._virtual_plan() is not None
    em_only._run_em_patterns(False)
    assert em_only._P_virtual is None


def test_linker_virtual_auto_gate():
    """auto mode only engages above max_resident_pairs."""
    df = _df(200, seed=23)
    small = Splink(_linker_settings(), df=df)
    small.get_scored_comparisons()
    assert small._virtual is None  # tiny job: resident regime
    big = Splink(_linker_settings(max_resident_pairs=1024), df=df)
    big.get_scored_comparisons()
    assert big._virtual is not None


def test_virtual_zero_pairs_returns_empty_frame():
    """Unique keys -> zero candidates: a valid empty result, not a crash
    (and the materialised path agrees)."""
    df = pd.DataFrame(
        {
            "unique_id": range(8),
            "name": [f"u{k}" for k in range(8)],
            "key": [f"k{k}" for k in range(8)],  # unique: no pairs
        }
    )
    base = {
        "link_type": "dedupe_only",
        "comparison_columns": [{"col_name": "name", "num_levels": 2}],
        "blocking_rules": ["l.key = r.key"],
        "max_iterations": 3,
    }
    import warnings as w

    with w.catch_warnings():
        w.simplefilter("ignore")
        on = Splink(
            dict(base, device_pair_generation="on"), df=df
        ).get_scored_comparisons()
        off = Splink(
            dict(base, device_pair_generation="off"), df=df
        ).get_scored_comparisons()
    assert len(on) == 0 and len(off) == 0
    assert "match_probability" in on.columns
    # inference path too
    with w.catch_warnings():
        w.simplefilter("ignore")
        inf = Splink(
            dict(base, device_pair_generation="on", max_iterations=0), df=df
        ).manually_apply_fellegi_sunter_weights()
    assert len(inf) == 0


@pytest.mark.parametrize("chunk", [4, 2048])
def test_virtual_pairs_equal_host_blocking_link_and_dedupe(chunk):
    df = _df(180, seed=29)
    df_l, df_r = df.iloc[:100].copy(), df.iloc[100:].copy()
    # overlapping uid spaces: the (source, uid) ordering and equal-key drop
    # must both reproduce
    df_r = df_r.assign(unique_id=df_r["unique_id"] - 80)
    s = _settings(
        ["l.city = r.city", "l.dob = r.dob"], link_type="link_and_dedupe"
    )
    table = concat_tables(df_l, df_r, s)
    want = block_using_rules(s, table, n_left=len(df_l))
    plan = build_virtual_plan(s, table, n_left=len(df_l), chunk=chunk)
    assert plan is not None
    i, j = _pairs_from_plan(plan)
    assert len(i) == want.n_pairs
    assert _pair_set(i, j) == _pair_set(want.idx_l, want.idx_r)


def test_linker_virtual_link_and_dedupe_matches_materialised():
    df = _df(160, seed=31)
    df_l, df_r = df.iloc[:90].copy(), df.iloc[90:].copy()
    base = {
        "link_type": "link_and_dedupe",
        "comparison_columns": [{"col_name": "name", "num_levels": 2}],
        "blocking_rules": ["l.city = r.city"],
        "max_iterations": 3,
        "max_resident_pairs": 1024,
    }
    a = Splink(
        dict(base, device_pair_generation="on"), df_l=df_l, df_r=df_r
    ).get_scored_comparisons()
    b = Splink(
        dict(base, device_pair_generation="off"), df_l=df_l, df_r=df_r
    ).get_scored_comparisons()
    key = ["unique_id_l", "unique_id_r", "_source_table_l", "_source_table_r"]
    a = a.sort_values(key).reset_index(drop=True)
    b = b.sort_values(key).reset_index(drop=True)
    assert len(a) == len(b)
    np.testing.assert_allclose(
        a["match_probability"], b["match_probability"], rtol=1e-12
    )
    np.testing.assert_array_equal(
        a["_source_table_l"].to_numpy(), b["_source_table_l"].to_numpy()
    )


def test_monster_group_falls_back(monkeypatch):
    # a group exceeding MAX_UNITS_PER_GROUP (here: tiny synthetic caps)
    # must reject the plan rather than corrupt the unit ordering key
    monkeypatch.setattr(pairgen, "MAX_UNITS_PER_GROUP", 3)
    df = pd.DataFrame(
        {
            "unique_id": range(40),
            "name": ["x"] * 40,
            "key": ["same"] * 40,  # one 40-row group
        }
    )
    s = _settings(["l.key = r.key"])
    table = encode_table(df, s)
    assert build_virtual_plan(s, table, chunk=4) is None
    # and the linker quietly uses host blocking instead
    base = {
        "link_type": "dedupe_only",
        "comparison_columns": [{"col_name": "name", "num_levels": 2}],
        "blocking_rules": ["l.key = r.key"],
        "max_iterations": 2,
        "max_resident_pairs": 1024,
        "device_pair_generation": "on",
    }
    out = Splink(base, df=df).get_scored_comparisons()
    assert len(out) == 40 * 39 // 2


@pytest.mark.parametrize("chunk", [4, 2048])
def test_virtual_link_and_dedupe_duplicate_source_uid_keys(chunk):
    """DUPLICATE (source, uid) combos: the equal-key drop must key on the
    (source, uid) pair — plain uid codes would wrongly drop legitimate
    cross-source same-uid pairs."""
    # left has uid 5 twice; right has uid 5 twice too — within-source
    # duplicate keys AND cross-source same-uid pairs both present
    df_l = pd.DataFrame(
        {
            "unique_id": [1, 5, 5, 7, 9],
            "name": ["a", "b", "c", "d", "e"],
            "city": ["x"] * 5,
        }
    )
    df_r = pd.DataFrame(
        {
            "unique_id": [5, 5, 7, 11],
            "name": ["f", "g", "h", "i"],
            "city": ["x"] * 4,
        }
    )
    s = _settings(["l.city = r.city"], link_type="link_and_dedupe")
    table = concat_tables(df_l, df_r, s)
    want = block_using_rules(s, table, n_left=len(df_l))
    plan = build_virtual_plan(s, table, n_left=len(df_l), chunk=chunk)
    assert plan is not None and plan.uid_codes is not None
    i, j = _pairs_from_plan(plan)
    assert len(i) == want.n_pairs
    assert _pair_set(i, j) == _pair_set(want.idx_l, want.idx_r)
    # cross-source same-uid pairs survive (uid 5 left vs uid 5 right)
    uidv = table.unique_id
    src = table.source_table
    cross_same = [
        (a, b)
        for a, b in zip(i, j)
        if uidv[a] == uidv[b] and src[a] != src[b]
    ]
    assert cross_same, "cross-source same-uid pairs must not be dropped"


@pytest.mark.parametrize("chunk", [4, 2048])
@pytest.mark.parametrize(
    "rules",
    [
        # same-vocab string inequality residual
        ["l.city = r.city and l.dob != r.dob"],
        # numeric threshold residual (abs + comparison)
        ["l.city = r.city and abs(l.age - r.age) < 5"],
        # residual on an EARLIER rule exercises the prev-holds path
        ["l.city = r.city and l.dob != r.dob", "l.dob = r.dob"],
        # string literal + IS NULL shapes
        ["l.city = r.city and l.name != 'ann'"],
        ["l.city = r.city and l.name is not null"],
        # ordering comparison over string ranks
        ["l.city = r.city and l.dob < r.dob"],
    ],
)
def test_virtual_residuals_equal_host_blocking(chunk, rules):
    rng = np.random.default_rng(37)
    n = 220
    df = _df(n, seed=37)
    df["age"] = rng.integers(20, 60, n).astype(float)
    df.loc[rng.random(n) < 0.1, "age"] = np.nan
    s = _settings(rules)
    table = encode_table(df, s)
    want = block_using_rules(s, table)
    plan = build_virtual_plan(s, table, chunk=chunk)
    assert plan is not None, rules
    i, j = _pairs_from_plan(plan)
    assert len(i) == want.n_pairs
    assert _pair_set(i, j) == _pair_set(want.idx_l, want.idx_r)


def test_virtual_residual_device_kernel_matches_host():
    """The compiled residual closures run INSIDE the jitted kernel and
    must agree with the host evaluate_residual oracle (x64 on in the CPU
    tier, so numeric thresholds are bit-identical)."""
    rng = np.random.default_rng(43)
    n = 260
    df = _df(n, seed=43)
    df["age"] = rng.integers(20, 60, n).astype(float)
    df.loc[rng.random(n) < 0.15, "age"] = np.nan
    s = _settings(
        [
            "l.city = r.city and abs(l.age - r.age) <= 3",
            "l.dob = r.dob and l.name != r.name",
        ]
    )
    table = encode_table(df, s)
    plan = build_virtual_plan(s, table, chunk=8)
    assert plan is not None and plan.res_ops
    program = GammaProgram(s, table)
    ids, counts, n_real = compute_virtual_pattern_ids(
        program, plan, batch_size=128
    )
    pids = ids.pid
    i, j = _pairs_from_plan(plan)  # host oracle (incl. residual masks)
    assert n_real == len(i)
    want_p, want_c = program.compute_pattern_ids(i, j, batch_size=128)
    np.testing.assert_array_equal(counts, want_c)
    sentinel = program.n_patterns
    np.testing.assert_array_equal(
        pids[pids != sentinel].astype(np.int32), want_p.astype(np.int32)
    )


def test_virtual_residual_linker_e2e():
    rng = np.random.default_rng(47)
    n = 240
    df = _df(n, seed=47)
    df["age"] = rng.integers(20, 60, n).astype(float)
    base = {
        "link_type": "dedupe_only",
        "comparison_columns": [{"col_name": "name", "num_levels": 2}],
        "blocking_rules": ["l.city = r.city and abs(l.age - r.age) < 10"],
        "max_iterations": 3,
        "max_resident_pairs": 1024,
    }
    a = Splink(
        dict(base, device_pair_generation="on"), df=df
    ).get_scored_comparisons()
    b = Splink(
        dict(base, device_pair_generation="off"), df=df
    ).get_scored_comparisons()
    key = ["unique_id_l", "unique_id_r"]
    a = a.sort_values(key).reset_index(drop=True)
    b = b.sort_values(key).reset_index(drop=True)
    assert len(a) == len(b) and len(a) > 0
    np.testing.assert_allclose(
        a["match_probability"], b["match_probability"], rtol=1e-12
    )


def test_virtual_residual_on_raw_passthrough_column():
    # a passthrough column (blocking-rule-only reference) is an object
    # array on the host; the device path compares it via lexicographic
    # ranks — same result as host object comparison
    rng = np.random.default_rng(3)
    df = _df(60, seed=3)
    df["note"] = rng.choice(["p", "q", "r", None], 60)
    s = complete_settings_dict(
        {
            "link_type": "dedupe_only",
            "comparison_columns": [{"col_name": "name", "num_levels": 2}],
            "blocking_rules": ["l.city = r.city and l.note != r.note"],
        }
    )
    table = encode_table(df, s)
    want = block_using_rules(s, table)
    plan = build_virtual_plan(s, table, chunk=8)
    assert plan is not None
    i, j = _pairs_from_plan(plan)
    assert len(i) == want.n_pairs
    assert _pair_set(i, j) == _pair_set(want.idx_l, want.idx_r)


def test_virtual_residual_cross_column_compare():
    # different columns (different vocabularies) compare through a union
    # vocabulary — parity with the host's elementwise object comparison
    df = _df(80, seed=5)
    s = _settings(["l.city = r.city and l.name != r.dob"])
    table = encode_table(df, s)
    want = block_using_rules(s, table)
    plan = build_virtual_plan(s, table, chunk=8)
    assert plan is not None
    i, j = _pairs_from_plan(plan)
    assert len(i) == want.n_pairs
    assert _pair_set(i, j) == _pair_set(want.idx_l, want.idx_r)


def test_virtual_residual_str_numeric_mismatch_falls_back():
    # the host raises a type-mismatch for a bare string-vs-number compare;
    # the device must not accept a plan it would crash on
    df = _df(30, seed=2)
    for rule in (
        "l.city = r.city and l.name > 5",
        "l.city = r.city and l.name != 7",
    ):
        s = _settings([rule])
        assert build_virtual_plan(s, encode_table(df, s)) is None, rule


def test_virtual_residual_string_typed_numeric_values():
    """A string-typed column holding numeric values: the host orders it
    through str()-coerced ranks ('10' < '2'); the device must match."""
    rng = np.random.default_rng(61)
    n = 90
    df = pd.DataFrame(
        {
            "unique_id": np.arange(n),
            "name": rng.choice(["a", "b"], n),
            "city": rng.choice(["x", "y", "z"], n),
            # ints in a string-typed compared column: 2 vs 10 order as
            # strings, not numbers
            "code": rng.integers(1, 30, n),
        }
    )
    s = complete_settings_dict(
        {
            "link_type": "dedupe_only",
            "comparison_columns": [
                {"col_name": "name", "num_levels": 2},
                {"col_name": "code", "num_levels": 2},  # string by default
            ],
            "blocking_rules": ["l.city = r.city and l.code < r.code"],
        }
    )
    table = encode_table(df, s)
    want = block_using_rules(s, table)
    plan = build_virtual_plan(s, table, chunk=8)
    assert plan is not None
    i, j = _pairs_from_plan(plan)
    assert len(i) == want.n_pairs
    assert _pair_set(i, j) == _pair_set(want.idx_l, want.idx_r)


# ----------------------------------------------------------------------
# Mesh-sharded virtual pair generation (VERDICT r3 next-#3): the device
# pair stream shards over the mesh's data axis and must stay bitwise
# identical to the single-device pass; the linker composes it with
# mesh EM end-to-end.
# ----------------------------------------------------------------------


def test_virtual_pattern_ids_mesh_bit_parity():
    from splink_tpu.parallel.mesh import make_mesh

    df = _df(300, seed=29)
    s = _settings(
        ["l.city = r.city", "l.dob = r.dob", "l.name = r.name"],
        cols=[
            {"col_name": "name", "num_levels": 2},
            {"col_name": "dob", "num_levels": 3},
        ],
    )
    t = encode_table(df, s)
    plan = build_virtual_plan(s, t, chunk=32)
    assert plan is not None
    prog = GammaProgram(s, t)
    pids1, counts1, n1 = compute_virtual_pattern_ids(prog, plan, 997)
    mesh = make_mesh(8)
    pids2, counts2, n2 = compute_virtual_pattern_ids(
        prog, plan, 997, mesh=mesh
    )
    assert n1 == n2
    np.testing.assert_array_equal(counts1, counts2)
    for one, sharded in zip(pids1, pids2):  # pattern ids, then the row pairs
        np.testing.assert_array_equal(one, sharded)


def test_virtual_mesh_with_derived_keys_and_residuals():
    from splink_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(31)
    n = 260
    df = pd.DataFrame(
        {
            "unique_id": np.arange(n),
            "name": rng.choice(["ann", "bob", "cat", None], n),
            "surname": rng.choice(
                ["smithson", "smithers", "smyth", "jones", None], n
            ),
            "city": rng.choice(["c0", "c1", "c2"], n),
            "dob": rng.choice(["d0", "d1"], n),
        }
    )
    s = _settings(
        [
            "substr(l.surname, 1, 3) = substr(r.surname, 1, 3)",
            "l.city = r.city and length(l.surname) = length(r.surname)",
        ],
        cols=[{"col_name": "name", "num_levels": 2}],
    )
    t = encode_table(df, s)
    plan = build_virtual_plan(s, t, chunk=64)
    assert plan is not None
    prog = GammaProgram(s, t)
    pids1, counts1, n1 = compute_virtual_pattern_ids(prog, plan, 640)
    pids2, counts2, n2 = compute_virtual_pattern_ids(
        prog, plan, 640, mesh=make_mesh(8)
    )
    assert n1 == n2
    np.testing.assert_array_equal(counts1, counts2)
    for one, sharded in zip(pids1, pids2):  # pattern ids, then the row pairs
        np.testing.assert_array_equal(one, sharded)


def test_linker_virtual_mesh_e2e_matches_single_device():
    """Full pipeline under a mesh: virtual pair generation shards its
    batches; scores must match the single-device virtual run exactly."""
    df = _df(260, seed=37)
    base = _linker_settings(
        device_pair_generation="on", max_resident_pairs=1024
    )
    single = Splink(base, df=df).get_scored_comparisons()
    meshed = Splink(
        dict(base, mesh={"data": 8}), df=df
    ).get_scored_comparisons()
    key = ["unique_id_l", "unique_id_r"]
    single = single.sort_values(key).reset_index(drop=True)
    meshed = meshed.sort_values(key).reset_index(drop=True)
    assert len(single) == len(meshed)
    np.testing.assert_array_equal(
        single[key].to_numpy(), meshed[key].to_numpy()
    )
    np.testing.assert_allclose(
        single["match_probability"], meshed["match_probability"], rtol=1e-12
    )
