"""TF-weighted approximate blocking (ISSUE 14 tentpole b): IDF-weighted
minhash sampling + TF-weighted Jaccard verification/ranking.

The contract under test (docs/blocking.md#tf-weighting):

  * recall at a FIXED pair budget with weighting on is >= the unweighted
    tier's on the typo corpus (the ShallowBlocker rarity-weighting
    claim);
  * candidate sets stay deterministic across runs, the budget stays a
    hard cap and emission stays best-first (shrinking the budget yields
    a prefix);
  * the IDF table round-trips through the LinkageIndex artifact and the
    serve fallback's query-side signatures share it (garbled queries
    still recover their twins);
  * weighting OFF is bit-compatible with previous rounds (same kernel,
    same band keys);
  * the weighted kernels audit clean in all analysis layers and the
    registrations are falsifiable (broken twins trip TA-DTYPE /
    SA-COLL).
"""

import warnings

import numpy as np
import pandas as pd
import pytest

from splink_tpu.approx.lsh import generate_approx_candidates
from splink_tpu.approx.minhash import (
    DF_TABLE_SIZE,
    band_key_arrays,
    gram_df_table,
    idf_weights,
)
from splink_tpu.data import encode_table
from splink_tpu.settings import complete_settings_dict

N_BASE = 80


def _settings(**over):
    s = {
        "link_type": "dedupe_only",
        "comparison_columns": [
            {"col_name": "first_name"},
            {"col_name": "surname"},
        ],
        "blocking_rules": [
            "l.first_name = r.first_name",
            "l.surname = r.surname",
        ],
        "approx_blocking": True,
        "approx_threshold": 0.2,
        "approx_tf_weighting": True,
    }
    s.update(over)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return complete_settings_dict(s)


def _corrupt(value: str, rng) -> str:
    k = int(rng.integers(0, len(value)))
    return value[:k] + "#" + value[k + 1 :]


def typo_corpus(n=N_BASE, seed=7):
    rng = np.random.default_rng(seed)
    firsts = ["amelia", "oliver", "isla", "george", "ava", "noah", "emily"]
    lasts = ["smith", "jones", "taylor", "brown", "wilson", "evans"]
    base = pd.DataFrame(
        {
            "unique_id": range(n),
            "first_name": [f"{rng.choice(firsts)}{k:02d}" for k in range(n)],
            "surname": [f"{rng.choice(lasts)}{k:02d}" for k in range(n)],
        }
    )
    twins = base.copy()
    twins["unique_id"] = twins["unique_id"] + n
    crng = np.random.default_rng(seed + 1)
    twins["first_name"] = [_corrupt(v, crng) for v in twins["first_name"]]
    twins["surname"] = [_corrupt(v, crng) for v in twins["surname"]]
    df = pd.concat([base, twins], ignore_index=True)
    true = {(k, k + n) for k in range(n)}
    return df, true


def _recall_at(settings, table, true, budget):
    res = generate_approx_candidates(settings, table)
    assert res is not None
    i, j, coll, sim, stats = res
    order = np.lexsort((j, i, -coll, -sim))[:budget]
    emitted = set(zip(i[order].tolist(), j[order].tolist()))
    return len(true & emitted) / len(true), stats


def test_weighted_recall_at_tight_budget_beats_unweighted():
    """The perf claim at test scale: where the budget is the binding
    constraint (budget = n on this corpus), the TF-weighted ranking puts
    strictly more true twins inside it than the unweighted tier (the
    builders once counted 89.0% -> 97.1% at 8n on a larger corpus)."""
    df, true = typo_corpus()
    budget = N_BASE
    s_on = _settings(approx_pair_budget=budget)
    s_off = _settings(approx_pair_budget=budget, approx_tf_weighting=False)
    rec_on, stats_on = _recall_at(s_on, encode_table(df, s_on), true, budget)
    rec_off, stats_off = _recall_at(
        s_off, encode_table(df, s_off), true, budget
    )
    assert stats_on["tf_weighted"] is True
    assert stats_off["tf_weighted"] is False
    assert rec_on > rec_off
    assert rec_on >= 0.85


def test_weighted_candidates_deterministic():
    df, _ = typo_corpus()
    s = _settings()
    table = encode_table(df, s)
    r1 = generate_approx_candidates(s, table)
    r2 = generate_approx_candidates(s, table)
    for a, b in zip(r1[:4], r2[:4]):
        assert np.array_equal(a, b)


def test_weighted_budget_prefix_best_first():
    """Shrinking the budget yields a PREFIX of the larger emission under
    the TF-weighted ranking — progressive blocking survives weighting."""
    from splink_tpu.blocking import block_using_rules

    df, _ = typo_corpus(40)
    big = _settings(approx_pair_budget=400)
    small = _settings(approx_pair_budget=100)
    t_big = encode_table(df, big)
    t_small = encode_table(df, small)
    pairs_big = block_using_rules(big, t_big)
    pairs_small = block_using_rules(small, t_small)
    exact = _settings(approx_blocking=False)
    n_exact = block_using_rules(exact, encode_table(df, exact)).n_pairs
    big_approx = list(
        zip(
            pairs_big.idx_l[n_exact:].tolist(),
            pairs_big.idx_r[n_exact:].tolist(),
        )
    )
    small_approx = list(
        zip(
            pairs_small.idx_l[n_exact:].tolist(),
            pairs_small.idx_r[n_exact:].tolist(),
        )
    )
    assert len(small_approx) <= 100
    assert small_approx == big_approx[: len(small_approx)]


def test_unweighted_band_keys_unchanged_by_new_kernel_parameter():
    """weighted=False traces the exact kernel previous rounds shipped:
    passing idf=None through band_key_arrays yields the same keys as a
    direct unweighted call (bit-compatibility of the default)."""
    df, _ = typo_corpus(24)
    s = _settings(approx_tf_weighting=False)
    table = encode_table(df, s)
    from splink_tpu.approx.lsh import column_arrays

    cols = column_arrays(table, ["first_name", "surname"])
    k1, h1 = band_key_arrays(cols, 2, 8, 2)
    k2, h2 = band_key_arrays(cols, 2, 8, 2, idf=None)
    assert np.array_equal(k1, k2) and np.array_equal(h1, h2)


def test_idf_table_shape_and_weights():
    df, _ = typo_corpus(24)
    s = _settings()
    table = encode_table(df, s)
    from splink_tpu.approx.lsh import column_arrays

    cols = column_arrays(table, ["first_name", "surname"])
    counts, n = gram_df_table(cols, 2)
    assert counts.shape == (DF_TABLE_SIZE,)
    assert n == table.n_rows
    assert counts.sum() > 0
    idf = idf_weights(counts, n)
    assert idf.dtype == np.float32
    assert (idf > 0).all()
    # rarity is monotone: an empty bucket outweighs a crowded one
    assert idf[np.argmin(counts)] >= idf[np.argmax(counts)]


def test_weighted_idf_changes_band_keys():
    """The weighted sampler actually samples differently: with a skewed
    IDF table at least one record's band keys differ from unweighted."""
    df, _ = typo_corpus(24)
    s = _settings()
    table = encode_table(df, s)
    from splink_tpu.approx.lsh import column_arrays

    cols = column_arrays(table, ["first_name", "surname"])
    counts, n = gram_df_table(cols, 2)
    idf = idf_weights(counts, n)
    k_un, _ = band_key_arrays(cols, 2, 8, 2)
    k_w, _ = band_key_arrays(cols, 2, 8, 2, idf=idf)
    assert not np.array_equal(k_un, k_w)


def test_serve_fallback_shares_idf_and_recovers_twins(tmp_path):
    """End to end through the serve artifact: a TF-weighted approx index
    round-trips its IDF table, and garbled queries (every exact key
    corrupted) recover their reference twins through the weighted
    fallback band path, approx-tagged."""
    from splink_tpu import Splink
    from splink_tpu.serve import BucketPolicy, QueryEngine, load_index

    df, _ = typo_corpus(60)
    base = df.iloc[:60].reset_index(drop=True)
    garbled = df.iloc[60:].reset_index(drop=True)
    s = _settings(max_iterations=2)
    linker = Splink(dict(s), df=base)
    linker.get_scored_comparisons()
    index = linker.export_index()
    assert index.approx is not None and index.approx.idf is not None
    index.save(tmp_path)
    loaded = load_index(tmp_path)
    assert loaded.approx.idf is not None
    assert np.array_equal(loaded.approx.idf, index.approx.idf)
    assert (
        loaded.content_fingerprint() == index.content_fingerprint()
    )
    eng = QueryEngine(
        loaded, top_k=8, policy=BucketPolicy((64,), (256, 1024))
    )
    eng.warmup()
    res = eng.query(garbled)
    assert len(res) > 0
    assert res["approx"].any()
    recovered = 0
    for k in range(len(garbled)):
        uid = garbled.iloc[k]["unique_id"]
        mine = res[res["unique_id_q"] == uid]
        if (mine["unique_id_m"] == uid - 60).any():
            recovered += 1
    assert recovered / len(garbled) >= 0.9


# ---------------------------------------------------------------------------
# Audit falsifiability twins
# ---------------------------------------------------------------------------


def test_weighted_kernels_registered_and_clean():
    from splink_tpu.analysis.trace_audit import run_audit

    findings, audited = run_audit(
        ["approx_minhash_weighted", "approx_verify_weighted"]
    )
    assert audited == 2
    assert not findings, "\n".join(f.format() for f in findings)


def test_weighted_shard_kernels_registered_and_clean():
    from splink_tpu.analysis.shard_audit import run_shard_audit

    findings, audited = run_shard_audit(
        ["approx_minhash_weighted_sharded", "approx_verify_weighted_sharded"]
    )
    assert audited == 2
    assert not findings, "\n".join(f.format() for f in findings)


def test_bad_weighted_race_trips_ta_dtype():
    """A doctored race whose uniform derives through an unpinned float
    conversion goes float64 under the forced-x64 trace — TA-DTYPE."""
    from splink_tpu.analysis.trace_audit import KernelSpec, audit_kernel

    def build():
        import jax.numpy as jnp

        def bad(hk, w):
            u = (hk.astype(jnp.float64) + 0.5) * (2.0 ** -32)  # unpinned
            return -jnp.log(u) / w[:, None]

        hk = jnp.zeros((8, 4), jnp.uint32)
        w = jnp.ones(8, jnp.float32)
        return bad, (hk, w), {}

    spec = KernelSpec(name="bad_weighted_race_dtype", build=build)
    findings = audit_kernel(spec)
    assert any(f.rule == "TA-DTYPE" for f in findings), [
        f.format() for f in findings
    ]


def test_bad_weighted_idf_shard_trips_sa_coll():
    """A twin that shards the IDF table over the record axis forces GSPMD
    to all-gather it for the per-gram weight lookup — SA-COLL (the
    production kernel replicates the table)."""
    from splink_tpu.analysis.shard_audit import (
        audit_shard_kernel,
        register_shard_kernel,
    )

    registry: dict = {}

    @register_shard_kernel(
        "bad_weighted_idf_sharded", n_pairs=64, registry=registry
    )
    def _build():
        import jax

        from splink_tpu.analysis.shard_audit import audit_mesh
        from splink_tpu.parallel.mesh import pair_sharding

        mesh = audit_mesh()
        shard = pair_sharding(mesh)
        idf = jax.device_put(
            np.ones(DF_TABLE_SIZE, np.float32), shard
        )  # WRONG: must replicate
        slots = jax.device_put(np.zeros(64, np.int32), shard)

        def bad(idf, slots):
            return idf[slots]

        return bad, (idf, slots), {}

    findings = audit_shard_kernel(registry["bad_weighted_idf_sharded"], None)
    assert any(f.rule == "SA-COLL" for f in findings), [
        f.format() for f in findings
    ]
