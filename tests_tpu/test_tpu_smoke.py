"""Hardware smoke tier: compile and run the TPU-only code paths for real.

Covers the gap that shipped the round-1 regression: Pallas kernels were only
ever tested with interpret=True, so Mosaic lowering was never exercised. Each
test here runs the real compiled artifact on the chip and checks values
against the vmapped JAX implementations (which are themselves oracle-tested
in the CPU tier, tests/test_string_kernels.py).

Reference analogue: the "real engine" Spark tier of the reference suite
(/root/reference/tests/test_spark.py:22-68).
"""

import jax.numpy as jnp
import numpy as np
import pandas as pd


def _dev(*arrays):
    return tuple(jnp.asarray(a) for a in arrays)


class TestPallasKernelsOnHardware:
    def test_jaro_winkler_matches_vmapped(self, string_batch):
        from splink_tpu.ops import strings
        from splink_tpu.ops.strings_pallas import jaro_winkler_pallas

        s1, s2, l1, l2 = _dev(*string_batch)
        got = np.asarray(jaro_winkler_pallas(s1, s2, l1, l2))
        want = np.asarray(strings.jaro_winkler_vmapped(s1, s2, l1, l2, 0.1, 0.7))
        np.testing.assert_allclose(got, want, atol=1e-6)

    def test_jaro_winkler_known_value(self):
        from splink_tpu.ops.strings_pallas import jaro_winkler_pallas

        m1 = np.zeros((1, 16), np.uint8)
        m2 = np.zeros((1, 16), np.uint8)
        m1[0, :6] = np.frombuffer(b"MARTHA", np.uint8)
        m2[0, :6] = np.frombuffer(b"MARHTA", np.uint8)
        v = float(jaro_winkler_pallas(*_dev(m1, m2, [6], [6]))[0])
        assert abs(v - 0.9611) < 1e-3

    def test_levenshtein_matches_vmapped(self, string_batch):
        from splink_tpu.ops import strings
        from splink_tpu.ops.strings_pallas import levenshtein_pallas

        s1, s2, l1, l2 = _dev(*string_batch)
        got = np.asarray(levenshtein_pallas(s1, s2, l1, l2))
        want = np.asarray(strings.levenshtein_vmapped(s1, s2, l1, l2))
        np.testing.assert_allclose(got, want.astype(np.float32), atol=0)

    def test_dispatch_selects_pallas_on_tpu(self):
        from splink_tpu.ops.strings_pallas import pallas_supported

        a = jnp.zeros((8, 24), jnp.uint8)
        assert pallas_supported(a)


class TestPipelineOnHardware:
    def test_linker_end_to_end_on_device(self):
        """Full Splink flow — blocking, gamma program, fused EM — on the chip.

        Uses a jaro_winkler string column so the GammaProgram routes through
        the Pallas kernel (non-interpret)."""
        import splink_tpu

        rng = np.random.default_rng(7)
        names = ["olivia", "liam", "emma", "noah", "amelia", "oliver",
                 "sophia", "elijah", "isabella", "lucas"]
        rows = []
        for i in range(150):
            f = names[rng.integers(len(names))] + str(rng.integers(100))
            city = ["london", "leeds", "york", "bath"][rng.integers(4)]
            rows.append({"unique_id": 2 * i, "name": f, "city": city})
            g = list(f)
            g[1], g[2] = g[2], g[1]
            rows.append({"unique_id": 2 * i + 1, "name": "".join(g), "city": city})
        df = pd.DataFrame(rows)
        settings = {
            "link_type": "dedupe_only",
            "blocking_rules": ["l.city = r.city"],
            "comparison_columns": [
                {"col_name": "name", "data_type": "string", "num_levels": 3},
                {"col_name": "city", "data_type": "string", "num_levels": 2},
            ],
            "max_iterations": 10,
        }
        linker = splink_tpu.Splink(settings, df=df)
        scored = linker.get_scored_comparisons()
        dup = scored[(scored.unique_id_l // 2) == (scored.unique_id_r // 2)]
        non = scored[(scored.unique_id_l // 2) != (scored.unique_id_r // 2)]
        assert dup.match_probability.median() > 0.8
        assert non.match_probability.median() < 0.5

    def test_run_em_on_device(self):
        from splink_tpu.em import run_em
        from splink_tpu.models.fellegi_sunter import FSParams

        rng = np.random.default_rng(3)
        C, N = 4, 50_000
        m_t = np.tile([0.05, 0.1, 0.85], (C, 1))
        u_t = np.tile([0.7, 0.2, 0.1], (C, 1))
        is_m = rng.random(N) < 0.25
        G = np.zeros((N, C), np.int8)
        for c in range(C):
            G[:, c] = np.where(
                is_m, rng.choice(3, N, p=m_t[c]), rng.choice(3, N, p=u_t[c])
            )
        params0 = FSParams(
            lam=jnp.asarray(0.5),
            m=jnp.asarray(np.tile([0.1, 0.2, 0.7], (C, 1))),
            u=jnp.asarray(np.tile([0.7, 0.2, 0.1], (C, 1))),
        )
        out = run_em(
            jnp.asarray(G), params0, max_levels=3, max_iterations=40,
            em_convergence=1e-6,
        )
        assert abs(float(out.params.lam) - 0.25) < 0.02
        assert np.abs(np.asarray(out.params.m) - m_t).max() < 0.03


class TestCaseCompilerOnHardware:
    def test_case_sql_gamma_on_device(self):
        """A hand-written case_expression (general CASE compiler) lowers and
        runs inside the jitted gamma program on the chip."""
        from splink_tpu.data import encode_table
        from splink_tpu.gammas import GammaProgram
        from splink_tpu.settings import complete_settings_dict

        df = pd.DataFrame(
            {
                "unique_id": range(6),
                "name": ["martha", "martha", "marhta", "marx", "zz", None],
                "age": [40.0, 41.0, 39.0, 80.0, 40.0, None],
            }
        )
        expr = """case
            when name_l is null or name_r is null then -1
            when name_l = name_r and abs(age_l - age_r) <= 1 then 2
            when jaro_winkler_sim(name_l, name_r) > 0.9 then 1
            else 0 end"""
        s = complete_settings_dict(
            {
                "link_type": "dedupe_only",
                "comparison_columns": [
                    {
                        "custom_name": "combo",
                        "custom_columns_used": ["name", "age"],
                        "num_levels": 3,
                        "case_expression": expr,
                    }
                ],
                "blocking_rules": ["l.unique_id = r.unique_id"],
            }
        )
        table = encode_table(df, s)
        prog = GammaProgram(s, table)
        G = prog.compute(
            np.zeros(5, np.int64), np.arange(1, 6, dtype=np.int64)
        )
        assert G[:, 0].tolist() == [2, 1, 0, 0, -1]


class TestFloat64FallbackOnHardware:
    def test_float64_setting_warns_and_runs_f32_on_tpu(self):
        """TPU has no float64: the setting must warn and fall back to
        float32 rather than enabling x64 and failing to lower."""
        import warnings

        import splink_tpu

        df = pd.DataFrame(
            {
                "unique_id": range(40),
                "name": [f"n{i % 7}" for i in range(40)],
                "city": ["a", "b"] * 20,
            }
        )
        settings = {
            "link_type": "dedupe_only",
            "blocking_rules": ["l.city = r.city"],
            "comparison_columns": [
                {"col_name": "name", "comparison": {"kind": "exact"}}
            ],
            "float64": True,
            "max_iterations": 3,
        }
        linker = splink_tpu.Splink(settings, df=df)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            out = linker.get_scored_comparisons()
        assert out.match_probability.dtype == np.float32
        assert any("float64" in str(w.message) for w in caught)


class TestVirtualPairsOnHardware:
    def test_device_pair_generation_matches_materialised(self):
        """The virtual pair index (pairs decoded ON the chip from unit
        structure) scores identically to the materialised pattern pipeline
        on real hardware — int32 searchsorted, f32 triangle decode, masks
        and histogram all lower to the device."""
        import splink_tpu

        rng = np.random.default_rng(11)
        n = 4000
        df = pd.DataFrame(
            {
                "unique_id": np.arange(n),
                "name": rng.choice(
                    ["ann", "bob", "cat", "dan", None], n
                ),
                "dob": rng.choice([f"d{k}" for k in range(40)], n),
                "postcode": rng.choice([f"p{k}" for k in range(25)], n),
            }
        )
        base = {
            "link_type": "dedupe_only",
            "comparison_columns": [
                {"col_name": "name", "num_levels": 3},
            ],
            # second rule carries a residual predicate: it lowers to an
            # on-device mask inside the virtual kernel
            "blocking_rules": [
                "l.dob = r.dob",
                "l.postcode = r.postcode and l.name != r.name",
            ],
            "max_resident_pairs": 2048,  # force the streamed regime
            "max_iterations": 4,
        }
        on = splink_tpu.Splink(
            dict(base, device_pair_generation="on"), df=df
        )
        a = on.get_scored_comparisons()
        assert on._virtual is not None
        off = splink_tpu.Splink(
            dict(base, device_pair_generation="off"), df=df
        )
        b = off.get_scored_comparisons()
        key = ["unique_id_l", "unique_id_r"]
        a = a.sort_values(key).reset_index(drop=True)
        b = b.sort_values(key).reset_index(drop=True)
        assert len(a) == len(b)
        np.testing.assert_array_equal(a[key].to_numpy(), b[key].to_numpy())
        np.testing.assert_allclose(
            a.match_probability, b.match_probability, rtol=1e-6
        )

    def test_overlap_blocking_on_device(self):
        """Blocking/scoring overlap on the chip: async device dispatch
        during host joins, bitwise-equal scores vs sequential."""
        import splink_tpu

        rng = np.random.default_rng(13)
        n = 3000
        df = pd.DataFrame(
            {
                "unique_id": np.arange(n),
                "name": rng.choice(["ann", "bob", "cat", "dan"], n),
                "dob": rng.choice([f"d{k}" for k in range(30)], n),
            }
        )
        base = {
            "link_type": "dedupe_only",
            "comparison_columns": [{"col_name": "name", "num_levels": 2}],
            "blocking_rules": ["l.dob = r.dob"],
            "max_iterations": 3,
            "device_pair_generation": "off",
        }
        a = splink_tpu.Splink(dict(base), df=df).get_scored_comparisons()
        b = splink_tpu.Splink(
            dict(base, overlap_blocking=False), df=df
        ).get_scored_comparisons()
        key = ["unique_id_l", "unique_id_r"]
        a = a.sort_values(key).reset_index(drop=True)
        b = b.sort_values(key).reset_index(drop=True)
        np.testing.assert_allclose(
            a.match_probability, b.match_probability, rtol=0, atol=0
        )


class TestRound4OnHardware:
    """Round-4 surfaces on the real chip: derived blocking keys feeding
    the virtual pair index, device function-residual masks, and the
    jar-exact charset-Jaccard kernel."""

    def test_derived_keys_and_function_residuals_on_device(self):
        from splink_tpu import Splink

        rng = np.random.default_rng(61)
        n = 3000
        df = pd.DataFrame(
            {
                "unique_id": np.arange(n),
                "surname": rng.choice(
                    ["smithson", "smithers", "smyth", "jones", "jonas", None],
                    n,
                ),
                "first_name": rng.choice(["ann", "bob", "cat"], n),
                "city": rng.choice([f"c{k}" for k in range(10)], n),
            }
        )
        base = {
            "link_type": "dedupe_only",
            "comparison_columns": [
                {"col_name": "first_name", "num_levels": 3}
            ],
            "blocking_rules": [
                "substr(l.surname, 1, 3) = substr(r.surname, 1, 3)",
                "l.city = r.city and length(l.surname) = length(r.surname)",
            ],
            "max_iterations": 4,
            "max_resident_pairs": 1024,
        }
        key = ["unique_id_l", "unique_id_r"]
        on = (
            Splink(dict(base, device_pair_generation="on"), df=df)
            .get_scored_comparisons()
            .sort_values(key)
            .reset_index(drop=True)
        )
        off = (
            Splink(dict(base, device_pair_generation="off"), df=df)
            .get_scored_comparisons()
            .sort_values(key)
            .reset_index(drop=True)
        )
        assert len(on) == len(off) and len(on) > 1000
        np.testing.assert_array_equal(
            on[key].to_numpy(), off[key].to_numpy()
        )
        np.testing.assert_allclose(
            on.match_probability, off.match_probability, rtol=1e-5
        )

    def test_charset_jaccard_on_device_matches_golden(self):
        """The jar-exact charset Jaccard must survive real XLA:TPU
        lowering (integer-form rounding in f32)."""
        import json
        import os

        from splink_tpu.data import encode_string_column
        from splink_tpu.ops.qgram import charset_jaccard

        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tests", "data", "jar_similarity_vectors.json",
        )
        with open(path) as fh:
            vectors = json.load(fh)[:256]
        a = encode_string_column([v["a"] for v in vectors], width=32)
        b = encode_string_column([v["b"] for v in vectors], width=32)
        w = max(a.bytes_.shape[1], b.bytes_.shape[1])
        pa = np.pad(a.bytes_, ((0, 0), (0, w - a.bytes_.shape[1])))
        pb = np.pad(b.bytes_, ((0, 0), (0, w - b.bytes_.shape[1])))
        got = np.asarray(
            charset_jaccard(*_dev(pa, pb, a.lengths, b.lengths), None),
            np.float64,
        )
        jar = np.array([v["jaccard"] for v in vectors])
        # exact ties may differ by 0.01 (jar f64 artifact) — allow those
        assert (np.abs(got - jar) < 0.0101).all()
        assert (np.abs(got - jar) < 1e-6).mean() > 0.95


class TestMaskedQgramOnHardware:
    def test_masked_qgram_matches_self_contained_on_device(self):
        """The precomputed-aux q-gram kernels (packed mask/count/norm
        lanes, cross-matrix-only per pair) must lower and bit-match the
        self-contained kernels on real XLA:TPU."""
        from splink_tpu.data import encode_string_column
        from splink_tpu.ops import qgram

        rng = np.random.default_rng(13)
        vals = ["".join(rng.choice(list("abcdef"), rng.integers(1, 18)))
                for _ in range(300)] + ["", None]
        col = encode_string_column(
            np.array(rng.choice(np.array(vals, object), 4096), object),
            width=24,
        )
        q = 2
        mask, count, sumsq = qgram.qgram_row_aux(
            col.bytes_, col.lengths, col.token_ids, q
        )
        il = rng.integers(0, len(col.lengths), 4096)
        ir = rng.integers(0, len(col.lengths), 4096)
        s1, s2, l1, l2 = _dev(
            col.bytes_[il], col.bytes_[ir], col.lengths[il], col.lengths[ir]
        )
        plain = np.asarray(qgram.qgram_jaccard(s1, s2, l1, l2, q))
        fast = np.asarray(
            qgram.qgram_jaccard_masked(
                s1, s2, l1, l2,
                *_dev(mask[il], count[il], count[ir]), q,
            )
        )
        np.testing.assert_array_equal(plain, fast)
        plain_c = np.asarray(qgram.qgram_cosine_distance(s1, s2, l1, l2, q))
        fast_c = np.asarray(
            qgram.qgram_cosine_masked(
                s1, s2, l1, l2, *_dev(sumsq[il], sumsq[ir]), q
            )
        )
        np.testing.assert_array_equal(plain_c, fast_c)

    def test_six_column_virtual_histogram_with_masked_qgram(self):
        """Config-4-shaped program (JW x3, exact x2, masked qgram) through
        the virtual pair index on device: histogram must match the
        materialised pattern pass bit-for-bit."""
        from splink_tpu import Splink
        from splink_tpu.gammas import _qgram_key

        rng = np.random.default_rng(17)
        n = 4000
        firsts = [f"fn{i:03d}" for i in range(60)]
        surs = [f"sur{i:03d}" for i in range(80)]
        df = pd.DataFrame(
            {
                "unique_id": np.arange(n),
                "first_name": rng.choice(firsts, n),
                "surname": rng.choice(surs, n),
                "dob": rng.choice([f"19{k:02d}-01-01" for k in range(40)], n),
                "city": rng.choice([f"c{k}" for k in range(12)], n),
                "postcode": rng.choice([f"p{k:04d}" for k in range(300)], n),
            }
        )
        cols = [
            {"col_name": "first_name", "num_levels": 3},
            {"col_name": "surname", "num_levels": 3},
            {"col_name": "dob", "comparison": {"kind": "exact"}},
            {"col_name": "city", "comparison": {"kind": "exact"}},
            {"col_name": "postcode", "num_levels": 2},
            {"custom_name": "surname_qgram", "custom_columns_used": ["surname"],
             "num_levels": 2,
             "comparison": {"kind": "qgram_jaccard", "column": "surname",
                            "thresholds": [0.6]}},
        ]
        base = {
            "link_type": "dedupe_only",
            "comparison_columns": cols,
            "blocking_rules": ["l.dob = r.dob", "l.postcode = r.postcode"],
            "max_iterations": 3,
        }
        lk_virtual = Splink(
            {**base, "device_pair_generation": "on", "max_resident_pairs": 1024},
            df=df,
        )
        assert lk_virtual._virtual_plan() is not None
        _, counts_v, prog = lk_virtual._ensure_pattern_ids()
        assert _qgram_key("surname", 2) in prog._layout
        lk_host = Splink(
            {**base, "device_pair_generation": "off"}, df=df
        )
        _, counts_h, _ = lk_host._ensure_pattern_ids()
        np.testing.assert_array_equal(np.asarray(counts_v), np.asarray(counts_h))


class TestServeOfflineParityOnHardware:
    """Serve <-> offline score parity is BIT-identity on the TPU too
    (docs/serving.md): both logits accumulate the comparison columns left
    to right (fellegi_sunter.log_bayes_factor / fold_logit). A jnp.sum
    over the column axis associates differently here than XLA CPU's
    in-order walk and left the two a few float32 ulps apart at six
    columns — a case the CPU tier cannot see."""

    def test_match_logit_is_the_fold_on_device(self, rng):
        import jax

        from splink_tpu.models.fellegi_sunter import (
            FSParams,
            fold_logit,
            match_logit,
        )

        for n_cols in (6, 8):
            params = FSParams(
                lam=jnp.float32(0.11),
                m=jnp.asarray(rng.dirichlet(np.ones(3), n_cols), jnp.float32),
                u=jnp.asarray(rng.dirichlet(np.ones(3), n_cols), jnp.float32),
            )
            G = jnp.asarray(rng.integers(-1, 3, (1 << 16, n_cols)), jnp.int8)
            fold = np.asarray(jax.jit(fold_logit)(G, params))
            offline = np.asarray(jax.jit(match_logit)(G, params))
            np.testing.assert_array_equal(fold, offline)

    def test_six_column_serve_scores_are_the_offline_floats(self):
        from chip_smoke import smoke_settings
        from chipbench.datagen import make_people
        from splink_tpu import Splink
        from splink_tpu.serve import BucketPolicy, QueryEngine

        df = make_people(3900, seed=4)
        linker = Splink(smoke_settings(max_iterations=5), df=df)
        df_e = linker.get_scored_comparisons()
        offline = dict(
            zip(
                zip(df_e.unique_id_l.to_numpy(), df_e.unique_id_r.to_numpy()),
                df_e.match_probability.to_numpy(),
            )
        )
        index = linker.export_index()
        engine = QueryEngine(
            index, top_k=64, policy=BucketPolicy((128,), (64, 256))
        )
        queries = df.iloc[:512]
        top_p, top_rows, top_valid, _ = engine.query_arrays(queries)
        uid_q = queries.unique_id.to_numpy()
        checked = differ = 0
        for q in range(len(queries)):
            for r in np.flatnonzero(top_valid[q]):
                m = int(index.unique_id[top_rows[q, r]])
                a = int(uid_q[q])
                if m == a:
                    continue
                checked += 1
                differ += np.float32(offline[(min(a, m), max(a, m))]) != top_p[q, r]
        assert checked > 300
        assert differ == 0, f"{differ} of {checked} served scores off"
