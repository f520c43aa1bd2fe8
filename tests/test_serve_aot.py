"""AOT executable store (serve/aot.py) + fused megakernel parity.

Two contracts from the cold-start ISSUE:

  * restore correctness — a sidecar-restored menu answers BIT-identically
    to an in-process-compiled engine with zero backend compiles, and EVERY
    invalidation path (corrupt blob, jaxlib mismatch, settings-hash /
    index-fingerprint mismatch, stale bucket policy, fused-flag flip)
    degrades to a fresh compile with a structured warning — never a wrong
    or foreign executable, never a crash (the true fresh-PROCESS restore
    is gated by ``make warmup-smoke``; these tests cover the matrix);
  * fused↔unfused parity — the fused gamma→score→top-k path (the default)
    is bit-identical to the retained unfused oracle at f32 and f64 over
    the full offline-pair coverage set.
"""

import json
import os

import numpy as np
import pandas as pd
import pytest

from splink_tpu import Splink
from splink_tpu.serve import BucketPolicy, QueryEngine, load_index
from splink_tpu.serve.aot import MENU_NAME
from splink_tpu.utils.logging_utils import DegradationWarning


def people_df(n=120, seed=11):
    rng = np.random.default_rng(seed)
    firsts = ["amelia", "oliver", "isla", "george", "ava", "noah", "emily"]
    lasts = ["smith", "jones", "taylor", "brown", "wilson", "evans"]
    return pd.DataFrame(
        {
            "unique_id": range(n),
            "first_name": [str(rng.choice(firsts)) for _ in range(n)],
            "surname": [str(rng.choice(lasts)) for _ in range(n)],
            "dob": [f"19{rng.integers(40, 99)}" for _ in range(n)],
        }
    )


def serve_settings(**over):
    s = {
        "link_type": "dedupe_only",
        "comparison_columns": [
            {"col_name": "first_name", "num_levels": 3},
            {
                "col_name": "surname",
                "num_levels": 2,
                "comparison": {"kind": "exact"},
            },
        ],
        "blocking_rules": ["l.dob = r.dob", "l.surname = r.surname"],
        "max_iterations": 4,
    }
    s.update(over)
    return s


POLICY = BucketPolicy((16,), (64, 128))  # 2 combos: cheap but >1 blob


@pytest.fixture(scope="module")
def saved(tmp_path_factory):
    """(df, index_dir, aot_dir, answers): one trained + exported index
    with a committed AOT sidecar and the warm engine's recorded answers
    for the full query frame."""
    df = people_df()
    linker = Splink(serve_settings(), df=df)
    linker.get_scored_comparisons()
    index_dir = str(tmp_path_factory.mktemp("aot_index"))
    linker.export_index(index_dir)
    aot_dir = os.path.join(index_dir, "aot")
    engine = QueryEngine(load_index(index_dir), top_k=8, policy=POLICY,
                         aot_dir=aot_dir)
    engine.warmup()
    engine.save_aot()
    answers = engine.query_arrays(df)
    return df, index_dir, aot_dir, answers


def _fresh_engine(index_dir, aot_dir, **over):
    kw = dict(top_k=8, policy=POLICY, aot_dir=aot_dir)
    kw.update(over)
    return QueryEngine(load_index(index_dir), **kw)


def _assert_bit_identical(expected, got):
    for name, e, g in zip(("p", "rows", "valid", "ncand"), expected, got):
        assert e.dtype == g.dtype and e.shape == g.shape, name
        assert np.array_equal(e, g), name


def _edit_menu(aot_dir, mutate):
    path = os.path.join(aot_dir, MENU_NAME)
    with open(path, encoding="utf-8") as fh:
        menu = json.load(fh)
    mutate(menu)
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(menu, fh)


# ---------------------------------------------------------------------------
# Restore path
# ---------------------------------------------------------------------------


def test_aot_restore_full_menu_zero_compiles(saved):
    """A fresh engine restores the whole menu from the sidecar — zero
    backend compiles, zero persistent-cache reads — and answers
    bit-identically to the engine that compiled it."""
    df, index_dir, aot_dir, answers = saved
    eng = _fresh_engine(index_dir, aot_dir)
    warm = eng.warmup()
    assert warm["aot_restored"] == warm["combinations"] == 2
    assert warm["compiles"] == 0 and warm["cache_hits"] == 0
    _assert_bit_identical(answers, eng.query_arrays(df))


def test_save_after_restore_writes_a_valid_sidecar(saved, tmp_path):
    """save_aot() on a RESTORED menu must not poison the sidecar:
    re-serializing a deserialized executable succeeds silently but the
    blob fails to deserialize ('Symbols not found'), so save_aot
    re-lowers a fresh twin for every aot-sourced entry. Gate: restore →
    save to a new dir → a third engine restores the NEW sidecar fully."""
    df, index_dir, aot_dir, answers = saved
    restored = _fresh_engine(index_dir, aot_dir)
    warm = restored.warmup()
    assert warm["aot_restored"] == warm["combinations"] == 2
    resaved = str(tmp_path / "aot2")
    restored.save_aot(resaved)
    third = _fresh_engine(index_dir, resaved)
    warm3 = third.warmup()
    assert warm3["aot_restored"] == warm3["combinations"] == 2, warm3
    assert warm3["compiles"] == 0, warm3
    _assert_bit_identical(answers, third.query_arrays(df))


def test_save_after_cache_warm_warmup_writes_a_valid_sidecar(
    saved, tmp_path, monkeypatch
):
    """The same hazard through the PERSISTENT COMPILE CACHE: a menu the
    cache served was itself deserialized, so save_aot must re-compile it
    for real — from a fresh jit wrapper (jax memoises trace -> lowering ->
    executable per function object) with the cache genuinely bypassed (jax
    memoises "is the cache in use" per process). The second run of any
    process pair hits this: warm cache, warmup, save."""
    import jax

    df, index_dir, _aot_dir, answers = saved
    prev = jax.config.jax_persistent_cache_min_compile_time_secs
    # the user's own tuning, which enable_compilation_cache leaves alone
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        _fresh_engine(index_dir, None).warmup()  # fills the cache
        cached = _fresh_engine(index_dir, None)
        warm = cached.warmup()
        assert warm["cache_hits"] == warm["combinations"] == 2, warm
        resaved = str(tmp_path / "aot_from_cache")
        cached.save_aot(resaved)
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", prev)
    third = _fresh_engine(index_dir, resaved)
    warm3 = third.warmup()
    assert warm3["aot_restored"] == warm3["combinations"] == 2, warm3
    _assert_bit_identical(answers, third.query_arrays(df))


def test_missing_sidecar_is_a_plain_cold_start(saved, tmp_path):
    """No sidecar at the path: NOT a degradation (no warning) — the
    engine compiles the menu exactly as an unconfigured one would."""
    df, index_dir, _, answers = saved
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("error", DegradationWarning)
        eng = _fresh_engine(index_dir, str(tmp_path / "nowhere"))
        warm = eng.warmup()
    assert warm["aot_restored"] == 0
    assert warm["compiles"] + warm["cache_hits"] == warm["combinations"]
    _assert_bit_identical(answers, eng.query_arrays(df))


# ---------------------------------------------------------------------------
# Invalidation matrix: every path degrades to a fresh compile with one
# structured warning, bit-identical results, no crash
# ---------------------------------------------------------------------------


def _assert_degrades_to_fresh_compile(saved, expect_restored=0,
                                      match="serve_aot"):
    df, index_dir, aot_dir, answers = saved
    eng = _fresh_engine(index_dir, aot_dir)
    with pytest.warns(DegradationWarning, match=match):
        warm = eng.warmup()
    assert warm["aot_restored"] == expect_restored
    assert (
        warm["compiles"] + warm["cache_hits"]
        == warm["combinations"] - expect_restored
    )
    _assert_bit_identical(answers, eng.query_arrays(df))
    return warm


def test_corrupted_blob_falls_back_per_shape(saved):
    """A torn/tampered blob (sha256 mismatch) degrades ONLY its shape to
    a fresh compile; the other blobs still restore. The pickle payload is
    never deserialized."""
    _, _, aot_dir, _ = saved
    blobs = sorted(
        f for f in os.listdir(aot_dir)
        if f.startswith("exec-") and f.endswith(".bin")
    )
    assert len(blobs) == 2
    victim = os.path.join(aot_dir, blobs[0])
    original = open(victim, "rb").read()
    try:
        with open(victim, "wb") as fh:
            fh.write(original[:100] + b"\x00garbage\x00" + original[100:])
        _assert_degrades_to_fresh_compile(
            saved, expect_restored=1, match="corrupt_blob"
        )
    finally:
        with open(victim, "wb") as fh:
            fh.write(original)


def test_jaxlib_version_mismatch_invalidates_store(saved):
    """A sidecar produced by a different jaxlib is machine code of
    unknown provenance: the whole store is rejected."""
    _, _, aot_dir, _ = saved
    menu_path = os.path.join(aot_dir, MENU_NAME)
    original = open(menu_path).read()
    try:
        _edit_menu(
            aot_dir,
            lambda m: m["environment"].__setitem__("jaxlib", "0.0.1"),
        )
        _assert_degrades_to_fresh_compile(saved, match="jaxlib")
    finally:
        open(menu_path, "w").write(original)


def test_target_fingerprint_mismatch_invalidates_store(saved):
    """A different host ISA (the SIGILL hazard) rejects the store."""
    _, _, aot_dir, _ = saved
    menu_path = os.path.join(aot_dir, MENU_NAME)
    original = open(menu_path).read()
    try:
        _edit_menu(
            aot_dir,
            lambda m: m["environment"].__setitem__("target", "deadbeef"),
        )
        _assert_degrades_to_fresh_compile(saved, match="target")
    finally:
        open(menu_path, "w").write(original)


def test_settings_hash_mismatch_invalidates_store(saved):
    """An index rebuilt under different settings must not serve the old
    executables (they bake the old comparison program)."""
    _, _, aot_dir, _ = saved
    menu_path = os.path.join(aot_dir, MENU_NAME)
    original = open(menu_path).read()
    try:
        _edit_menu(
            aot_dir,
            lambda m: m["binding"].__setitem__(
                "index_state_hash", "0000000000000000"
            ),
        )
        _assert_degrades_to_fresh_compile(saved, match="index_state_hash")
    finally:
        open(menu_path, "w").write(original)


def test_index_fingerprint_mismatch_invalidates_store(saved):
    """Same settings, different index CONTENT (e.g. a re-export over new
    reference rows): the executables would run, but the sidecar belongs
    to another artifact — rejected."""
    _, _, aot_dir, _ = saved
    menu_path = os.path.join(aot_dir, MENU_NAME)
    original = open(menu_path).read()
    try:
        _edit_menu(
            aot_dir,
            lambda m: m["binding"].__setitem__("index_fingerprint", "ff00"),
        )
        _assert_degrades_to_fresh_compile(saved, match="index_fingerprint")
    finally:
        open(menu_path, "w").write(original)


def test_stale_bucket_policy_invalidates_store(saved):
    """An engine with a different shape menu (changed candidate buckets)
    cannot use the saved executables — the binding names the full menu."""
    df, index_dir, aot_dir, answers = saved
    eng = _fresh_engine(
        index_dir, aot_dir, policy=BucketPolicy((16,), (64, 128, 256))
    )
    with pytest.warns(DegradationWarning, match="candidate_buckets"):
        warm = eng.warmup()
    assert warm["aot_restored"] == 0
    assert warm["compiles"] + warm["cache_hits"] == warm["combinations"] == 3
    # the wider menu still answers identically on this corpus
    _assert_bit_identical(answers, eng.query_arrays(df))


def test_fused_flag_mismatch_invalidates_store(saved):
    """Flipping the scoring path (fused <-> unfused oracle) changes the
    executable: the sidecar binding rejects the other path's blobs."""
    df, index_dir, aot_dir, answers = saved
    eng = _fresh_engine(index_dir, aot_dir, fused=False)
    with pytest.warns(DegradationWarning, match="fused"):
        warm = eng.warmup()
    assert warm["aot_restored"] == 0
    # the unfused oracle remains bit-identical (the fused-parity contract)
    _assert_bit_identical(answers, eng.query_arrays(df))


def test_unreadable_menu_degrades(saved):
    """A truncated/garbage menu JSON is an unreadable sidecar, not a
    crash."""
    _, _, aot_dir, _ = saved
    menu_path = os.path.join(aot_dir, MENU_NAME)
    original = open(menu_path).read()
    try:
        open(menu_path, "w").write("{not json")
        _assert_degrades_to_fresh_compile(saved, match="unreadable")
    finally:
        open(menu_path, "w").write(original)


def test_save_requires_warm_engine(saved, tmp_path):
    _, index_dir, _, _ = saved
    eng = QueryEngine(load_index(index_dir), top_k=8, policy=POLICY)
    with pytest.raises(RuntimeError, match="warmup"):
        eng.save_aot(str(tmp_path / "aot"))
    with pytest.raises(ValueError, match="sidecar"):
        eng.save_aot()


# ---------------------------------------------------------------------------
# Fused <-> unfused parity (the oracle contract)
# ---------------------------------------------------------------------------


def test_fused_unfused_parity_f32(saved):
    """The fused megakernel is bit-identical to the unfused oracle over
    the full query frame at f32 — top-k high enough that every offline
    pair is covered (the same coverage set the serve<->offline parity
    test walks)."""
    df, index_dir, _, _ = saved
    policy = BucketPolicy((16, 128), (64, 256))
    fused = QueryEngine(load_index(index_dir), top_k=64, policy=policy)
    oracle = QueryEngine(
        load_index(index_dir), top_k=64, policy=policy, fused=False
    )
    assert fused.fused and not oracle.fused
    _assert_bit_identical(
        oracle.query_arrays(df), fused.query_arrays(df)
    )


def test_fused_unfused_parity_f64():
    """Same parity on the float64 tier (the x64 leak surface)."""
    df = people_df(60, seed=3)
    linker = Splink(
        serve_settings(float64=True, max_iterations=3), df=df
    )
    index = linker.export_index()
    assert index.dtype == "float64"
    policy = BucketPolicy((64,), (128,))
    fused = QueryEngine(index, top_k=64, policy=policy)
    oracle = QueryEngine(index, top_k=64, policy=policy, fused=False)
    got_f = fused.query_arrays(df)
    got_o = oracle.query_arrays(df)
    assert got_f[0].dtype == np.float64
    _assert_bit_identical(got_o, got_f)


def test_f64_sidecar_cross_process_contract(tmp_path):
    """float64 CPU executables may fail to RE-LINK in a fresh process
    (jaxlib's CPU deserialize reports 'Symbols not found' for some f64
    programs — they resolve in the building process but not across the
    boundary; observed on jaxlib 0.4.36). The contract this test pins is
    outcome-agnostic: whether the restore succeeds (a future jaxlib) or
    degrades, the fresh process must never crash, must perform
    compiles + cache_hits + aot_restored == combinations, and must answer
    BIT-identically to the building process."""
    import subprocess
    import sys

    driver = tmp_path / "driver.py"
    driver.write_text(
        """
import sys, json
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np, pandas as pd
sys.path.insert(0, {repo!r})
from splink_tpu.serve import QueryEngine, load_index, BucketPolicy
work = {work!r}
phase = sys.argv[1]
policy = BucketPolicy((16,), (64,))
if phase == "build":
    from splink_tpu import Splink
    rng = np.random.default_rng(5)
    n = 60
    df = pd.DataFrame({{
        "unique_id": range(n),
        "name": ["".join(chr(97 + rng.integers(0, 26)) for _ in range(7))
                  for _ in range(n)],
        "dob": [f"19{{rng.integers(40, 50)}}" for _ in range(n)],
    }})
    df.to_parquet(work + "/ref.parquet")
    s = {{"link_type": "dedupe_only", "float64": True, "max_iterations": 2,
         "comparison_columns": [{{"col_name": "name", "num_levels": 3}}],
         "blocking_rules": ["l.dob = r.dob"]}}
    linker = Splink(s, df=df)
    linker.get_scored_comparisons()
    linker.export_index(work + "/idx")
    eng = QueryEngine(load_index(work + "/idx"), policy=policy,
                      aot_dir=work + "/idx/aot")
    eng.warmup()
    eng.save_aot()
    p, r, v, nc = eng.query_arrays(df)
    np.savez(work + "/ans.npz", p=p, r=r, v=v, nc=nc)
else:
    import warnings
    from splink_tpu.utils.logging_utils import DegradationWarning
    df = pd.read_parquet(work + "/ref.parquet")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DegradationWarning)
        eng = QueryEngine(load_index(work + "/idx"), policy=policy,
                          aot_dir=work + "/idx/aot")
        warm = eng.warmup()
        got = eng.query_arrays(df)
    assert (
        warm["compiles"] + warm["cache_hits"] + warm["aot_restored"]
        == warm["combinations"]
    ), warm
    ref = np.load(work + "/ans.npz")
    for k, g in zip(("p", "r", "v", "nc"), got):
        assert ref[k].dtype == g.dtype and np.array_equal(ref[k], g), k
    assert got[0].dtype == np.float64
    print(json.dumps(warm))
""".format(repo=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
           work=str(tmp_path))
    )
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla")
    for phase in ("build", "serve"):
        out = subprocess.run(
            [sys.executable, str(driver), phase],
            env=env, capture_output=True, text=True,
        )
        assert out.returncode == 0, out.stderr[-2000:]
    warm = json.loads(out.stdout.strip().splitlines()[-1])
    assert warm["combinations"] == 1


def test_serve_fused_setting_selects_path():
    """serve_fused=False in settings selects the oracle path without the
    engine kwarg (and the two paths still agree)."""
    df = people_df(40, seed=5)
    linker = Splink(
        serve_settings(serve_fused=False, max_iterations=2), df=df
    )
    index = linker.export_index()
    oracle = QueryEngine(index, top_k=8, policy=POLICY)
    assert oracle.fused is False
    fused = QueryEngine(index, top_k=8, policy=POLICY, fused=True)
    _assert_bit_identical(
        oracle.query_arrays(df), fused.query_arrays(df)
    )
