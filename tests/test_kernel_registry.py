"""The process's registry of jitted gamma programs (utils/kernel_registry.py):
a second linker on the same model builds nothing and scores the same bits; a
linker that differs in ANY fact a kernel closes over gets its own program;
what cannot be signed is built per linker; a registered kernel pins no
linker, table or device array; the registry is bounded (least recently used
goes) and keys a mesh by value.
"""

import copy
import gc
import threading
import weakref

import jax
import numpy as np
import pandas as pd
import pytest

from splink_tpu import Splink, register_comparison
from splink_tpu.utils import kernel_registry
from splink_tpu.utils.profiling import spans

_FIRST = ["ann", "anne", "bob", "rob", "cat", "kat", "dan", "den", "eve"]
_LAST = ["smith", "smyth", "jones", "janes", "taylor", "tailor", "brown"]


def _people(n: int = 400, seed: int = 5) -> pd.DataFrame:
    rng = np.random.default_rng(seed)
    return pd.DataFrame(
        {
            "unique_id": np.arange(n),
            "first_name": rng.choice(_FIRST, n),
            "surname": rng.choice(_LAST, n),
            "city": rng.choice(["x", "y", "z"], n),
            "age": rng.integers(20, 60, n).astype(float),
        }
    )


def _dedupe_settings() -> dict:
    """The c4 cell's path at a tiny size: the virtual pair index, one
    pattern kernel per rule over the shared gamma body."""
    return {
        "link_type": "dedupe_only",
        "comparison_columns": [
            {"col_name": "first_name", "num_levels": 3,
             "comparison": {"kind": "jaro_winkler", "thresholds": [0.94, 0.7]}},
            {"col_name": "surname", "num_levels": 3},
            {"col_name": "age", "data_type": "numeric", "num_levels": 2,
             "comparison": {"kind": "numeric_abs", "thresholds": [2.0]}},
        ],
        "blocking_rules": ["l.city = r.city", "l.surname = r.surname"],
        "max_iterations": 3,
        "device_pair_generation": "on",
        "max_resident_pairs": 1024,
        "pair_batch_size": 1 << 16,
    }


def _link_settings() -> dict:
    """The c3 cell's path: materialised pairs, the flagged G kernel."""
    s = _dedupe_settings()
    s.update(link_type="link_only", blocking_rules=["l.city = r.city"])
    for key in ("device_pair_generation", "max_resident_pairs"):
        del s[key]
    return s


def _run(settings: dict, df: pd.DataFrame):
    """One job the way the benchmark's runner makes them: a fresh linker on
    a deep copy of the settings. -> (linker, scored frame)."""
    settings = copy.deepcopy(settings)
    if settings["link_type"] == "link_only":
        cut = 2 * len(df) // 3
        linker = Splink(settings, df_l=df.iloc[:cut], df_r=df.iloc[cut:])
    else:
        linker = Splink(settings, df=df)
    return linker, linker.get_scored_comparisons()


def _lookups(linker) -> list[dict]:
    return [s["counts"] for s in spans(run=linker.run_id)
            if s["name"] == "kernel_lookup"]


def _gamma_pass_builds(linker) -> set[str]:
    """Names of the build spans under the linker's gamma pass."""
    table = spans(run=linker.run_id)
    by_id = {s["id"]: s for s in table}
    return {
        s["name"] for s in table
        if s["kind"] == "build"
        and by_id[s["parent"]]["name"] in ("gammas", "gammas_patterns")
    }


# ---------------------------------------------------------------------------
# (a) the second linker of a key builds nothing and scores the same bits
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("make_settings", [_dedupe_settings, _link_settings])
def test_second_linker_on_the_same_model_builds_nothing(make_settings):
    df = _people()
    first, frame_1 = _run(make_settings(), df)
    assert {"jax_lower", "jax_backend_compile"} <= _gamma_pass_builds(first)
    assert all(c["hit"] == 0 and c["shared"] == 1 for c in _lookups(first))
    n_keys = len(kernel_registry.keys())

    second, frame_2 = _run(make_settings(), df)
    assert _gamma_pass_builds(second) == {"kernel_lookup"}
    assert _lookups(second) == [dict(c, hit=1) for c in _lookups(first)]
    assert len(kernel_registry.keys()) == n_keys
    pd.testing.assert_frame_equal(frame_1, frame_2, check_exact=True)


# ---------------------------------------------------------------------------
# (b) one changed fact at a time: a different program, the right output
# ---------------------------------------------------------------------------


def _jw_threshold(s, df):
    s["comparison_columns"][0]["comparison"]["thresholds"][1] = 0.8


def _num_levels(s, df):
    s["comparison_columns"][1]["num_levels"] = 2


def _dropped_column(s, df):
    del s["comparison_columns"][2]


def _wider_string(s, df):
    df.loc[0, "first_name"] = "bartholomew-maximilian-alexander-the-third"


def _float64(s, df):
    s["float64"] = True


def _added_rule(s, df):
    s["blocking_rules"].append("l.first_name = r.first_name")


_CHANGES = [_jw_threshold, _num_levels, _dropped_column, _wider_string,
            _float64, _added_rule]


@pytest.mark.parametrize("change", _CHANGES, ids=lambda f: f.__name__[1:])
def test_a_changed_fact_gets_its_own_program(change):
    settings, df = _dedupe_settings(), _people()
    base, _ = _run(settings, df)
    base_keys = set(kernel_registry.keys())
    assert base_keys

    df = df.copy()
    change(settings, df)
    warm, frame_warm = _run(settings, df)  # the base's programs registered
    new_keys = set(kernel_registry.keys()) - base_keys
    missed = [c["fun"] for c in _lookups(warm) if not c["hit"]]
    if change is _added_rule:
        # same gamma body, same first two rules; the third rule's kernel
        # (n_prev = 2) is new
        assert missed == ["virtual_pattern"] and len(new_keys) == 1
    else:
        assert len(missed) == len(_lookups(warm)) == len(new_keys)
    assert {"jax_lower", "jax_backend_compile"} <= _gamma_pass_builds(warm)

    kernel_registry.clear()
    cold, frame_cold = _run(settings, df)  # what an untouched process gives
    assert all(c["hit"] == 0 for c in _lookups(cold))
    pd.testing.assert_frame_equal(frame_warm, frame_cold, check_exact=True)


def test_the_base_model_differs_from_each_change():
    """The changes of the test above are real: each moves the scored frame
    (or its shape), so a stale kernel would have shown."""
    settings, df = _dedupe_settings(), _people()
    _, base = _run(settings, df)
    for change in (_jw_threshold, _num_levels, _dropped_column):
        s, d = copy.deepcopy(settings), df.copy()
        change(s, d)
        _, frame = _run(s, d)
        common = [c for c in base.columns if c in frame.columns]
        assert list(frame.columns) != list(base.columns) or not (
            frame[common].equals(base[common])
        ), change.__name__


# ---------------------------------------------------------------------------
# (c) what cannot be signed is built per linker
# ---------------------------------------------------------------------------


def test_a_registered_comparison_is_built_per_linker():
    def initials(ctx, col_settings):
        pc = ctx.col("first_name")
        return (pc.chars_l[:, 0] == pc.chars_r[:, 0]).astype(np.int8)

    register_comparison("test_registry_initials", initials)
    settings = _link_settings()
    settings["comparison_columns"][0] = {
        "custom_name": "initial", "custom_columns_used": ["first_name"],
        "num_levels": 2,
        "comparison": {"kind": "custom", "fn": "test_registry_initials"},
    }
    df = _people()
    for _ in range(2):
        linker, frame = _run(settings, df)
        looked = _lookups(linker)
        assert looked and all(c == {"fun": c["fun"], "hit": 0, "shared": 0, "devices": 1}
                              for c in looked)
        assert {"jax_lower", "jax_backend_compile"} <= _gamma_pass_builds(linker)
        assert set(frame["gamma_initial"]) <= {0, 1}
    assert kernel_registry.keys() == []


def test_settings_json_cannot_write_are_not_shared():
    from splink_tpu.data import encode_table
    from splink_tpu.gammas import GammaProgram
    from splink_tpu.settings import complete_settings_dict

    settings = complete_settings_dict(_link_settings())
    table = encode_table(_people(60), settings)
    assert GammaProgram(settings, table)._sig is not None
    settings["comparison_columns"][0]["comparison"]["thresholds"] = [
        np.float32(0.94), np.float32(0.7)
    ]
    assert GammaProgram(settings, table)._sig is None


# ---------------------------------------------------------------------------
# (d) a registered kernel pins nothing of the linker that built it
# ---------------------------------------------------------------------------


def _residual_settings() -> dict:
    s = _dedupe_settings()
    s["blocking_rules"][1] = (
        "l.surname = r.surname and l.first_name != r.first_name "
        "and l.city < 'z'"
    )
    return s


@pytest.mark.parametrize(
    "make_settings", [_dedupe_settings, _residual_settings, _link_settings]
)
def test_a_registered_kernel_pins_no_linker_state(make_settings):
    linker, _ = _run(make_settings(), _people())
    assert all(c["shared"] == 1 for c in _lookups(linker))
    program = linker._pattern_program or _program_of(linker)
    dead = [weakref.ref(linker), weakref.ref(program),
            weakref.ref(program._packed), weakref.ref(linker._ensure_encoded())]
    held = kernel_registry.keys()
    assert held
    gc.disable()
    try:
        del linker, program
        # the program is in no reference cycle: its packed table leaves the
        # device with the linker, not when the cyclic collector next runs
        assert [r() for r in dead[:3]] == [None] * 3
    finally:
        gc.enable()
    gc.collect()
    assert [r() for r in dead] == [None] * 4
    assert kernel_registry.keys() == held


def _program_of(linker):
    """The materialised regime drops its GammaProgram after the pass: build
    the one a second pass would (its kernels come from the registry)."""
    from splink_tpu.gammas import GammaProgram

    return GammaProgram(linker.settings, linker._ensure_encoded(),
                        float_dtype=linker._float_dtype)


def test_a_residual_is_signed_by_what_it_took_from_the_table():
    """Equal source on equal vocabularies: equal signatures, so the kernels
    share. A literal that binds to another rank, or operand slots in another
    order, is another closure: another signature."""
    from splink_tpu.data import encode_table
    from splink_tpu.pairgen import compile_residual_device
    from splink_tpu.settings import complete_settings_dict

    settings = complete_settings_dict(_dedupe_settings())
    src = "(l['first_name'] != r['first_name']) & (l['city'] < 'y')"

    def compiled(df, taken=()):
        """``taken``: operand slots an earlier rule registered."""
        table = encode_table(df, settings)
        fn = compile_residual_device(
            table, src, [np.zeros(1)] * len(taken),
            {key: i for i, key in enumerate(taken)}, {},
        )
        assert fn is not None
        return fn, weakref.ref(table)

    df = _people(80)
    fn_a, table_a = compiled(df)
    fn_b, _ = compiled(df.copy())
    assert fn_a.signature == fn_b.signature and hash(fn_a.signature)
    gc.collect()
    assert table_a() is None  # the closure holds no table
    other = df.copy()
    other["city"] = other["city"].replace({"x": "a", "z": "b"})  # 'y' ranks last
    assert compiled(other)[0].signature != fn_a.signature
    assert compiled(df, [("num", "age")])[0].signature != fn_a.signature


# ---------------------------------------------------------------------------
# (e) bounded, least recently used goes; threads
# ---------------------------------------------------------------------------


def test_the_bound_evicts_the_least_recently_used(monkeypatch):
    monkeypatch.setattr(kernel_registry, "MAX_ENTRIES", 3)
    built = []

    def lookup(key):
        return kernel_registry.lookup("t", key, lambda: built.append(key) or key)

    for key in ("a", "b", "c"):
        lookup(key)
    lookup("a")  # touched: "b" is now the oldest
    lookup("d")
    assert kernel_registry.keys() == ["c", "a", "d"]
    lookup("b")
    assert built == ["a", "b", "c", "d", "b"]
    assert kernel_registry.keys() == ["a", "d", "b"]
    # an unsigned program is built every time and never kept
    assert [kernel_registry.lookup("t", None, object) for _ in range(2)]
    assert kernel_registry.keys() == ["a", "d", "b"]


@pytest.mark.parametrize("churn", [False, True], ids=["same_keys", "evicting"])
def test_lookups_from_threads(monkeypatch, churn):
    """More threads than cores on a short switch interval, all looking up the
    same four keys. Without evictions every thread gets THE program of its
    key (two racing builds: one lands, both callers get it); with other keys
    pushing the bound, the bound holds at every moment."""
    import os
    import sys

    if churn:
        monkeypatch.setattr(kernel_registry, "MAX_ENTRIES", 8)
    n_threads = 4 * (os.cpu_count() or 4)
    start = threading.Barrier(n_threads)
    seen: list[tuple] = []
    errors: list[BaseException] = []

    def work(t: int):
        try:
            start.wait(timeout=30)
            for i in range(100):
                key = ("shared", i % 4)
                seen.append((key, kernel_registry.lookup("t", key, object)))
                if churn:
                    kernel_registry.lookup("t", ("own", t, i), object)
                    assert len(kernel_registry.keys()) <= 8
        except BaseException as e:  # noqa: BLE001 - reported by the assert below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(t,)) for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    assert len(seen) == 100 * n_threads
    if not churn:
        assert len({(key, id(fn)) for key, fn in seen}) == 4
        assert sorted(kernel_registry.keys()) == [("shared", k) for k in range(4)]


# ---------------------------------------------------------------------------
# (f) a mesh is keyed by value
# ---------------------------------------------------------------------------


@pytest.mark.skipif(jax.device_count() < 4, reason="needs four devices")
def test_equal_meshes_share_an_entry_and_other_devices_do_not():
    from jax.sharding import Mesh

    from splink_tpu.data import encode_table
    from splink_tpu.gammas import GammaProgram
    from splink_tpu.pairgen import make_virtual_pattern_fn
    from splink_tpu.parallel.mesh import DATA_AXIS
    from splink_tpu.settings import complete_settings_dict

    settings = complete_settings_dict(_dedupe_settings())
    table = encode_table(_people(60), settings)
    devices = jax.devices()

    def kernels(devs):
        mesh = Mesh(np.array(devs), (DATA_AXIS,))
        program = GammaProgram(copy.deepcopy(settings), table)
        return (program._pattern_batch_for_mesh(mesh),
                make_virtual_pattern_fn(program, 64, n_prev=0,
                                        has_uid_mask=False, mesh=mesh))

    first = kernels(devices[:2])
    again = kernels(list(devices[:2]))
    other = kernels(devices[2:4])
    assert first[0] is again[0] and first[1] is again[1]
    assert other[0] is not first[0] and other[1] is not first[1]
    assert kernel_registry.mesh_key(None) is None
    # key = (fun, program signature, variant); the mesh sits in the variant
    at = {"virtual_pattern": 2, "pattern_batch_mesh": 0}
    assert {k[2][at[k[0]]] for k in kernel_registry.keys() if k[0] in at} == {
        ((devices[0].id, devices[1].id), (2,), (DATA_AXIS,)),
        ((devices[2].id, devices[3].id), (2,), (DATA_AXIS,)),
    }
