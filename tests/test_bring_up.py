"""Nothing on the main path hides the device: a backend that does not come
up raises, a device plan that fails to build raises, Pallas dispatch keys on
the one backend name there is, and the native loader never takes a library
built from other source. (The compile-cache placement tests live in
test_observability.py.)"""

import copy
import os
import shutil

import numpy as np
import pandas as pd
import pytest

SETTINGS = {
    "link_type": "dedupe_only",
    "comparison_columns": [{"col_name": "name", "num_levels": 2}],
    "blocking_rules": ["l.dob = r.dob"],
    "max_iterations": 1,
}


def _df(n=40):
    return pd.DataFrame(
        {"unique_id": range(n), "name": ["ann", "bob"] * (n // 2),
         "dob": [f"d{k % 5}" for k in range(n)]}
    )


def test_backend_that_fails_to_initialise_raises_out_of_splink(monkeypatch):
    """No accelerator -> CPU fallback: the device-probe helper is gone, and
    the first backend touch (placing the compile cache) propagates."""
    import jax

    import splink_tpu.resilience as resilience
    from splink_tpu import Splink
    from splink_tpu.resilience import retry
    from splink_tpu.utils import compile_cache

    gone = "ensure" + "_devices"  # (spelled so a grep for it stays empty)
    assert not hasattr(retry, gone) and not hasattr(resilience, gone)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "_applied", None)

    def dead_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", dead_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        Splink(dict(SETTINGS), df=_df())
    assert jax.config.jax_platforms == "cpu"  # nothing switched platforms


@pytest.mark.parametrize(
    "backend,expected", [("tpu", True), ("tpu_plugin", False), ("cpu", False),
                         ("gpu", False)]
)
def test_pallas_supported_only_on_the_tpu_backend(monkeypatch, backend, expected):
    import jax
    import jax.numpy as jnp

    from splink_tpu.ops.strings_pallas import pallas_supported

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert pallas_supported(jnp.zeros((4, 8), jnp.uint8)) is expected
    # shape/dtype gates hold on the TPU too
    assert not pallas_supported(jnp.zeros((4, 40), jnp.uint8))
    assert not pallas_supported(jnp.zeros((4, 8), jnp.uint32))


def test_device_plan_build_error_propagates(monkeypatch, tmp_path):
    """A device plan that fails to BUILD used to log a warning and hand
    the job to the host join — a TPU compile error in the sort-join kernels
    would have been invisible. It raises now, on both device tiers."""
    from splink_tpu import Splink, blocking_device

    def broken(*_a, **_k):
        raise RuntimeError("sort-join kernel failed to compile")

    monkeypatch.setattr(blocking_device, "build_device_plan", broken)
    with pytest.raises(RuntimeError, match="sort-join kernel"):
        Splink({**SETTINGS, "device_blocking": "on"},
               df=_df()).get_scored_comparisons()
    with pytest.raises(RuntimeError, match="sort-join kernel"):
        Splink({**SETTINGS, "build_spill_dir": str(tmp_path / "spill")},
               df=_df()).get_scored_comparisons()


def test_native_loader_refuses_a_library_not_built_from_its_source(
    monkeypatch, tmp_path
):
    """The library's name carries a hash of source, flags and CPU: a file
    under the old fixed name (or another hash) is never loaded, and a
    changed source gets a new name."""
    from splink_tpu import native

    if not native.available():
        pytest.skip("no toolchain: numpy fallbacks active")
    src = tmp_path / "src"
    src.mkdir()
    shutil.copy(native.SOURCE, src / "host_kernels.cpp")
    foreign = [tmp_path / "libsplink_host.so",
               tmp_path / "libsplink_host-0123456789abcdef.so"]
    for f in foreign:
        f.write_bytes(b"\x7fELF not a library built here")
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    monkeypatch.setattr(native, "SOURCE", str(src / "host_kernels.cpp"))
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_built_here", False)

    path = native.library_path()
    assert path not in map(str, foreign)
    info = native.build_info()
    assert info["available"] and info["built_in_this_process"]
    assert info["library"] == os.path.basename(path)
    assert not any(f.exists() for f in foreign)  # swept, never loaded
    data = np.frombuffer(b"abcd", np.uint8)
    out, lens = native.encode_fixed_width(data, np.array([0, 4]), 8)
    assert bytes(out[0, :4]) == b"abcd" and lens[0] == 4

    with open(native.SOURCE, "a") as fh:
        fh.write("\n// a later revision\n")
    assert native.library_path() != path


def test_chip_smoke_model_is_the_benchmarks_config_4():
    """The bring-up smoke drives config 4 as the benchmark defines it —
    ``chipbench/configs/baseline_c4.json`` is the one place — less the three
    keys that file lists under ``assumed`` for its cell's size."""
    import json
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        import chip_smoke
    finally:
        sys.path.remove(root)
    from splink_tpu.settings import complete_settings_dict

    with open(os.path.join(root, "chipbench", "configs", "baseline_c4.json")) as f:
        config = json.load(f)
    model = chip_smoke.smoke_settings()
    complete_settings_dict(copy.deepcopy(model))  # validates: raises on a bad key
    assert len(model["comparison_columns"]) == 6
    assert model["comparison_columns"] == config["settings"]["comparison_columns"]
    assert len(model["blocking_rules"]) == 3
    assert model["blocking_rules"] == config["settings"]["blocking_rules"]
    assert set(chip_smoke.CELL_KEYS) <= set(config["assumed"])
    assert set(config["settings"]) - set(model) == set(chip_smoke.CELL_KEYS)
    assert chip_smoke.smoke_settings(max_iterations=5)["max_iterations"] == 5
