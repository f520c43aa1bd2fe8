"""TPU smoke tier configuration.

Unlike tests/conftest.py (which forces an 8-virtual-device CPU platform for
the oracle/golden tier), this tier runs on the TPU and FAILS without one: it
exists so TPU *lowering* is exercised by the suite — the round-1 Pallas iota
bug shipped precisely because every Pallas test passed interpret=True. It is
the finer-grained companion of chip_smoke.py.

Run it through the chip tool, in the same call as the smoke so they share
the compile cache:  make tpu-smoke   (python -m pytest tests_tpu/ -q).
It must be a separate pytest invocation from tests/ — the unit tier's
conftest pins the process to CPU before jax initialises. One process per
chip: nothing here starts a child that needs the device.
"""

import numpy as np
import pytest


def pytest_sessionstart(session):
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise pytest.UsageError(
            f"tests_tpu needs a TPU; jax reports platform {platform!r}"
        )
    from splink_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


@pytest.fixture
def string_batch(rng):
    """~1k variable-length lowercase ASCII pairs, incl. duplicates/transposes."""
    B, L = 1024, 24
    lens1 = rng.integers(0, L + 1, B).astype(np.int32)
    lens2 = rng.integers(0, L + 1, B).astype(np.int32)
    s1 = (rng.integers(97, 123, (B, L)) * (np.arange(L) < lens1[:, None])).astype(
        np.uint8
    )
    s2 = (rng.integers(97, 123, (B, L)) * (np.arange(L) < lens2[:, None])).astype(
        np.uint8
    )
    # make a slice of exact duplicates and near-duplicates (transpositions)
    s2[:256], lens2[:256] = s1[:256], lens1[:256]
    for i in range(128, 256):
        if lens1[i] >= 2:
            j = int(rng.integers(0, lens1[i] - 1))
            s2[i, j], s2[i, j + 1] = s2[i, j + 1], s2[i, j]
    return s1, s2, lens1, lens2
