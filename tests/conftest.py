"""Test configuration: run everything on CPU with 8 virtual devices.

This is the JAX analogue of the reference's "multi-node without a cluster"
strategy (sqlite unit tier + local Spark, /root/reference/tests/conftest.py):
kernels and EM are validated on CPU against independent numpy oracles, and
multi-chip sharding is exercised on a virtual 8-device mesh.

Must run before jax is imported anywhere in the test process.
"""

import os
import tempfile

# Force CPU: the test tier runs on 8 virtual CPU devices; x64 (needed for
# oracle-exact comparisons) is also unavailable on TPU.
os.environ["JAX_PLATFORMS"] = "cpu"
# Hermetic persistent compilation cache: the program's own default is ONE
# fixed directory inside the checkout (utils/compile_cache.py), never a
# temporary name — this mkdtemp is the TEST tier's business only. The env
# var takes precedence over everything, so pinning it to a per-session temp
# dir keeps test runs from reading entries left by earlier runs
# (compile-count assertions account for in-session cache hits via
# obs.metrics.compile_stats). Tests that exercise the unset-variable path
# monkeypatch-delete the var.
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    import atexit
    import shutil

    _xla_cache_dir = tempfile.mkdtemp(prefix="splink_tpu_test_xla_cache_")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _xla_cache_dir
    atexit.register(shutil.rmtree, _xla_cache_dir, True)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def empty_kernel_registry():
    """Every test starts with no jitted gamma program registered, so a test
    that counts compiles or build spans sees its own linker build, whatever
    ran before it in the process (utils/kernel_registry.py)."""
    from splink_tpu.utils import kernel_registry

    kernel_registry.clear()


@pytest.fixture
def basic_settings():
    """A small two-column dedupe settings dict used across tests."""
    return {
        "link_type": "dedupe_only",
        "proportion_of_matches": 0.3,
        "comparison_columns": [
            {"col_name": "first_name", "num_levels": 2, "comparison": {"kind": "exact"}},
            {"col_name": "surname", "num_levels": 2, "comparison": {"kind": "exact"}},
        ],
        "blocking_rules": [],
    }


@pytest.fixture
def rng():
    return np.random.default_rng(42)


# ----------------------------------------------------------------------
# Independent Python oracles (deliberately separate implementations from the
# JAX kernels they validate).
# ----------------------------------------------------------------------


def py_jaro_winkler(s1, s2, p=0.1, boost_threshold=0.7):
    """Jar-exact commons-text JaroWinklerDistance (verified against the
    reference jar's bytecode — scripts/jvm_mini.py, golden table
    tests/data/jar_similarity_vectors.json): the greedy match iterates the
    SHORTER string over the longer, transpositions are integer-halved, the
    Winkler prefix is uncapped with a min(p, 1/maxlen) scaling factor, the
    boost applies only at jaro >= threshold, and m == 0 (including both
    strings empty) gives 0.0."""
    if len(s1) > len(s2):
        s1, s2 = s2, s1  # jaro term m/l1 + m/l2 is symmetric
    l1, l2 = len(s1), len(s2)
    if l1 == 0:
        return 0.0
    window = max(l2 // 2 - 1, 0)
    used2 = [False] * l2
    matched1 = []
    for i, c in enumerate(s1):
        for j in range(max(0, i - window), min(l2, i + window + 1)):
            if not used2[j] and s2[j] == c:
                used2[j] = True
                matched1.append(i)
                break
    m = len(matched1)
    if m == 0:
        return 0.0
    seq1 = [s1[i] for i in matched1]
    seq2 = [s2[j] for j in range(l2) if used2[j]]
    t = sum(a != b for a, b in zip(seq1, seq2)) // 2  # Java integer halving
    jaro = (m / l1 + m / l2 + (m - t) / m) / 3
    ell = 0
    for a, b in zip(s1, s2):
        if a == b:
            ell += 1
        else:
            break
    if jaro < boost_threshold:
        return jaro
    return jaro + ell * min(p, 1.0 / l2) * (1 - jaro)


def py_levenshtein(s1, s2):
    d = list(range(len(s2) + 1))
    for i, c1 in enumerate(s1):
        nd = [i + 1]
        for j, c2 in enumerate(s2):
            nd.append(min(d[j + 1] + 1, nd[j] + 1, d[j] + (c1 != c2)))
        d = nd
    return d[-1]
