"""Pallas Jaro-Winkler kernel vs the Python oracle (interpret mode on CPU)."""

import numpy as np
import pytest

from splink_tpu.ops.strings_pallas import jaro_winkler_pallas

from conftest import py_jaro_winkler


def _encode(strings, width):
    b = np.zeros((len(strings), width), np.uint8)
    ln = np.zeros(len(strings), np.int32)
    for i, s in enumerate(strings):
        e = s.encode()[:width]
        b[i, : len(e)] = np.frombuffer(e, np.uint8)
        ln[i] = len(e)
    return b, ln


CASES = [
    ("martha", "marhta"),
    ("dixon", "dicksonx"),
    ("jellyfish", "smellyfish"),
    ("", ""),
    ("", "abc"),
    ("abc", ""),
    ("a", "a"),
    ("ab", "ba"),
    ("abcdefgh", "abcdefgh"),
    ("crate", "trace"),
    ("dwayne", "duane"),
    ("aaaaaaaa", "aaaa"),
]


@pytest.mark.parametrize("width", [8, 16])
def test_matches_oracle_on_known_cases(width):
    s1 = [a for a, _ in CASES]
    s2 = [b for _, b in CASES]
    b1, l1 = _encode(s1, width)
    b2, l2 = _encode(s2, width)
    got = np.asarray(
        jaro_winkler_pallas(b1, b2, l1, l2, 0.1, 0.7, interpret=True)
    )
    want = np.array(
        [py_jaro_winkler(a[:width], b[:width]) for a, b in CASES], np.float32
    )
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_matches_oracle_random(rng):
    n, width = 700, 8  # > one lane tile so the grid has multiple steps
    letters = np.array(list("abcdefgh"))
    strs1 = ["".join(letters[rng.integers(0, 8, rng.integers(0, 9))]) for _ in range(n)]
    strs2 = ["".join(letters[rng.integers(0, 8, rng.integers(0, 9))]) for _ in range(n)]
    b1, l1 = _encode(strs1, width)
    b2, l2 = _encode(strs2, width)
    got = np.asarray(jaro_winkler_pallas(b1, b2, l1, l2, 0.1, 0.7, interpret=True))
    want = np.array(
        [py_jaro_winkler(a, b) for a, b in zip(strs1, strs2)], np.float32
    )
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_levenshtein_matches_oracle(rng):
    from splink_tpu.ops.strings_pallas import levenshtein_pallas

    from conftest import py_levenshtein

    n, width = 700, 8
    letters = np.array(list("abcde"))
    strs1 = ["".join(letters[rng.integers(0, 5, rng.integers(0, 9))]) for _ in range(n)]
    strs2 = ["".join(letters[rng.integers(0, 5, rng.integers(0, 9))]) for _ in range(n)]
    b1, l1 = _encode(strs1, width)
    b2, l2 = _encode(strs2, width)
    got = np.asarray(levenshtein_pallas(b1, b2, l1, l2, interpret=True))
    want = np.array([py_levenshtein(a, b) for a, b in zip(strs1, strs2)], np.float32)
    np.testing.assert_array_equal(got, want)


def test_levenshtein_edge_cases():
    from splink_tpu.ops.strings_pallas import levenshtein_pallas

    cases = [("", ""), ("", "abc"), ("abc", ""), ("kitten", "sitting"),
             ("flaw", "lawn"), ("abcdefgh", "abcdefgh")]
    b1, l1 = _encode([a for a, _ in cases], 8)
    b2, l2 = _encode([b for _, b in cases], 8)
    got = np.asarray(levenshtein_pallas(b1, b2, l1, l2, interpret=True))
    assert got.tolist() == [0, 3, 3, 3, 2, 0]


def test_matches_vmapped_kernel(rng):
    from splink_tpu.ops.strings import jaro_winkler_vmapped

    n, width = 300, 16
    letters = np.array(list("abcdefghijkl"))
    strs1 = ["".join(letters[rng.integers(0, 12, rng.integers(0, 17))]) for _ in range(n)]
    strs2 = ["".join(letters[rng.integers(0, 12, rng.integers(0, 17))]) for _ in range(n)]
    b1, l1 = _encode(strs1, width)
    b2, l2 = _encode(strs2, width)
    got = np.asarray(jaro_winkler_pallas(b1, b2, l1, l2, 0.1, 0.7, interpret=True))
    want = np.asarray(jaro_winkler_vmapped(b1, b2, l1, l2, 0.1, 0.7))
    np.testing.assert_allclose(got, want, atol=1e-5)


# --------------------------------------------------------------------------
# Code points at widths 40-64: both kernels, exact against the oracle and
# the vector forms, neighbouring code points above 256 told apart
# --------------------------------------------------------------------------

# the last character of each pair differs by one code point (+-1) in four
# scripts: a bfloat16 pass would read every such pair as equal
NEIGHBOURS = [("прива", "привб"), ("αγάπη", "αγάπθ"), ("序曲一", "序曲丂"),
              ("crème", "crèmf"), ("ŁÓDŹ", "ŁÓDŸ")]


def _encode_points(strings, width):
    b = np.zeros((len(strings), width), np.uint32)
    ln = np.zeros(len(strings), np.int32)
    for i, s in enumerate(strings):
        cps = [ord(c) for c in s[:width]]
        b[i, : len(cps)] = cps
        ln[i] = len(cps)
    return b, ln


def _point_cases(rng, width, n=60):
    """Lengths 0, 1 and the full width, random mixed-script strings of every
    length between, and the neighbour pairs."""
    alphabet = [chr(c) for c in (*range(0x430, 0x440), *range(0x3B1, 0x3B9),
                                 *range(0x4E00, 0x4E08), 0xE9, 0xE8, 0x61, 0x62)]
    def draw(k):
        return "".join(alphabet[j] for j in rng.integers(0, len(alphabet), k))
    full = draw(width)
    pairs = [("", ""), ("", "а"), ("а", ""), ("а", "а"), ("а", "б"),
             (full, full), (full, full[:-1] + chr(ord(full[-1]) + 1)),
             (full, full[1:]), (full[::-1], full), *NEIGHBOURS]
    for _ in range(n):
        a = draw(int(rng.integers(0, width + 1)))
        b = (a[: int(rng.integers(0, len(a) + 1))] + draw(int(rng.integers(0, 6))))[:width]
        pairs.append((a, b) if rng.random() < 0.5 else (b, a))
    return pairs


def _levenshtein_kernel_eagerly(b1, b2, l1, l2):
    """The Levenshtein kernel's body (``strings_pallas._lev_kernel``) on one
    lane tile, run op by op with ``jnp.roll`` for the sublane roll: the
    Pallas interpreter compiles its L x log L unrolled shifts in minutes at
    widths past 32, so the body is taken out of ``pallas_call`` here (the
    call's blocks at these widths are the lowering test's below, its
    plumbing the interpreted tests' above)."""
    import types

    import jax
    import jax.numpy as jnp

    from splink_tpu.ops import strings_pallas as SP

    n, L = b1.shape
    s1T = jnp.asarray(b1.T, jnp.float32)
    s2pT = jnp.concatenate([jnp.full((1, n), -1.0), jnp.asarray(b2.T, jnp.float32)])
    out = np.zeros((1, n), np.float32)
    real, SP.pltpu = SP.pltpu, types.SimpleNamespace(
        roll=lambda x, shift, axis: jnp.roll(x, shift, axis))
    try:
        with jax.disable_jit():
            SP._lev_kernel(s1T, s2pT, jnp.asarray(l1, jnp.float32)[None],
                           jnp.asarray(l2, jnp.float32)[None], out, L=L)
    finally:
        SP.pltpu = real
    return out[0]


@pytest.mark.parametrize("width", [40, 48, 64])
def test_code_points_at_wide_widths_match_oracle_and_vector_forms(width):
    from splink_tpu.ops.strings import _jaro_winkler_vector_vmapped, levenshtein_vmapped

    from conftest import py_jaro_winkler, py_levenshtein

    pairs = _point_cases(np.random.default_rng(width), width, n=40)
    b1, l1 = _encode_points([a for a, _ in pairs], width)
    b2, l2 = _encode_points([b for _, b in pairs], width)
    jw = np.asarray(jaro_winkler_pallas(b1, b2, l1, l2, 0.1, 0.7, interpret=True))
    want = np.array([py_jaro_winkler(a, b) for a, b in pairs], np.float32)
    np.testing.assert_allclose(jw, want, atol=1e-5)
    if width == 64:  # the vector form a column past the kernels' width takes
        np.testing.assert_allclose(
            jw, np.asarray(_jaro_winkler_vector_vmapped(b1, b2, l1, l2, 0.1, 0.7)), atol=1e-6)
    lev = _levenshtein_kernel_eagerly(b1, b2, l1, l2)
    np.testing.assert_array_equal(lev, [py_levenshtein(a, b) for a, b in pairs])
    np.testing.assert_array_equal(lev, np.asarray(levenshtein_vmapped(b1, b2, l1, l2)))
    # neighbouring code points are different characters: no pair of them
    # reads as equal (a rounded code would give 1.0 and distance 0)
    k = [pairs.index(p) for p in NEIGHBOURS]
    assert (jw[k] < 1.0).all() and (lev[k] == 1).all()


# --------------------------------------------------------------------------
# The ASCII kernels of widths <= 32 are the ones they were: block shapes,
# and the Mosaic module itself (lowered for the TPU, no chip needed)
# --------------------------------------------------------------------------

# sha1 of each kernel's Mosaic module text, its kernel name masked, as the
# commit before the kernels took widths past 32 lowered it (jax 0.9.0)
ASCII_MODULES = {
    ("jaro_winkler_pallas", 8): "588799e04a47f441",
    ("jaro_winkler_pallas", 24): "4e8a2c77e8e7406b",
    ("jaro_winkler_pallas", 32): "1afbcae19a7b5e3b",
    ("levenshtein_pallas", 8): "2bb4166fef59bce5",
    ("levenshtein_pallas", 24): "a9b4b52cadf9a139",
    ("levenshtein_pallas", 32): "1679c1aede4cec0f",
}


def _mosaic_modules(monkeypatch, fn, width, dtype, batch=1 << 16):
    """[(module text with the kernel name masked, kernel name)] of each
    Pallas call ``fn`` lowers to for the TPU at (batch, width) ``dtype``."""
    import jax
    import jax.numpy as jnp
    from jax._src.pallas.mosaic import pallas_call_registration as registration

    seen = []
    lower = registration.mosaic.lower_module_to_custom_call

    def spy(ctx, *args, module, kernel_name, **kw):
        seen.append((str(module).replace(kernel_name, "KERNEL"), kernel_name))
        return lower(ctx, *args, module=module, kernel_name=kernel_name, **kw)

    monkeypatch.setattr(registration.mosaic, "lower_module_to_custom_call", spy)
    codes = jax.ShapeDtypeStruct((batch, width), dtype)
    lens = jax.ShapeDtypeStruct((batch,), jnp.int32)
    with jax.enable_x64(False):  # as the program runs on the chip
        jax.jit(fn).trace(codes, codes, lens, lens).lower(lowering_platforms=("tpu",))
    return seen


def _windows(text):
    import re

    return re.findall(r"window_bounds = array<i64: ([0-9, ]+)>", text)


@pytest.mark.parametrize("kernel", ["jaro_winkler_pallas", "levenshtein_pallas"])
@pytest.mark.parametrize("width", [8, 24, 32])
def test_ascii_columns_keep_their_kernel(monkeypatch, kernel, width):
    import hashlib

    import jax.numpy as jnp

    from splink_tpu.ops import strings_pallas as SP

    [(text, name)] = _mosaic_modules(monkeypatch, getattr(SP, kernel), width, jnp.uint8)
    assert name == kernel  # the device trace names the op after it
    rows = width + (kernel == "levenshtein_pallas")
    assert _windows(text) == [f"{width}, 512", f"{rows}, 512", "1, 512", "1, 512", "1, 512"]
    assert hashlib.sha1(text.encode()).hexdigest()[:16] == ASCII_MODULES[kernel, width]


@pytest.mark.parametrize("width", [40, 64])
def test_code_points_past_32_take_half_a_lane_tile(monkeypatch, width):
    import jax.numpy as jnp

    from splink_tpu.ops import strings_pallas as SP

    assert SP.lane_tile(width) == 256 and SP.lane_tile(32) == 512
    for kernel in (SP.jaro_winkler_pallas, SP.levenshtein_pallas):
        [(text, _)] = _mosaic_modules(monkeypatch, kernel, width, jnp.uint32)
        rows = width + (kernel is SP.levenshtein_pallas)
        # one (L, T) float32 block at most 64 kB, as at width 32
        assert _windows(text)[:2] == [f"{width}, 256", f"{rows}, 256"]
