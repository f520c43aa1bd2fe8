"""The HBM bytes the Pallas string kernels have to move, per evaluation, from
a comparison's width: what ``readers/trace_op_roofline.py`` sets against the
kernels' device seconds.

One evaluation of a pair at width ``L`` (``splink_tpu/ops/strings_pallas.py``,
inputs transposed to float32 lanes):

* ``jaro_winkler_pallas`` reads the two (L, n) code blocks and the two (1, n)
  lengths and writes one (1, n) similarity: 4 (2 L + 3) bytes;
* ``levenshtein_pallas`` reads the (L, n) and the shifted (L + 1, n) code
  blocks and the two lengths and writes one distance: 4 (2 L + 4) bytes.

The width is the one the program's encoder gives a column
(``splink_tpu/data.py``): its longest value rounded up to a multiple of 8,
capped by ``max_string_length`` (default 24) rounded up likewise.
"""

from __future__ import annotations

KINDS = ("jaro_winkler", "levenshtein")  # the comparison kinds with a Pallas kernel
DEFAULT_WIDTH = 24


def kernel_bytes(kind: str, width: int) -> int:
    """HBM bytes one evaluation at ``width`` moves."""
    if kind == "jaro_winkler":
        return 4 * (2 * width + 3)
    if kind == "levenshtein":
        return 4 * (2 * width + 4)
    raise ValueError(f"no Pallas string kernel for kind {kind!r}")


def _pad8(n: int) -> int:
    return max(-(-n // 8) * 8, 8)


def string_columns(settings: dict) -> list[tuple[str, str, int]]:
    """(column, kind, max_string_length) of every Jaro-Winkler or
    Levenshtein comparison; a column without a ``comparison`` takes the
    default, Jaro-Winkler."""
    out = []
    for col in settings["comparison_columns"]:
        kind = (col.get("comparison") or {}).get("kind", "jaro_winkler")
        if kind in KINDS:
            out.append((col["col_name"], kind, int(col.get("max_string_length", DEFAULT_WIDTH))))
    return out


def evaluations(settings: dict, df) -> list[tuple[str, int]]:
    """(kind, width) of every string-kernel evaluation a pair takes, one a
    column of ``string_columns``, at the width the encoder gives it in
    ``df``."""
    out = []
    for name, kind, cap in string_columns(settings):
        longest = int(df[name].str.len().max()) if df[name].notna().any() else 1
        out.append((kind, min(_pad8(longest), _pad8(cap))))
    return out


def bytes_per_pair(evals: list[tuple[str, int]]) -> int:
    return sum(kernel_bytes(kind, width) for kind, width in evals)
