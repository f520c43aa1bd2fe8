"""The ``job_case_library`` runner over MusicBrainz-schema frames: its window,
memory reading, reference and comparison (the ones the traffic file names),
imported and not copied, with the frames made by
``chipbench.datagen_music.make_music`` — ``job.make_frames`` knows only the
``people`` generator, and ``job_case_library.run`` takes its frames from the
``make_frames`` name of its own module, which ``run`` points here for the call,
as ``job_febrl`` does.

Two guards stop a run before its set-up with a non-zero exit:

* ``require_pallas_forms``: the cell measures the string kernels at widths
  48-64 on code points. A program whose dispatch sends such a column to the
  vmapped vector forms (``strings_pallas.pallas_supported`` false for a
  (1, width) uint32 array on the chip, as it is where the gate stops at
  width 32 and uint8) runs another job, whose per-pair (L, L)
  intermediates need not even fit a batch.
* ``require_untruncated``: the configuration guarantees that no value is
  longer than its column's ``max_string_length``, so that the program never
  compares a truncated string where the reference compares the whole one.
"""

from __future__ import annotations

import numpy as np

from chipbench import string_bytes
from chipbench.runners import job_case_library


def require_pallas_forms(settings: dict) -> None:
    """Exit non-zero unless, on this backend, a column of code points at each
    compared width takes the Pallas kernels."""
    import jax.numpy as jnp

    from splink_tpu.ops.strings_pallas import pallas_supported

    for name, _, width in string_bytes.string_columns(settings):
        if not pallas_supported(jnp.zeros((1, width), jnp.uint32)):
            raise SystemExit(
                f"chipbench: the program sends {name!r} (code points, width {width}) "
                "to the vector forms, not the Pallas string kernels: this cell's "
                "configuration cannot run on it")


def require_untruncated(settings: dict, df) -> None:
    for name, _, width in string_bytes.string_columns(settings):
        longest = int(df[name].str.len().max())
        if longest > width:
            raise SystemExit(f"chipbench: a {name!r} value of {longest} characters is "
                             f"longer than its max_string_length {width}")


def make_frames(config: dict, traffic: dict, seed: int) -> dict:
    """The job's one frame: the configuration's songs (``population_seed``)
    in the order and under the unique ids ``seed`` gives them, as
    ``job.make_frames`` orders its own."""
    from chipbench import datagen_music
    from chipbench.run import rehearsing

    if not rehearsing():  # off the chip no string kernel is Pallas
        require_pallas_forms(config["settings"])
    gen = dict(config["generator"])
    kind, population = gen.pop("kind"), gen.pop("population_seed")
    if kind != "music":
        raise ValueError(f"unknown generator kind {kind!r}")
    if traffic["inputs"] != "single":
        raise ValueError(f"unknown inputs {traffic['inputs']!r}")
    df = datagen_music.make_music(seed=population, **gen)
    df = df.iloc[np.random.default_rng(seed).permutation(len(df))].reset_index(drop=True)
    df["unique_id"] = np.arange(len(df))
    require_untruncated(config["settings"], df)
    return {"df": df}


def run(ctx: dict) -> dict:
    """Drive one cell as ``job_case_library.run`` drives it, from these frames,
    and reckon the string kernels' bytes a pair (``string_bytes_per_pair``,
    ``chipbench/string_bytes.py``) from the frame's widths."""
    evals = []

    def frames(config, traffic, seed):
        out = make_frames(config, traffic, seed)
        evals[:] = string_bytes.evaluations(config["settings"], out["df"])
        return out

    people = job_case_library.make_frames
    job_case_library.make_frames = frames
    try:
        out = job_case_library.run(ctx)
    finally:
        job_case_library.make_frames = people
    out["string_bytes_per_pair"] = string_bytes.bytes_per_pair(evals)
    return out
