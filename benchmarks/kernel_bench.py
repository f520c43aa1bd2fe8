"""Kernel-only throughput: Pallas vs vmapped-JAX string similarity.

Chained-execution timing with a single value fetch: see _time_chain for
the measurement traps this harness guards against (constant folding
via closures, repeated input buffers, and closing the clock before the
device has finished). The first (compile) call is excluded; the reported figure is
wall clock over ``--chain`` dispatches divided by the chain length.

    python benchmarks/kernel_bench.py [--pairs 1048576] [--width 24] [--chain 8]

Prints one JSON line per (kernel, implementation).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _random_strings(rng, n, width):
    # realistic name-like lengths in [3, width]
    lengths = rng.integers(3, width + 1, n).astype(np.int32)
    chars = rng.integers(97, 123, (n, width)).astype(np.uint8)
    mask = np.arange(width)[None, :] < lengths[:, None]
    return (chars * mask).astype(np.uint8), lengths


def _time_chain(fn, arg_sets, chain):
    """Seconds per invocation of fn over a chain of dispatches with ONE
    value fetch at the end.

    Measurement traps this guards against (each produced impossible
    throughput numbers on real hardware before):
      * arrays are passed as jit ARGUMENTS, never closed over — a nullary
        jit treats closures as compile-time constants, which lets XLA
        constant-fold or DCE parts of the computation;
      * every dispatch gets a DISTINCT input buffer set (arg_sets
        cycles), so no timed (executable, input-buffers) pair repeats;
      * the wall clock closes on a VALUE read back: each kernel reduces
        to a scalar, a jitted combiner adds the chain's scalars on
        device, and float() of the result ends the window (one fetch,
        amortised over the chain). chip_smoke.py's barrier leg shows
        ``block_until_ready`` blocks on the TPU v5e as well; ROADMAP A0
        picks the barrier for the cell runner.
    """
    import functools
    import operator

    import jax

    assert len(arg_sets) > chain, "need a distinct input set per dispatch"
    fsum = jax.jit(lambda *a: fn(*a).sum())
    combiner = jax.jit(lambda *xs: functools.reduce(operator.add, xs))
    # warm on the LAST set only — the timed dispatches use sets 0..chain-1,
    # so no timed (executable, buffers) pair has ever executed before
    float(fsum(*arg_sets[-1]))
    float(combiner(*[fsum(*arg_sets[-1])] * chain))
    t0 = time.perf_counter()
    outs = [fsum(*arg_sets[k]) for k in range(chain)]
    float(combiner(*outs))
    return (time.perf_counter() - t0) / chain


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pairs", type=int, default=1 << 20)
    ap.add_argument("--width", type=int, default=24)
    ap.add_argument("--chain", type=int, default=8)
    ap.add_argument("--platform", default=None)
    args = ap.parse_args()

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    import jax
    import jax.numpy as jnp

    from splink_tpu.ops import strings as so
    from splink_tpu.ops.strings_pallas import (
        jaro_winkler_pallas,
        levenshtein_pallas,
        pallas_supported,
    )

    rng = np.random.default_rng(0)
    arg_sets = []
    for _ in range(args.chain + 1):
        a_chars, a_len = _random_strings(rng, args.pairs, args.width)
        b_chars, b_len = _random_strings(rng, args.pairs, args.width)
        arg_sets.append((jnp.asarray(a_chars), jnp.asarray(b_chars),
                         jnp.asarray(a_len), jnp.asarray(b_len)))
    s1, s2, l1, l2 = arg_sets[0]

    jw_vmap = jax.jit(so.jaro_winkler_batch)
    lev_vmap = jax.jit(
        lambda a, b, c, d: jax.vmap(so.levenshtein_single)(a, b, c, d)
    )
    cases = [("jaro_winkler", "vmapped", jw_vmap),
             ("levenshtein", "vmapped", lev_vmap)]
    if pallas_supported(s1):
        cases += [
            ("jaro_winkler", "pallas",
             jax.jit(lambda a, b, c, d: jaro_winkler_pallas(
                 a, b, c, d, 0.1, 0.7))),
            ("levenshtein", "pallas", jax.jit(levenshtein_pallas)),
        ]
    else:
        print(json.dumps({"note": "pallas unsupported on this backend; "
                          "vmapped only"}))

    for kernel, impl, fn in cases:
        sec = _time_chain(fn, arg_sets, args.chain)
        print(json.dumps({
            "kernel": kernel,
            "impl": impl,
            "pairs": args.pairs,
            "width": args.width,
            "seconds_per_call": round(sec, 4),
            "pairs_per_sec": round(args.pairs / sec),
            "device": str(jax.devices()[0]),
            "sync": f"chained x{args.chain}, one value fetch",
        }))


if __name__ == "__main__":
    main()
