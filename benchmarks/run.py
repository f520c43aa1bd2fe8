"""Benchmark runner for the five BASELINE.md configs.

Usage: python benchmarks/run.py --config N [--scale F]

Each config prints one JSON line with end-to-end wall-clock, pairs scored,
throughput and EM statistics, plus a simple match-quality check against the
generator's ground-truth clusters. --scale shrinks row counts for smoke runs
(e.g. --scale 0.01 for config 4 runs 100k rows instead of 10M).

Configs (BASELINE.json):
  1. FEBRL-style 1k dedupe, 2 exact-match columns
  2. FEBRL-style 10k dedupe, jaro-winkler on first_name/surname
  3. 1M x 1M link_only, one blocking rule + term-frequency adjustment
  4. 10M dedupe, 3 blocking rules / 6 comparison columns, full jit EM
  5. 100M-pair-scale dedupe, streamed gamma batches + streaming EM
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.datagen import make_people, split_for_linking  # noqa: E402


def _quality(df_e, threshold=0.8):
    """Precision/recall of predicted matches vs generator clusters."""
    if "cluster_l" not in df_e.columns or not len(df_e):
        return {}
    pred = df_e.match_probability >= threshold
    truth = df_e.cluster_l == df_e.cluster_r
    tp = int((pred & truth).sum())
    return {
        "pairs_truth": int(truth.sum()),
        "precision": round(tp / max(int(pred.sum()), 1), 4),
        "recall_blocked": round(tp / max(int(truth.sum()), 1), 4),
    }


def _run_linker(settings, t0, **inputs):
    from splink_tpu import Splink
    from splink_tpu.utils.profiling import reset_timings, stage_timings

    reset_timings()
    linker = Splink(settings, **inputs)
    df_e = linker.get_scored_comparisons()
    elapsed = time.perf_counter() - t0
    out = {
        "rows": sum(len(v) for v in inputs.values()),
        "pairs": len(df_e),
        "seconds": round(elapsed, 3),
        "pairs_per_sec": round(len(df_e) / elapsed),
        "em_iterations": len(linker.params.param_history),
        "lambda": round(linker.params.params["λ"], 5),
        # per-stage wall: with overlap_blocking (default) the "blocking"
        # stage includes the async device dispatches riding inside it, and
        # gammas/gammas_patterns is only the final drain — blocking+drain ≈
        # max(blocking, scoring) is the overlap working as designed
        "stages": {
            k: round(sum(v), 3) for k, v in stage_timings().items()
        },
    }
    out.update(_quality(df_e))
    return linker, df_e, out


def config_1(scale):
    n = max(int(1000 * scale), 100)
    df = make_people(n, seed=1)
    t0 = time.perf_counter()
    settings = {
        "link_type": "dedupe_only",
        "comparison_columns": [
            {"col_name": "first_name", "comparison": {"kind": "exact"}},
            {"col_name": "surname", "comparison": {"kind": "exact"}},
        ],
        "blocking_rules": ["l.dob = r.dob"],
        "additional_columns_to_retain": ["cluster"],
    }
    _, _, out = _run_linker(settings, t0, df=df)
    return out


def config_2(scale):
    n = max(int(10_000 * scale), 100)
    df = make_people(n, seed=2)
    t0 = time.perf_counter()
    settings = {
        "link_type": "dedupe_only",
        "comparison_columns": [
            {"col_name": "first_name", "num_levels": 3},
            {"col_name": "surname", "num_levels": 3},
        ],
        "blocking_rules": ["l.dob = r.dob", "l.postcode = r.postcode"],
        "additional_columns_to_retain": ["cluster"],
    }
    _, _, out = _run_linker(settings, t0, df=df)
    return out


def config_3(scale):
    n = max(int(1_000_000 * scale), 1000)
    df = make_people(n, duplicate_rate=0.5, seed=3)
    df_l, df_r = split_for_linking(df)
    t0 = time.perf_counter()
    settings = {
        "link_type": "link_only",
        "comparison_columns": [
            {"col_name": "first_name", "num_levels": 3,
             "term_frequency_adjustments": True},
            {"col_name": "surname", "num_levels": 3},
            {"col_name": "city", "comparison": {"kind": "exact"}},
        ],
        "blocking_rules": ["l.dob = r.dob"],
        "additional_columns_to_retain": ["cluster"],
    }
    linker, df_e, out = _run_linker(settings, t0, df_l=df_l, df_r=df_r)
    t1 = time.perf_counter()
    linker.make_term_frequency_adjustments(df_e)
    out["tf_seconds"] = round(time.perf_counter() - t1, 3)
    return out


def config_4_settings() -> dict:
    """BASELINE config 4's model: six comparison columns, three blocking
    rules. The one definition — ``config_4`` and ``chip_smoke.py`` both
    build on it."""
    return {
        "link_type": "dedupe_only",
        "comparison_columns": [
            {"col_name": "first_name", "num_levels": 3},
            {"col_name": "surname", "num_levels": 3},
            {"col_name": "dob", "comparison": {"kind": "exact"}},
            {"col_name": "city", "comparison": {"kind": "exact"}},
            {"col_name": "postcode", "num_levels": 2},
            {"custom_name": "surname_qgram", "custom_columns_used": ["surname"],
             "num_levels": 2,
             "comparison": {"kind": "qgram_jaccard", "column": "surname",
                            "thresholds": [0.6]}},
        ],
        "blocking_rules": [
            "l.dob = r.dob",
            "l.postcode = r.postcode AND l.surname = r.surname",
            "l.first_name = r.first_name AND l.surname = r.surname",
        ],
        "retain_matching_columns": False,
        "retain_intermediate_calculation_columns": False,
        "additional_columns_to_retain": ["cluster"],
    }


def config_4(scale):
    """10M-row dedupe. At full scale the dob blocking rule alone yields
    ~3.3B candidate pairs, so output is consumed as a stream (the full
    scored frame would not fit host memory as one DataFrame) and quality
    metrics aggregate incrementally. EM runs pattern-compressed: one device
    pass histograms the gamma vectors, iterations run on the tiny weighted
    pattern matrix."""
    from splink_tpu import Splink

    n = max(int(10_000_000 * scale), 1000)
    df = make_people(n, seed=4)
    settings = config_4_settings()
    settings["spill_dir"] = os.environ.get(
        "SPLINK_TPU_SPILL_DIR", os.path.join(os.path.dirname(__file__), "spill")
    )
    if os.environ.get("SPLINK_TPU_BENCH_FORCE_VIRTUAL"):
        # sub-scale runs sit below the auto threshold (2^28 pairs); force
        # the device pair path so the CPU tier still exercises/benches it
        settings["device_pair_generation"] = "on"
        settings["max_resident_pairs"] = 1 << 20
    n_rows = len(df)
    t0 = time.perf_counter()
    linker = Splink(settings, df=df)
    linker.release_input()
    del df

    if os.environ.get("SPLINK_TPU_BENCH_TRAIN_ONLY"):
        # the BASELINE north-star #2 measurement exactly: EM convergence
        # on the dedupe, no per-pair output (estimate_parameters is the
        # histogram-only pass under device pair generation)
        params = linker.estimate_parameters()
        elapsed = time.perf_counter() - t0
        return {
            "rows": n_rows,
            "seconds": round(elapsed, 3),
            "train_only": True,
            "em_iterations": len(params.param_history),
            "converged": bool(params.is_converged()),
            "lambda": round(params.params["λ"], 5),
        }

    t1 = time.perf_counter()
    if linker._virtual_plan() is not None:
        # device pair generation: "blocking" is just the unit-plan build —
        # no pair materialisation, no spill; pairs decode inside the
        # device scoring pass timed below
        t_block = time.perf_counter() - t1
    else:
        linker._ensure_pairs()
        t_block = time.perf_counter() - t1

    t1 = time.perf_counter()
    if linker._use_pattern_pipeline():
        # the score stream below is part of this config — same hint the
        # public get_scored_comparisons sets, so the virtual pass keeps
        # its ids and the stream is LUT-only
        linker._virtual_want_ids = True
        linker._ensure_pattern_ids()
        t_gamma = time.perf_counter() - t1
        t1 = time.perf_counter()
        linker._run_em_patterns(False)
    else:
        G = linker._ensure_gammas()
        t_gamma = time.perf_counter() - t1
        t1 = time.perf_counter()
        linker._run_em(G, False)
    t_em = time.perf_counter() - t1

    t1 = time.perf_counter()
    scored = tp = pred = truth = 0
    for chunk in linker.stream_scored_comparisons_after_em():
        scored += len(chunk)
        p = chunk.match_probability.to_numpy() >= 0.8
        t = (chunk.cluster_l == chunk.cluster_r).to_numpy()
        tp += int((p & t).sum())
        pred += int(p.sum())
        truth += int(t.sum())
    t_score = time.perf_counter() - t1
    elapsed = time.perf_counter() - t0
    return {
        "rows": n_rows,
        "pairs": scored,
        "seconds": round(elapsed, 3),
        "pairs_per_sec": round(scored / elapsed),
        "blocking_seconds": round(t_block, 3),
        "gamma_seconds": round(t_gamma, 3),
        "em_seconds": round(t_em, 3),
        "score_stream_seconds": round(t_score, 3),
        "em_iterations": len(linker.params.param_history),
        "lambda": round(linker.params.params["λ"], 5),
        "pairs_truth": truth,
        "precision": round(tp / max(pred, 1), 4),
        "recall_blocked": round(tp / max(truth, 1), 4),
    }


def config_5(scale):
    """Streamed regime end-to-end: the pattern-id pipeline (one device pass
    over the pair index, EM on the weighted pattern histogram, LUT-scored
    chunked output) with the pair index spilled to disk — the linker's
    production path for pair sets above max_resident_pairs."""
    from splink_tpu import Splink

    n = max(int(20_000_000 * scale), 1000)  # pair count scales with blocking density
    df = make_people(n, seed=5)
    t0 = time.perf_counter()
    settings = {
        "link_type": "dedupe_only",
        "comparison_columns": [
            {"col_name": "first_name", "num_levels": 3},
            {"col_name": "surname", "num_levels": 3},
            {"col_name": "city", "comparison": {"kind": "exact"}},
        ],
        "blocking_rules": ["l.dob = r.dob", "l.postcode = r.postcode"],
        "max_resident_pairs": 1024,  # force the streamed regime at any size
        "retain_matching_columns": False,
        "retain_intermediate_calculation_columns": False,
        # /tmp is tmpfs (RAM-backed) on many distros, which would defeat the
        # point of spilling; default next to this script, allow override.
        "spill_dir": os.environ.get(
            "SPLINK_TPU_SPILL_DIR", os.path.join(os.path.dirname(__file__), "spill")
        ),
    }
    n_rows = len(df)
    linker = Splink(settings, df=df)
    linker.release_input()
    del df
    scored = 0
    for chunk in linker.stream_scored_comparisons():
        scored += len(chunk)
    elapsed = time.perf_counter() - t0
    return {
        "rows": n_rows,
        "pairs": scored,
        "seconds": round(elapsed, 3),
        "pairs_per_sec": round(scored / elapsed),
        "em_iterations": len(linker.params.param_history),
        "converged": bool(linker.params.is_converged()),
        "lambda": round(linker.params.params["λ"], 5),
        "streamed": True,
    }


CONFIGS = {1: config_1, 2: config_2, 3: config_3, 4: config_4, 5: config_5}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", type=int, required=True, choices=sorted(CONFIGS))
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument(
        "--platform",
        default=None,
        help="Force a jax platform (e.g. cpu). The environment may pre-import "
        "jax with a default platform, so the JAX_PLATFORMS env var alone is "
        "not reliable — this flag uses jax.config.update before first use.",
    )
    args = ap.parse_args()
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    out = CONFIGS[args.config](args.scale)
    out["config"] = args.config
    out["scale"] = args.scale
    print(json.dumps(out))


if __name__ == "__main__":
    main()
