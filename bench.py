"""Benchmark: scored record-pairs/sec through the full device pipeline.

Measures the production path on whatever accelerator jax exposes (one TPU v5e
chip under the driver): pandas input -> host encode -> packed uint32 row
table (one gather per pair side, splink_tpu/gammas.py) -> vmapped comparison
kernels (2x jaro-winkler, exact, numeric) -> gamma bucketing -> log-space
Fellegi-Sunter scoring, streamed in pair batches; plus a fused-EM convergence
run on the resulting gamma matrix.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
vs_baseline is measured against the BASELINE.md north-star target of 50M
scored pairs/sec per v5e-8, i.e. 6.25M pairs/sec/chip (the reference itself
publishes no numbers — BASELINE.md: "None exist").
"""

import json
import os
import sys
import time

import numpy as np

TARGET_PAIRS_PER_SEC_PER_CHIP = 50e6 / 8  # north star: 50M/s on a v5e-8



def _device_identity() -> dict:
    """The device this process measures on, as jax reports it. Initialises
    the backend, so a process that spawns chip-needing children calls it
    only after the last child has exited (a chip belongs to one process).
    A backend that does not come up raises: nothing here falls back."""
    import jax

    dev = jax.devices()[0]
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
    }


N_ROWS = int(os.environ.get("SPLINK_TPU_BENCH_ROWS", 1_000_000))
N_PAIRS = int(os.environ.get("SPLINK_TPU_BENCH_PAIRS", 8 * (1 << 20)))  # ~8.4M
BATCH = min(1 << 20, N_PAIRS)
# whole batches only: the batch loop, the throughput division and the
# warmup-tail reservation all assume BATCH | N_PAIRS
N_PAIRS = max(BATCH, (N_PAIRS // BATCH) * BATCH)

SETTINGS = {
    "link_type": "dedupe_only",
    "comparison_columns": [
        {
            "col_name": "first_name",
            "num_levels": 3,
            "comparison": {"kind": "jaro_winkler", "thresholds": [0.94, 0.88]},
        },
        {
            "col_name": "surname",
            "num_levels": 3,
            "comparison": {"kind": "jaro_winkler", "thresholds": [0.94, 0.88]},
        },
        {"col_name": "city", "num_levels": 2, "comparison": {"kind": "exact"}},
        {
            "col_name": "dob",
            "num_levels": 2,
            "data_type": "numeric",
            "comparison": {"kind": "numeric_abs", "thresholds": [1.0]},
        },
    ],
    # referenced so the encode includes blk; the primary phase streams
    # random pair batches and never runs blocking itself
    "blocking_rules": ["l.blk = r.blk"],
}


def _make_df(rng, n_rows):
    import pandas as pd

    firsts = np.array(
        ["amelia", "oliver", "isla", "george", "ava", "noah", "emily", "arthur",
         "sophia", "lily", "freya", "leo", "ivy", "oscar", "grace", "archie"]
    )
    lasts = np.array(
        ["smith", "jones", "taylor", "brown", "wilson", "evans", "thomas",
         "roberts", "johnson", "lewis", "walker", "robinson"]
    )
    cities = np.array([f"city{k:03d}" for k in range(200)])
    return pd.DataFrame(
        {
            "unique_id": np.arange(n_rows),
            "first_name": firsts[rng.integers(0, len(firsts), n_rows)],
            "surname": lasts[rng.integers(0, len(lasts), n_rows)],
            "city": cities[rng.integers(0, len(cities), n_rows)],
            "dob": rng.integers(1940, 2000, n_rows).astype(np.float64),
            # blocking key sized for ~16M within-group pairs at N_ROWS
            # (the virtual-pipeline phase blocks on this)
            "blk": rng.integers(0, max(n_rows // 32, 1), n_rows),
        }
    )


def _bench_virtual_pipeline(settings, table, prog):
    """Device pair generation end to end: unit-plan build + one device
    pass computing pattern ids/histogram with pairs decoded IN KERNEL.
    Returns a dict of extras."""
    from splink_tpu.pairgen import (
        build_virtual_plan,
        compute_virtual_pattern_ids,
    )

    t0 = time.perf_counter()
    plan = build_virtual_plan(settings, table)  # l.blk = r.blk
    plan_time = time.perf_counter() - t0
    if plan is None:
        raise RuntimeError("virtual plan rejected for the bench settings")
    # full warmup pass compiles the per-rule kernels (cached on the
    # plan), so the timed passes measure steady-state throughput
    compute_virtual_pattern_ids(prog, plan, BATCH, return_ids=False)
    # histogram-only pass: what EM consumes — no per-pair D2H at all
    t0 = time.perf_counter()
    _, counts, n_real = compute_virtual_pattern_ids(
        prog, plan, BATCH, return_ids=False
    )
    hist_time = time.perf_counter() - t0
    # ids pass: what the score-output stream drives (per-pair D2H)
    t0 = time.perf_counter()
    compute_virtual_pattern_ids(prog, plan, BATCH)
    virt_time = time.perf_counter() - t0
    # NOTE key rename vs BENCH_r01: virtual_pattern_pairs_per_sec /
    # virtual_pass_seconds measured the ids-returning pass; the renamed
    # *_hist_* keys time the histogram-only (EM-path) pass, which never
    # downloads per-pair bytes — not comparable to the old numbers
    return {
        "virtual_hist_pairs_per_sec": round(plan.n_candidates / hist_time),
        "virtual_candidates": plan.n_candidates,
        "virtual_real_pairs": n_real,
        "virtual_plan_seconds": round(plan_time, 3),
        "virtual_hist_pass_seconds": round(hist_time, 3),
        "virtual_ids_pass_seconds": round(virt_time, 3),
    }


def _bench_virtual_qgram(df):
    """The heavier gamma program config 4 runs: the 4 flagship comparisons
    PLUS a q-gram Jaccard on surname (masked precomputed-aux kernel),
    through the virtual pair index, histogram-only."""
    from splink_tpu.data import encode_table
    from splink_tpu.gammas import GammaProgram
    from splink_tpu.pairgen import (
        build_virtual_plan,
        compute_virtual_pattern_ids,
    )
    from splink_tpu.settings import complete_settings_dict

    s = dict(SETTINGS)
    s["comparison_columns"] = list(s["comparison_columns"]) + [
        {
            "custom_name": "surname_qgram",
            "custom_columns_used": ["surname"],
            "num_levels": 2,
            "comparison": {
                "kind": "qgram_jaccard",
                "column": "surname",
                "thresholds": [0.6],
            },
        }
    ]
    s = complete_settings_dict(s)
    table = encode_table(df, s)
    prog = GammaProgram(s, table)
    plan = build_virtual_plan(s, table)
    if plan is None:
        raise RuntimeError("virtual plan rejected for the q-gram settings")
    compute_virtual_pattern_ids(prog, plan, BATCH, return_ids=False)
    t0 = time.perf_counter()
    compute_virtual_pattern_ids(prog, plan, BATCH, return_ids=False)
    hist_time = time.perf_counter() - t0
    return {
        "virtual_hist_qgram5col_pairs_per_sec": round(
            plan.n_candidates / hist_time
        ),
        "virtual_hist_qgram5col_seconds": round(hist_time, 3),
    }


def bench_serve():
    """Online-serving benchmark (`python bench.py serve`): train a small
    model over the fixture corpus, freeze it into a LinkageIndex, warm
    every bucket combination, then push micro-batched query traffic
    through the LinkageService and report steady-state latency percentiles
    + throughput. The compile counter proves the bucket contract: warmup
    compiles == bucket combinations, steady state == ZERO.

    Round 9 additions (request tracing, obs v2): the open burst runs
    three times — tracing off / sampled at 10% / full — so the BENCH json
    carries the measured tracing-overhead table, and the full-rate run
    emits the per-phase tail attribution (queue_wait/coalesce/dispatch/
    compile/execute/transfer ms at p50/p99) from the service's
    phase_summary()."""
    identity = _device_identity()
    import jax

    from splink_tpu import Splink
    from splink_tpu.obs.metrics import compile_requests, install_compile_monitor
    from splink_tpu.serve import LinkageService, QueryEngine

    install_compile_monitor()
    n_rows = int(os.environ.get("SPLINK_TPU_BENCH_SERVE_ROWS", 200_000))
    n_queries = int(os.environ.get("SPLINK_TPU_BENCH_SERVE_QUERIES", 2000))
    rng = np.random.default_rng(0)
    df = _make_df(rng, n_rows)

    settings = dict(SETTINGS)
    settings["max_iterations"] = 5
    settings["serve_top_k"] = 5
    # the bench offers the whole query set as one burst; admission control
    # (tested separately) would shed half of it at the default depth, so
    # size the queue to the burst and measure pure serving throughput
    settings["serve_queue_depth"] = n_queries
    linker = Splink(settings, df=df)
    t0 = time.perf_counter()
    linker.estimate_parameters()
    train_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    index = linker.export_index()
    build_s = time.perf_counter() - t0

    engine = QueryEngine(index)
    t0 = time.perf_counter()
    warm = engine.warmup()
    warmup_s = time.perf_counter() - t0
    c_warm = compile_requests()

    records = df.sample(
        n=min(n_queries, len(df)), replace=n_queries > len(df),
        random_state=0,
    ).to_dict(orient="records")
    while len(records) < n_queries:
        records.extend(records[: n_queries - len(records)])
    svc = LinkageService(engine, deadline_ms=2.0)
    # phase 1 — closed loop: one request in flight at a time. Latency here
    # is the TRUE per-request number (coalescing deadline + one bucketed
    # dispatch), no queueing ahead of it.
    seq_lat = []
    for r in records[:100]:
        t0 = time.perf_counter()
        svc.query(dict(r), timeout=60)
        seq_lat.append((time.perf_counter() - t0) * 1000.0)
    seq_p50, seq_p99 = np.percentile(np.asarray(seq_lat), [50, 99])
    # phase 2 — open burst: the whole query set offered at once; the
    # headline is throughput (per-request latency includes queueing).
    t0 = time.perf_counter()
    futures = [svc.submit(dict(r)) for r in records]
    for f in futures:
        f.result()
    wall = time.perf_counter() - t0
    svc.close()
    c_end = compile_requests()
    summary = svc.latency_summary()

    # phase 3 — tracing-overhead tiers (obs v2): the same open burst with
    # request tracing off / sampled at 10% / full rate. One long-lived
    # service per tier over the shared warmed engine; the tiers are
    # INTERLEAVED round-robin and each takes its best-of-N burst — a
    # single ~1s burst on a shared CPU container drifts run to run by far
    # more than the overhead being measured (sequential tiers measured
    # the sampled run 40% FASTER than off on one capture), and
    # interleaving exposes every tier to the same drift. The full-rate
    # tier also yields the per-phase tail attribution.
    repeats = int(os.environ.get("SPLINK_TPU_BENCH_TRACE_REPEATS", 3))
    tiers = {
        rate: LinkageService(engine, deadline_ms=2.0,
                             trace_sample_rate=rate)
        for rate in (0.0, 0.1, 1.0)
    }
    best = {rate: 0.0 for rate in tiers}
    for _ in range(repeats):
        for rate, tsvc in tiers.items():
            t0 = time.perf_counter()
            futs = [tsvc.submit(dict(r)) for r in records]
            for f in futs:
                f.result()
            best[rate] = max(
                best[rate], n_queries / (time.perf_counter() - t0)
            )
    phases = tiers[1.0].phase_summary()
    for tsvc in tiers.values():
        tsvc.close()
    qps_off, qps_sampled, qps_full = best[0.0], best[0.1], best[1.0]
    c_traced = compile_requests()
    phase_fields = {}
    for phase, stats in phases.items():
        phase_fields[f"{phase}_p50_ms"] = round(stats["p50_ms"], 3)
        phase_fields[f"{phase}_p99_ms"] = round(stats["p99_ms"], 3)

    print(json.dumps({
        "metric": "serve_queries_per_sec",
        "value": round(n_queries / wall, 1),
        "unit": "queries/sec",
        "n_reference_rows": n_rows,
        "n_queries": n_queries,
        "top_k": engine.top_k,
        "train_seconds": round(train_s, 3),
        "index_build_seconds": round(build_s, 3),
        "warmup_seconds": round(warmup_s, 3),
        "warmup_combinations": warm["combinations"],
        "warmup_compiles": warm["compiles"],
        "steady_state_compiles": c_end - c_warm,
        "sequential_p50_ms": round(float(seq_p50), 3),
        "sequential_p99_ms": round(float(seq_p99), 3),
        "p50_ms": round(summary.get("p50_ms", 0.0), 3),
        "p95_ms": round(summary.get("p95_ms", 0.0), 3),
        "p99_ms": round(summary.get("p99_ms", 0.0), 3),
        "shed": summary["shed"],
        "batches": summary["batches"],
        "qps_trace_off": round(qps_off, 1),
        "qps_trace_sampled_10pct": round(qps_sampled, 1),
        "qps_trace_full": round(qps_full, 1),
        "trace_overhead_sampled_pct": round(
            100 * (1 - qps_sampled / qps_off), 2
        ),
        "trace_overhead_full_pct": round(100 * (1 - qps_full / qps_off), 2),
        "traced_steady_state_compiles": c_traced - c_end,
        **phase_fields,
        "device": str(jax.devices()[0]),
        **identity,
    }))


def _coldstart_child(phase: str, workdir: str) -> int:
    """One cold-start child process (`bench.py coldstart-child <phase>
    <workdir>`). ``build`` trains + exports the index, compiles the serve
    menu (populating the persistent compile cache) and commits the AOT
    sidecar. ``serve`` measures process-cold -> first-query-served wall
    time; the SPLINK_TPU_COLD_AOT env var selects whether the sidecar is
    offered (the compile-cache tier is selected by the inherited
    JAX_COMPILATION_CACHE_DIR pointing at the warm vs a fresh dir)."""
    t_start = time.perf_counter()
    from splink_tpu.obs.metrics import compile_stats, install_compile_monitor
    from splink_tpu.serve import QueryEngine, load_index

    install_compile_monitor()
    index_dir = os.path.join(workdir, "index")
    n_rows = int(os.environ.get("SPLINK_TPU_BENCH_COLD_ROWS", 200_000))
    rng = np.random.default_rng(0)
    df = _make_df(rng, n_rows)
    if phase == "build":
        from splink_tpu import Splink

        settings = dict(SETTINGS)
        settings["max_iterations"] = 5
        settings["serve_top_k"] = 5
        linker = Splink(settings, df=df)
        linker.estimate_parameters()
        linker.export_index(index_dir)
        engine = QueryEngine(
            load_index(index_dir), aot_dir=os.path.join(index_dir, "aot")
        )
        warm = engine.warmup()
        engine.save_aot()
        print(json.dumps({"phase": "build", "warm": warm}), flush=True)
        return 0
    t_import = time.perf_counter()
    aot_dir = (
        os.path.join(index_dir, "aot")
        if os.environ.get("SPLINK_TPU_COLD_AOT") == "1"
        else None
    )
    engine = QueryEngine(load_index(index_dir), aot_dir=aot_dir)
    t_load = time.perf_counter()
    warm = engine.warmup()
    t_warm = time.perf_counter()
    engine.query_arrays(df.head(16))
    t_query = time.perf_counter()
    print(json.dumps({
        "phase": "serve",
        "import_seconds": round(t_import - t_start, 3),
        "index_load_seconds": round(t_load - t_import, 3),
        "warmup_seconds": round(t_warm - t_load, 3),
        "first_query_seconds": round(t_query - t_warm, 3),
        "cold_to_first_query_seconds": round(t_query - t_start, 3),
        "warm": warm,
        "compile_stats": compile_stats(),
    }), flush=True)
    return 0


def bench_coldstart():
    """Cold-start benchmark (`python bench.py coldstart`): process-cold ->
    first-query-served wall time across the three warmup tiers —

      no-cache    every menu program backend-compiles (the pre-ISSUE cost
                  a restarted replica paid),
      cache-warm  the persistent XLA compile cache serves every program
                  (now on for the CPU tier too, keyed by target
                  fingerprint),
      aot         the serialized-executable sidecar restores the menu
                  with the compiler never invoked (and a FRESH compile
                  cache, proving independence);

    each tier is a REAL fresh interpreter (subprocess), plus steady-state
    fused-vs-unfused engine throughput and latency percentiles in the
    driver process. One JSON line. The children need the chip one after
    another, so this parent stays off jax until the last one has exited."""
    import shutil
    import subprocess

    # fixed in-checkout work directory (git-ignored): the warm/cold compile
    # caches of the three tiers live under it, wiped at the start
    workdir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".bench_work", "coldstart"
    )
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    try:
        warm_cache = os.path.join(workdir, "xla_warm")
        fresh = lambda name: os.path.join(workdir, name)  # noqa: E731

        def child(phase, cache_dir, aot):
            env = dict(os.environ)
            env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
            env["SPLINK_TPU_COLD_AOT"] = "1" if aot else "0"
            # cache EVERY program regardless of its compile time: the tier
            # comparison needs the warm-cache leg fully warm, not "warm
            # above the threshold". Through jax's own variables — the
            # tuning enable_compilation_cache leaves alone.
            env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
            env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "coldstart-child", phase, workdir],
                env=env, capture_output=True, text=True, check=True,
            )
            return json.loads(out.stdout.strip().splitlines()[-1])

        child("build", warm_cache, aot=False)
        tiers = {
            "nocache": child("serve", fresh("xla_cold_a"), aot=False),
            "cache_warm": child("serve", warm_cache, aot=False),
            "aot": child("serve", fresh("xla_cold_b"), aot=True),
        }
        # contract checks — mislabelled tiers make the round worthless
        warm = {name: t["warm"] for name, t in tiers.items()}
        assert warm["nocache"]["compiles"] > 0, warm
        assert warm["cache_warm"]["compiles"] == 0, warm
        assert warm["cache_warm"]["cache_hits"] > 0, warm
        assert warm["aot"]["compiles"] == 0, warm
        assert warm["aot"]["cache_hits"] == 0, warm
        assert (
            warm["aot"]["aot_restored"] == warm["aot"]["combinations"]
        ), warm

        # steady-state fused vs unfused (driver process, warmed engines);
        # the last child has exited, so the parent may take the device
        identity = _device_identity()
        from splink_tpu.serve import QueryEngine, load_index

        n_queries = int(
            os.environ.get("SPLINK_TPU_BENCH_COLD_QUERIES", 1000)
        )
        rng = np.random.default_rng(0)
        df = _make_df(
            rng, int(os.environ.get("SPLINK_TPU_BENCH_COLD_ROWS", 200_000))
        )
        queries = df.sample(n=n_queries, random_state=1)
        index_dir = os.path.join(workdir, "index")
        engines = {
            label: QueryEngine(load_index(index_dir), fused=fused)
            for label, fused in (("fused", True), ("unfused", False))
        }
        for eng in engines.values():
            eng.warmup()
        # INTERLEAVED best-of-N, the round-9 lesson: a single burst on a
        # shared 2-core container drifts run to run by far more than the
        # fused-vs-unfused delta, so both tiers must see the same drift
        repeats = int(os.environ.get("SPLINK_TPU_BENCH_COLD_REPEATS", 3))
        best = {label: 0.0 for label in engines}
        lat = {label: [] for label in engines}
        for _ in range(repeats):
            for label, eng in engines.items():
                for s in range(0, 60):
                    q = queries.iloc[s : s + 1]
                    t0 = time.perf_counter()
                    eng.query_arrays(q)
                    lat[label].append((time.perf_counter() - t0) * 1000.0)
                t0 = time.perf_counter()
                eng.query_arrays(queries)
                best[label] = max(
                    best[label], n_queries / (time.perf_counter() - t0)
                )
        steady = {}
        for label in engines:
            p50, p99 = np.percentile(np.asarray(lat[label]), [50, 99])
            steady[label] = {
                "qps": round(best[label], 1),
                "p50_ms": round(float(p50), 3),
                "p99_ms": round(float(p99), 3),
            }
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    print(json.dumps({
        "metric": "serve_cold_start_seconds",
        "value": tiers["aot"]["cold_to_first_query_seconds"],
        "unit": "seconds",
        "cold_nocache_seconds": tiers["nocache"]["cold_to_first_query_seconds"],
        "cold_cache_warm_seconds": tiers["cache_warm"]["cold_to_first_query_seconds"],
        "cold_aot_seconds": tiers["aot"]["cold_to_first_query_seconds"],
        "warmup_nocache_seconds": tiers["nocache"]["warmup_seconds"],
        "warmup_cache_warm_seconds": tiers["cache_warm"]["warmup_seconds"],
        "warmup_aot_seconds": tiers["aot"]["warmup_seconds"],
        "speedup_vs_nocache": round(
            tiers["nocache"]["cold_to_first_query_seconds"]
            / tiers["aot"]["cold_to_first_query_seconds"], 2,
        ),
        "menu_combinations": tiers["aot"]["warm"]["combinations"],
        "aot_restored": tiers["aot"]["warm"]["aot_restored"],
        "cache_hits_warm_tier": tiers["cache_warm"]["warm"]["cache_hits"],
        "fused_qps": steady["fused"]["qps"],
        "fused_p50_ms": steady["fused"]["p50_ms"],
        "fused_p99_ms": steady["fused"]["p99_ms"],
        "unfused_qps": steady["unfused"]["qps"],
        "unfused_p50_ms": steady["unfused"]["p50_ms"],
        "unfused_p99_ms": steady["unfused"]["p99_ms"],
        "tiers_detail": tiers,
        **identity,
    }))


def bench_blocking():
    """Blocking-tier benchmark (`python bench.py blocking`): host join vs
    the device-native candidate-generation tier over the same rules and
    corpus, pairs/sec end to end through block_using_rules (sink
    included). The device tier is measured twice: budgeted CHUNKED
    emission (the production default — fixed-shape chunks under
    blocking_chunk_pairs) and RESIDENT emission (one batch per rule, the
    shape a single-pass consumer would drive). Warmup runs precede every
    timed pass so steady state is what's measured; the compile counter
    proves the chunk contract (steady state == ZERO recompiles)."""
    identity = _device_identity()
    import jax

    from splink_tpu.blocking import block_using_rules
    from splink_tpu.blocking_device import (
        build_device_plan,
        iter_device_pairs,
    )
    from splink_tpu.data import encode_table
    from splink_tpu.obs.metrics import compile_requests, install_compile_monitor
    from splink_tpu.settings import complete_settings_dict

    install_compile_monitor()
    n_rows = int(os.environ.get("SPLINK_TPU_BENCH_BLOCKING_ROWS", 1_000_000))
    rng = np.random.default_rng(0)
    df = _make_df(rng, n_rows)
    settings = complete_settings_dict(
        {
            **{k: v for k, v in SETTINGS.items()},
            # two rules: the ~16M-pair blk key plus a 3-column conjunction,
            # so the sequential-rule dedup mask is on the measured path
            "blocking_rules": [
                "l.blk = r.blk",
                "l.first_name = r.first_name and l.surname = r.surname "
                "and l.city = r.city",
            ],
        }
    )
    table = encode_table(df, settings)

    host_cfg = dict(settings)
    host_cfg["device_blocking"] = "off"
    t0 = time.perf_counter()
    host_pairs = block_using_rules(host_cfg, table)
    host_s = time.perf_counter() - t0
    n_pairs = host_pairs.n_pairs
    del host_pairs

    dev_cfg = dict(settings)
    dev_cfg["device_blocking"] = "on"
    # warmup compiles the per-rule kernels (cached on nothing persistent
    # across block_using_rules calls — so time the DRIVER level, where the
    # plan's kernel cache persists, for the steady-state numbers)
    t0 = time.perf_counter()
    plan = build_device_plan(dev_cfg, table)
    plan_s = time.perf_counter() - t0
    if plan is None:
        print(json.dumps({
            "metric": "blocking_pairs_per_sec",
            "value": round(n_pairs / host_s),
            "unit": "pairs/sec",
            "blocking_error": "device plan rejected",
            "host_pairs_per_sec": round(n_pairs / host_s),
            **identity,
        }))
        return
    chunk = int(dev_cfg["blocking_chunk_pairs"])

    def drive(budget):
        total = 0
        for _r, i, _j in iter_device_pairs(plan, budget):
            total += len(i)
        return total

    drive(chunk)  # warmup: compiles every per-rule chunked kernel
    c0 = compile_requests()
    t0 = time.perf_counter()
    emitted = drive(chunk)
    chunked_s = time.perf_counter() - t0
    c1 = compile_requests()
    resident_budget = max(rp.total for rp in plan.rules)
    drive(resident_budget)  # warmup the resident-shape kernels
    t0 = time.perf_counter()
    drive(resident_budget)
    resident_s = time.perf_counter() - t0
    # end-to-end through the sink (what a linker run pays)
    t0 = time.perf_counter()
    dev_pairs = block_using_rules(dev_cfg, table)
    e2e_s = time.perf_counter() - t0
    assert dev_pairs.n_pairs == n_pairs == emitted, (
        n_pairs, emitted, dev_pairs.n_pairs,
    )

    print(json.dumps({
        "metric": "blocking_pairs_per_sec",
        "value": round(n_pairs / chunked_s),
        "unit": "pairs/sec",
        "n_rows": n_rows,
        "n_pairs": n_pairs,
        "candidates": plan.n_candidates,
        "host_pairs_per_sec": round(n_pairs / host_s),
        "host_seconds": round(host_s, 3),
        "device_chunked_pairs_per_sec": round(n_pairs / chunked_s),
        "device_chunked_seconds": round(chunked_s, 3),
        "device_resident_pairs_per_sec": round(n_pairs / resident_s),
        "device_resident_seconds": round(resident_s, 3),
        "device_e2e_pairs_per_sec": round(n_pairs / e2e_s),
        "plan_seconds": round(plan_s, 3),
        "chunk_pairs": chunk,
        "speedup_vs_host": round(host_s / chunked_s, 2),
        "steady_state_recompiles": c1 - c0,
        "device": str(jax.devices()[0]),
        **identity,
    }))


def bench_approx():
    """Approximate-blocking benchmark (`python bench.py approx`): the
    minhash-LSH recall tier over a typo corpus — every blocking key of
    every duplicate carries a seeded single-character corruption, so the
    EXACT tier's recall of the true matches collapses while the approx
    tier recovers them under its pair budget. Measured end to end through
    ``block_using_rules`` (signatures + band joins + verification +
    ranking + budget-ordered emission), tier-labelled next to the exact
    device join over the same corpus; steady state is recompile-free
    (compile counter gated)."""
    identity = _device_identity()
    import jax

    from splink_tpu.approx.lsh import (
        build_approx_plan,
        generate_approx_candidates,
    )
    from splink_tpu.blocking import block_using_rules
    from splink_tpu.data import encode_table
    from splink_tpu.obs.metrics import compile_requests, install_compile_monitor
    from splink_tpu.settings import complete_settings_dict

    install_compile_monitor()
    n_base = int(os.environ.get("SPLINK_TPU_BENCH_APPROX_ROWS", 50_000))
    rng = np.random.default_rng(0)
    base = _make_df(rng, n_base)
    # near-unique keys so the candidate space is dominated by real near-
    # duplicates; every twin corrupts BOTH blocking keys
    base["first_name"] = base["first_name"].astype(str) + (
        np.arange(n_base) % 1000
    ).astype(str)
    base["surname"] = base["surname"].astype(str) + (
        np.arange(n_base) % 997
    ).astype(str)
    twins = base.copy()
    twins["unique_id"] = twins["unique_id"] + n_base
    crng = np.random.default_rng(1)

    def corrupt(v):
        k = int(crng.integers(0, len(v)))
        return v[:k] + "#" + v[k + 1 :]

    twins["first_name"] = [corrupt(v) for v in twins["first_name"]]
    twins["surname"] = [corrupt(v) for v in twins["surname"]]
    import pandas as pd

    df = pd.concat([base, twins], ignore_index=True)
    budget = int(
        os.environ.get("SPLINK_TPU_BENCH_APPROX_BUDGET", 8 * n_base)
    )
    settings = complete_settings_dict(
        {
            **{k: v for k, v in SETTINGS.items()},
            "blocking_rules": [
                "l.first_name = r.first_name",
                "l.surname = r.surname",
            ],
            "approx_blocking": True,
            "approx_threshold": 0.2,
            "approx_pair_budget": budget,
        }
    )
    table = encode_table(df, settings)

    # exact tier over the same corpus (the recall baseline)
    exact_cfg = dict(settings)
    exact_cfg["approx_blocking"] = False
    t0 = time.perf_counter()
    exact_pairs = block_using_rules(exact_cfg, table)
    exact_s = time.perf_counter() - t0
    true = set(zip(range(n_base), range(n_base, 2 * n_base)))
    exact_set = set(zip(exact_pairs.idx_l.tolist(), exact_pairs.idx_r.tolist()))
    exact_recall = len(true & exact_set) / len(true)
    n_exact = exact_pairs.n_pairs
    del exact_pairs

    # approx tier: plan build (signatures + band joins) then candidate
    # generation + ranking. The warm pass runs with an effectively
    # unbounded budget so it ALSO measures unbudgeted recall (the
    # production-budget pass prunes its working set to O(budget) and so
    # only ever holds the top candidates); the timed pass runs the real
    # budget — pruning cost included, that is what production pays.
    t0 = time.perf_counter()
    plan = build_approx_plan(settings, table)
    plan_s = time.perf_counter() - t0
    assert plan is not None
    unb_cfg = dict(settings)
    unb_cfg["approx_pair_budget"] = 1 << 30
    ui, uj, _uc, _us, _ust = generate_approx_candidates(
        unb_cfg, table, plan=plan
    )  # warm + unbudgeted coverage
    recall_unbudgeted = len(true & set(zip(ui.tolist(), uj.tolist()))) / len(
        true
    )
    del ui, uj
    c0 = compile_requests()
    t0 = time.perf_counter()
    res = generate_approx_candidates(settings, table, plan=plan)
    approx_s = time.perf_counter() - t0
    c1 = compile_requests()
    ai, aj, _coll, _sim, stats = res
    # recall AT BUDGET: rank exactly as emission does
    import numpy as _np

    rank = _np.lexsort((aj, ai, -_coll, -_sim))[:budget]
    emitted = set(zip(ai[rank].tolist(), aj[rank].tolist()))
    recall_at_budget = len(true & emitted) / len(true)

    # end to end through block_using_rules (what a linker run pays)
    t0 = time.perf_counter()
    all_pairs = block_using_rules(settings, table)
    e2e_s = time.perf_counter() - t0
    n_approx_emitted = all_pairs.n_pairs - n_exact

    out = {
        "metric": "approx_blocking_pairs_per_sec",
        "value": round(stats["candidates"] / approx_s),
        "unit": "candidates/sec",
        "n_rows": 2 * n_base,
        "approx_candidates": stats["candidates"],
        "approx_survivors": stats["survivors"],
        "approx_emitted": n_approx_emitted,
        "approx_budget": budget,
        "approx_bands": stats["bands"],
        "approx_rows_per_band": stats["rows_per_band"],
        "approx_q": stats["q"],
        "recall_at_budget": round(recall_at_budget, 4),
        "recall_unbudgeted": round(recall_unbudgeted, 4),
        "exact_recall": round(exact_recall, 4),
        "exact_pairs": n_exact,
        "exact_pairs_per_sec": round(n_exact / exact_s) if exact_s else 0,
        "plan_seconds": round(plan_s, 3),
        "approx_seconds": round(approx_s, 3),
        "e2e_seconds": round(e2e_s, 3),
        "steady_state_recompiles": c1 - c0,
        "oversize_buckets_dropped": stats["oversize_buckets_dropped"],
        "device": str(jax.devices()[0]),
        **identity,
    }
    assert n_approx_emitted <= budget, (n_approx_emitted, budget)
    print(json.dumps(out))


def bench_tf():
    """Term-frequency benchmark (`python bench.py tf`, round 14): the two
    TF tiers of ISSUE 14 measured together.

    Serving half: ONE index built from a TF-flagged model serves two
    engines — the fused TF fold on (the new default) and off (the
    previous behaviour) — INTERLEAVED best-of-N open bursts over the
    same warmed shapes, so the shared-container drift hits both tiers
    alike; the compile counter gates zero steady-state compile requests
    with the fold on, and one query batch is parity-checked bit-exact
    against the offline ``tf_match_probability`` column.

    Blocking half: the round-11 typo corpus (every blocking key of every
    twin corrupted) at the SAME 8n pair budget, recall measured with and
    without ``approx_tf_weighting`` — the claim is recall-per-budget,
    anchored against round 11's 89.1%."""
    identity = _device_identity()
    import jax
    import pandas as pd

    from splink_tpu import Splink
    from splink_tpu.obs.metrics import (
        compile_requests,
        install_compile_monitor,
    )
    from splink_tpu.serve import LinkageService, QueryEngine

    install_compile_monitor()
    n_rows = int(os.environ.get("SPLINK_TPU_BENCH_TF_SERVE_ROWS", 200_000))
    n_queries = int(os.environ.get("SPLINK_TPU_BENCH_TF_QUERIES", 2000))
    repeats = int(os.environ.get("SPLINK_TPU_BENCH_TF_REPEATS", 5))
    rng = np.random.default_rng(0)
    df = _make_df(rng, n_rows)

    settings = dict(SETTINGS)
    settings["comparison_columns"] = [
        dict(c) for c in SETTINGS["comparison_columns"]
    ]
    for c in settings["comparison_columns"]:
        if c["col_name"] in ("first_name", "surname", "city"):
            c["term_frequency_adjustments"] = True
    settings["max_iterations"] = 5
    settings["serve_top_k"] = 5
    settings["serve_queue_depth"] = n_queries
    linker = Splink(settings, df=df)
    t0 = time.perf_counter()
    linker.estimate_parameters()
    train_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    index = linker.export_index()
    build_s = time.perf_counter() - t0
    assert index.tf_fold_columns(), "TF fold data missing from the index"

    engines = {}
    warm = {}
    t0 = time.perf_counter()
    for name, tf in (("tf_on", True), ("tf_off", False)):
        eng = QueryEngine(index, tf_adjust=tf)
        warm[name] = eng.warmup()
        engines[name] = eng
    warmup_s = time.perf_counter() - t0

    records = df.sample(
        n=min(n_queries, len(df)), replace=n_queries > len(df),
        random_state=0,
    ).to_dict(orient="records")
    while len(records) < n_queries:
        records.extend(records[: n_queries - len(records)])

    # parity gates on the measured build (the tf-smoke holds the full
    # serve<->offline gate): the fused TF program is bit-identical to the
    # unfused oracle, and the fold actually moves scores vs TF-off
    probe = df.iloc[:256].reset_index(drop=True)
    p_on, rows_on, valid_on, _ = engines["tf_on"].query_arrays(probe)
    oracle = QueryEngine(index, fused=False)
    oracle.warmup()
    p_or, rows_or, valid_or, _ = oracle.query_arrays(probe)
    assert np.array_equal(p_on, p_or) and np.array_equal(rows_on, rows_or)
    p_off_probe, _, valid_off, _ = engines["tf_off"].query_arrays(probe)
    tf_moved = int(np.sum(valid_on & valid_off & (p_on != p_off_probe)))
    # steady state starts HERE: warmup + parity probes are done
    c_warm = compile_requests()

    tiers = {
        name: LinkageService(eng, deadline_ms=2.0)
        for name, eng in engines.items()
    }
    best = {name: 0.0 for name in tiers}
    for rep in range(repeats):
        # alternate tier ORDER per repeat as well as interleaving: the
        # 2-core container's burst throughput drifts ~3x run to run, and
        # a fixed order systematically hands one tier the colder slot
        order = tuple(tiers) if rep % 2 == 0 else tuple(reversed(tiers))
        for name in order:
            svc = tiers[name]
            t0 = time.perf_counter()
            futs = [svc.submit(dict(r)) for r in records]
            for f in futs:
                f.result()
            best[name] = max(
                best[name], n_queries / (time.perf_counter() - t0)
            )
    for svc in tiers.values():
        svc.close()
    c_end = compile_requests()

    # ---- blocking half: the round-11 typo corpus at the 8n budget ----
    from splink_tpu.approx.lsh import (
        build_approx_plan,
        generate_approx_candidates,
    )
    from splink_tpu.data import encode_table
    from splink_tpu.settings import complete_settings_dict

    n_base = int(os.environ.get("SPLINK_TPU_BENCH_TF_APPROX_ROWS", 20_000))
    base = _make_df(np.random.default_rng(0), n_base)
    base["first_name"] = base["first_name"].astype(str) + (
        np.arange(n_base) % 1000
    ).astype(str)
    base["surname"] = base["surname"].astype(str) + (
        np.arange(n_base) % 997
    ).astype(str)
    twins = base.copy()
    twins["unique_id"] = twins["unique_id"] + n_base
    crng = np.random.default_rng(1)

    def corrupt(v):
        k = int(crng.integers(0, len(v)))
        return v[:k] + "#" + v[k + 1 :]

    twins["first_name"] = [corrupt(v) for v in twins["first_name"]]
    twins["surname"] = [corrupt(v) for v in twins["surname"]]
    corpus = pd.concat([base, twins], ignore_index=True)
    budget = 8 * n_base
    true = set(zip(range(n_base), range(n_base, 2 * n_base)))

    recalls = {}
    approx_secs = {}
    for key, weighting in (("tf", True), ("unweighted", False)):
        s = complete_settings_dict(
            {
                **{k: v for k, v in SETTINGS.items()},
                "blocking_rules": [
                    "l.first_name = r.first_name",
                    "l.surname = r.surname",
                ],
                "approx_blocking": True,
                "approx_threshold": 0.2,
                "approx_pair_budget": budget,
                "approx_tf_weighting": weighting,
            }
        )
        table = encode_table(corpus, s)
        t0 = time.perf_counter()
        plan = build_approx_plan(s, table)
        ai, aj, coll, sim, stats = generate_approx_candidates(
            s, table, plan=plan
        )
        approx_secs[key] = time.perf_counter() - t0
        rank = np.lexsort((aj, ai, -coll, -sim))[:budget]
        emitted = set(zip(ai[rank].tolist(), aj[rank].tolist()))
        recalls[key] = len(true & emitted) / len(true)

    qps_on, qps_off = best["tf_on"], best["tf_off"]
    print(json.dumps({
        "metric": "serve_tf_queries_per_sec",
        "value": round(qps_on, 1),
        "unit": "queries/sec",
        "n_reference_rows": n_rows,
        "n_queries": n_queries,
        "repeats": repeats,
        "train_seconds": round(train_s, 3),
        "index_build_seconds": round(build_s, 3),
        "warmup_seconds": round(warmup_s, 3),
        "warmup_compiles_tf_on": warm["tf_on"]["compiles"],
        "warmup_compiles_tf_off": warm["tf_off"]["compiles"],
        "qps_tf_on": round(qps_on, 1),
        "qps_tf_off": round(qps_off, 1),
        "tf_overhead_pct": round(100 * (1 - qps_on / qps_off), 2),
        "steady_state_compile_requests": c_end - c_warm,
        "tf_fold_columns": len(index.tf_fold_columns()),
        "tf_fused_unfused_parity": True,  # asserted above, bit-exact
        "tf_scores_moved_on_probe": tf_moved,
        "n_typo_rows": 2 * n_base,
        "approx_budget": budget,
        "recall_at_budget_tf": round(recalls["tf"], 4),
        "recall_at_budget_unweighted": round(recalls["unweighted"], 4),
        "recall_at_budget_r11_anchor": 0.891,
        "approx_seconds_tf": round(approx_secs["tf"], 3),
        "approx_seconds_unweighted": round(approx_secs["unweighted"], 3),
        "device": str(jax.devices()[0]),
        **identity,
    }))


def bench_drift():
    """Drift-sketch overhead benchmark (`python bench.py drift`): the
    quality observatory's serve-hot-path cost. Trains a model with
    ``quality_profile`` on (the training-reference profile rides the
    LinkageIndex), then pushes the SAME open-burst query traffic through
    two services over the shared warmed index — one engine sketching
    (device gamma/score histograms + drift windows + alert evaluation),
    one with the sketch off — INTERLEAVED round-robin best-of-N, the
    round-9 tracing-tier protocol: a single burst on a shared CPU
    container drifts run to run by more than the overhead being measured,
    and interleaving exposes both tiers to the same drift. Also gates the
    sketch-on steady state at ZERO compile requests and reports the
    profile-capture cost at build time and the clean-stream PSI ceiling
    the windows saw."""
    identity = _device_identity()
    import jax

    from splink_tpu import Splink
    from splink_tpu.obs.metrics import compile_requests, install_compile_monitor
    from splink_tpu.serve import LinkageService, QueryEngine

    install_compile_monitor()
    n_base = int(os.environ.get("SPLINK_TPU_BENCH_DRIFT_ROWS", 100_000))
    n_queries = int(os.environ.get("SPLINK_TPU_BENCH_DRIFT_QUERIES", 2000))
    repeats = int(os.environ.get("SPLINK_TPU_BENCH_DRIFT_REPEATS", 3))
    rng = np.random.default_rng(0)
    # base + one noisy duplicate each (the drift-smoke corpus shape): the
    # matched training population then carries variance in the city
    # channel, and a serve-time query stream drawn from the same corpus
    # is a draw from the training distribution — the clean-stream PSI the
    # windows report is shot noise + the residual top-k-truncation bias,
    # not a real population shift. A twin-less random corpus makes the
    # serve-time matched population (perfect self-matches) genuinely
    # different from training's coincidental matches and fires the alert
    # on a "clean" stream.
    import pandas as pd

    base = _make_df(rng, n_base)
    twins = base.copy()
    twins["unique_id"] = twins["unique_id"] + n_base
    flip = rng.random(n_base) < 0.3
    cities = np.array([f"city{k:03d}" for k in range(200)])
    twins.loc[flip, "city"] = cities[
        rng.integers(0, len(cities), int(flip.sum()))
    ]
    df = pd.concat([base, twins], ignore_index=True)
    n_rows = len(df)

    settings = dict(SETTINGS)
    settings["max_iterations"] = 5
    settings["serve_top_k"] = 5
    settings["serve_queue_depth"] = n_queries
    settings["quality_profile"] = True
    settings["drift_window_s"] = 2.0
    linker = Splink(settings, df=df)
    linker.estimate_parameters()

    # profile-capture cost: export the index with and without the profile
    # kernel (same trained params, same arrays otherwise)
    t0 = time.perf_counter()
    index = linker.export_index()
    build_profiled_s = time.perf_counter() - t0
    assert index.profile is not None
    bare = dict(settings)
    bare["quality_profile"] = False
    linker_bare = Splink(bare, df=df)
    linker_bare.params = linker.params  # same trained model
    t0 = time.perf_counter()
    index_bare = linker_bare.export_index()
    build_bare_s = time.perf_counter() - t0
    assert index_bare.profile is None
    del index_bare, linker_bare

    eng_on = QueryEngine(index)
    assert eng_on.sketch is not None
    eng_off = QueryEngine(index, sketch=False)
    assert eng_off.sketch is None
    t0 = time.perf_counter()
    warm_on = eng_on.warmup()
    warm_off = eng_off.warmup()
    warmup_s = time.perf_counter() - t0
    c_warm = compile_requests()

    records = df.sample(
        n=min(n_queries, len(df)), replace=n_queries > len(df),
        random_state=0,
    ).to_dict(orient="records")
    while len(records) < n_queries:
        records.extend(records[: n_queries - len(records)])

    tiers = {
        "sketch_on": LinkageService(eng_on, deadline_ms=2.0),
        "sketch_off": LinkageService(eng_off, deadline_ms=2.0),
    }
    best = {k: 0.0 for k in tiers}
    for _ in range(repeats):
        for key, tsvc in tiers.items():
            t0 = time.perf_counter()
            futs = [tsvc.submit(dict(r)) for r in records]
            for f in futs:
                f.result()
            best[key] = max(
                best[key], n_queries / (time.perf_counter() - t0)
            )
    for tsvc in tiers.values():
        tsvc.close()  # forces the final drift drain before the snapshot
    snap = tiers["sketch_on"].drift_snapshot()
    c_end = compile_requests()
    qps_on, qps_off = best["sketch_on"], best["sketch_off"]
    short = snap.get("short") or snap.get("long") or {}
    print(json.dumps({
        "metric": "drift_sketch_overhead_pct",
        "value": round(100 * (1 - qps_on / qps_off), 2),
        "unit": "percent",
        "n_reference_rows": n_rows,
        "n_queries": n_queries,
        "repeats": repeats,
        "qps_sketch_on": round(qps_on, 1),
        "qps_sketch_off": round(qps_off, 1),
        "profile_build_seconds": round(build_profiled_s, 3),
        "bare_build_seconds": round(build_bare_s, 3),
        "profile_capture_seconds": round(
            max(build_profiled_s - build_bare_s, 0.0), 3
        ),
        "warmup_seconds": round(warmup_s, 3),
        "warmup_combinations_on": warm_on["combinations"],
        "warmup_combinations_off": warm_off["combinations"],
        "steady_state_compiles": c_end - c_warm,
        "clean_max_psi": short.get("max_psi"),
        "drift_windows": snap.get("windows_observed") or 0,
        "alert_active": snap.get("alert_active"),
        "device": str(jax.devices()[0]),
        **identity,
    }))
    assert c_end - c_warm == 0, "sketching must not recompile steady state"


def bench_perf():
    """Performance-observatory overhead benchmark (`python bench.py
    perf`): the serve-time KernelWatch's hot-path cost. Pushes the SAME
    open-burst query traffic through two services over one shared warmed
    index — one with the kernel watch on (per-batch window bookkeeping +
    the PhaseProfile execute split), one with it off — INTERLEAVED
    best-of-N (the round-9/round-12 protocol: a shared 2-core container
    drifts run to run by more than the overhead being measured). Gates
    the watch-on steady state at ZERO compile requests, reports the
    post-warmup anchors/p95s the watch converged to, and times the
    layer-4 perf audit over the serve kernels (the CI half's cost)."""
    identity = _device_identity()
    import jax

    from splink_tpu import Splink
    from splink_tpu.analysis.perf_audit import run_perf_audit
    from splink_tpu.obs.metrics import compile_requests, install_compile_monitor
    from splink_tpu.serve import LinkageService, QueryEngine

    install_compile_monitor()
    n_base = int(os.environ.get("SPLINK_TPU_BENCH_PERF_ROWS", 200_000))
    n_queries = int(os.environ.get("SPLINK_TPU_BENCH_PERF_QUERIES", 2000))
    repeats = int(os.environ.get("SPLINK_TPU_BENCH_PERF_REPEATS", 5))
    rng = np.random.default_rng(0)
    df = _make_df(rng, n_base)

    settings = dict(SETTINGS)
    settings["max_iterations"] = 5
    settings["serve_top_k"] = 5
    settings["serve_queue_depth"] = n_queries
    # modest query buckets: the open burst then coalesces into dozens of
    # batches per round instead of two giant ones, so the watch's anchor
    # warmup (ANCHOR_SKIP + ANCHOR_SAMPLES batches) completes and the
    # measured shape matches real serving traffic
    settings["serve_query_buckets"] = [16, 64]
    linker = Splink(settings, df=df)
    linker.estimate_parameters()
    index = linker.export_index()

    engine = QueryEngine(index)
    t0 = time.perf_counter()
    warm = engine.warmup()
    warmup_s = time.perf_counter() - t0
    c_warm = compile_requests()

    records = df.sample(
        n=min(n_queries, len(df)), replace=n_queries > len(df),
        random_state=0,
    ).to_dict(orient="records")
    while len(records) < n_queries:
        records.extend(records[: n_queries - len(records)])

    tiers = {
        "watch_on": LinkageService(
            engine, deadline_ms=2.0, perf_alert_ratio=3.0, name="watch_on",
        ),
        "watch_off": LinkageService(
            engine, deadline_ms=2.0, perf_alert_ratio=0, name="watch_off",
        ),
    }
    best = {k: 0.0 for k in tiers}
    order = list(tiers.items())
    for rep in range(repeats):
        # alternate which tier runs first each repeat: the container's
        # slow drift then hits both orders equally (round-9 protocol)
        for key, tsvc in (order if rep % 2 == 0 else order[::-1]):
            t0 = time.perf_counter()
            futs = [tsvc.submit(dict(r)) for r in records]
            for f in futs:
                f.result()
            best[key] = max(
                best[key], n_queries / (time.perf_counter() - t0)
            )
    snap = tiers["watch_on"].perf_snapshot()
    for tsvc in tiers.values():
        tsvc.close()
    c_end = compile_requests()
    qps_on, qps_off = best["watch_on"], best["watch_off"]
    batch = (snap.get("phases") or {}).get("batch") or {}
    execute = (snap.get("phases") or {}).get("execute") or {}

    # the CI half's cost at bench scale: the layer-4 audit over the two
    # serving megakernels (measure + compare, committed-baseline path)
    t0 = time.perf_counter()
    audit_findings, audit_shapes = run_perf_audit(
        ["serve_score_fused", "serve_score_topk"]
    )
    audit_s = time.perf_counter() - t0

    print(json.dumps({
        "metric": "kernelwatch_overhead_pct",
        "value": round(100 * (1 - qps_on / qps_off), 2),
        "unit": "percent",
        "n_reference_rows": n_base,
        "n_queries": n_queries,
        "repeats": repeats,
        "qps_watch_on": round(qps_on, 1),
        "qps_watch_off": round(qps_off, 1),
        "warmup_seconds": round(warmup_s, 3),
        "warmup_combinations": warm["combinations"],
        "steady_state_compiles": c_end - c_warm,
        "batch_anchor_ms": batch.get("anchor_ms"),
        "batch_p95_ms": (batch.get("short") or {}).get("p95_ms"),
        "execute_anchor_ms": execute.get("anchor_ms"),
        "execute_p95_ms": (execute.get("short") or {}).get("p95_ms"),
        "alert_active": snap.get("alert_active"),
        "perf_audit_serve_shapes": audit_shapes,
        "perf_audit_serve_findings": len(audit_findings),
        "perf_audit_serve_seconds": round(audit_s, 1),
        "device": str(jax.devices()[0]),
        **identity,
    }))
    assert c_end - c_warm == 0, "the watch must not recompile steady state"
    assert not audit_findings, [f.format() for f in audit_findings]


def _proc_rss_mb(field: str = "VmRSS") -> float:
    """Current (VmRSS) or high-water (VmHWM) resident set, MB, procfs."""
    try:
        with open("/proc/self/status") as fh:
            for line in fh:
                if line.startswith(field):
                    return int(line.split()[1]) / 1024.0
    except OSError:
        pass
    return 0.0


def _scale_child(mode: str, n_rows: str, out_path: str) -> int:
    """One fresh-process build phase for `bench.py scale`: encode a
    deterministic WIDE corpus (6 x 32-byte string columns, so the packed
    reference matrix — the term the out-of-core build bounds — dominates
    every other O(n) allocation), train 1 cheap EM iteration, then build
    the serving index resident or out-of-core. Reports the BUILD phase's
    RETAINED RSS delta (VmRSS after the build minus VmRSS just before
    it, inputs released and gc'd — the resident build keeps the full
    packed matrix live, the out-of-core one O(chunk) plus droppable page
    cache), plus wall and the content fingerprint the parent asserts
    identical across modes."""
    import resource
    import tempfile

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import warnings

    import pandas as pd

    from splink_tpu import Splink

    warnings.filterwarnings("ignore")
    n = int(n_rows)
    rng = np.random.default_rng(7)
    cols = {f"f{k}": rng.integers(0, 50_000, n).astype(str) for k in range(6)}
    df = pd.DataFrame(
        {
            "unique_id": np.arange(n),
            # blocks of 20 rows: ~10 pairs/row trains EM while keeping the
            # serve-rule bucket dictionary (n/20 entries, built by BOTH
            # build modes) small next to the packed matrix — the term the
            # out-of-core path actually bounds
            "city": (np.arange(n) // 20).astype(str),
            **cols,
        }
    )
    settings = {
        "link_type": "dedupe_only",
        "blocking_rules": ["l.city = r.city"],
        "comparison_columns": [
            {"col_name": f"f{k}", "num_levels": 2,
             "comparison": {"kind": "exact"}, "max_string_length": 32}
            for k in range(6)
        ],
        "max_iterations": 1,
    }
    if mode == "ooc":
        settings["build_spill_dir"] = tempfile.mkdtemp(prefix="bench_scale_")
        settings["build_spill_chunk_rows"] = 16384
        settings["emit_shard_chunks"] = 4
    import gc

    linker = Splink(settings, df=df)
    linker.estimate_parameters()
    linker.release_input()  # billions-row posture: encoded table only
    del df, cols
    gc.collect()
    # RETAINED footprint delta across the build: encode/EM transients have
    # already peaked and been collected, so VmRSS-after minus VmRSS-before
    # isolates what the BUILD leaves resident — the full packed matrix on
    # the resident path, O(chunk) + droppable page cache out of core
    rss_before = _proc_rss_mb("VmRSS")
    t0 = time.perf_counter()
    index = linker.export_index()
    fp = index.content_fingerprint()
    build_wall = time.perf_counter() - t0
    gc.collect()
    rss_after = _proc_rss_mb("VmRSS")
    peak_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    with open(out_path, "w") as fh:
        json.dump(
            {
                "mode": mode,
                "n_rows": n,
                "n_lanes": int(index.n_lanes),
                "build_wall_s": round(build_wall, 3),
                "build_rss_delta_mb": round(max(rss_after - rss_before, 0), 1),
                "peak_rss_mb": round(peak_kb / 1024.0, 1),
                "fingerprint": fp,
            },
            fh,
        )
    return 0


def bench_wire():
    """Wire-tier benchmark (`python bench.py wire`, round 16): the cost
    of putting the serving tier behind the multi-host RPC protocol.

    ONE trained index serves two tiers over the SAME warmed engine —
    ``local`` submits straight into the LinkageService, ``remote`` routes
    every query through a loopback WireServer + RemoteReplica (frame
    encode → TCP → dispatch → frame decode, the full multi-host path
    minus the physical network). The tiers run INTERLEAVED best-of-N
    open bursts (shared-container drift hits both alike); the headline
    is the remote/local throughput ratio plus the closed-loop RTT the
    wire adds per request. Gates: one query batch parity-checked
    bit-identical across the wire, and ZERO steady-state compile
    requests in either tier (frames never touch the compile cache)."""
    identity = _device_identity()
    import jax

    from splink_tpu.obs.metrics import (
        compile_requests,
        install_compile_monitor,
    )
    from splink_tpu import Splink
    from splink_tpu.serve import (
        LinkageService,
        QueryEngine,
        RemoteReplica,
        WireServer,
    )

    install_compile_monitor()
    n_rows = int(os.environ.get("SPLINK_TPU_BENCH_WIRE_ROWS", 200_000))
    n_queries = int(os.environ.get("SPLINK_TPU_BENCH_WIRE_QUERIES", 2000))
    repeats = int(os.environ.get("SPLINK_TPU_BENCH_WIRE_REPEATS", 5))
    rng = np.random.default_rng(0)
    df = _make_df(rng, n_rows)

    settings = dict(SETTINGS)
    settings["max_iterations"] = 5
    settings["serve_top_k"] = 5
    settings["serve_queue_depth"] = n_queries
    linker = Splink(settings, df=df)
    t0 = time.perf_counter()
    linker.estimate_parameters()
    train_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    index = linker.export_index()
    build_s = time.perf_counter() - t0

    engine = QueryEngine(index)
    t0 = time.perf_counter()
    warm = engine.warmup()
    warmup_s = time.perf_counter() - t0

    records = df.sample(
        n=min(n_queries, len(df)), replace=n_queries > len(df),
        random_state=0,
    ).to_dict(orient="records")
    while len(records) < n_queries:
        records.extend(records[: n_queries - len(records)])

    svc = LinkageService(engine, deadline_ms=None)
    server = WireServer(svc).start()
    remote = RemoteReplica(
        ("127.0.0.1", server.port),
        pool_size=2,
        request_timeout_ms=120_000.0,
    )

    # parity gate: one probe batch across the wire, bit-identical
    probe = records[:64]
    local_res = [svc.query(dict(r), timeout=120) for r in probe]
    remote_res = [
        f.result(timeout=120)
        for f in [remote.submit(dict(r)) for r in probe]
    ]
    mismatches = 0
    for lo, re in zip(local_res, remote_res):
        assert not lo.shed and not re.shed, (lo.reason, re.reason)
        if len(lo.matches) != len(re.matches) or any(
            str(lu) != str(ru) or lp != rp
            for (lu, lp), (ru, rp) in zip(lo.matches, re.matches)
        ):
            mismatches += 1
    assert mismatches == 0, f"wire parity: {mismatches} mismatched queries"

    # closed loop: the per-request RTT each tier adds, one in flight
    def closed_loop(fn, n=100):
        lats = []
        for r in records[:n]:
            t0 = time.perf_counter()
            fn(dict(r))
            lats.append((time.perf_counter() - t0) * 1000.0)
        return np.percentile(np.asarray(lats), [50, 99])

    seq_local = closed_loop(lambda r: svc.query(r, timeout=120))
    seq_remote = closed_loop(
        lambda r: remote.submit(r).result(timeout=120)
    )

    # steady state starts HERE: warmup + parity + closed loops done
    c_warm = compile_requests()
    tiers_fn = {
        "local": lambda r: svc.submit(r),
        "remote": lambda r: remote.submit(r),
    }
    best = {name: 0.0 for name in tiers_fn}
    for rep in range(repeats):
        order = (
            tuple(tiers_fn) if rep % 2 == 0 else tuple(reversed(tiers_fn))
        )
        for name in order:
            submit = tiers_fn[name]
            t0 = time.perf_counter()
            futs = [submit(dict(r)) for r in records]
            for f in futs:
                res = f.result(timeout=600)
                assert not res.shed, (name, res.reason)
            best[name] = max(
                best[name], n_queries / (time.perf_counter() - t0)
            )
    c_end = compile_requests()
    link = remote.latency_summary()
    remote.close()
    server.close()
    svc.close()

    qps_local, qps_remote = best["local"], best["remote"]
    print(json.dumps({
        "metric": "wire_remote_queries_per_sec",
        "value": round(qps_remote, 1),
        "unit": "queries/sec",
        "local_queries_per_sec": round(qps_local, 1),
        "remote_over_local": round(qps_remote / qps_local, 3),
        "closed_loop_local_ms": {
            "p50": round(float(seq_local[0]), 3),
            "p99": round(float(seq_local[1]), 3),
        },
        "closed_loop_remote_ms": {
            "p50": round(float(seq_remote[0]), 3),
            "p99": round(float(seq_remote[1]), 3),
        },
        "wire_rtt_added_p50_ms": round(
            float(seq_remote[0] - seq_local[0]), 3
        ),
        "parity_queries_checked": len(probe),
        "parity_mismatches": mismatches,
        "reconnects": link.get("reconnects", 0),
        "n_reference_rows": n_rows,
        "n_queries": n_queries,
        "repeats": repeats,
        "train_seconds": round(train_s, 3),
        "index_build_seconds": round(build_s, 3),
        "warmup_seconds": round(warmup_s, 3),
        "warmup_combinations": warm["combinations"],
        "steady_state_compiles": c_end - c_warm,
        "device": str(jax.devices()[0]),
        **identity,
    }))
    assert c_end - c_warm == 0, (
        f"wire bench steady state performed {c_end - c_warm} recompiles"
    )


def bench_fleet():
    """Fleet observability benchmark (`python bench.py fleet`, round 17):
    what the stitched cross-host observability plane costs.

    ONE trained index behind ONE loopback WireServer serves two tracing
    routers over separate RemoteReplica links — ``stitched`` (wire v2
    span piggyback + clock-offset graft, the default) and ``flat``
    (``fleet_stitching`` off: same tracing, same wire, no graft). The
    tiers run INTERLEAVED best-of-N open bursts; the headline is the
    stitched throughput plus the flat/stitched ratio (the price of the
    waterfall). Alongside: the per-hop decomposition of the loopback
    wire overhead (serialize / network / server_queue / server_execute /
    deserialize, from the stitched link's KernelWatch), and the cost of
    one federation scrape + /metrics render over the live remotes.
    Gates: every stitched burst query closes with a grafted remote span,
    and ZERO steady-state compile requests — the observability plane
    never touches the compile cache."""
    identity = _device_identity()
    import jax

    from splink_tpu.obs.events import register_ambient, unregister_ambient
    from splink_tpu.obs.exposition import render_samples
    from splink_tpu.obs.fleet import FleetAggregator
    from splink_tpu.obs.metrics import (
        compile_requests,
        install_compile_monitor,
    )
    from splink_tpu import Splink
    from splink_tpu.serve import (
        LinkageService,
        QueryEngine,
        RemoteReplica,
        ReplicaRouter,
        WireServer,
    )

    install_compile_monitor()
    n_rows = int(os.environ.get("SPLINK_TPU_BENCH_FLEET_ROWS", 200_000))
    n_queries = int(os.environ.get("SPLINK_TPU_BENCH_FLEET_QUERIES", 2000))
    repeats = int(os.environ.get("SPLINK_TPU_BENCH_FLEET_REPEATS", 5))
    n_scrapes = int(os.environ.get("SPLINK_TPU_BENCH_FLEET_SCRAPES", 200))
    rng = np.random.default_rng(0)
    df = _make_df(rng, n_rows)

    settings = dict(SETTINGS)
    settings["max_iterations"] = 5
    settings["serve_top_k"] = 5
    settings["serve_queue_depth"] = n_queries
    linker = Splink(settings, df=df)
    t0 = time.perf_counter()
    linker.estimate_parameters()
    train_s = time.perf_counter() - t0
    index = linker.export_index()

    engine = QueryEngine(index)
    t0 = time.perf_counter()
    warm = engine.warmup()
    warmup_s = time.perf_counter() - t0

    records = df.sample(
        n=min(n_queries, len(df)), replace=n_queries > len(df),
        random_state=0,
    ).to_dict(orient="records")
    while len(records) < n_queries:
        records.extend(records[: n_queries - len(records)])

    svc = LinkageService(engine, deadline_ms=None, name="fleet-host")
    server = WireServer(svc, name="fleet-host").start()
    rep_on = RemoteReplica(
        ("127.0.0.1", server.port), pool_size=2,
        request_timeout_ms=120_000.0,
    )
    rep_off = RemoteReplica(
        ("127.0.0.1", server.port), pool_size=2,
        request_timeout_ms=120_000.0,
        settings={"fleet_stitching": False},
    )
    router_on = ReplicaRouter([rep_on], hedge_ms=0, trace_sample_rate=1.0)
    router_off = ReplicaRouter([rep_off], hedge_ms=0, trace_sample_rate=1.0)

    class _StitchCount:
        def __init__(self):
            self.stitched = 0
            self.flat = 0

        def emit(self, type, **fields):
            if type != "request_trace":
                return
            if isinstance(fields.get("remote_span"), dict):
                self.stitched += 1
            else:
                self.flat += 1

    counter = _StitchCount()
    register_ambient(counter)

    # warm both links (connection pools, anchor samples) off the clock
    for r in records[:64]:
        router_on.submit(dict(r)).result(timeout=120)
        router_off.submit(dict(r)).result(timeout=120)

    # steady state starts HERE
    c_warm = compile_requests()
    tiers_fn = {
        "stitched": router_on,
        "flat": router_off,
    }
    best = {name: 0.0 for name in tiers_fn}
    for rep in range(repeats):
        order = (
            tuple(tiers_fn) if rep % 2 == 0 else tuple(reversed(tiers_fn))
        )
        for name in order:
            target = tiers_fn[name]
            t0 = time.perf_counter()
            futs = [target.submit(dict(r)) for r in records]
            for f in futs:
                res = f.result(timeout=600)
                assert not res.shed, (name, res.reason)
            best[name] = max(
                best[name], n_queries / (time.perf_counter() - t0)
            )
    c_end = compile_requests()

    # per-hop attribution of the loopback wire overhead (stitched link)
    hops = {}
    for hop, st in sorted(rep_on.wire_phases().items()):
        short = st.get("short") or {}
        hops[hop] = {
            "p50_ms": round(float(short.get("p50_ms", 0.0) or 0.0), 4),
            "p95_ms": round(float(short.get("p95_ms", 0.0) or 0.0), 4),
            "observations": int(st.get("observations", 0)),
        }
    link = rep_on.latency_summary()

    # federation scrape + /metrics render cost over the live remotes
    agg = FleetAggregator(
        local=None, remotes=[rep_on, rep_off], min_scrape_interval_s=0.0
    )
    scrape_ms = []
    for _ in range(n_scrapes):
        t0 = time.perf_counter()
        merged = agg.scrape(force=True)
        scrape_ms.append((time.perf_counter() - t0) * 1000.0)
        assert merged is not None
    t0 = time.perf_counter()
    metrics_text = render_samples(agg.prometheus_samples())
    render_ms = (time.perf_counter() - t0) * 1000.0
    scrape_pcts = np.percentile(np.asarray(scrape_ms), [50, 95])

    unregister_ambient(counter)
    for closer in (rep_on, rep_off, router_on, router_off):
        closer.close()
    server.close()
    svc.close()

    qps_on, qps_off = best["stitched"], best["flat"]
    burst_total = n_queries * repeats
    print(json.dumps({
        "metric": "fleet_stitched_queries_per_sec",
        "value": round(qps_on, 1),
        "unit": "queries/sec",
        "flat_queries_per_sec": round(qps_off, 1),
        "stitched_over_flat": round(qps_on / qps_off, 3),
        "stitched_traces_delivered": counter.stitched,
        "flat_traces_delivered": counter.flat,
        "wire_hop_ms": hops,
        "server_share_p50_ms": round(
            float(link.get("server", {}).get("p50_ms", 0.0)), 3
        ),
        "network_share_p50_ms": round(
            float(link.get("network", {}).get("p50_ms", 0.0)), 3
        ),
        "federation_scrape_p50_ms": round(float(scrape_pcts[0]), 3),
        "federation_scrape_p95_ms": round(float(scrape_pcts[1]), 3),
        "metrics_render_ms": round(render_ms, 3),
        "metrics_bytes": len(metrics_text.encode("utf-8")),
        "n_reference_rows": n_rows,
        "n_queries": n_queries,
        "repeats": repeats,
        "n_scrapes": n_scrapes,
        "train_seconds": round(train_s, 3),
        "warmup_seconds": round(warmup_s, 3),
        "warmup_combinations": warm["combinations"],
        "steady_state_compiles": c_end - c_warm,
        "device": str(jax.devices()[0]),
        **identity,
    }))
    assert counter.stitched >= burst_total, (
        f"only {counter.stitched}/{burst_total} stitched traces delivered"
    )
    assert c_end - c_warm == 0, (
        f"fleet bench steady state performed {c_end - c_warm} recompiles"
    )


def bench_scale():
    """Offline-scale benchmark (`python bench.py scale`, BENCHMARKS.md
    round 15): (a) resident vs out-of-core index build — wall and
    per-process peak RSS at 3 corpus sizes (fresh subprocess per phase so
    ru_maxrss isolates each build), fingerprints asserted identical;
    (b) sharded vs single-shard spill emission pairs/s on the virtual
    8-device mesh (the multi-host write-path shape, CPU tier)."""
    import subprocess
    import tempfile
    import warnings

    from splink_tpu.blocking_device import (
        build_device_plan,
        emit_pairs_sharded,
    )
    from splink_tpu.data import encode_table
    from splink_tpu.obs.metrics import compile_requests, install_compile_monitor
    from splink_tpu.parallel.mesh import make_mesh
    from splink_tpu.settings import complete_settings_dict
    from splink_tpu.spill import PairSpillStore

    warnings.filterwarnings("ignore")
    install_compile_monitor()
    sizes = [
        int(v)
        for v in os.environ.get(
            "SPLINK_TPU_BENCH_SCALE_ROWS", "100000,400000,800000"
        ).split(",")
    ]
    tmp = tempfile.mkdtemp(prefix="bench_scale_parent_")
    sweep = []
    for n in sizes:
        row = {"n_rows": n}
        for mode in ("resident", "ooc"):
            out = os.path.join(tmp, f"{mode}_{n}.json")
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "scale-child", mode, str(n), out],
                capture_output=True, text=True, timeout=1800,
                env={**os.environ, "JAX_PLATFORMS": "cpu"},
            )
            if proc.returncode != 0:
                print(proc.stderr[-2000:], file=sys.stderr)
                sys.exit(2)
            child = json.load(open(out))
            row[f"{mode}_build_wall_s"] = child["build_wall_s"]
            row[f"{mode}_build_rss_delta_mb"] = child["build_rss_delta_mb"]
            row[f"{mode}_peak_rss_mb"] = child["peak_rss_mb"]
            row[f"{mode}_fingerprint"] = child["fingerprint"]
        assert row["resident_fingerprint"] == row["ooc_fingerprint"], (
            f"fingerprint divergence at n={n}"
        )
        row["fingerprint_identical"] = True
        del row["resident_fingerprint"], row["ooc_fingerprint"]
        sweep.append(row)
        print(json.dumps({"phase": "build_sweep", **row}), flush=True)

    # ---- sharded vs single-shard emission throughput (virtual mesh) ----
    # (every child has exited: the parent may take the device now)
    identity = _device_identity()
    n_emit = int(os.environ.get("SPLINK_TPU_BENCH_SCALE_EMIT_ROWS", 200_000))
    rng = np.random.default_rng(3)
    import pandas as pd

    df = pd.DataFrame(
        {
            "unique_id": np.arange(n_emit),
            "first_name": rng.integers(0, 50, n_emit).astype(str),
            "surname": rng.integers(0, 40, n_emit).astype(str),
            "block": (np.arange(n_emit) % (n_emit // 400)).astype(str),
        }
    )
    s = complete_settings_dict(
        {
            "link_type": "dedupe_only",
            "comparison_columns": [
                {"col_name": "first_name"},
                {"col_name": "surname"},
            ],
            "blocking_rules": [
                "l.block = r.block",
                "l.block = r.block and l.surname = r.surname",
            ],
        }
    )
    table = encode_table(df, s)
    plan = build_device_plan(s, table)
    mesh = make_mesh(8)
    emit = {}
    for label, shards, m in (
        ("single_shard", 1, None),
        ("sharded_mesh8", 8, mesh),
    ):
        # warmup drive (compile), then the timed drive
        for rep in ("warm", "timed"):
            store = PairSpillStore.attach(
                os.path.join(tmp, f"emit_{label}_{rep}"), np.int32, {}
            )
            c0 = compile_requests()
            t0 = time.perf_counter()
            with store:
                stats = emit_pairs_sharded(
                    plan, store, 1 << 20, n_shards=shards, mesh=m
                )
            store.finalize()
            wall = time.perf_counter() - t0
            if rep == "timed":
                emit[label] = {
                    "pairs": stats["pairs"],
                    "segments": stats["segments"],
                    "wall_s": round(wall, 3),
                    "pairs_per_sec": round(stats["pairs"] / max(wall, 1e-9)),
                    "steady_state_compile_requests": compile_requests() - c0,
                }
        print(json.dumps({"phase": f"emit_{label}", **emit[label]}), flush=True)

    print(json.dumps({
        "metric": "ooc_build_rss_delta_mb_at_max_corpus",
        "value": sweep[-1]["ooc_build_rss_delta_mb"],
        "unit": "MB",
        "build_sweep": sweep,
        "emission": emit,
        "build_rss_growth_resident": round(
            (sweep[-1]["resident_build_rss_delta_mb"] or 0.1)
            / max(sweep[0]["resident_build_rss_delta_mb"], 0.1), 2
        ),
        "build_rss_growth_ooc": round(
            (sweep[-1]["ooc_build_rss_delta_mb"] or 0.1)
            / max(sweep[0]["ooc_build_rss_delta_mb"], 0.1), 2
        ),
        **identity,
    }))


def main():
    identity = _device_identity()
    import jax
    import jax.numpy as jnp

    # bench.py never builds a Splink facade, so it enables the persistent
    # compile cache itself — through the same function, to the same place
    from splink_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()

    from splink_tpu.data import encode_table
    from splink_tpu.em import run_em, run_em_checkpointed
    from splink_tpu.gammas import GammaProgram
    from splink_tpu.models.fellegi_sunter import FSParams, match_probability
    from splink_tpu.settings import complete_settings_dict

    # Telemetry record of the bench run (splink_tpu/obs): stage spans with
    # the compile-vs-execute split, plus a JSONL artifact the summarize CLI
    # renders. The compile monitor also feeds the BENCH json's
    # compile_seconds/jit_compiles keys (BENCHMARKS.md). Never fatal.
    from splink_tpu.obs.metrics import compile_totals, install_compile_monitor

    install_compile_monitor()
    obs = None
    tel_dir = os.environ.get("SPLINK_TPU_BENCH_TELEMETRY_DIR", "bench_telemetry")
    if tel_dir:
        try:
            from splink_tpu.obs.runtime import RunContext

            obs = RunContext.from_settings({"telemetry_dir": tel_dir})
            if not obs.enabled:
                obs = None
        except Exception as e:  # noqa: BLE001 - telemetry must not kill bench
            print(f"bench: telemetry disabled ({e})", file=sys.stderr)
            obs = None

    from contextlib import nullcontext

    def span(name):
        return obs.span(name) if obs is not None else nullcontext()

    rng = np.random.default_rng(0)
    settings = complete_settings_dict(dict(SETTINGS))

    df = _make_df(rng, N_ROWS)
    t_enc = time.perf_counter()
    with span("encode"):
        table = encode_table(df, settings)
    encode_time = time.perf_counter() - t_enc
    prog = GammaProgram(settings, table)

    n_cols, max_levels = 4, 3
    m = np.array([[0.05, 0.15, 0.8], [0.1, 0.2, 0.7], [0.1, 0.9, 0.0], [0.2, 0.8, 0.0]])
    u = np.array([[0.85, 0.1, 0.05], [0.8, 0.15, 0.05], [0.9, 0.1, 0.0], [0.7, 0.3, 0.0]])
    params = FSParams(
        lam=jnp.asarray(0.2, jnp.float32),
        m=jnp.asarray(m, jnp.float32),
        u=jnp.asarray(u, jnp.float32),
    )

    @jax.jit
    def score_batch(idx_l, idx_r, params):
        """packed row gathers -> comparison kernels -> gammas -> FS score.
        Also returns the batch's probability sum: the scalar the timing
        barrier fetches (an eager .sum() outside jit would be one more
        blocking dispatch per batch)."""
        G = prog._gamma_batch(idx_l, idx_r)
        p = match_probability(G, params)
        return G, p, p.sum()

    # pair batches (simulating blocked-pair index streams); one extra
    # batch reserved for warmup so no timed (executable, input-buffers)
    # pair has executed before
    idx_l = rng.integers(0, N_ROWS, N_PAIRS + BATCH).astype(np.int32)
    idx_r = rng.integers(0, N_ROWS, N_PAIRS + BATCH).astype(np.int32)
    batches = [
        (jnp.asarray(idx_l[s : s + BATCH]), jnp.asarray(idx_r[s : s + BATCH]))
        for s in range(0, N_PAIRS, BATCH)
    ]
    warm_batch = (jnp.asarray(idx_l[N_PAIRS:]), jnp.asarray(idx_r[N_PAIRS:]))

    # the clock closes on a VALUE read back: reduce every batch's
    # probabilities to a scalar on device, combine, and float() it
    # (chip_smoke.py's barrier leg measures that block_until_ready blocks
    # on this machine too; ROADMAP A0 picks the barrier for the cell runner)
    psum_fn = jax.jit(lambda *xs: sum(x.sum() for x in xs))

    # warmup / compile (score_batch AND the psum combiner — an unwarmed
    # combiner would charge its trace+compile to the timed window)
    G0, p0, s0 = score_batch(*warm_batch, params)
    float(s0)
    float(psum_fn(*([s0] * len(batches))))

    # First measured batch alone, value-fetch barrier: a headline lands
    # within seconds of compile finishing. The driver records the stdout
    # TAIL, so if the run dies midway this partial line is still the
    # recorded result; the full-run line below overwrites it on success.
    t0 = time.perf_counter()
    G1, p1, s1 = score_batch(*batches[0], params)
    float(s1)
    first_batch_time = time.perf_counter() - t0
    first_rate = BATCH / first_batch_time
    print(
        json.dumps(
            {
                "metric": "scored_record_pairs_per_sec_per_chip",
                "value": round(first_rate),
                "unit": "pairs/sec",
                "vs_baseline": round(first_rate / TARGET_PAIRS_PER_SEC_PER_CHIP, 3),
                "partial": "first measured batch only",
                "n_pairs": BATCH,
                **identity,
            }
        ),
        flush=True,
    )

    t0 = time.perf_counter()
    Gs = [G1]
    psums = [s1]
    with span("score"):
        for bl, br in batches[1:]:
            G, p, s = score_batch(bl, br, params)
            Gs.append(G)
            psums.append(s)
        float(psum_fn(*psums))
    score_time = first_batch_time + (time.perf_counter() - t0)
    pairs_per_sec = N_PAIRS / score_time

    # EM convergence on the full gamma matrix (kept in HBM)
    G_all = jnp.concatenate(Gs)
    init = FSParams(
        lam=jnp.asarray(0.5, jnp.float32),
        m=jnp.asarray(np.tile([0.3, 0.3, 0.4], (n_cols, 1)), jnp.float32),
        u=jnp.asarray(np.tile([0.4, 0.3, 0.3], (n_cols, 1)), jnp.float32),
    )
    res = run_em(G_all, init, max_iterations=25, max_levels=max_levels,
                 em_convergence=1e-4)
    float(res.params.lam)  # value fetch = real barrier
    t1 = time.perf_counter()
    with span("em"):
        res = run_em(G_all, init, max_iterations=25, max_levels=max_levels,
                     em_convergence=1e-4)
        float(res.params.lam)  # value fetch = real barrier
    em_time = time.perf_counter() - t1

    # Checkpointed EM capture (splink_tpu/resilience): the in-loop host
    # hook reaches the host at every K-iteration boundary, so a run that
    # dies mid-EM leaves the last boundary's partial line in the stdout
    # tail the driver records — and a resumable on-disk checkpoint when
    # SPLINK_TPU_BENCH_CKPT_DIR is set. Bit-identical trajectory to
    # run_em; overhead is reported against em_seconds.
    ckpt_dir = os.environ.get("SPLINK_TPU_BENCH_CKPT_DIR") or None

    def _segment_progress(done, hist, seg_converged):
        print(
            json.dumps(
                {
                    "metric": "em_checkpoint_progress",
                    "iteration": done,
                    "lam": float(hist["lam"][done]),
                    "converged": bool(seg_converged),
                }
            ),
            flush=True,
        )

    # warm the hooked program (host_hook=True compiles separately from
    # the plain-run program timed above)
    float(
        run_em_checkpointed(
            G_all, init, max_iterations=25, max_levels=max_levels,
            em_convergence=1e-4, on_segment=lambda *_: None,
        ).params.lam
    )
    t2 = time.perf_counter()
    res_ck = run_em_checkpointed(
        G_all, init, max_iterations=25, max_levels=max_levels,
        em_convergence=1e-4, checkpoint_dir=ckpt_dir, checkpoint_every=5,
        on_segment=_segment_progress,
    )
    em_ckpt_time = time.perf_counter() - t2

    extras = _bench_virtual_pipeline(settings, table, prog)
    extras.update(_bench_virtual_qgram(df))

    # compile-vs-execute split: process-wide jit totals from the compile
    # monitor. Timed phases above run AFTER their warmup, so their wall is
    # execute-only; compile_seconds is the cold-start cost a persistent
    # compilation cache amortises away (BENCHMARKS.md).
    n_compiles, compile_seconds = compile_totals()
    extras["jit_compiles"] = n_compiles
    extras["compile_seconds"] = round(compile_seconds, 3)
    extras["execute_seconds"] = round(score_time + em_time, 3)
    if obs is not None:
        obs.finish()
        extras["telemetry_jsonl"] = obs.sink.path

    print(json.dumps({
        "metric": "scored_record_pairs_per_sec_per_chip",
        "value": round(pairs_per_sec),
        "unit": "pairs/sec",
        "vs_baseline": round(pairs_per_sec / TARGET_PAIRS_PER_SEC_PER_CHIP, 3),
        "n_pairs": N_PAIRS,
        "score_seconds": round(score_time, 3),
        "em_seconds": round(em_time, 3),
        "em_updates": int(res.n_updates),
        "em_ckpt_seconds": round(em_ckpt_time, 3),
        "em_ckpt_updates": int(res_ck.n_updates),
        "em_ckpt_overhead_pct": round(100 * (em_ckpt_time - em_time) / em_time, 1),
        "encode_seconds": round(encode_time, 3),
        "device": str(jax.devices()[0]),
        **identity,
        **extras,
    }))


if __name__ == "__main__":
    if "coldstart-child" in sys.argv[1:]:
        i = sys.argv.index("coldstart-child")
        sys.exit(_coldstart_child(sys.argv[i + 1], sys.argv[i + 2]))
    elif "coldstart" in sys.argv[1:]:
        bench_coldstart()
    elif "serve" in sys.argv[1:]:
        bench_serve()
    elif "blocking" in sys.argv[1:]:
        bench_blocking()
    elif "approx" in sys.argv[1:]:
        bench_approx()
    elif "drift" in sys.argv[1:]:
        bench_drift()
    elif "tf" in sys.argv[1:]:
        bench_tf()
    elif "perf" in sys.argv[1:]:
        bench_perf()
    elif "scale-child" in sys.argv[1:]:
        i = sys.argv.index("scale-child")
        sys.exit(_scale_child(sys.argv[i + 1], sys.argv[i + 2], sys.argv[i + 3]))
    elif "wire" in sys.argv[1:]:
        bench_wire()
    elif "fleet" in sys.argv[1:]:
        bench_fleet()
    elif "scale" in sys.argv[1:]:
        bench_scale()
    else:
        main()
