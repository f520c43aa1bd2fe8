"""Chip smoke: the offline and the serve path, once, on the TPU.

    python chip_smoke.py            # on a machine with a TPU; exit 0 = it runs

The quickest proof that the system still starts on the chip: one process
drives BASELINE config 4 (six comparison columns, three blocking rules,
people from chipbench/datagen.py at a seed) through the entry points a user
calls — ``Splink(...).get_scored_comparisons()``, ``export_index`` ->
``load_index`` -> ``QueryEngine.warmup()`` -> ``LinkageService.submit()`` —
and checks what comes out against a pandas join, against a second linker on
the virtual pair index, and against the offline frame. Any failed check or
exception in any leg is a non-zero exit. Without a TPU it exits 2 and
prints no result.

This is a SMOKE, not a benchmark: the seconds it prints are set-up facts
(cold compile per leg, cache hits on a second run), not metrics. The line
before last, ``summary: {...}``, carries them per leg and ends with
``"claim": null``. The last line of standard output is the verdict and
nothing else: ``{"ok": true, "device": {"platform": "tpu", "kind": "...",
"count": N}}``, the device as jax reports it.

``--rehearse-on-cpu`` runs the same legs on the CPU backend at a small size
(Pallas kernels interpreted, device blocking forced on) to debug the script
without a chip. Every line it prints is labelled a rehearsal and it prints
no verdict line.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import tempfile
import time
import warnings

import numpy as np

ROWS = 390_000  # > AUTO_MIN_PAIRS candidate pairs
REHEARSAL_ROWS = 12_000
SEED = 4
SERVE_REQUESTS = 300

REHEARSAL = False
_T0 = time.perf_counter()


def say(msg: str) -> None:
    tag = "REHEARSAL(cpu) " if REHEARSAL else ""
    print(f"{tag}[{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


class Leg:
    """Times one leg: wall seconds split into seconds inside the backend
    compiler (persistent-cache reads included; set-up) and the rest (run:
    host work, tracing, device time), plus real compiles and cache hits."""

    results: dict = {}
    backend_compile_s = 0.0

    @classmethod
    def install(cls) -> None:
        import jax

        def on_duration(name: str, secs: float, **_kw) -> None:
            if name.endswith("backend_compile_duration"):
                cls.backend_compile_s += secs

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def __init__(self, name: str):
        self.name = name

    def _counters(self):
        from splink_tpu.obs.metrics import compile_stats

        return compile_stats(), Leg.backend_compile_s, time.perf_counter()

    def __enter__(self):
        say(f"--- leg {self.name}")
        self.start = self._counters()
        self.facts: dict = {}
        return self.facts

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        (c0, b0, t0), (c1, b1, t1) = self.start, self._counters()
        Leg.results[self.name] = {
            "setup_compile_s": round(b1 - b0, 2),
            "run_s": round((t1 - t0) - (b1 - b0), 2),
            "compiles": c1["compiles"] - c0["compiles"],
            "cache_hits": c1["cache_hits"] - c0["cache_hits"],
            **self.facts,
        }
        say(f"leg {self.name} passed: {json.dumps(Leg.results[self.name])}")
        return False


# ---------------------------------------------------------------------------
# Model: BASELINE config 4 as the benchmark defines it
# (chipbench/configs/baseline_c4.json "settings"), nothing dropped
# ---------------------------------------------------------------------------

JW_COLUMNS = ["first_name", "surname", "postcode"]
CONFIG_4 = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "chipbench", "configs", "baseline_c4.json")
# what that file lists under "assumed" for its cell's size: the smoke's legs
# choose the pair index and the batch themselves
CELL_KEYS = ("device_pair_generation", "max_resident_pairs", "pair_batch_size")


def smoke_settings(**extra) -> dict:
    with open(CONFIG_4) as f:
        model = json.load(f)["settings"]
    for key in CELL_KEYS:
        del model[key]
    return {**model, **extra}


def rule_keys() -> list[list[str]]:
    """The equality columns of each blocking rule, for the pandas oracle."""
    keys = []
    for rule in smoke_settings()["blocking_rules"]:
        terms = rule.split(" AND ")
        cols = [m.group(1) for t in terms
                if (m := re.fullmatch(r"l\.(\w+) = r\.\1", t.strip()))]
        check(len(cols) == len(terms), f"not an equi-join rule: {rule}")
        keys.append(cols)
    return keys


def oracle_pair_count(df) -> int:
    """Distinct unordered pairs satisfying any blocking rule, by pandas
    self-merge (null keys never match)."""
    keys = set()
    n = len(df)
    for cols in rule_keys():
        side = df[["unique_id", *cols]].dropna()
        m = side.merge(side, on=cols, suffixes=("_l", "_r"))
        m = m[m.unique_id_l < m.unique_id_r]
        keys.update(pair_key(m.unique_id_l.to_numpy(),
                             m.unique_id_r.to_numpy(), n).tolist())
    return len(keys)


def pair_key(uid_a, uid_b, n_ids: int):
    """One int64 per unordered uid pair (uids are 0..n_ids-1)."""
    lo, hi = np.minimum(uid_a, uid_b), np.maximum(uid_a, uid_b)
    return np.asarray(lo, np.int64) * n_ids + np.asarray(hi, np.int64)


def frame_key(df_e, n_ids: int):
    """(sorted pair keys, probabilities in that order): a scored frame as
    a set of (uid_l, uid_r, p)."""
    key = pair_key(df_e.unique_id_l.to_numpy(), df_e.unique_id_r.to_numpy(),
                   n_ids)
    order = np.argsort(key, kind="stable")
    return key[order], df_e.match_probability.to_numpy()[order]


def rank_separation(df_e) -> float:
    """P(a planted duplicate pair outscores a non-match pair) — the
    Mann-Whitney statistic over the scored frame."""
    p = df_e.match_probability.to_numpy()
    truth = (df_e.cluster_l == df_e.cluster_r).to_numpy()
    ranks = np.empty(len(p))
    ranks[np.argsort(p, kind="stable")] = np.arange(1, len(p) + 1)
    n1, n0 = int(truth.sum()), int((~truth).sum())
    check(n1 > 0 and n0 > 0, "scored frame lacks duplicates or non-matches")
    return float((ranks[truth].sum() - n1 * (n1 + 1) / 2) / (n1 * n0))


def check_frame(df_e, params, what: str) -> dict:
    p = df_e.match_probability.to_numpy()
    lam = float(params.params["λ"])
    updates = len(params.param_history)
    check(np.isfinite(p).all(), f"{what}: NaN/inf in match_probability")
    check(((p >= 0) & (p <= 1)).all(), f"{what}: probability outside [0, 1]")
    check(updates > 1, f"{what}: EM took {updates} update(s)")
    check(np.isfinite(lam) and 0 < lam < 1, f"{what}: lambda = {lam}")
    sep = rank_separation(df_e)
    check(sep > 0.8, f"{what}: duplicates outrank non-matches only {sep:.3f}")
    return {"pairs": len(df_e), "em_updates": updates,
            "lambda": round(lam, 5), "rank_separation": round(sep, 4)}


# ---------------------------------------------------------------------------
# Legs
# ---------------------------------------------------------------------------


def leg_identity() -> dict:
    import jax
    import jaxlib

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not REHEARSAL:
        print(
            f"chip_smoke: no TPU (jax reports {device}); this script only "
            "runs on the chip (--rehearse-on-cpu debugs it without one)",
            file=sys.stderr,
        )
        sys.exit(2)
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 - version string only
        libtpu = "not installed"

    # a checkout holds no *.so: whatever an earlier run left is removed, so
    # the library this run loads is built by it from the committed source
    here = os.path.dirname(os.path.abspath(__file__))
    for so in glob.glob(os.path.join(here, "splink_tpu", "native", "*.so")):
        os.remove(so)

    from splink_tpu import native
    from splink_tpu.obs.metrics import install_compile_monitor
    from splink_tpu.utils.compile_cache import enable_compilation_cache

    install_compile_monitor()
    Leg.install()
    cache_dir = enable_compilation_cache()
    info = native.build_info()
    say(
        f"identity: platform: {device['platform']}, device_kind: "
        f"{device['kind']}, devices: {device['count']}, jax {jax.__version__}, "
        f"jaxlib {jaxlib.__version__}, libtpu {libtpu}, compile cache: "
        f"{cache_dir} (JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'}), "
        f"native host library: {info}"
    )
    check(info["available"], "native host library did not build/load")
    check(info["built_in_this_process"],
          "the host library was not built in this run")
    check(
        info["library"] == os.path.basename(native.library_path()),
        "loaded host library does not match native/src/host_kernels.cpp",
    )
    # a degradation (resident EM -> streamed, stale AOT sidecar, ...) is a
    # structured warning in production; here it fails the smoke
    from splink_tpu.utils.logging_utils import DegradationWarning

    warnings.simplefilter("error", DegradationWarning)
    return device


def leg_kernels(df, serve_shape: int) -> None:
    """Both Pallas kernels through Mosaic at every width the model's
    encoder produces and at MAX_PALLAS_WIDTH, equal to the vmapped vector
    form on the same device; dispatch must select them."""
    import jax
    import jax.numpy as jnp

    from splink_tpu.data import encode_string_column
    from splink_tpu.ops import strings as S
    from splink_tpu.ops import strings_pallas as SP

    interpret = REHEARSAL
    rng = np.random.default_rng(SEED)
    cols = {c: encode_string_column(df[c]) for c in JW_COLUMNS}
    widths = sorted({c.width for c in cols.values()} | {SP.MAX_PALLAS_WIDTH})
    batches = sorted({SP.LANE_TILE, 4 * SP.LANE_TILE, serve_shape, 50})
    if REHEARSAL:  # the interpreter takes minutes per unrolled shape
        widths, batches = widths[:1], batches[:2]
    jw_vector = jax.jit(S._jaro_winkler_vector_vmapped)
    lev_vector = jax.jit(S.levenshtein_vmapped)
    src = cols["surname"]
    worst = 0.0
    for width in widths:
        chars = np.zeros((len(src.bytes_), width), np.uint8)
        w = min(width, src.width)
        chars[:, :w] = src.bytes_[:, :w]
        lens = np.minimum(src.lengths, width).astype(np.int32)
        for batch in batches:
            ia = rng.integers(0, len(chars), batch)
            ib = np.where(rng.random(batch) < 0.3, ia,
                          rng.integers(0, len(chars), batch))
            s1, s2 = jnp.asarray(chars[ia]), jnp.asarray(chars[ib])
            l1, l2 = jnp.asarray(lens[ia]), jnp.asarray(lens[ib])
            got = SP.jaro_winkler_pallas(s1, s2, l1, l2, interpret=interpret)
            want = jw_vector(s1, s2, l1, l2, 0.1, 0.7)
            diff = float(jnp.max(jnp.abs(got - want)))
            worst = max(worst, diff)
            check(diff <= 1e-6, f"jaro_winkler_pallas != vector form at "
                  f"width {width} batch {batch}: max diff {diff}")
            got = SP.levenshtein_pallas(s1, s2, l1, l2, interpret=interpret)
            want = lev_vector(s1, s2, l1, l2)
            check(bool(jnp.all(got.astype(jnp.int32) == want)),
                  f"levenshtein_pallas != vector form at width {width} "
                  f"batch {batch}")
    pad = lambda s: np.frombuffer(s.ljust(8, b"\0"), np.uint8)[None]  # noqa: E731
    a, b = jnp.asarray(pad(b"MARTHA")), jnp.asarray(pad(b"MARHTA"))
    six = jnp.asarray([6], jnp.int32)
    martha = float(
        SP.jaro_winkler_pallas(a, b, six, six, interpret=interpret)[0]
    )
    check(round(martha, 4) == 0.9611, f"MARTHA/MARHTA = {martha}")
    if not REHEARSAL:
        check(SP.pallas_supported(a), "pallas_supported is false on the TPU")
        for fn, args in ((S.jaro_winkler, (a, b, six, six)),
                         (S.levenshtein, (a, b, six, six))):
            hlo = jax.jit(fn).lower(*args).compile().as_text()
            check("tpu_custom_call" in hlo,
                  f"{fn.__name__} dispatch did not select the Mosaic kernel")
    say(f"kernels: widths {widths} x batches {batches}, JW max |pallas - "
        f"vector| = {worst:.2e}, MARTHA/MARHTA = {martha:.4f}, "
        f"{'interpreted' if interpret else 'compiled by Mosaic'}")


def leg_barrier() -> dict:
    """One jitted step timed three ways (median of three rounds, a fresh
    input buffer each time). Every bench in the repo closes its clock on a
    value fetch; this says whether block_until_ready would do."""
    import jax
    import jax.numpy as jnp

    n, reps = (1024, 8) if REHEARSAL else (4096, 64)

    @jax.jit
    def step(x):
        def body(_, y):
            return (y @ x) * jnp.bfloat16(1e-3)

        y = jax.lax.fori_loop(0, reps, body, x)
        return y, y[0, 0].astype(jnp.float32)

    xs = iter([jnp.full((n, n), 1.0 + k, jnp.bfloat16) for k in range(10)])
    float(step(next(xs))[1])  # compile + warm
    call, block, fetch = [], [], []
    for _ in range(3):
        t0 = time.perf_counter()
        out = step(next(xs))
        call.append(time.perf_counter() - t0)
        out[0].block_until_ready()
        t0 = time.perf_counter()
        step(next(xs))[0].block_until_ready()
        block.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        float(step(next(xs))[1])
        fetch.append(time.perf_counter() - t0)
    ms = lambda v: round(float(np.median(v)) * 1e3, 3)  # noqa: E731
    facts = {"call_return_ms": ms(call), "block_until_ready_ms": ms(block),
             "value_fetch_ms": ms(fetch)}
    say(f"barrier: {facts} ({reps} bf16 matmuls of {n}x{n})")
    return facts


def run_virtual_linker(df, **extra):
    """Device pair generation on with a lowered resident cap: the virtual
    pair index, the pattern histogram pass, pattern EM and the score
    stream. Returns (facts, scored frame, linker)."""
    from splink_tpu import Splink

    linker = Splink(
        smoke_settings(device_pair_generation="on",
                         max_resident_pairs=1 << 20, **extra),
        df=df,
    )
    df_e = linker.get_scored_comparisons()
    check(linker.device_pair_generation_active,
          "the virtual pair index was not taken")
    return check_frame(df_e, linker.params, "virtual linker"), df_e, linker


def leg_offline(df, workdir: str):
    """Linker A: defaults (device_blocking auto, resident EM), against a
    pandas join. Linker B: the virtual pair index, against A. Then one
    short checkpointed estimate_parameters (the ordered io_callback inside
    the EM while_loop)."""
    from splink_tpu import Splink
    from splink_tpu.obs.events import read_events

    tel = os.path.join(workdir, "telemetry")
    extra = {"telemetry_dir": tel}
    if REHEARSAL:  # on the CPU backend "auto" keeps the host join
        extra["device_blocking"] = "on"
    linker_a = Splink(smoke_settings(**extra), df=df)
    df_a = linker_a.get_scored_comparisons()
    facts = {"materialised": check_frame(df_a, linker_a.params, "linker A")}
    events = read_events(os.path.join(tel, f"run_{linker_a.run_id}.jsonl"))
    tier = [e for e in events if e["type"] == "blocking_device"]
    check(len(tier) == 1 and tier[0]["completed"]
          and tier[0]["pairs"] == len(df_a),
          f"device blocking did not produce linker A's pairs: {tier}")
    check(not [e for e in events if e["type"] == "degradation"],
          "linker A degraded")
    want = oracle_pair_count(df)
    check(len(df_a) == want,
          f"linker A scored {len(df_a)} pairs, pandas join says {want}")
    facts["blocking_tier"] = "device"
    facts["device_blocking_pairs"] = tier[0]["pairs"]

    facts["virtual"], df_b, linker_b = run_virtual_linker(df)
    ka, pa = frame_key(df_a, len(df))
    kb, pb = frame_key(df_b, len(df))
    check(np.array_equal(ka, kb), "linkers A and B disagree on the pair set")
    # Trained against trained: the two regimes sum in different orders and
    # each stops when an update moves m/u by less than 1e-4, so their
    # parameters agree to that stop rule, not to a bit ...
    la, ma, ua, _ = linker_a.params.to_arrays(dtype=np.float64)
    lb, mb, ub, _ = linker_b.params.to_arrays(dtype=np.float64)
    gap = max(abs(float(la) - float(lb)), float(np.max(np.abs(ma - mb))),
              float(np.max(np.abs(ua - ub))))
    check(gap <= 1e-4, f"linkers A and B trained parameters {gap} apart")
    facts["max_param_gap_materialised_vs_virtual"] = float(f"{gap:.2e}")
    facts["max_score_diff_materialised_vs_virtual"] = float(
        f"{np.max(np.abs(pa - pb)):.2e}")
    # ... and under the SAME parameters the virtual regime (pattern LUT
    # score stream) must give linker A's float for every pair
    linker_b.params = linker_a.params
    kb, pb = frame_key(linker_b.manually_apply_fellegi_sunter_weights(),
                       len(df))
    check(np.array_equal(ka, kb) and np.array_equal(pa, pb),
          f"under linker A's parameters linker B scores "
          f"{int((pa != pb).sum())} of {len(pa)} pairs differently, worst "
          f"{float(np.max(np.abs(pa - pb))):.3e}")
    facts["scores_bit_equal_under_same_parameters"] = True

    ckpt = os.path.join(workdir, "ckpt")
    small = df.iloc[: max(len(df) // 20, 2000)]
    linker_c = Splink(
        smoke_settings(max_iterations=6, checkpoint_interval=2), df=small
    )
    params = linker_c.estimate_parameters(checkpoint_dir=ckpt)
    lam = float(params.params["λ"])
    check(os.path.exists(os.path.join(ckpt, "em_checkpoint.json")),
          "checkpointed EM wrote no checkpoint")
    check(len(params.param_history) > 1 and 0 < lam < 1,
          f"checkpointed EM: {len(params.param_history)} updates, λ={lam}")
    facts["checkpointed_em_updates"] = len(params.param_history)
    return facts, linker_a, df_a, df_b


def leg_serve(linker, df, df_e, workdir: str) -> dict:
    """export_index -> fresh load_index -> warmup -> LinkageService, then
    the AOT sidecar: save, restore in-process, answer one query."""
    from splink_tpu.obs.metrics import compile_requests
    from splink_tpu.serve import LinkageService, QueryEngine, load_index

    index_dir = os.path.join(workdir, "index")
    aot_dir = os.path.join(index_dir, "aot")
    linker.export_index(index_dir)
    engine = QueryEngine(load_index(index_dir), aot_dir=aot_dir)
    warm = engine.warmup()
    check(warm["compiles"] + warm["cache_hits"] == warm["combinations"]
          and warm["aot_restored"] == 0, f"warmup accounting: {warm}")
    c0 = compile_requests()

    keys, probs = frame_key(df_e, len(df))
    offline = dict(zip(keys.tolist(), probs.astype(np.float32)))
    records = df.sample(SERVE_REQUESTS, random_state=SEED).to_dict(
        orient="records"
    )
    served_p, offline_p = [], []
    with LinkageService(engine) as svc:
        futures = [svc.submit(dict(r)) for r in records]
        for rec, fut in zip(records, futures):
            res = fut.result(timeout=300)
            check(not res.shed, f"request shed: {res.reason}")
            q = int(rec["unique_id"])
            for uid, p in res.matches:
                if int(uid) == q:
                    continue
                key = int(pair_key(q, int(uid), len(df)))
                check(key in offline, f"served pair {(q, uid)} not offline")
                served_p.append(np.float32(p))
                offline_p.append(offline[key])
        summary = svc.latency_summary()
    steady = compile_requests() - c0
    served_p, offline_p = np.asarray(served_p), np.asarray(offline_p)
    check(len(served_p) > SERVE_REQUESTS // 2,
          f"only {len(served_p)} served pairs checked")
    differ = served_p != offline_p
    check(not differ.any(),
          f"serve/offline parity: {int(differ.sum())} of {len(differ)} "
          "served scores are not the offline float, worst "
          f"{float(np.max(np.abs(served_p - offline_p))):.3e}")
    check(steady == 0, f"{steady} compile requests in steady-state serving")

    engine.save_aot()
    restored = QueryEngine(load_index(index_dir), aot_dir=aot_dir)
    warm2 = restored.warmup()
    check(warm2["aot_restored"] == warm2["combinations"]
          and warm2["compiles"] == 0, f"AOT restore accounting: {warm2}")
    one = restored.query(df.iloc[:1])
    check(len(one) > 0 and np.isfinite(one.match_probability).all(),
          "AOT-restored engine answered nothing")
    return {"warmup": warm, "requests": len(records),
            "served": summary["served"], "pairs_checked": len(differ),
            "pairs_bit_equal_to_offline": int((~differ).sum()),
            "steady_state_compile_requests": steady, "aot_restore": warm2}


def leg_mesh(df, df_single) -> dict:
    """The virtual/pattern path again, sharded over every device: equal to
    the single-device result; in the pattern kernels this run compiled,
    each Pallas call fed batch / N pairs with no all-gather anywhere; every
    device holding memory."""
    import jax

    from splink_tpu.parallel.mesh import make_mesh

    n = jax.device_count()
    check(len({d.id for d in make_mesh(n).devices.flat}) == n,
          "mesh repeats a device")
    virtual, df_m, linker = run_virtual_linker(df, mesh={"data": n})
    facts = {"virtual": virtual}
    ks, ps = frame_key(df_single, len(df))
    km, pm = frame_key(df_m, len(df))
    check(np.array_equal(ks, km), "mesh run disagrees on the pair set")
    worst = float(np.max(np.abs(ps - pm)))
    check(worst <= 1e-5, f"mesh run disagrees on scores by {worst}")
    facts["max_score_diff_vs_single_device"] = worst

    kernels = linker.virtual_kernel_hlo()
    check(kernels, "the mesh run recorded no pattern kernel")
    facts["pattern_kernels"] = []
    for batch, hlo in kernels:
        # opcodes, sync or async (-start; its result type is a tuple)
        ops = re.findall(r"\s(all-gather|all-reduce|all-to-all|"
                         r"collective-permute|reduce-scatter)(?:-start)?\(",
                         hlo)
        calls = [ln for ln in hlo.splitlines()
                 if "custom-call(" in ln and "tpu_custom_call" in ln]
        check(set(ops) <= {"all-reduce"} and "all-gather" not in hlo,
              f"pattern kernel (batch {batch}) moves pairs between devices: "
              f"{sorted(set(ops))}")
        if not REHEARSAL:
            check(calls, f"pattern kernel (batch {batch}) holds no Mosaic call")
            for ln in calls:
                # result and operand shapes; the rest is the kernel's body
                shapes = ln.split("custom_call_target")[0]
                dims = {int(d)
                        for shape in re.findall(r"\[([\d,]+)\]", shapes)
                        for d in shape.split(",")}
                check(batch not in dims and batch // n in dims,
                      f"a Pallas call in the pattern kernel (batch {batch}, "
                      f"{n} devices) is not fed batch / N pairs: {ln[:300]}")
        facts["pattern_kernels"].append(
            {"batch": batch, "per_device_pairs_into_pallas": batch // n,
             "pallas_calls": len(calls), "all_reduce": len(ops),
             "all_gather": 0})
    mem = {str(d): (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for d in jax.local_devices()}
    if not REHEARSAL:
        check(all(v > 0 for v in mem.values()), f"idle device: {mem}")
    facts["peak_bytes_in_use"] = mem
    return facts


def main() -> int:
    global REHEARSAL
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    ap.add_argument("--rows", type=int, default=0,
                    help=f"rows (default {ROWS}; rehearsal "
                    f"{REHEARSAL_ROWS})")
    args = ap.parse_args()
    REHEARSAL = args.rehearse_on_cpu
    if REHEARSAL:
        os.environ["JAX_PLATFORMS"] = "cpu"
    rows = args.rows or (REHEARSAL_ROWS if REHEARSAL else ROWS)

    device = leg_identity()
    import jax

    from chipbench.datagen import make_people

    t0 = time.perf_counter()
    df = make_people(rows, seed=SEED)
    say(f"data: make_people({rows}, seed={SEED}) -> {len(df)} rows in "
        f"{time.perf_counter() - t0:.1f}s")

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        with Leg("barrier") as facts:
            facts.update(leg_barrier())
        with Leg("kernels"):
            from splink_tpu.serve import BucketPolicy
            from splink_tpu.settings import complete_settings_dict

            policy = BucketPolicy.from_settings(
                complete_settings_dict(smoke_settings())
            )
            leg_kernels(df, serve_shape=policy.query_buckets[0]
                        * policy.candidate_buckets[0])
        with Leg("offline") as facts:
            off, linker_a, df_a, df_b = leg_offline(df, workdir)
            facts.update(off)
        with Leg("serve") as facts:
            facts.update(leg_serve(linker_a, df, df_a, workdir))
        if jax.device_count() > 1:
            with Leg("mesh") as facts:
                facts.update(leg_mesh(df, df_b))
        else:
            say("leg mesh did not run: one device")

    summary = {"device": device, "smoke": "not a benchmark", "rows": len(df),
               "legs": Leg.results,
               "wall_s": round(time.perf_counter() - _T0, 1), "claim": None}
    say(f"summary: {json.dumps(summary)}")
    if not REHEARSAL:
        # the verdict, alone on the last line: exactly these keys
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
