"""Layer-4 perf audit: measured per-kernel runtime/memory baselines.

The falsifiability contract every audit layer holds: a healthy kernel
measured against its own fresh baseline audits clean, and a baseline
doctored to claim the kernel used to be faster/smaller makes the gate
fire (PA-TIME / PA-MEM) — then a refresh clears it. Every test that
asserts on PA-TIME, or on an audit being clean, takes its clock readings
from the ``readings`` fixture and not from the container: the kernel still
compiles and runs, but the milliseconds the audit compares are the test's,
so firing and clearing follow the baseline and not the machine's load
(under six workers a real reading of a 0.05 ms kernel is anything).
"""

import copy
import json

import pytest

from splink_tpu.analysis import perf_audit as pa
from splink_tpu.analysis.trace_audit import REGISTRY, _ensure_default_registry


def _measured_baselines(names, best_of=2):
    """Fresh baselines dict for the named kernels, shaped like the
    committed file."""
    kernels = {}
    for cell in pa.perf_plan(names):
        kernels.setdefault(cell.kernel, {})[cell.label] = pa.measure_cell(
            cell, best_of=best_of
        )
    return {"tiers": {pa.current_tier(): {"kernels": kernels}}}


@pytest.fixture(scope="module")
def tf_gather_baselines():
    """One cheap kernel (reg + x4) measured once for the module."""
    return _measured_baselines(["tf_gather"])


# what the ``readings`` fixture answers until a test says otherwise: well
# above the real bands' absolute floors (1 ms execute, 500 ms compile)
EXECUTE_MS, COMPILE_MS = 50.0, 2000.0


@pytest.fixture
def readings(monkeypatch):
    """The audit's two clock seams answer from this dict: ``_compile_cell``
    still compiles and ``_execute_best_of`` still runs the kernel, and both
    report the milliseconds held here (a list is read one entry a call, its
    last entry for good)."""
    held = {"execute_ms": EXECUTE_MS, "compile_ms": COMPILE_MS}

    def reading(key):
        value = held[key]
        if isinstance(value, list):
            return value.pop(0) if len(value) > 1 else value[0]
        return value

    real_compile, real_execute = pa._compile_cell, pa._execute_best_of

    def compile_cell(name, factor):
        return (*real_compile(name, factor)[:3], reading("compile_ms"))

    def execute_best_of(compiled, args, kwargs, best_of):
        real_execute(compiled, args, kwargs, 1)
        return reading("execute_ms")

    monkeypatch.setattr(pa, "_compile_cell", compile_cell)
    monkeypatch.setattr(pa, "_execute_best_of", execute_best_of)
    return held


def _timed_as(baselines):
    """The measured baselines with every record's two clock metrics set to
    what ``readings`` answers."""
    out = copy.deepcopy(baselines)
    for shapes in out["tiers"][pa.current_tier()]["kernels"].values():
        for rec in shapes.values():
            rec["execute_ms"], rec["compile_ms"] = EXECUTE_MS, COMPILE_MS
    return out


def test_perf_plan_covers_registry():
    """Every non-excluded layer-2 kernel is in the plan at its registered
    shape; excluded kernels are absent; scaled kernels carry their extra
    shapes."""
    _ensure_default_registry()
    plan = pa.perf_plan()
    kernels = {c.kernel for c in plan}
    assert kernels == set(REGISTRY) - set(pa.PERF_EXCLUDED)
    by_kernel = {}
    for c in plan:
        by_kernel.setdefault(c.kernel, []).append(c.label)
    for name, labels in by_kernel.items():
        assert labels[0] == "reg"
        want = ["reg"] + [f"x{f}" for f in pa.PERF_SCALES.get(name, (0, ()))[1]]
        assert labels == want
    assert pa.perf_plan(["tf_gather"])[0].kernel == "tf_gather"
    with pytest.raises(KeyError):
        pa.perf_plan(["no_such_kernel"])


def test_scaled_inputs_tile_only_the_batch_axis():
    """Tiling touches exactly the arrays whose leading axis is the
    declared batch length — lookup tables and parameters keep their
    registered shapes."""
    _ensure_default_registry()
    spec = REGISTRY["gamma_batch"]
    fn, args, kwargs = spec.built()
    packed, il, ir = args
    s_args, _ = pa._scaled_args("gamma_batch", args, kwargs, 4)
    assert s_args[0].shape == packed.shape  # the packed table: untouched
    assert s_args[1].shape[0] == il.shape[0] * 4
    assert s_args[2].shape[0] == ir.shape[0] * 4
    assert s_args[1].dtype == il.dtype
    # factor 1 is the identity
    same_args, _ = pa._scaled_args("gamma_batch", args, kwargs, 1)
    assert same_args[1].shape == il.shape


def test_measure_cell_records_all_metrics(tf_gather_baselines):
    rec = tf_gather_baselines["tiers"][pa.current_tier()]["kernels"][
        "tf_gather"]["reg"]
    assert rec["compile_ms"] > 0
    assert rec["execute_ms"] > 0
    assert rec["argument_bytes"] > 0
    assert rec["output_bytes"] > 0
    assert "temp_bytes" in rec
    # the CPU backend reports no memory_stats: the measured peak is null
    # BY DESIGN (the audit only gates it when both sides recorded it)
    assert rec["peak_device_bytes"] is None


def test_fresh_baseline_audits_clean(tf_gather_baselines, readings):
    """A kernel that reads what its baseline says — and one that reads
    1.9x of it, inside the +100% band — audits clean."""
    findings, n = pa.run_perf_audit(
        ["tf_gather"], _timed_as(tf_gather_baselines), best_of=2, remeasure=2
    )
    assert n == 2  # reg + x4
    assert findings == []
    readings.update(execute_ms=95.0, compile_ms=3800.0)
    findings, _ = pa.run_perf_audit(
        ["tf_gather"], _timed_as(tf_gather_baselines), best_of=2, remeasure=2
    )
    assert findings == []


def test_inflated_baseline_stays_clean_one_sided(tf_gather_baselines,
                                                 readings):
    """The runtime gate is ONE-SIDED: a baseline slower/bigger than the
    measurement (the kernel got faster) is an improvement, not a
    finding."""
    inflated = _timed_as(tf_gather_baselines)
    for shapes in inflated["tiers"][pa.current_tier()]["kernels"].values():
        for rec in shapes.values():
            for key in ("compile_ms", "execute_ms", "temp_bytes",
                        "argument_bytes", "output_bytes"):
                if rec.get(key) is not None:
                    rec[key] = rec[key] * 100 + 1000
    findings, _ = pa.run_perf_audit(
        ["tf_gather"], inflated, best_of=2, remeasure=2
    )
    assert findings == []


def test_doctored_time_baseline_fires_pa_time(tf_gather_baselines, readings):
    """A baseline claiming the kernel used to run 1000x faster makes
    PA-TIME fire — through the median-of-K noise guard, at the rule's own
    bands — and the message carries the diff-style drift numbers."""
    honest = _timed_as(tf_gather_baselines)
    doctored = copy.deepcopy(honest)
    kern = doctored["tiers"][pa.current_tier()]["kernels"]["tf_gather"]
    kern["reg"]["execute_ms"] = kern["reg"]["execute_ms"] / 1000.0
    findings, _ = pa.run_perf_audit(
        ["tf_gather"], doctored, best_of=2, remeasure=2
    )
    assert [(f.rule, f.path) for f in findings] == [
        ("PA-TIME", "tf_gather@reg")
    ], findings
    assert "execute_ms: baseline 0.050, measured 50.000" in findings[0].message
    assert "median of 2 re-runs" in findings[0].message
    # the refresh clears it (the falsifiability round-trip)
    findings, _ = pa.run_perf_audit(
        ["tf_gather"], honest, best_of=2, remeasure=2
    )
    assert findings == []


@pytest.mark.parametrize("metric", pa.TIME_KEYS)
def test_one_slow_reading_is_absorbed_by_the_median_guard(
        tf_gather_baselines, readings, metric):
    """The first reading of a cell lands 10x over its baseline and every
    re-measurement lands on it: the median-of-K guard reads a spike, and
    PA-TIME stays quiet. Readings that STAY 10x over fire."""
    honest = _timed_as(tf_gather_baselines)
    steady = readings[metric]
    readings[metric] = [steady * 10, steady]
    findings, _ = pa.run_perf_audit(
        ["tf_gather"], honest, best_of=2, remeasure=3
    )
    assert findings == []
    readings[metric] = steady * 10
    findings, _ = pa.run_perf_audit(
        ["tf_gather"], honest, best_of=2, remeasure=3
    )
    assert {(f.rule, f.path) for f in findings} == {
        ("PA-TIME", "tf_gather@reg"), ("PA-TIME", "tf_gather@x4")
    }
    assert all(metric in f.message for f in findings)


def test_doctored_mem_baseline_fires_pa_mem(tf_gather_baselines):
    """A baseline claiming the executable used to move fewer bytes makes
    PA-MEM fire deterministically (no noise guard needed: the metric is
    an XLA memory_analysis estimate, not a clock)."""
    doctored = copy.deepcopy(tf_gather_baselines)
    kern = doctored["tiers"][pa.current_tier()]["kernels"]["tf_gather"]
    kern["x4"]["argument_bytes"] = kern["x4"]["argument_bytes"] / 10.0
    findings, _ = pa.run_perf_audit(
        ["tf_gather"], doctored, best_of=2, remeasure=2
    )
    mem = [f for f in findings if f.rule == "PA-MEM"]
    assert mem and "argument_bytes" in mem[0].message
    assert mem[0].path == "tf_gather@x4"
    findings, _ = pa.run_perf_audit(
        ["tf_gather"], tf_gather_baselines, best_of=2, remeasure=2
    )
    assert [f for f in findings if f.rule == "PA-MEM"] == []


def test_missing_baseline_fires_pa_base(tf_gather_baselines):
    findings, _ = pa.run_perf_audit(
        ["tf_gather"], {"tiers": {}}, best_of=2, remeasure=2
    )
    assert {f.rule for f in findings} == {"PA-BASE"}
    assert len(findings) == 2  # one per shape
    # a different-tier block is NOT this tier's baseline
    other = {"tiers": {"not-a-backend": copy.deepcopy(
        tf_gather_baselines["tiers"][pa.current_tier()])}}
    findings, _ = pa.run_perf_audit(
        ["tf_gather"], other, best_of=2, remeasure=2
    )
    assert {f.rule for f in findings} == {"PA-BASE"}


def test_update_baselines_roundtrip(tmp_path, readings):
    """update_baselines writes a tier-keyed file the audit then passes
    against; a second tier's block survives a refresh of this tier."""
    path = tmp_path / "perf_baselines.json"
    # seed a foreign-tier block that the refresh must preserve
    path.write_text(json.dumps({
        "tiers": {"tpu": {"kernels": {"tf_gather": {"reg": {
            "execute_ms": 1.0}}}}},
    }))
    new = pa.update_baselines(["tf_gather"], str(path), best_of=2)
    assert "tpu" in new["tiers"], "foreign tier block must survive"
    assert "tf_gather" in new["tiers"][pa.current_tier()]["kernels"]
    on_disk = json.loads(path.read_text())
    assert on_disk["_meta"]["refresh"] == "make perf-baselines"
    findings, _ = pa.run_perf_audit(
        ["tf_gather"], on_disk, best_of=2, remeasure=2
    )
    assert findings == []


def test_committed_baselines_shape():
    """The committed file carries a cpu-tier block covering the full
    plan (the CLI gate `python -m splink_tpu.analysis --perf-audit` runs
    against it; actually measuring here would put container noise inside
    tier-1, which is what perf-smoke is for)."""
    baselines = pa.load_baselines()
    assert "cpu" in baselines.get("tiers", {})
    kernels = baselines["tiers"]["cpu"]["kernels"]
    for cell in pa.perf_plan():
        rec = kernels.get(cell.kernel, {}).get(cell.label)
        assert rec is not None, f"missing committed cell {cell.kernel}@{cell.label}"
        assert rec["execute_ms"] > 0
        assert rec["compile_ms"] > 0


def test_excluded_kernels_documented():
    """Exclusions must name registered kernels (a rename would silently
    un-exclude) and carry a reason the listing renders."""
    _ensure_default_registry()
    for name, reason in pa.PERF_EXCLUDED.items():
        assert name in REGISTRY
        assert reason
    listing = pa.format_plan(pa.perf_plan())
    assert "em_step_checkpointed" in listing
    assert "excluded" in listing


def test_cli_list_perf_kernels(capsys):
    from splink_tpu.analysis.__main__ import main

    assert main(["--list-perf-kernels"]) == 0
    out = capsys.readouterr().out
    assert "tf_gather" in out
    assert "perf_baselines.json" in out
