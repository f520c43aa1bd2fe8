"""Fault-tolerant EM execution (splink_tpu/resilience): checkpoint/resume,
retry with backoff, deterministic fault injection, graceful degradation.

The load-bearing assertions are BIT-IDENTITY ones: a run interrupted by a
real SIGKILL (injected via the fault plan, no atexit, no finally blocks)
and resumed from its checkpoint must produce exactly the parameters and
per-iteration history an uninterrupted run produces — on both the streamed
and the segmented resident EM paths. Anything weaker (allclose) would let
a subtly wrong resume (off-by-one iteration, float round-trip loss,
replayed history drift) hide inside the tolerance.
"""

import json
import os
import signal
import subprocess
import sys
import tempfile
import warnings

import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

import splink_tpu
from splink_tpu import Splink
from splink_tpu.ops.gamma import apply_null
from splink_tpu.resilience import (
    CheckpointMismatchError,
    EMCheckpoint,
    RetryError,
    RetryPolicy,
    classify_error,
    is_oom,
    load_checkpoint,
    retry_call,
    save_checkpoint,
)
from splink_tpu.resilience.checkpoint import (
    CHECKPOINT_VERSION,
    CheckpointError,
    checkpoint_path,
)
from splink_tpu.resilience.faults import FaultPlan, InjectedFault, reset_plans
from splink_tpu.utils.logging_utils import DegradationWarning

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_fault_plans():
    """Fault-plan event budgets are per-process state; tests must not see
    another test's partially fired plan."""
    reset_plans()
    yield
    reset_plans()


def _df(n=150, seed=0):
    rng = np.random.default_rng(seed)
    firsts = np.array(["amelia", "oliver", "isla", "george", "ava", "noah"])
    lasts = np.array(["smith", "jones", "taylor", "brown"])
    return pd.DataFrame(
        {
            "unique_id": np.arange(n),
            "first_name": firsts[rng.integers(0, 6, n)],
            "surname": lasts[rng.integers(0, 4, n)],
            "city": [f"c{i % 4}" for i in range(n)],
        }
    )


def _settings(**overrides):
    s = {
        "link_type": "dedupe_only",
        "blocking_rules": ["l.city = r.city"],
        "comparison_columns": [
            {
                "col_name": "first_name",
                "num_levels": 2,
                "comparison": {"kind": "exact"},
            },
            {
                "col_name": "surname",
                "num_levels": 2,
                "comparison": {"kind": "exact"},
            },
        ],
        "max_iterations": 8,
        # keep EM running the full iteration budget: an early convergence
        # would collapse the interrupted/resumed/uninterrupted runs into
        # the same few iterations and weaken the resume assertions
        "em_convergence": 1e-12,
    }
    s.update(overrides)
    return s


# Exact comparison as a CUSTOM kernel: a registered kernel disqualifies
# the pattern-id pipeline (it could emit out-of-range gammas), which is
# what routes estimate_parameters through streamed EM over G — the
# path carrying the batch_fetch/em_iteration fault sites and the
# EMCheckpointer hook. Same gamma semantics as kind "exact".
_CUSTOM_EXACT_REGISTRATION = """
import jax.numpy as jnp
import splink_tpu
from splink_tpu.ops.gamma import apply_null

def _custom_exact_first(ctx, col_settings):
    pc = ctx.col("first_name")
    return apply_null((pc.tok_l == pc.tok_r).astype(jnp.int8), pc.null)

splink_tpu.register_comparison("ckpt_exact_first", _custom_exact_first)
"""
exec(_CUSTOM_EXACT_REGISTRATION)


def _settings_streamed(**overrides):
    """Settings that reach the REAL streamed-stats EM driver: a custom
    comparison kernel (no pattern pipeline) plus a residency threshold
    below the pair count (no resident regime)."""
    return _settings(
        comparison_columns=[
            {
                "col_name": "first_name",
                "num_levels": 2,
                "comparison": {"kind": "custom", "fn": "ckpt_exact_first"},
            },
            {
                "col_name": "surname",
                "num_levels": 2,
                "comparison": {"kind": "exact"},
            },
        ],
        max_resident_pairs=1024,
        pair_batch_size=1024,
        **overrides,
    )


def _assert_bit_identical(a: Splink, b: Splink):
    """Final params AND full per-iteration history, exactly equal."""
    sa = json.dumps(
        {"current": a.params.params, "history": a.params.param_history},
        sort_keys=True,
    )
    sb = json.dumps(
        {"current": b.params.params, "history": b.params.param_history},
        sort_keys=True,
    )
    assert sa == sb


# ----------------------------------------------------------------------
# checkpoint.py unit behaviour
# ----------------------------------------------------------------------


def _mk_ckpt(**over):
    kw = dict(
        state_hash="abc123",
        iteration=3,
        lam=0.25,
        m=[[0.9, 0.1]],
        u=[[0.2, 0.8]],
        histories={
            "lam": [0.2, 0.22, 0.24, 0.25],
            "m": [[[0.9, 0.1]]] * 4,
            "u": [[[0.2, 0.8]]] * 4,
            "ll": None,
        },
    )
    kw.update(over)
    return EMCheckpoint(**kw)


def test_checkpoint_roundtrip_and_atomicity(tmp_path):
    save_checkpoint(tmp_path, _mk_ckpt())
    # atomic write leaves no temp litter next to the checkpoint
    assert os.listdir(tmp_path) == [os.path.basename(checkpoint_path(tmp_path))]
    got = load_checkpoint(tmp_path, expect_hash="abc123")
    assert got.iteration == 3 and got.lam == 0.25
    lam, m, u = got.params_arrays()
    assert lam.dtype == np.float32 and m.shape == (1, 2)
    h = got.history_arrays()
    assert h["ll"] is None and len(h["lam"]) == 4


def test_checkpoint_absent_dir_returns_none(tmp_path):
    assert load_checkpoint(tmp_path / "nowhere") is None


def test_checkpoint_hash_mismatch_rejected(tmp_path):
    save_checkpoint(tmp_path, _mk_ckpt())
    with pytest.raises(CheckpointMismatchError, match="different job"):
        load_checkpoint(tmp_path, expect_hash="deadbeef")


def test_checkpoint_version_mismatch_rejected(tmp_path):
    save_checkpoint(tmp_path, _mk_ckpt(version=CHECKPOINT_VERSION + 1))
    with pytest.raises(CheckpointMismatchError, match="format version"):
        load_checkpoint(tmp_path)


def test_checkpoint_corrupt_file_raises(tmp_path):
    with open(checkpoint_path(tmp_path), "w") as f:
        f.write("{not json")
    with pytest.raises(CheckpointError, match="unreadable"):
        load_checkpoint(tmp_path)


def test_checkpoint_float64_roundtrip_exact(tmp_path):
    """float64 values survive the JSON round trip bit-for-bit (Python
    floats ARE f64; f32 widens losslessly) — the property the resumed
    trajectory's bit-identity rests on."""
    lam = 0.1 + 0.2  # not exactly representable shorter than full f64
    save_checkpoint(tmp_path, _mk_ckpt(lam=lam, dtype="float64"))
    got = load_checkpoint(tmp_path)
    assert got.params_arrays()[0] == np.float64(lam)


# ----------------------------------------------------------------------
# retry.py unit behaviour
# ----------------------------------------------------------------------


def test_retry_transient_then_success():
    calls, naps = [], []
    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionError("connection reset")
        return "ok"
    assert retry_call(flaky, sleep=naps.append) == "ok"
    assert len(calls) == 3
    # bounded exponential backoff: 0.5, 1.0
    assert naps == [0.5, 1.0]


def test_retry_deterministic_propagates_immediately():
    calls = []
    def bad():
        calls.append(1)
        raise ValueError("shape mismatch")
    with pytest.raises(ValueError):
        retry_call(bad, sleep=lambda _: None)
    assert len(calls) == 1


def test_retry_identical_failures_abort_early():
    """3 consecutive byte-identical failures end the budget even though
    each is classified transient."""
    calls = []
    def same():
        calls.append(1)
        raise ConnectionError("always the same")
    with pytest.raises(RetryError, match="identical failures"):
        retry_call(same, sleep=lambda _: None)
    assert len(calls) == 3


def test_retry_budget_exhausted():
    calls = []
    def varying():
        calls.append(1)
        raise TimeoutError(f"drop #{len(calls)}")
    policy = RetryPolicy(max_retries=2)
    with pytest.raises(RetryError, match="budget exhausted"):
        retry_call(varying, policy=policy, sleep=lambda _: None)
    assert len(calls) == 3  # 1 + max_retries


def test_classify_and_oom_markers():
    assert classify_error(RuntimeError("RESOURCE_EXHAUSTED: oom")) == "transient"
    assert classify_error(RuntimeError("UNAVAILABLE: Socket closed")) == "transient"
    assert classify_error(BrokenPipeError()) == "transient"
    assert classify_error(ValueError("bad shape")) == "deterministic"
    assert is_oom(RuntimeError("RESOURCE_EXHAUSTED: out of HBM"))
    assert not is_oom(RuntimeError("UNAVAILABLE: Socket closed"))
    oom = InjectedFault("resident_em", "oom", {})
    assert is_oom(oom) and classify_error(oom) == "transient"


# ----------------------------------------------------------------------
# faults.py unit behaviour
# ----------------------------------------------------------------------


def test_fault_plan_grammar_and_budget():
    plan = FaultPlan.from_spec(
        "batch_fetch@iter=2:batch=3, em_iteration@iter=4:kind=oom:times=2"
    )
    # no match: wrong site / wrong coords
    plan.fire("batch_fetch", iter=1, batch=3)
    plan.fire("segment", iter=2, batch=3)
    with pytest.raises(InjectedFault, match="Socket closed"):
        plan.fire("batch_fetch", iter=2, batch=3)
    # budget spent (times defaults to 1): same coords no longer fire
    plan.fire("batch_fetch", iter=2, batch=3)
    # times=2 fires twice, with the OOM marker
    for _ in range(2):
        with pytest.raises(InjectedFault, match="RESOURCE_EXHAUSTED"):
            plan.fire("em_iteration", iter=4)
    plan.fire("em_iteration", iter=4)


def test_fault_plan_rejects_unknown_kind():
    with pytest.raises(ValueError, match="kind"):
        FaultPlan.from_spec("batch_fetch@kind=meteor")


def test_empty_plan_is_noop():
    plan = FaultPlan.from_spec("")
    assert not plan
    plan.fire("anything", iter=0)


# ----------------------------------------------------------------------
# In-process recovery paths
# ----------------------------------------------------------------------


def test_streamed_resume_matches_uninterrupted(tmp_path):
    """A 3-iteration streamed run + resume-to-8 equals a straight 8 —
    params and history bit-identical (the settings hash deliberately
    excludes max_iterations: extending the cap is a legitimate resume)."""
    df = _df()
    part = Splink(_settings_streamed(max_iterations=3), df=df)
    assert not part._regime("patterns")  # genuinely streamed EM
    part.estimate_parameters(checkpoint_dir=tmp_path)
    assert os.path.exists(checkpoint_path(tmp_path))

    resumed = Splink(_settings_streamed(), df=df)
    resumed.estimate_parameters(checkpoint_dir=tmp_path, resume=True)

    oracle = Splink(_settings_streamed(), df=df)
    oracle.estimate_parameters()
    _assert_bit_identical(resumed, oracle)


def test_resident_segmented_resume_matches_uninterrupted(tmp_path):
    """Same contract on the segmented resident path: run_em_checkpointed's
    K-iteration segments are the same compiled while_loop body, so the
    trajectory is bit-identical with or without checkpointing, across an
    interrupt/resume boundary."""
    df = _df()
    part = Splink(_settings(max_iterations=3), df=df)
    part.estimate_parameters(checkpoint_dir=tmp_path)

    resumed = Splink(_settings(), df=df)
    resumed.estimate_parameters(checkpoint_dir=tmp_path, resume=True)

    oracle = Splink(_settings(), df=df)
    oracle.estimate_parameters()
    _assert_bit_identical(resumed, oracle)


def test_resident_checkpointing_is_invisible(tmp_path):
    """checkpoint_dir alone (no resume) must not change results at all."""
    df = _df()
    with_ckpt = Splink(_settings(checkpoint_interval=3), df=df)
    with_ckpt.estimate_parameters(checkpoint_dir=tmp_path)
    plain = Splink(_settings(), df=df)
    plain.estimate_parameters()
    _assert_bit_identical(with_ckpt, plain)
    ckpt = load_checkpoint(tmp_path)
    assert ckpt.iteration == 8


def test_stale_checkpoint_rejected(tmp_path):
    """A checkpoint written under different computation-defining settings
    (extra comparison column here) is rejected with a clear error, never
    silently trained on."""
    df = _df()
    a = Splink(_settings(max_iterations=2), df=df)
    a.estimate_parameters(checkpoint_dir=tmp_path)

    other = _settings(
        comparison_columns=[
            {
                "col_name": "first_name",
                "num_levels": 2,
                "comparison": {"kind": "exact"},
            }
        ]
    )
    b = Splink(other, df=df)
    with pytest.raises(CheckpointMismatchError, match="different job"):
        b.estimate_parameters(checkpoint_dir=tmp_path, resume=True)


def test_resume_topology_mismatch_rejected(tmp_path):
    """A checkpoint written by a 2-process run cannot resume on 1 process:
    global_pair_slice would feed different slices than the histories
    assume."""
    df = _df()
    linker = Splink(_settings(max_resident_pairs=1024), df=df)
    save_checkpoint(
        tmp_path, _mk_ckpt(state_hash=linker._em_state_hash(), process_count=2)
    )
    with pytest.raises(RuntimeError, match="process"):
        linker.estimate_parameters(checkpoint_dir=tmp_path, resume=True)


def test_resident_oom_degrades_to_streamed():
    """Injected device OOM entering the resident path falls back to the
    streamed path (same update math over host batches) with a structured
    DegradationWarning — and completes with matching parameters."""
    df = _df()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        degraded = Splink(
            _settings(fault_plan="resident_em@kind=oom"), df=df
        )
        degraded.estimate_parameters()
    assert any(
        issubclass(w.category, DegradationWarning) for w in caught
    ), [str(w.message) for w in caught]

    # bit-identical to the streamed driver it degraded onto (driven
    # directly: pattern-capable settings would otherwise route a small
    # max_resident_pairs through the pattern pipeline, a different path)
    streamed = Splink(_settings(), df=df)
    G = streamed._ensure_gammas()
    streamed._run_em_streamed_driver(streamed._em_batches(G), False, len(G))
    _assert_bit_identical(degraded, streamed)
    # ...and matching the resident run it replaced. Bounded near what was
    # measured, not at float32 resolution: under the installed XLA CPU
    # backend the resident while_loop accumulates the M-step denominators
    # (sufficient_stats' "nc,n->c" contraction) one term at a time in
    # float32 — m_den is 2.6e-5 off a float64 run after ONE update over
    # these 2,738 pairs, where the streamed step is within 2e-7 — so the
    # resident trajectory drifts from the streamed one: lambda 7.4e-5
    # apart (relative) after the 8 updates. jaxlib 0.4.36 held 1e-5 here.
    # Not repaired in this PR (ROADMAP D11); on the TPU the resident and
    # the pattern regime agreed to 1e-6 at 3.4M pairs (chip_smoke.py)
    resident = Splink(_settings(), df=df)
    resident.estimate_parameters()
    np.testing.assert_allclose(
        degraded.params.params["λ"], resident.params.params["λ"], rtol=2e-4
    )


def test_resident_oom_mid_run_with_checkpointing_no_double_apply(tmp_path):
    """An OOM that strikes AFTER checkpoint boundaries have replayed
    updates into self.params (the segment fault site fires inside the
    in-loop hook) must roll params back before the streamed fallback —
    otherwise the already-replayed updates would be applied twice and
    the history would carry up to 2x max_iterations entries."""
    df = _df()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        degraded = Splink(
            _settings(
                fault_plan="segment@iter=4:kind=oom", checkpoint_interval=2
            ),
            df=df,
        )
        degraded.estimate_parameters(checkpoint_dir=tmp_path)
    assert any(issubclass(w.category, DegradationWarning) for w in caught)
    streamed = Splink(_settings(), df=df)
    G = streamed._ensure_gammas()
    streamed._run_em_streamed_driver(streamed._em_batches(G), False, len(G))
    _assert_bit_identical(degraded, streamed)


def test_resume_without_checkpoint_dir_raises():
    """resume=True with no checkpoint directory (argument or settings
    key) must raise, not silently retrain from scratch."""
    with pytest.raises(ValueError, match="checkpoint_dir"):
        Splink(_settings(), df=_df()).estimate_parameters(resume=True)


def test_resume_with_lowered_cap_returns_truncated_params(tmp_path):
    """Resuming with max_iterations BELOW the checkpoint's iteration must
    return the truncated trajectory's own params (history index done),
    not the checkpoint's later ones."""
    df = _df()
    full = Splink(_settings(max_iterations=6), df=df)
    full.estimate_parameters(checkpoint_dir=tmp_path)

    lowered = Splink(_settings(max_iterations=4), df=df)
    lowered.estimate_parameters(checkpoint_dir=tmp_path, resume=True)

    oracle = Splink(_settings(max_iterations=4), df=df)
    oracle.estimate_parameters()
    _assert_bit_identical(lowered, oracle)


def test_resume_completed_run_keeps_true_log_likelihood(tmp_path):
    """Resuming an already-complete checkpointed run with compute_ll must
    reproduce the run's EXACT final log likelihood — not the 0.0 filler
    the persisted ll history once carried at not-yet-computed indices
    (they persist as null, and the post-run re-save includes the final
    post-loop value)."""
    df = _df()
    first = Splink(_settings(), df=df)
    first.estimate_parameters(compute_ll=True, checkpoint_dir=tmp_path)
    ll_true = first.params.params["log_likelihood"]
    assert np.isfinite(ll_true) and ll_true != 0.0

    again = Splink(_settings(), df=df)
    again.estimate_parameters(
        compute_ll=True, checkpoint_dir=tmp_path, resume=True
    )
    assert again.params.params["log_likelihood"] == ll_true


def test_transient_batch_fault_retried_bit_identical():
    """A transient failure mid-pass (batch fetch dies once at iteration 3)
    restarts the WHOLE pass: partial sufficient statistics are never
    reused, so the retried run is bit-identical to an undisturbed one."""
    df = _df()
    flaky = Splink(
        _settings_streamed(fault_plan="batch_fetch@iter=3:batch=0"), df=df
    )
    flaky.estimate_parameters()
    clean = Splink(_settings_streamed(), df=df)
    clean.estimate_parameters()
    _assert_bit_identical(flaky, clean)


def test_deterministic_stream_fault_aborts():
    """An unbounded repeating fault (times high enough to outlive the
    retry budget) reproduces byte-identically and must abort as
    deterministic, not spin forever."""
    df = _df()
    linker = Splink(
        _settings_streamed(fault_plan="batch_fetch@iter=1:batch=0:times=99"),
        df=df,
    )
    with pytest.raises(RetryError, match="identical failures"):
        linker.estimate_parameters()


# ----------------------------------------------------------------------
# Kill-and-resume: real SIGKILL via the fault plan, in a child process
# ----------------------------------------------------------------------

# The child trains with a checkpoint dir and an injected SIGKILL from the
# environment's fault plan — faithfully modelling host death (no atexit, no
# finally). The parent then resumes IN PROCESS and pins bit-identity
# against an uninterrupted oracle.
_KILL_CHILD = (
    _CUSTOM_EXACT_REGISTRATION
    + """
import json, sys
import pandas as pd
from splink_tpu import Splink

df = pd.read_json(sys.argv[1], orient="split")
settings = json.load(open(sys.argv[2]))
linker = Splink(settings, df=df)
linker.estimate_parameters(checkpoint_dir=sys.argv[3])
"""
)


def _run_kill_child(tmp_path, settings, df, fault_spec):
    df_json = tmp_path / "df.json"
    settings_json = tmp_path / "settings.json"
    ckpt_dir = tmp_path / "ckpt"
    df.to_json(df_json, orient="split")
    with open(settings_json, "w") as f:
        json.dump(settings, f)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["SPLINK_TPU_FAULTS"] = fault_spec
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (REPO_ROOT, env.get("PYTHONPATH")) if p
    )
    proc = subprocess.run(
        [sys.executable, "-c", _KILL_CHILD, str(df_json),
         str(settings_json), str(ckpt_dir)],
        env=env,
        capture_output=True,
        timeout=240,
    )
    # the child must have died from the injected SIGKILL, not finished or
    # failed some other way
    assert proc.returncode == -signal.SIGKILL, (
        proc.returncode,
        proc.stderr.decode(errors="replace")[-2000:],
    )
    assert os.path.exists(checkpoint_path(ckpt_dir)), "no durable checkpoint"
    return ckpt_dir


def test_streamed_kill_and_resume_bit_identical(tmp_path):
    """Streamed EM SIGKILLed after update 4 (checkpoint_interval=1, and
    the checkpoint hook runs before the em_iteration fault site, so update
    4 is durable) resumes to the exact final params and histories of an
    uninterrupted run."""
    df = _df()
    settings = _settings_streamed(checkpoint_interval=1)
    ckpt_dir = _run_kill_child(
        tmp_path, settings, df, "em_iteration@iter=4:kind=kill"
    )
    assert load_checkpoint(ckpt_dir).iteration == 4

    resumed = Splink(dict(settings), df=df)
    resumed.estimate_parameters(checkpoint_dir=ckpt_dir, resume=True)
    oracle = Splink(dict(settings), df=df)
    oracle.estimate_parameters()
    _assert_bit_identical(resumed, oracle)


def test_resident_segmented_kill_and_resume_bit_identical(tmp_path):
    """Segmented resident EM SIGKILLed at the second segment boundary
    (after the 5-iteration checkpoint was written) resumes bit-identical."""
    df = _df()
    settings = _settings(checkpoint_interval=5)
    ckpt_dir = _run_kill_child(
        tmp_path, settings, df, "segment@iter=5:kind=kill"
    )
    assert load_checkpoint(ckpt_dir).iteration == 5

    resumed = Splink(dict(settings), df=df)
    resumed.estimate_parameters(checkpoint_dir=ckpt_dir, resume=True)
    oracle = Splink(dict(settings), df=df)
    oracle.estimate_parameters()
    _assert_bit_identical(resumed, oracle)


def test_streamed_kill_at_converging_iteration_resumes_bit_identical(tmp_path):
    """A SIGKILL at the CONVERGING iteration must leave a checkpoint that
    records convergence (on_iteration carries the flag): the resume is
    then a no-op — not a spurious extra EM update appended past the
    uninterrupted run's history."""
    df = _df()
    # 0.05 is the loosest schema-valid em_convergence; on this data the
    # streamed driver converges on update 4 — kill exactly there
    settings = _settings_streamed(checkpoint_interval=1, em_convergence=0.05)
    ckpt_dir = _run_kill_child(
        tmp_path, settings, df, "em_iteration@iter=4:kind=kill"
    )
    ckpt = load_checkpoint(ckpt_dir)
    assert ckpt.iteration == 4 and ckpt.converged

    resumed = Splink(dict(settings), df=df)
    resumed.estimate_parameters(checkpoint_dir=ckpt_dir, resume=True)
    oracle = Splink(dict(settings), df=df)
    oracle.estimate_parameters()
    _assert_bit_identical(resumed, oracle)
