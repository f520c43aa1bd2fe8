"""Settings completion and validation semantics.

Mirrors the behaviours pinned by the reference's settings layer
(/root/reference/splink/settings.py): schema defaults, gamma_index
assignment, default m/u priors and their normalisation, default comparison
selection by (data_type, num_levels), and validation errors.
"""

import pytest

from splink_tpu.settings import complete_settings_dict
from splink_tpu.validate import ValidationError, validate_settings


def _minimal(**over):
    s = {
        "link_type": "dedupe_only",
        "comparison_columns": [{"col_name": "fname"}],
        "blocking_rules": ["l.dob = r.dob"],
    }
    s.update(over)
    return s


def test_non_column_defaults_filled():
    s = complete_settings_dict(_minimal())
    assert s["em_convergence"] == 0.0001
    assert s["max_iterations"] == 25
    assert s["proportion_of_matches"] == 0.3
    assert s["unique_id_column_name"] == "unique_id"
    assert s["retain_matching_columns"] is True
    assert s["retain_intermediate_calculation_columns"] is True
    assert s["additional_columns_to_retain"] == []
    assert s["backend"] == "jax"


def test_column_defaults_and_gamma_index():
    s = complete_settings_dict(
        _minimal(comparison_columns=[{"col_name": "a"}, {"col_name": "b"}])
    )
    for i, col in enumerate(s["comparison_columns"]):
        assert col["gamma_index"] == i
        assert col["num_levels"] == 2
        assert col["data_type"] == "string"
        assert col["term_frequency_adjustments"] is False


def test_default_m_u_priors_normalised():
    s = complete_settings_dict(
        _minimal(
            comparison_columns=[
                {"col_name": "a", "num_levels": 2},
                {"col_name": "b", "num_levels": 3},
                {"col_name": "c", "num_levels": 4},
            ]
        )
    )
    cols = s["comparison_columns"]
    assert cols[0]["m_probabilities"] == pytest.approx([0.1, 0.9])
    assert cols[0]["u_probabilities"] == pytest.approx([0.9, 0.1])
    assert cols[1]["m_probabilities"] == pytest.approx([0.1, 0.2, 0.7])
    assert cols[1]["u_probabilities"] == pytest.approx([0.7, 0.2, 0.1])
    assert cols[2]["m_probabilities"] == pytest.approx([0.1, 0.1, 0.1, 0.7])
    assert cols[2]["u_probabilities"] == pytest.approx([0.7, 0.1, 0.1, 0.1])


def test_user_probabilities_normalised():
    s = complete_settings_dict(
        _minimal(
            comparison_columns=[{"col_name": "a", "m_probabilities": [2, 6]}]
        )
    )
    assert s["comparison_columns"][0]["m_probabilities"] == pytest.approx([0.25, 0.75])


def test_wrong_length_probabilities_raises():
    with pytest.raises(ValueError, match="not equal to the number of levels"):
        complete_settings_dict(
            _minimal(
                comparison_columns=[
                    {"col_name": "a", "num_levels": 3, "m_probabilities": [0.5, 0.5]}
                ]
            )
        )


def test_default_comparisons_by_type_and_levels():
    s = complete_settings_dict(
        _minimal(
            comparison_columns=[
                {"col_name": "a", "num_levels": 3},
                {"col_name": "b", "data_type": "numeric", "num_levels": 2},
                {"col_name": "c", "data_type": "numeric", "num_levels": 3},
            ]
        )
    )
    cols = s["comparison_columns"]
    assert cols[0]["comparison"] == {"kind": "jaro_winkler", "thresholds": [0.94, 0.88]}
    assert cols[1]["comparison"] == {"kind": "numeric_abs", "thresholds": [0.00001]}
    assert cols[2]["comparison"] == {"kind": "numeric_perc", "thresholds": [0.0001, 0.05]}


def test_case_expression_translated():
    expr = """case
    when fname_l is null or fname_r is null then -1
    when jaro_winkler_sim(fname_l, fname_r) > 0.94 then 2
    when jaro_winkler_sim(fname_l, fname_r) > 0.88 then 1
    else 0 end"""
    s = complete_settings_dict(
        _minimal(
            comparison_columns=[
                {"col_name": "fname", "num_levels": 3, "case_expression": expr}
            ]
        )
    )
    assert s["comparison_columns"][0]["comparison"] == {
        "kind": "jaro_winkler",
        "thresholds": [0.94, 0.88],
    }


def test_invalid_link_type_rejected():
    with pytest.raises(ValidationError):
        validate_settings(_minimal(link_type="nope"))


def test_unknown_top_level_key_rejected():
    with pytest.raises(ValidationError):
        validate_settings(_minimal(blocking_rulez=[]))


def test_empty_blocking_rules_warns():
    with pytest.warns(UserWarning, match="blocking"):
        complete_settings_dict(_minimal(blocking_rules=[]))


def test_levels_above_four_need_explicit_config():
    with pytest.raises(ValueError, match="num_levels > 4"):
        complete_settings_dict(
            _minimal(comparison_columns=[{"col_name": "a", "num_levels": 5}])
        )


def test_backend_key_is_read_and_checked():
    import pandas as pd
    import pytest

    from splink_tpu import Splink

    df = pd.DataFrame({"unique_id": [0, 1], "a": ["x", "y"]})
    s = {
        "link_type": "dedupe_only",
        "comparison_columns": [{"col_name": "a", "comparison": {"kind": "exact"}}],
        "blocking_rules": ["l.a = r.a"],
    }
    # schema enum rejects unknown backends at validation
    with pytest.raises(Exception):
        Splink({**s, "backend": "torch"}, df=df)
    # and the accepted value flows through
    linker = Splink({**s, "backend": "jax"}, df=df)
    assert linker.settings["backend"] == "jax"


def test_observability_defaults_filled():
    """The telemetry keys complete from the schema (the schema is the
    single source of truth for their defaults); the removed profile_dir
    hook left no key behind."""
    s = complete_settings_dict(_minimal())
    assert "profile_dir" not in s
    assert s["telemetry_dir"] == ""
    assert s["telemetry_memory"] is True


def test_observability_keys_validate_types():
    """Schema validation rejects wrongly-typed observability keys (and
    profile_dir, now an unknown key: wrap the call in jax.profiler.trace
    instead) and accepts correctly-typed ones."""
    for bad in (
        {"profile_dir": "/tmp/prof"},
        {"telemetry_dir": 5},
        {"telemetry_dir": ["x"]},
        {"telemetry_memory": "yes"},
    ):
        with pytest.raises(ValidationError):
            validate_settings(_minimal(**bad))
    validate_settings(
        _minimal(
            telemetry_dir="/tmp/tel",
            telemetry_memory=False,
        )
    )


def test_serve_defaults_filled():
    """The online-serving keys complete from the schema (the schema is the
    single source of truth for their defaults)."""
    s = complete_settings_dict(_minimal())
    assert s["serve_query_buckets"] == [16, 128, 1024]
    assert s["serve_candidate_buckets"] == [32, 256, 2048]
    assert s["serve_queue_depth"] == 1024
    assert s["serve_deadline_ms"] == 5
    assert s["serve_top_k"] == 5


def test_serve_keys_validate_types():
    """Schema validation rejects wrongly-typed serve keys and accepts
    correctly-typed ones."""
    for bad in (
        {"serve_query_buckets": 16},
        {"serve_query_buckets": ["x"]},
        {"serve_candidate_buckets": "big"},
        {"serve_queue_depth": "deep"},
        {"serve_queue_depth": 0},
        {"serve_deadline_ms": "soon"},
        {"serve_top_k": 0},
        {"serve_top_k": [5]},
    ):
        with pytest.raises(ValidationError):
            validate_settings(_minimal(**bad))
    validate_settings(
        _minimal(
            serve_query_buckets=[8, 64],
            serve_candidate_buckets=[16, 512],
            serve_queue_depth=64,
            serve_deadline_ms=1.5,
            serve_top_k=3,
        )
    )


def test_serve_bucket_policy_reads_settings():
    """BucketPolicy.from_settings consumes the completed keys and rejects
    non-power-of-two or unsorted bucket lists."""
    from splink_tpu.serve.bucketing import BucketPolicy

    s = complete_settings_dict(_minimal())
    policy = BucketPolicy.from_settings(s)
    assert policy.query_buckets == (16, 128, 1024)
    assert policy.candidate_buckets == (32, 256, 2048)
    with pytest.raises(ValueError, match="powers of two"):
        BucketPolicy.from_settings({**s, "serve_query_buckets": [12]})
    with pytest.raises(ValueError, match="ascending"):
        BucketPolicy.from_settings({**s, "serve_candidate_buckets": [64, 32]})


def test_telemetry_settings_flow_into_run_context(tmp_path):
    """telemetry_dir turns the linker's RunContext on; telemetry_memory
    flows through; no telemetry_dir -> disabled context."""
    import pandas as pd

    from splink_tpu import Splink

    df = pd.DataFrame({"unique_id": [0, 1], "a": ["x", "x"]})
    base = {
        "link_type": "dedupe_only",
        "comparison_columns": [{"col_name": "a", "comparison": {"kind": "exact"}}],
        "blocking_rules": ["l.a = r.a"],
    }
    off = Splink(dict(base), df=df)
    assert off._obs.enabled is False
    on = Splink(
        {**base, "telemetry_dir": str(tmp_path), "telemetry_memory": False},
        df=df,
    )
    assert on._obs.enabled is True
    assert on._obs.memory_snapshots is False
    assert on._obs.sink.path.startswith(str(tmp_path))
    on._obs.close()


def test_serve_resilience_defaults_filled():
    """The serving-resilience keys complete from the schema: brown-out and
    hedging OFF by default, breaker threshold 3, 16 parity probes."""
    s = complete_settings_dict(_minimal())
    assert s["serve_brownout_top_k"] == 0
    assert s["serve_breaker_threshold"] == 3
    assert s["serve_hedge_ms"] == 0
    assert s["serve_probe_queries"] == 16


def test_serve_resilience_key_types_validated():
    """Type/bound violations on the resilience keys are rejected by the
    schema validator, not silently served."""
    for bad in (
        {"serve_breaker_threshold": "3"},
        {"serve_breaker_threshold": 0},
        {"serve_brownout_top_k": -1},
        {"serve_brownout_top_k": 2.5},
        {"serve_hedge_ms": "fast"},
        {"serve_hedge_ms": -5},
        {"serve_probe_queries": -1},
        {"serve_probe_queries": "many"},
    ):
        with pytest.raises(ValidationError):
            validate_settings(_minimal(**bad))
    # valid values pass (hedge_ms is a number: floats allowed)
    validate_settings(
        _minimal(
            serve_breaker_threshold=5,
            serve_brownout_top_k=2,
            serve_hedge_ms=12.5,
            serve_probe_queries=0,
        )
    )


def test_serve_fused_key():
    """serve_fused completes true (the fused megakernel is the default
    serving path), validates as a strict boolean, and false (the unfused
    parity oracle) passes."""
    s = complete_settings_dict(_minimal())
    assert s["serve_fused"] is True
    for bad in ({"serve_fused": "yes"}, {"serve_fused": 1}):
        with pytest.raises(ValidationError):
            validate_settings(_minimal(**bad))
    validate_settings(_minimal(serve_fused=False))


def test_serve_tf_adjust_key():
    """serve_tf_adjust completes true (TF-flagged models serve ADJUSTED
    scores by default once the artifact carries the fold data) and
    validates as a strict boolean."""
    s = complete_settings_dict(_minimal())
    assert s["serve_tf_adjust"] is True
    for bad in ({"serve_tf_adjust": "yes"}, {"serve_tf_adjust": 1}):
        with pytest.raises(ValidationError):
            validate_settings(_minimal(**bad))
    validate_settings(_minimal(serve_tf_adjust=False))


def test_approx_tf_weighting_key():
    """approx_tf_weighting completes false (the unweighted tier is the
    bit-compatible default) and validates as a strict boolean."""
    s = complete_settings_dict(_minimal())
    assert s["approx_tf_weighting"] is False
    for bad in (
        {"approx_tf_weighting": "on"},
        {"approx_tf_weighting": 1},
    ):
        with pytest.raises(ValidationError):
            validate_settings(_minimal(**bad))
    validate_settings(_minimal(approx_tf_weighting=True))


def test_serve_observability_defaults_filled():
    """The obs v2 keys complete from the schema: tracing OFF (sample rate
    0), exposition endpoint OFF (port 0), flight recorder ON at 256
    records."""
    s = complete_settings_dict(_minimal())
    assert s["serve_trace_sample_rate"] == 0
    assert s["obs_exposition_port"] == 0
    assert s["obs_flight_records"] == 256


def test_serve_observability_key_types_validated():
    """Type/bound violations on the obs v2 keys are rejected by the
    schema validator, not silently served."""
    for bad in (
        {"serve_trace_sample_rate": "all"},
        {"serve_trace_sample_rate": -0.1},
        {"serve_trace_sample_rate": 1.5},
        {"obs_exposition_port": -1},
        {"obs_exposition_port": 99999},
        {"obs_exposition_port": 1.5},
        {"obs_flight_records": -1},
        {"obs_flight_records": "many"},
    ):
        with pytest.raises(ValidationError):
            validate_settings(_minimal(**bad))
    # valid values pass (the sample rate is a number: floats allowed)
    validate_settings(
        _minimal(
            serve_trace_sample_rate=0.25,
            obs_exposition_port=9464,
            obs_flight_records=0,
        )
    )


def test_approx_blocking_defaults_filled():
    """The approximate-blocking keys complete from the schema: tier OFF by
    default, q=2 grams, a 16x2 LSH banding, verification off, 4M budget."""
    s = complete_settings_dict(_minimal())
    assert s["approx_blocking"] is False
    assert s["approx_q"] == 2
    assert s["approx_bands"] == 16
    assert s["approx_rows_per_band"] == 2
    assert s["approx_threshold"] == 0
    assert s["approx_pair_budget"] == 4194304


def test_approx_blocking_key_types_validated():
    """Type/bound violations on the approx keys are rejected by the schema
    validator (the PR 5/7 key-validation pattern)."""
    for bad in (
        {"approx_blocking": "yes"},
        {"approx_blocking": 1},
        {"approx_q": 0},
        {"approx_q": 9},
        {"approx_q": "two"},
        {"approx_bands": 0},
        {"approx_bands": 2.5},
        {"approx_rows_per_band": 0},
        {"approx_rows_per_band": "many"},
        {"approx_threshold": -0.1},
        {"approx_threshold": 1.5},
        {"approx_threshold": "strict"},
        {"approx_pair_budget": 0},
        {"approx_pair_budget": "big"},
    ):
        with pytest.raises(ValidationError):
            validate_settings(_minimal(**bad))
    # valid values pass (threshold is a number: floats allowed)
    validate_settings(
        _minimal(
            approx_blocking=True,
            approx_q=3,
            approx_bands=32,
            approx_rows_per_band=1,
            approx_threshold=0.4,
            approx_pair_budget=1024,
        )
    )


def test_offline_scale_defaults_filled():
    """The out-of-core write-path keys complete from the schema: spill
    path OFF (empty dir), 1M-row build chunks, auto shard count."""
    s = complete_settings_dict(_minimal())
    assert s["build_spill_dir"] == ""
    assert s["build_spill_chunk_rows"] == 1048576
    assert s["emit_shard_chunks"] == 0


def test_offline_scale_key_types_validated():
    """Type/bound violations on the write-path keys are rejected by the
    schema validator (the PR 5/7 key-validation pattern)."""
    for bad in (
        {"build_spill_dir": 7},
        {"build_spill_dir": True},
        {"build_spill_chunk_rows": 0},
        {"build_spill_chunk_rows": 1023},
        {"build_spill_chunk_rows": "big"},
        {"emit_shard_chunks": -1},
        {"emit_shard_chunks": "auto"},
        {"emit_shard_chunks": 2.5},
    ):
        with pytest.raises(ValidationError):
            validate_settings(_minimal(**bad))
    validate_settings(
        _minimal(
            build_spill_dir="/tmp/build",
            build_spill_chunk_rows=4096,
            emit_shard_chunks=8,
        )
    )


def test_quality_observatory_defaults_filled():
    """The drift-observatory keys complete from the schema: profile
    capture OFF by default (legacy builds unchanged), 16 score bins, a
    60 s short window, the standard 0.25 PSI action threshold."""
    s = complete_settings_dict(_minimal())
    assert s["quality_profile"] is False
    assert s["drift_sketch_bins"] == 16
    assert s["drift_window_s"] == 60
    assert s["drift_alert_psi"] == 0.25


def test_quality_observatory_key_types_validated():
    """Type/bound violations on the drift-observatory keys are rejected
    by the schema validator (the PR 5/7 key-validation pattern)."""
    for bad in (
        {"quality_profile": "yes"},
        {"quality_profile": 1},
        {"drift_sketch_bins": 1},
        {"drift_sketch_bins": 257},
        {"drift_sketch_bins": 8.5},
        {"drift_sketch_bins": "fine"},
        {"drift_window_s": 0},
        {"drift_window_s": -5},
        {"drift_window_s": "hour"},
        {"drift_alert_psi": -0.1},
        {"drift_alert_psi": "strict"},
    ):
        with pytest.raises(ValidationError):
            validate_settings(_minimal(**bad))
    # valid values pass (window/threshold are numbers: floats allowed;
    # drift_alert_psi=0 disables alerting but still validates)
    validate_settings(
        _minimal(
            quality_profile=True,
            drift_sketch_bins=32,
            drift_window_s=2.5,
            drift_alert_psi=0,
        )
    )


def test_perf_observatory_defaults_filled():
    """The kernel-watch keys complete from the schema: the serve-time
    regression alert is ON by default (host-side arithmetic only) at the
    3x two-window ratio over a 30 s short window."""
    s = complete_settings_dict(_minimal())
    assert s["perf_alert_ratio"] == 3
    assert s["perf_window_s"] == 30


def test_perf_observatory_key_types_validated():
    """Type/bound violations on the kernel-watch keys are rejected by the
    schema validator (the established key-validation pattern)."""
    for bad in (
        {"perf_alert_ratio": -1},
        {"perf_alert_ratio": "strict"},
        {"perf_window_s": 0},
        {"perf_window_s": -3},
        {"perf_window_s": "minute"},
    ):
        with pytest.raises(ValidationError):
            validate_settings(_minimal(**bad))
    # valid values pass (perf_alert_ratio=0 disables the watch entirely)
    validate_settings(_minimal(perf_alert_ratio=0, perf_window_s=2.5))


def test_wire_defaults_filled():
    """The wire-tier keys complete from the schema: no wire serving by
    default (port 0), a 500 ms dial budget, a 4 MiB frame cap and no
    remote hosts."""
    s = complete_settings_dict(_minimal())
    assert s["wire_port"] == 0
    assert s["wire_connect_timeout_ms"] == 500
    assert s["wire_max_frame_bytes"] == 4 * 1024 * 1024
    assert s["wire_max_connections"] == 64
    assert s["wire_remote_hosts"] == []


def test_wire_key_types_validated():
    """Type/bound violations on the wire-tier keys are rejected by the
    schema validator (the established key-validation pattern)."""
    for bad in (
        {"wire_port": -1},
        {"wire_port": 65536},
        {"wire_port": "auto"},
        {"wire_port": 8080.5},
        {"wire_connect_timeout_ms": 0},
        {"wire_connect_timeout_ms": -200},
        {"wire_connect_timeout_ms": "fast"},
        {"wire_max_frame_bytes": 4095},
        {"wire_max_frame_bytes": "4MB"},
        {"wire_max_frame_bytes": 1.5},
        {"wire_max_connections": 0},
        {"wire_max_connections": -4},
        {"wire_max_connections": "many"},
        {"wire_max_connections": 8.5},
        {"wire_remote_hosts": "host:9000"},
        {"wire_remote_hosts": [9000]},
        {"wire_remote_hosts": [["host", 9000]]},
    ):
        with pytest.raises(ValidationError):
            validate_settings(_minimal(**bad))
    # valid values pass (the timeout is a number: floats allowed)
    validate_settings(
        _minimal(
            wire_port=9400,
            wire_connect_timeout_ms=250.5,
            wire_max_frame_bytes=65536,
            wire_max_connections=4,
            wire_remote_hosts=["10.0.0.2:9400", "10.0.0.3:9400"],
        )
    )


def test_fleet_defaults_filled():
    """The fleet-observability keys complete from the schema: stitching
    on, network-phase alerting off, a temp bundle dir and a 30 s bundle
    rate limit."""
    s = complete_settings_dict(_minimal())
    assert s["fleet_stitching"] is True
    assert s["fleet_net_alert_ratio"] == 0
    assert s["fleet_bundle_dir"] == ""
    assert s["fleet_incident_interval_s"] == 30.0


def test_fleet_key_types_validated():
    """Type/bound violations on the fleet keys are rejected by the schema
    validator (the established key-validation pattern)."""
    for bad in (
        {"fleet_stitching": "yes"},
        {"fleet_stitching": 1},
        {"fleet_net_alert_ratio": -0.5},
        {"fleet_net_alert_ratio": "strict"},
        {"fleet_bundle_dir": 7},
        {"fleet_incident_interval_s": 0},
        {"fleet_incident_interval_s": -30},
        {"fleet_incident_interval_s": "fast"},
    ):
        with pytest.raises(ValidationError):
            validate_settings(_minimal(**bad))
    # valid values pass (ratio 0 disables alerting, not the decomposition)
    validate_settings(
        _minimal(
            fleet_stitching=False,
            fleet_net_alert_ratio=0,
            fleet_bundle_dir="/tmp/bundles",
            fleet_incident_interval_s=2.5,
        )
    )
