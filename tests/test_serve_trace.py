"""Request-level serve tracing (obs v2): attribution and race correctness.

The contracts under test:

* the phase partition TELESCOPES — per delivered request, the phase
  durations sum to the measured wall latency (the `make trace-smoke`
  invariant, asserted here at the 5% tolerance);
* every submitted request closes its span tree exactly once, with the
  machine-readable outcome/reason (delivered / shed+reason / discarded);
* a hedged request yields exactly ONE delivered span tree — the losing
  attempt closes shed (when the second replica shed it) or discarded
  (when both replicas served) — never two delivered trees;
* traces survive a mid-traffic ``swap_index`` and record the generation
  they were served on;
* tracing adds zero steady-state recompiles (the compiled programs are
  untouched — the jaxpr audit already pins them; this asserts the
  runtime counter too).

Plus unit tiers for the SLO burn-rate math, the flight recorder ring /
dump / trigger behaviour, the Prometheus exposition endpoint and the
``obs attribute`` CLI report.
"""

import json
import os
import time

import numpy as np
import pandas as pd
import pytest

from splink_tpu import Splink
from splink_tpu.obs.cli import (
    attribute_events,
    parse_prometheus_text,
    render_dash,
    summarize_events,
)
from splink_tpu.obs.events import (
    read_events,
    register_ambient,
    unregister_ambient,
)
from splink_tpu.obs.exposition import ExpositionServer, Sample
from splink_tpu.obs.flight import FlightRecorder
from splink_tpu.obs.reqtrace import (
    PHASES,
    PhaseProfile,
    RequestTrace,
    ServeTracer,
    TraceRoot,
)
from splink_tpu.obs.slo import SLOTracker
from splink_tpu.resilience import faults
from splink_tpu.serve import (
    BucketPolicy,
    LinkageService,
    QueryEngine,
    ReplicaRouter,
)

WAIT = 60


def people_df(n=100, seed=5):
    rng = np.random.default_rng(seed)
    firsts = ["amelia", "oliver", "isla", "george", "ava", "noah", "emily"]
    lasts = ["smith", "jones", "taylor", "brown", "wilson", "evans"]
    return pd.DataFrame(
        {
            "unique_id": range(n),
            "first_name": [str(rng.choice(firsts)) for _ in range(n)],
            "surname": [str(rng.choice(lasts)) for _ in range(n)],
            "dob": [f"19{rng.integers(40, 99)}" for _ in range(n)],
        }
    )


def trace_settings(**over):
    s = {
        "link_type": "dedupe_only",
        "comparison_columns": [
            {"col_name": "first_name", "num_levels": 3},
            {
                "col_name": "surname",
                "num_levels": 2,
                "comparison": {"kind": "exact"},
            },
        ],
        "blocking_rules": ["l.dob = r.dob", "l.surname = r.surname"],
        "max_iterations": 3,
        "serve_top_k": 8,
        "serve_breaker_threshold": 2,
        "serve_probe_queries": 0,
    }
    s.update(over)
    return s


@pytest.fixture(scope="module")
def trained():
    df = people_df()
    linker = Splink(trace_settings(), df=df)
    linker.estimate_parameters()
    index = linker.export_index()
    return df, linker, index


@pytest.fixture(scope="module")
def engine(trained):
    _, _, index = trained
    eng = QueryEngine(index, policy=BucketPolicy((16,), (64, 256)))
    eng.warmup()
    return eng


class _Capture:
    """In-memory ambient sink (duck-typed EventSink) for event assertions."""

    def __init__(self):
        self.events = []

    def emit(self, type, **fields):
        self.events.append({"type": type, **fields})

    def of(self, type):
        return [e for e in self.events if e["type"] == type]


@pytest.fixture()
def capture():
    cap = _Capture()
    register_ambient(cap)
    yield cap
    unregister_ambient(cap)


@pytest.fixture()
def clean_faults(monkeypatch):
    faults.reset_plans()
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    yield monkeypatch
    faults.reset_plans()


def _service(engine, **over):
    kw = dict(
        deadline_ms=2.0,
        watchdog_interval_s=0.02,
        breaker_cooldown_s=0.2,
        trace_sample_rate=1.0,
        flight_records=0,  # unit flight tests register their own recorder
    )
    kw.update(over)
    return LinkageService(engine, **kw)


def _phase_sum(ev):
    return sum((ev.get("phases_ms") or {}).values())


# ---------------------------------------------------------------------------
# unit tier: trace context + sampling
# ---------------------------------------------------------------------------


def test_phase_partition_telescopes_exactly():
    """Clamped boundary marks make the phases sum to the wall EXACTLY,
    including out-of-order marks (a request that enqueued after batch
    formation started) and a profile that overshoots the engine window."""
    tr = RequestTrace(root=TraceRoot(), t_submit=100.0)
    tr.marks = {
        "admit": 100.001,
        "form": 100.0005,  # earlier than admit: queue_wait clamps to 0
        "pop": 100.010,
        "engine_out": 100.050,
    }
    profile = PhaseProfile(compile_s=0.010, execute_s=0.020,
                           transfer_s=0.030)  # 60ms > the 40ms window
    phases, wall = tr.phase_durations(100.060, profile)
    assert wall == pytest.approx(0.060)
    assert sum(phases.values()) == pytest.approx(wall, abs=1e-12)
    assert phases["queue_wait"] == 0.0
    assert phases["dispatch"] >= 0.0
    # the overshooting profile rescales into the window, preserving ratios
    assert phases["transfer"] == pytest.approx(phases["compile"] * 3)
    assert set(phases) <= set(PHASES)


def test_phase_partition_shed_at_admission():
    tr = RequestTrace(root=TraceRoot(), t_submit=5.0)
    phases, wall = tr.phase_durations(5.002)
    assert set(phases) == {"deliver"}
    assert wall == pytest.approx(0.002)


def test_sampling_stride_deterministic():
    tracer = ServeTracer(0.25)
    takes = [tracer.maybe_start() is not None for _ in range(100)]
    assert sum(takes) == 25
    assert ServeTracer(0.0).maybe_start() is None
    full = ServeTracer(1.0)
    assert all(full.maybe_start() is not None for _ in range(10))


def test_root_claims_exactly_one_delivery():
    root = TraceRoot()
    assert root.claim_delivery() is True
    assert root.claim_delivery() is False
    tracer = ServeTracer(1.0)
    a = RequestTrace(root=root, attempt=5)
    ev = tracer.close(a, "delivered")
    assert ev["outcome"] == "discarded"  # the root was already claimed


# ---------------------------------------------------------------------------
# service e2e: attribution + shed reasons + zero recompiles
# ---------------------------------------------------------------------------


def test_delivered_phases_sum_to_wall(engine, trained, capture):
    from splink_tpu.obs.metrics import compile_requests

    df, _, _ = trained
    records = df.head(40).to_dict(orient="records")
    svc = _service(engine)
    c0 = compile_requests()
    futures = [svc.submit(dict(r)) for r in records]
    results = [f.result(timeout=WAIT) for f in futures]
    c1 = compile_requests()
    svc.close()
    assert not any(r.shed for r in results)
    assert c1 - c0 == 0, "tracing must not add steady-state recompiles"
    traces = capture.of("request_trace")
    delivered = [e for e in traces if e["outcome"] == "delivered"]
    assert len(delivered) == len(records), (
        "every submitted request must close exactly one delivered tree"
    )
    for ev in delivered:
        assert set(ev["phases_ms"]) == set(PHASES)
        assert _phase_sum(ev) == pytest.approx(
            ev["wall_ms"], rel=0.05, abs=0.05
        ), f"phases must sum to wall: {ev}"
        assert ev["phases_ms"]["compile"] == pytest.approx(0.0, abs=1e-6), (
            "steady state must attribute zero compile time"
        )
    # the trace ids are unique per request
    assert len({e["trace_id"] for e in delivered}) == len(delivered)
    # and the service's phase summary aggregates them
    assert set(svc.phase_summary()) == set(PHASES) | {"wall"}


def test_queue_full_shed_closes_trace(engine, trained, capture):
    df, _, _ = trained
    svc = _service(engine, queue_depth=1, autostart=False)
    with pytest.warns(Warning):
        futures = [
            svc.submit(dict(r))
            for r in df.head(8).to_dict(orient="records")
        ]
    svc.start()
    results = [f.result(timeout=WAIT) for f in futures]
    svc.close()
    shed = [e for e in capture.of("request_trace")
            if e["outcome"] == "shed"]
    assert shed and all(e["reason"] == "queue_full" for e in shed)
    assert len(shed) == sum(r.shed for r in results)
    # a shed-at-admission tree records only host-side phases
    for ev in shed:
        assert _phase_sum(ev) == pytest.approx(
            ev["wall_ms"], rel=0.05, abs=0.05
        )


def test_timeout_cancel_closes_trace_with_reason(
    engine, trained, capture, clean_faults
):
    df, _, _ = trained
    clean_faults.setenv(
        faults.ENV_VAR, "serve_batch@times=1:kind=slow:delay_ms=400"
    )
    svc = _service(engine, autostart=False)
    filler = [svc.submit(r) for r in df.head(6).to_dict(orient="records")]
    svc.start()
    with pytest.warns(Warning):
        res = svc.query(df.iloc[10].to_dict(), timeout=0.1)
    assert res.shed and res.reason == "timeout"
    for f in filler:
        f.result(timeout=WAIT)
    svc.close()
    timeouts = [e for e in capture.of("request_trace")
                if e.get("reason") == "timeout"]
    assert len(timeouts) == 1
    assert timeouts[0]["outcome"] == "shed"


def test_breaker_shed_closes_trace_with_reason(
    engine, trained, capture, clean_faults
):
    df, _, _ = trained
    clean_faults.setenv(faults.ENV_VAR, "serve_batch@times=2")
    svc = _service(engine, autostart=False, breaker_cooldown_s=30.0)
    wave = df.head(6).to_dict(orient="records")
    with pytest.warns(Warning):
        futures = [svc.submit(dict(r)) for r in wave]
        svc.start()
        [f.result(timeout=WAIT) for f in futures]  # failed batch 1
        for _ in range(2):  # batch 2 opens the breaker; then fail-fast
            futures = [svc.submit(dict(r)) for r in wave]
            [f.result(timeout=WAIT) for f in futures]
    svc.close()
    reasons = {e["reason"] for e in capture.of("request_trace")
               if e["outcome"] == "shed"}
    assert "batch_error" in reasons
    assert "breaker_open" in reasons


def test_trace_survives_mid_traffic_swap(engine, trained, capture):
    df, _, index = trained
    svc = _service(engine)
    records = df.head(60).to_dict(orient="records")
    futures = [svc.submit(dict(r)) for r in records[:30]]
    stats = svc.swap_index(index)  # same content; in-flight drain on old
    post = [svc.submit(dict(r)) for r in records[30:]]
    results = [f.result(timeout=WAIT) for f in futures + post]
    svc.close()
    assert not any(r.shed for r in results)
    assert stats["generation"] >= 1
    delivered = [e for e in capture.of("request_trace")
                 if e["outcome"] == "delivered"]
    assert len(delivered) == len(records)
    for ev in delivered:
        assert _phase_sum(ev) == pytest.approx(
            ev["wall_ms"], rel=0.05, abs=0.05
        ), "attribution must hold across the swap"
    gens = {e["generation"] for e in delivered}
    assert max(gens) == stats["generation"], (
        "post-swap traces must record the new generation"
    )


# ---------------------------------------------------------------------------
# router: hedge/failover trace propagation
# ---------------------------------------------------------------------------


def _wait_for(predicate, timeout=WAIT):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.01)
    return False


@pytest.fixture
def slow_engine(engine, monkeypatch):
    """The engine with 50 ms added to every batch: a primary that answers
    before the router's 1 ms hedge timer thread gets to run (seen once under
    a loaded six-worker run) cancels the hedge, and the race these tests
    are about never happens."""
    real = engine.query_arrays

    def slow(*args, **kwargs):
        time.sleep(0.05)
        return real(*args, **kwargs)

    monkeypatch.setattr(engine, "query_arrays", slow)
    return engine


def test_hedged_race_yields_one_delivered_tree(slow_engine, trained, capture):
    """Both replicas serve the hedged request: the first delivery claims
    the shared root, the second closes `discarded` — never two delivered
    trees for one trace."""
    engine = slow_engine
    df, _, _ = trained
    a = _service(engine, name="replica-a", trace_sample_rate=0.0)
    b = _service(engine, name="replica-b", trace_sample_rate=0.0)
    router = ReplicaRouter([a, b], hedge_ms=1, trace_sample_rate=1.0)
    res = router.query(df.iloc[0].to_dict(), timeout=WAIT)
    assert not res.shed
    # the loser's delivery may land after the winner resolved the caller
    assert _wait_for(
        lambda: len(capture.of("request_trace")) >= 2
    ), "both attempts must close their span trees"
    router.close()
    traces = capture.of("request_trace")
    tid = traces[0]["trace_id"]
    assert all(e["trace_id"] == tid for e in traces), (
        "hedge attempts must share one trace id"
    )
    outcomes = sorted(e["outcome"] for e in traces)
    assert outcomes.count("delivered") == 1, f"double count: {outcomes}"
    assert {e["attempt"] for e in traces} == {0, 1}
    assert router.hedges >= 1


def test_hedge_loser_shed_yields_one_delivered_tree(
    slow_engine, trained, capture
):
    """The satellite race: the hedge attempt lands on a replica that
    SHEDS it (closed) — exactly one delivered tree, and the loser's tree
    carries the machine-readable shed reason."""
    engine = slow_engine
    df, _, _ = trained
    a = _service(engine, name="replica-a", trace_sample_rate=0.0)
    b = _service(engine, name="replica-b", trace_sample_rate=0.0)
    b.close()  # the hedge target sheds everything with reason "closed"
    router = ReplicaRouter([a, b], hedge_ms=1, trace_sample_rate=1.0)
    res = router.query(df.iloc[0].to_dict(), timeout=WAIT)
    assert not res.shed
    assert _wait_for(lambda: len(capture.of("request_trace")) >= 2)
    router.close()
    traces = capture.of("request_trace")
    by_outcome = {}
    for e in traces:
        by_outcome.setdefault(e["outcome"], []).append(e)
    assert len(by_outcome.get("delivered", [])) == 1
    shed = by_outcome.get("shed", [])
    assert len(shed) == 1 and shed[0]["reason"] == "closed"
    assert len({e["trace_id"] for e in traces}) == 1


def test_router_unsampled_keeps_plain_submit_signature(engine, trained):
    """Duck-typed replicas without `accepts_trace` never see a trace
    kwarg, sampled or not (the PR 6 fake-replica contract)."""
    from splink_tpu.serve.service import QueryResult

    class Fake:
        health_state = "healthy"

        def submit(self, record, deadline_ms=None):
            from concurrent.futures import Future

            fut = Future()
            fut.set_result(QueryResult(matches=[("x", 1.0)]))
            return fut

        def latency_summary(self):
            return {}

    router = ReplicaRouter([Fake()], hedge_ms=0, trace_sample_rate=1.0)
    res = router.query({"first_name": "amelia"}, timeout=WAIT)
    assert not res.shed


# ---------------------------------------------------------------------------
# SLO tracker
# ---------------------------------------------------------------------------


def test_slo_burn_rate_math():
    clock = [1000.0]
    slo = SLOTracker(objective=0.99, windows=(10.0, 60.0),
                     clock=lambda: clock[0])
    for _ in range(99):
        slo.observe(True)
    slo.observe(False)  # 1% bad = exactly the error budget
    assert slo.hit_rate(10.0) == pytest.approx(0.99)
    assert slo.burn_rate(10.0) == pytest.approx(1.0)
    assert slo.burn_rate(60.0) == pytest.approx(1.0)
    # the bad sample ages out of the short window but not the long one
    clock[0] += 30.0
    for _ in range(50):
        slo.observe(True)
    assert slo.burn_rate(10.0) == 0.0
    assert slo.burn_rate(60.0) == pytest.approx(
        (1 / 150) / 0.01
    )
    snap = slo.snapshot()
    assert snap["windows"]["10"]["burn_rate"] == 0.0
    assert snap["total_bad"] == 1


def test_slo_alerts_fire_on_both_windows():
    clock = [0.0]
    slo = SLOTracker(objective=0.999, windows=(60.0, 300.0),
                     clock=lambda: clock[0])
    assert slo.alerts() == []  # idle: no samples, no alert
    for _ in range(10):
        slo.observe(False)  # 100% bad: burn = 1/0.001 = 1000
    fired = slo.alerts(pairs=((300.0, 60.0, 14.4),))
    assert fired and fired[0]["long_burn"] >= 14.4
    assert slo.hit_rate(60.0) == 0.0


def test_slo_empty_windows_are_not_violations():
    slo = SLOTracker()
    assert slo.hit_rate(60.0) is None
    assert slo.burn_rate(60.0) == 0.0


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_ring_is_bounded_and_dumps_atomically(tmp_path):
    rec = FlightRecorder(4, dump_dir=str(tmp_path), name="t")
    for i in range(10):
        rec.emit("health", replica="r", **{"from": "healthy"}, seq=i)
    snap = rec.snapshot()
    assert len(snap) == 4 and snap[-1]["seq"] == 9, "ring keeps newest N"
    path = rec.dump("manual")
    assert path and os.path.exists(path)
    events = read_events(path)
    assert events[0]["type"] == "flight_header"
    assert events[0]["trigger"] == "manual"
    assert events[0]["records"] == 4
    assert [e["seq"] for e in events[1:]] == [6, 7, 8, 9]
    # the dump round-trips through the summarize CLI
    assert "flight dump" in summarize_events(events)
    rec.close()


def test_flight_triggers_on_breaker_open_and_rate_limits(tmp_path):
    clock = [0.0]
    rec = FlightRecorder(8, dump_dir=str(tmp_path), name="t",
                         clock=lambda: clock[0])
    rec.emit("degradation", **{"from": "serve_engine", "to": "breaker_open"},
             reason="storm")
    assert len(rec.dumps) == 1, "breaker-open must dump"
    rec.emit("degradation", **{"from": "serve_engine", "to": "breaker_open"},
             reason="storm again")
    assert len(rec.dumps) == 1, "dumps are rate-limited per trigger"
    clock[0] += 2.0
    rec.emit("degradation",
             **{"from": "serve_index_swap", "to": "rolled_back"})
    rec.emit("serve_worker_restart", orphaned=3, crashes=1)
    assert len(rec.dumps) == 3, "rollback and restart are distinct triggers"
    rec.close()


def test_flight_captures_traces_and_disabled_recorder_noops(tmp_path):
    rec = FlightRecorder(8, dump_dir=str(tmp_path))
    rec.note_trace({"type": "request_trace", "outcome": "delivered",
                    "wall_ms": 1.0, "phases_ms": {}})
    assert rec.snapshot()[0]["type"] == "request_trace"
    rec.emit("request_trace", outcome="shed")  # NOT a transition type
    assert len(rec.snapshot()) == 1, "traces enter via note_trace only"
    rec.close()
    off = FlightRecorder(0)
    off.emit("degradation", to="breaker_open")
    assert off.dump("manual") is None and off.snapshot() == []


def test_service_flight_dump_on_breaker_storm(
    engine, trained, clean_faults, tmp_path
):
    """End to end: a breaker storm leaves a post-mortem JSONL containing
    the degradation timeline AND the recent span trees."""
    df, _, _ = trained
    clean_faults.setenv(faults.ENV_VAR, "serve_batch@times=2")
    svc = _service(engine, autostart=False, flight_records=64)
    svc._flight.dump_dir = str(tmp_path)
    register_ambient(svc._flight)
    wave = df.head(6).to_dict(orient="records")
    with pytest.warns(Warning):
        futures = [svc.submit(dict(r)) for r in wave]
        svc.start()
        [f.result(timeout=WAIT) for f in futures]  # failed batch 1
        futures = [svc.submit(dict(r)) for r in wave]
        [f.result(timeout=WAIT) for f in futures]  # batch 2: breaker opens
    assert _wait_for(lambda: svc._flight.dumps), "storm must dump"
    dump = read_events(svc._flight.dumps[0])
    svc.close()
    assert dump[0]["type"] == "flight_header"
    assert dump[0]["trigger"] == "breaker_open"
    types = {e["type"] for e in dump}
    assert "degradation" in types
    assert "request_trace" in types


# ---------------------------------------------------------------------------
# exposition + dashboard
# ---------------------------------------------------------------------------


def test_exposition_serves_prometheus_text():
    import urllib.request

    server = ExpositionServer(0)  # ephemeral port
    server.add_source("test", lambda: [
        Sample("demo_total", 3, {"replica": "a"}, "counter", "a demo"),
        Sample("demo_gauge", 1.5),
    ])
    port = server.start()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ) as resp:
            body = resp.read().decode()
        assert "# TYPE demo_total counter" in body
        assert 'demo_total{replica="a"} 3' in body
        assert "demo_gauge 1.5" in body
        rows = parse_prometheus_text(body)
        assert ("demo_total", {"replica": "a"}, 3.0) in rows
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=5
        ) as resp:
            health = json.loads(resp.read().decode())
        assert health["sources"] == ["test"]
    finally:
        server.close()


def test_exposition_skips_raising_source():
    server = ExpositionServer(0)
    server.add_source("bad", lambda: 1 / 0)
    server.add_source("good", lambda: [Sample("ok_gauge", 1)])
    assert "ok_gauge 1" in server.render()


def test_service_prometheus_samples_and_dash(engine, trained):
    df, _, _ = trained
    svc = _service(engine, name="dash-replica")
    for r in df.head(8).to_dict(orient="records"):
        svc.query(dict(r), timeout=WAIT)
    samples = svc.prometheus_samples()
    svc.close()
    names = {s.name for s in samples}
    assert {
        "splink_serve_served_total",
        "splink_serve_phase_ms",
        "splink_serve_slo_burn_rate",
        "splink_serve_health_rank",
    } <= names
    from splink_tpu.obs.exposition import render_samples

    frame = render_dash(parse_prometheus_text(render_samples(samples)))
    assert "replica dash-replica" in frame
    assert "phase p99 ms" in frame


# ---------------------------------------------------------------------------
# CLI: attribute + summarize sections
# ---------------------------------------------------------------------------


def _fake_trace(wall, phases, outcome="delivered", reason=None):
    return {
        "type": "request_trace",
        "trace_id": f"t{wall}",
        "outcome": outcome,
        "reason": reason,
        "wall_ms": wall,
        "phases_ms": phases,
    }


def test_attribute_report_decomposes_the_tail():
    events = [
        _fake_trace(1.0, {"queue_wait": 0.2, "execute": 0.8})
        for _ in range(99)
    ]
    events.append(
        _fake_trace(100.0, {"queue_wait": 95.0, "execute": 5.0})
    )
    events.append(_fake_trace(0.0, {}, outcome="shed", reason="timeout"))
    report = attribute_events(events)
    assert "p99=100.00" in report
    # the tail request's decomposition: queue_wait dominates
    assert "queue_wait" in report and "95.0%" in report
    assert "timeout=1" in report
    assert attribute_events([]) == (
        "(no delivered request traces in this record)"
    )


def test_summarize_renders_traces_and_blocking_sections():
    events = [
        _fake_trace(2.0, {p: 0.25 for p in PHASES}),
        _fake_trace(0.1, {}, outcome="shed", reason="queue_full"),
        {
            "type": "blocking_device",
            "rules": 1,
            "chunks": 3,
            "pairs": 1234,
            "candidates": 1300,
            "pairs_per_sec": 100000,
            "chunk_budget": 4096,
            "mean_chunk_fill": 0.8,
            "d2h_occupancy_mean": 1.5,
            "d2h_occupancy_max": 2,
            "completed": True,
            "per_rule": [{"rule": "l.a = r.a", "chunks": 3, "pairs": 1234}],
        },
    ]
    out = summarize_events(events)
    assert "request traces: 2 (delivered 1, shed 1)" in out
    assert "queue_full=1" in out
    assert "device blocking: 1 emission run(s)" in out
    assert "l.a = r.a" in out


def test_blocking_device_emission_publishes_stats(capture):
    """Satellite: the device blocking tier reports chunks/pairs/budget/
    D2H occupancy through the ambient channel."""
    from splink_tpu.blocking import block_using_rules
    from splink_tpu.data import encode_table
    from splink_tpu.settings import complete_settings_dict

    df = people_df(80, seed=9)
    settings = complete_settings_dict(
        trace_settings(device_blocking="on")
    )
    table = encode_table(df, settings)
    pairs = block_using_rules(settings, table)
    assert pairs.n_pairs > 0
    events = capture.of("blocking_device")
    assert len(events) == 1
    ev = events[0]
    assert ev["pairs"] == pairs.n_pairs
    assert ev["completed"] is True
    assert ev["chunks"] >= 1
    assert ev["d2h_occupancy_max"] >= 1
    assert 0.0 < ev["mean_chunk_fill"] <= 1.0
    assert len(ev["per_rule"]) == 2
    assert sum(r["pairs"] for r in ev["per_rule"]) == pairs.n_pairs
