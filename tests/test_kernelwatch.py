"""KernelWatch — serve-time execute-latency regression alerting.

Contracts under test:

* the anchor forms from post-warmup observations (cold samples skipped,
  median of the next batch) and the two-window p95 alert fires only past
  the sample floors on BOTH windows — then ages out when the regression
  stops (injected clock; no sleeping);
* the service feeds the watch from signals it already collects (batch
  wall + PhaseProfile splits), publishes edge-triggered
  ``perf_alert``/``perf_clear`` (the alert dumps the flight recorder with
  the window snapshot inside) and periodic ``perf_window`` reports, and
  adds ZERO steady-state compile requests;
* the Prometheus exposition carries the perf gauges, the per-phase
  native histogram and the process-level gauges, in scrape format;
* ``obs summarize`` renders perf_window/perf_alert/perf_clear with the
  torn-record or-0 tolerance;
* RunContext feeds each offline stage's execute split into a per-run
  watch whose snapshot lands in the run record.
"""

import json

import numpy as np
import pandas as pd
import pytest

from splink_tpu import Splink
from splink_tpu.obs.cli import summarize_events
from splink_tpu.obs.events import (
    read_events,
    register_ambient,
    unregister_ambient,
)
from splink_tpu.obs.exposition import process_samples, render_samples
from splink_tpu.obs.kernelwatch import (
    ANCHOR_SAMPLES,
    ANCHOR_SKIP,
    MIN_LONG_SAMPLES,
    MIN_SHORT_SAMPLES,
    KernelWatch,
)
from splink_tpu.serve import BucketPolicy, LinkageService, QueryEngine

WAIT = 60


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _fed(watch, phase="batch", n=ANCHOR_SKIP + ANCHOR_SAMPLES, v=0.005):
    for _ in range(n):
        watch.observe(phase, v)


# ---------------------------------------------------------------------------
# unit tier
# ---------------------------------------------------------------------------


def test_anchor_forms_after_warmup():
    clk = _Clock()
    kw = KernelWatch(window_s=10.0, alert_ratio=3.0, clock=clk)
    for i in range(ANCHOR_SKIP):
        kw.observe("batch", 99.0)  # cold samples: never the anchor
    assert kw.phase_stats("batch")["anchor_ms"] is None
    _fed(kw, n=ANCHOR_SAMPLES, v=0.004)
    st = kw.phase_stats("batch")
    assert st["anchor_ms"] == pytest.approx(4.0)
    # the cold samples entered neither the anchor nor the windows
    assert st["short"]["p95_ms"] == pytest.approx(4.0)


def test_two_window_alert_fires_and_ages_out():
    clk = _Clock()
    kw = KernelWatch(window_s=10.0, alert_ratio=3.0, clock=clk)
    _fed(kw, v=0.005)
    assert kw.alerts() == []  # steady state: no alert
    # sustained regression past 3x the 5ms anchor on both windows
    for _ in range(max(MIN_LONG_SAMPLES, MIN_SHORT_SAMPLES)):
        kw.observe("batch", 0.05)
    fired = kw.alerts()
    assert [a["phase"] for a in fired] == ["batch"]
    a = fired[0]
    assert a["anchor_ms"] == pytest.approx(5.0)
    assert a["short_p95_ms"] >= 3.0 * a["anchor_ms"]
    assert a["threshold"] == 3.0
    # the regression stops and the windows age out: the alert clears
    clk.t += kw.long_window_s + 1.0
    assert kw.alerts() == []


def test_single_slow_batch_cannot_alert():
    """One scheduler hiccup is not a regression: the p95 excludes the
    single largest window sample from rank eligibility, so one outlier —
    however extreme — cannot fire; a second one can start to."""
    kw = KernelWatch(window_s=10.0, alert_ratio=3.0, clock=_Clock())
    _fed(kw, v=0.005)
    kw.observe("batch", 5.0)  # a 1000x outlier, once
    assert kw.alerts() == []
    st = kw.phase_stats("batch")
    assert st["short"]["p95_ms"] == pytest.approx(5.0)  # still the anchor
    # and below the sample floors nothing alerts, however slow
    kw2 = KernelWatch(window_s=10.0, alert_ratio=3.0, clock=_Clock())
    for _ in range(ANCHOR_SKIP + ANCHOR_SAMPLES):
        kw2.observe("batch", 0.005)
    stats = {"batch": kw2.phase_stats("batch")}
    stats["batch"]["short"]["n"] = MIN_SHORT_SAMPLES - 1
    stats["batch"]["short"]["p95_ms"] = 999.0
    stats["batch"]["long"]["p95_ms"] = 999.0
    assert kw2.alerts(stats) == []


def test_heavy_tailed_noise_cannot_alert_without_median_shift():
    """Scheduler jitter on a loaded host moves the window p95 past the
    ratio while the median stays at the anchor — the sustained-regression
    confirmation (short-window p50 must also cross) keeps that from
    firing; a real regression moves both and fires."""
    clk = _Clock()
    kw = KernelWatch(window_s=10.0, alert_ratio=3.0, clock=clk)
    _fed(kw, v=0.005)
    # a quarter of the window 10x slow: p95 over 3x, median at the anchor
    for i in range(MIN_LONG_SAMPLES):
        kw.observe("batch", 0.05 if i % 4 == 0 else 0.005)
    st = kw.phase_stats("batch")
    assert st["short"]["p95_ms"] >= 3.0 * st["anchor_ms"]
    assert st["short"]["p50_ms"] == pytest.approx(st["anchor_ms"])
    assert kw.alerts() == []
    # the regression becomes sustained: the fast samples age out of the
    # short window, the median crosses, and the alert fires
    clk.t += kw.window_s + 1.0
    for _ in range(MIN_LONG_SAMPLES):
        kw.observe("batch", 0.05)
    fired = kw.alerts()
    assert [a["phase"] for a in fired] == ["batch"]
    assert fired[0]["short_p50_ms"] >= 3.0 * fired[0]["anchor_ms"]


def test_alert_ratio_zero_disables_alerting_not_measurement():
    kw = KernelWatch(window_s=10.0, alert_ratio=0.0, clock=_Clock())
    _fed(kw, v=0.005)
    for _ in range(MIN_LONG_SAMPLES):
        kw.observe("batch", 5.0)
    assert kw.alerts() == []
    st = kw.phase_stats("batch")
    assert st["ewma_ms"] is not None
    assert st["observations"] > 0


def test_ewma_and_histogram_accumulate():
    kw = KernelWatch(window_s=10.0, alert_ratio=3.0, clock=_Clock())
    _fed(kw, v=0.004)
    st = kw.phase_stats("batch")
    assert st["ewma_ms"] == pytest.approx(4.0, rel=0.01)
    counts, edges, total, n = kw.histogram("batch")
    assert sum(counts) == ANCHOR_SAMPLES == n
    assert total == pytest.approx(0.004 * ANCHOR_SAMPLES)
    # 4ms lands in the first bucket whose edge >= 4ms
    idx = next(i for i, e in enumerate(edges) if 0.004 <= e)
    assert counts[idx] == ANCHOR_SAMPLES
    # a past-last-edge sample counts in n/sum but NO finite bucket — the
    # exposition's +Inf bucket holds it (clamping would claim a 10000s
    # batch ran under the last edge)
    kw.observe("batch", 1e4)
    counts, _, total, n = kw.histogram("batch")
    assert counts[-1] == 0
    assert n == ANCHOR_SAMPLES + 1 == sum(counts) + 1
    assert total == pytest.approx(0.004 * ANCHOR_SAMPLES + 1e4)
    assert kw.histogram("nope") is None


def test_bad_observations_dropped():
    kw = KernelWatch(window_s=10.0, alert_ratio=3.0, clock=_Clock())
    kw.observe("batch", float("nan"))
    kw.observe("batch", -1.0)
    kw.observe("batch", None)
    kw.observe("batch", "slow")
    assert kw.phases() == []


def test_snapshot_shape():
    kw = KernelWatch(window_s=7.0, alert_ratio=2.0, clock=_Clock())
    _fed(kw, phase="execute", v=0.002)
    snap = kw.snapshot()
    assert snap["window_s"] == 7.0
    assert snap["long_window_s"] == 35.0
    assert "execute" in snap["phases"]
    assert snap["alerts"] == []
    json.dumps(snap)  # JSON-ready: the flight dump payload contract


# ---------------------------------------------------------------------------
# service integration
# ---------------------------------------------------------------------------


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


def perf_settings(**over):
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
        "blocking_rules": ["l.dob = r.dob"],
        "max_iterations": 3,
        "serve_top_k": 4,
        "serve_probe_queries": 0,
    }
    s.update(over)
    return s


@pytest.fixture(scope="module")
def engine():
    df = people_df()
    linker = Splink(perf_settings(), df=df)
    linker.estimate_parameters()
    index = linker.export_index()
    eng = QueryEngine(index, policy=BucketPolicy((16,), (64, 256)))
    eng.warmup()
    return df, eng


class _Capture:
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


def _serve(svc, df, n=8):
    futs = [
        svc.submit(dict(r))
        for r in df.sample(n, random_state=1)
        .drop(columns=["unique_id"])
        .to_dict(orient="records")
    ]
    return [f.result(timeout=WAIT) for f in futs]


def test_service_feeds_watch_without_recompiles(engine):
    from splink_tpu.obs.metrics import compile_requests, install_compile_monitor

    install_compile_monitor()
    df, eng = engine
    svc = LinkageService(eng, deadline_ms=1.0)
    assert svc._kwatch is not None, "perf_alert_ratio defaults on"
    try:
        _serve(svc, df)  # cover the warmed shapes once
        c0 = compile_requests()
        for _ in range(4):
            res = _serve(svc, df)
            assert not any(r.shed for r in res)
        assert compile_requests() - c0 == 0, (
            "the kernel watch must not add steady-state compile requests"
        )
        phases = svc._kwatch.phases()
        assert "batch" in phases
        # the execute/transfer splits ride the engine's existing profile
        assert "execute" in phases
        assert "transfer" in phases
        snap = svc.perf_snapshot()
        assert snap["enabled"] is True
        assert snap["alert_active"] is False
    finally:
        svc.close()


def test_watch_disabled_by_ratio_zero(engine):
    df, eng = engine
    svc = LinkageService(eng, deadline_ms=1.0, perf_alert_ratio=0)
    try:
        _serve(svc, df, n=4)
        snap = svc.perf_snapshot()
        assert snap["enabled"] is False
        assert "perf_alert_ratio" in snap["reason"]
        assert svc._kwatch is None
    finally:
        svc.close()


def test_swap_index_reanchors_the_watch(engine, monkeypatch):
    """An index hot-swap changes the legitimate steady-state cost of
    every phase: the service must rebind a FRESH KernelWatch (the anchor
    only ever forms once) and drop any active alert, exactly like the
    drift monitor — a stale anchor would judge the new index against the
    old one's speed and latch a false alert forever."""
    df, eng = engine
    svc = LinkageService(
        eng, deadline_ms=1.0, perf_alert_ratio=3.0, perf_window_s=5.0
    )
    try:
        old = svc._kwatch
        _fed(old, v=0.005)
        assert old.phase_stats("batch")["anchor_ms"] is not None
        svc._perf_alert_active = True
        monkeypatch.setattr(
            eng, "swap_index",
            lambda source, refresh_probes=False: {"swapped": True},
        )
        svc.swap_index("new-index-dir")
        assert svc._kwatch is not old
        assert svc._kwatch.phases() == []  # re-anchors on post-swap traffic
        assert svc._kwatch.window_s == old.window_s
        assert svc._kwatch.alert_ratio == old.alert_ratio
        assert svc._perf_alert_active is False
    finally:
        svc.close()


def test_perf_alert_edge_events_and_flight_dump(engine, capture, tmp_path):
    """A sustained regression fires ONE perf_alert (with the window
    snapshot), dumps the flight recorder, and recovery publishes ONE
    perf_clear — edge-triggered, level-held."""
    df, eng = engine
    svc = LinkageService(
        eng, deadline_ms=1.0, perf_alert_ratio=3.0, perf_window_s=5.0
    )
    svc._flight.dump_dir = str(tmp_path / "flight")
    clk = _Clock()
    kw = KernelWatch(window_s=5.0, alert_ratio=3.0, clock=clk)
    svc._kwatch = kw
    try:
        _fed(kw, v=0.005)
        svc._perf_tick(force=True)
        assert capture.of("perf_alert") == []
        for _ in range(MIN_LONG_SAMPLES):
            kw.observe("batch", 0.1)
        svc._perf_tick(force=True)
        svc._perf_tick(force=True)  # level held: still exactly one edge event
        alerts = capture.of("perf_alert")
        assert len(alerts) == 1
        assert alerts[0]["replica"] == svc.name
        assert alerts[0]["alerts"][0]["phase"] == "batch"
        # the event carries the full window snapshot (the dump payload)
        assert "batch" in alerts[0]["snapshot"]["phases"]
        assert svc.perf_snapshot()["alert_active"] is True
        deadline = 50
        while not svc._flight.dumps and deadline:
            deadline -= 1
            import time as _t

            _t.sleep(0.05)
        assert svc._flight.dumps, "perf_alert must dump the flight recorder"
        dump = read_events(svc._flight.dumps[0])
        assert dump[0]["trigger"] == "perf_alert"
        assert any(e.get("type") == "perf_alert" for e in dump)
        # regression ends: windows age out, ONE perf_clear
        clk.t += kw.long_window_s + 1.0
        svc._perf_tick(force=True)
        svc._perf_tick(force=True)
        assert len(capture.of("perf_clear")) == 1
        assert svc.perf_snapshot()["alert_active"] is False
    finally:
        svc.close()


def test_perf_window_reports_published(engine, capture):
    df, eng = engine
    svc = LinkageService(
        eng, deadline_ms=1.0, perf_alert_ratio=3.0, perf_window_s=0.2
    )
    try:
        # feed past the anchor warmup deterministically, then tick
        for _ in range(ANCHOR_SKIP + 4):
            svc._kwatch.observe("batch", 0.004)
        svc._perf_tick(force=True)
        assert capture.of("perf_window"), "periodic perf_window must publish"
        ev = capture.of("perf_window")[-1]
        assert ev["replica"] == svc.name
        assert "batch" in ev["phases"]
        assert ev["phases"]["batch"]["n"] > 0
    finally:
        svc.close()


def test_prometheus_perf_and_process_series(engine):
    df, eng = engine
    svc = LinkageService(eng, deadline_ms=1.0)
    try:
        # serve enough waves that the batch/execute/transfer rings hold
        # post-warmup samples (the first ANCHOR_SKIP batches are cold)
        for _ in range(ANCHOR_SKIP + 5):
            _serve(svc, df)
        text = render_samples(svc.prometheus_samples())
    finally:
        svc.close()
    assert "splink_serve_perf_watch" in text
    assert "splink_serve_perf_alert" in text
    assert 'splink_serve_perf_ewma_ms{phase="batch"' in text
    # the per-phase execute-time distribution is a NATIVE histogram
    assert "# TYPE splink_serve_phase_seconds histogram" in text
    assert 'splink_serve_phase_seconds_bucket{le="+Inf"' in text
    assert "splink_serve_phase_seconds_sum" in text
    # process-level gauges ride the same exposition
    assert "process_cpu_seconds_total" in text
    assert "process_start_time_seconds" in text


def test_process_samples_scrape_format():
    text = render_samples(process_samples())
    assert "# TYPE process_cpu_seconds_total counter" in text
    assert "process_uptime_seconds" in text
    # every row parses as "<name>[{labels}] <float>"
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        name, value = line.rsplit(" ", 1)
        float(value)
        assert name


# ---------------------------------------------------------------------------
# summarize / CLI rendering
# ---------------------------------------------------------------------------


def test_summarize_renders_perf_events():
    events = [
        {"type": "perf_window", "mono": 1.0, "replica": "serve",
         "window_s": 30.0,
         "phases": {"batch": {"anchor_ms": 5.0, "ewma_ms": 6.1,
                              "p95_ms": 7.5, "n": 40}}},
        {"type": "perf_alert", "mono": 2.0, "replica": "serve",
         "alerts": [{"phase": "batch", "anchor_ms": 5.0,
                     "short_p95_ms": 40.0, "long_p95_ms": 35.0,
                     "ratio": 8.0, "threshold": 3.0, "window_s": 30.0,
                     "long_window_s": 150.0}]},
        {"type": "perf_clear", "mono": 3.0, "replica": "serve"},
    ]
    out = summarize_events(events)
    assert "kernel perf: 1 window report(s), 1 alert(s)" in out
    assert "ALERT batch" in out
    assert "8.0x >= 3.0x" in out
    assert "alert cleared" in out


def test_summarize_tolerates_torn_perf_records():
    """The or-0 torn-record contract: missing fields render as 0, never
    crash — and a torn alert record still renders its line."""
    events = [
        {"type": "perf_window", "mono": 1.0, "phases": {"batch": {}}},
        {"type": "perf_window", "mono": 1.5, "phases": None},
        {"type": "perf_alert", "mono": 2.0, "alerts": [{}]},
        {"type": "perf_alert", "mono": 2.5},
        {"type": "perf_clear", "mono": 3.0},
    ]
    out = summarize_events(events)
    assert "kernel perf" in out
    assert "ALERT ?" in out


def test_runcontext_stage_kernelwatch(tmp_path):
    from splink_tpu.obs.runtime import RunContext

    ctx = RunContext.from_settings({"telemetry_dir": str(tmp_path)})
    assert ctx.enabled
    with ctx.span("encode"):
        pass
    with ctx.span("score"):
        pass
    ctx.finish()
    ctx.close()
    events = read_events(ctx.sink.path)
    metrics = [e for e in events if e.get("type") == "metrics"][-1]
    watch = metrics["records"]["kernel_watch"]
    assert set(watch["phases"]) == {"encode", "score"}
    assert watch["alerts"] == []  # offline: alerting disabled by design
