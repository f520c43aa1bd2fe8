"""Multi-host serving wire tier (splink_tpu/serve/wire.py + remote.py).

Frame-layer tiers (no jax): encode/read round-trip, the hostile
length-prefix rejection (bounded read — the 4-byte header is all that is
ever read of an oversized frame), torn frames, corrupt payloads, envelope
version mismatch, and concurrent submits interleaving on one connection.

Link-robustness tiers (fake service behind a real socket): in-flight
sheds on connection loss, deadline/timeout sweeping, per-remote breaker
open/fail-fast/recover, background reconnect with backoff, partition +
heal, and the piggybacked-health demotion path. Every test asserts the
core contract: no future hangs, no exception escapes through a future,
every shed carries a machine-readable reason.

Parity tier (one module-scoped trained fixture): remote answers are
BIT-identical to the same queries served locally against the same index —
JSON float serialisation round-trips every double exactly, so the wire
may not change a single probability.
"""

import socket
import struct
import threading
import time
from concurrent.futures import Future

import numpy as np
import pandas as pd
import pytest

from splink_tpu import Splink
from splink_tpu.obs import events
from splink_tpu.resilience import faults
from splink_tpu.resilience.retry import RetryPolicy
from splink_tpu.serve import (
    BucketPolicy,
    LinkageService,
    QueryEngine,
    QueryResult,
    RemoteReplica,
    Replica,
    ReplicaRouter,
    WireServer,
)
from splink_tpu.serve.wire import (
    WIRE_VERSION,
    CorruptFrame,
    FrameTooLarge,
    TornFrame,
    encode_frame,
    read_frame,
)

WAIT = 30  # "never hangs" budget per future

FAST_RETRY = RetryPolicy(base_delay=0.02, max_delay=0.1)


# ---------------------------------------------------------------------------
# Frame layer (no server)
# ---------------------------------------------------------------------------


def test_frame_roundtrip_over_socketpair():
    a, b = socket.socketpair()
    try:
        env = {"v": WIRE_VERSION, "kind": "query", "id": 7,
               "record": {"first_name": "amelia", "n": 3}}
        a.sendall(encode_frame(env))
        assert read_frame(b) == env
        # numpy payloads sanitise to Python types on encode
        a.sendall(encode_frame({"p": np.float32(0.25), "u": np.int64(9)}))
        got = read_frame(b)
        assert got == {"p": 0.25, "u": 9}
        assert isinstance(got["u"], int)
    finally:
        a.close()
        b.close()


def test_frame_clean_eof_returns_none():
    a, b = socket.socketpair()
    a.close()
    try:
        assert read_frame(b) is None
    finally:
        b.close()


def test_oversized_outbound_frame_raises_before_write():
    with pytest.raises(FrameTooLarge):
        encode_frame({"blob": "x" * 1000}, max_bytes=64)


def test_hostile_length_prefix_rejected_without_payload_read():
    """A prefix declaring 2 GiB is rejected after the 4-byte header: the
    reader raises without a single payload recv (nothing was sent, so a
    read attempt would block — completing instantly proves the bound)."""
    a, b = socket.socketpair()
    try:
        a.sendall(struct.pack(">I", 2**31))
        b.settimeout(2.0)  # a payload read would hit this and fail
        t0 = time.monotonic()
        with pytest.raises(FrameTooLarge):
            read_frame(b, max_bytes=1024)
        assert time.monotonic() - t0 < 1.0
    finally:
        a.close()
        b.close()


def test_torn_frame_raises():
    a, b = socket.socketpair()
    try:
        frame = encode_frame({"v": WIRE_VERSION, "kind": "query", "id": 1})
        a.sendall(frame[: len(frame) // 2])
        a.close()
        with pytest.raises(TornFrame):
            read_frame(b)
    finally:
        b.close()


def test_corrupt_payload_raises_corrupt_frame():
    a, b = socket.socketpair()
    try:
        payload = b"not json at all"
        a.sendall(struct.pack(">I", len(payload)) + payload)
        with pytest.raises(CorruptFrame):
            read_frame(b)
        # a JSON scalar is intact framing but not an envelope
        payload = b"42"
        a.sendall(struct.pack(">I", len(payload)) + payload)
        with pytest.raises(CorruptFrame):
            read_frame(b)
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# Server + client over a fake replica (no jax)
# ---------------------------------------------------------------------------


class FakeService:
    """Replica-shaped fake: resolves each submit on its own timer thread
    so responses complete out of order when delays say so."""

    name = "fake"
    accepts_trace = False

    def __init__(self, health_state="healthy"):
        self.health_state = health_state
        self.submissions = 0

    def submit(self, record, deadline_ms=None):
        self.submissions += 1
        fut = Future()
        delay = float(record.get("delay") or 0.0)
        res = QueryResult(
            matches=[(record.get("tag", "u"), 0.5)], n_candidates=1
        )
        if record.get("shed_reason"):
            res = QueryResult(shed=True, reason=record["shed_reason"])
        if delay:
            t = threading.Timer(delay, fut.set_result, [res])
            t.daemon = True
            t.start()
        else:
            fut.set_result(res)
        return fut

    def health(self):
        return {"state": self.health_state, "replica": self.name}

    def latency_summary(self):
        return {"p95_ms": 1.0}


@pytest.fixture()
def fake_server():
    svc = FakeService()
    server = WireServer(svc).start()
    yield svc, server
    server.close()


def _remote(server, **over):
    kw = dict(pool_size=1, retry_policy=FAST_RETRY,
              breaker_cooldown_s=0.1, request_timeout_ms=5_000.0)
    kw.update(over)
    return RemoteReplica(("127.0.0.1", server.port), **kw)


@pytest.fixture()
def clean_faults(monkeypatch):
    faults.reset_plans()
    monkeypatch.delenv(faults.ENV_VAR, raising=False)
    yield monkeypatch
    faults.reset_plans()


def test_remote_submit_roundtrip(fake_server):
    _, server = fake_server
    rep = _remote(server)
    try:
        res = rep.submit({"tag": "r1"}).result(timeout=WAIT)
        assert not res.shed and res.matches == [("r1", 0.5)]
        assert res.n_candidates == 1
    finally:
        rep.close()


def test_remote_propagates_server_side_shed_reason(fake_server):
    _, server = fake_server
    rep = _remote(server)
    try:
        res = rep.submit({"shed_reason": "queue_full"}).result(timeout=WAIT)
        assert res.shed and res.reason == "queue_full"
    finally:
        rep.close()


def test_concurrent_submits_interleave_on_one_connection(fake_server):
    """A slow request must not convoy fast ones behind it on the same
    connection: responses demultiplex by id, out of order."""
    _, server = fake_server
    rep = _remote(server, pool_size=1)
    try:
        f_slow = rep.submit({"delay": 0.5, "tag": "slow"})
        fasts = [rep.submit({"tag": f"fast{i}"}) for i in range(8)]
        t0 = time.monotonic()
        for i, f in enumerate(fasts):
            res = f.result(timeout=WAIT)
            assert not res.shed and res.matches == [(f"fast{i}", 0.5)]
        assert time.monotonic() - t0 < 0.4  # did not wait for the slow one
        res = f_slow.result(timeout=WAIT)
        assert not res.shed and res.matches == [("slow", 0.5)]
    finally:
        rep.close()


def test_version_mismatch_rejected_without_poisoning_connection(fake_server):
    """A wrong-version envelope gets an error reply; the connection keeps
    serving correctly-versioned requests interleaved behind it."""
    _, server = fake_server
    sock = socket.create_connection(("127.0.0.1", server.port), timeout=5)
    try:
        sock.sendall(encode_frame({"v": 99, "kind": "query", "id": 1,
                                   "record": {}}))
        env = read_frame(sock)
        assert env["kind"] == "error" and env["reason"] == "version_mismatch"
        assert env["id"] == 1
        sock.sendall(encode_frame({"v": WIRE_VERSION, "kind": "query",
                                   "id": 2, "record": {"tag": "ok"}}))
        env = read_frame(sock)
        assert env["kind"] == "result" and env["id"] == 2
        assert env["result"]["matches"] == [["ok", 0.5]]
    finally:
        sock.close()


def test_corrupt_payload_rejected_without_poisoning_connection(fake_server):
    _, server = fake_server
    sock = socket.create_connection(("127.0.0.1", server.port), timeout=5)
    try:
        payload = b"{torn json"
        sock.sendall(struct.pack(">I", len(payload)) + payload)
        env = read_frame(sock)
        assert env["kind"] == "error" and env["reason"] == "bad_frame"
        sock.sendall(encode_frame({"v": WIRE_VERSION, "kind": "query",
                                   "id": 3, "record": {"tag": "ok"}}))
        env = read_frame(sock)
        assert env["kind"] == "result" and env["id"] == 3
    finally:
        sock.close()


def test_hostile_prefix_gets_error_envelope_then_close(fake_server):
    """Server-side bounded read: a 1 GiB length prefix is answered with a
    frame_too_large error envelope and the connection closes — without
    the server ever reading (or allocating) the declared payload."""
    _, server = fake_server
    sock = socket.create_connection(("127.0.0.1", server.port), timeout=5)
    try:
        sock.sendall(struct.pack(">I", 2**30))
        env = read_frame(sock)
        assert env["kind"] == "error" and env["reason"] == "frame_too_large"
        assert read_frame(sock) is None  # server closed the stream
    finally:
        sock.close()


def test_health_piggybacked_on_every_response(fake_server):
    svc, server = fake_server
    rep = _remote(server)
    try:
        assert rep.submit({}).result(timeout=WAIT).shed is False
        assert rep.health_state == "healthy"
        svc.health_state = "degraded"
        assert rep.submit({}).result(timeout=WAIT).shed is False
        # the router's next ranking read sees the demotion, no watchdog
        # cadence involved
        assert rep.health_state == "degraded"
    finally:
        rep.close()


def test_kill_mid_request_sheds_inflight_machine_readably(fake_server):
    _, server = fake_server
    rep = _remote(server)
    try:
        fut = rep.submit({"delay": 10.0})
        time.sleep(0.1)
        server.kill()
        res = fut.result(timeout=WAIT)  # no hang
        assert res.shed and res.reason == "connection_lost"
    finally:
        rep.close()


def test_expired_deadline_sheds_before_dialing(fake_server):
    _, server = fake_server
    rep = _remote(server)
    try:
        res = rep.submit({}, deadline_ms=0).result(timeout=WAIT)
        assert res.shed and res.reason == "deadline"
    finally:
        rep.close()


def test_deadline_swept_clientside_when_server_stalls(fake_server):
    """A request whose deadline lapses in flight resolves shed client-
    side — the far side being wedged cannot hang the router."""
    _, server = fake_server
    rep = _remote(server)
    try:
        res = rep.submit({"delay": 5.0}, deadline_ms=80).result(timeout=WAIT)
        assert res.shed and res.reason == "deadline"
    finally:
        rep.close()


def test_request_timeout_bounds_deadline_less_requests(fake_server):
    _, server = fake_server
    rep = _remote(server, request_timeout_ms=100.0)
    try:
        res = rep.submit({"delay": 5.0}).result(timeout=WAIT)
        assert res.shed and res.reason == "timeout"
    finally:
        rep.close()


def test_breaker_opens_fails_fast_and_recovers(fake_server):
    svc, server = fake_server
    port = server.port
    rep = _remote(server, breaker_threshold=2, breaker_cooldown_s=0.1,
                  connect_timeout_ms=100.0)
    try:
        assert rep.submit({}).result(timeout=WAIT).shed is False
        server.kill()
        time.sleep(0.05)
        reasons = {rep.submit({}).result(timeout=WAIT).reason
                   for _ in range(6)}
        assert "breaker_open" in reasons
        assert reasons <= {"connection_lost", "remote_unreachable",
                           "breaker_open"}
        assert rep.health_state == "broken"
        # restart on the same port: the reconnector's handshake closes
        # the breaker and traffic resumes
        server2 = WireServer(svc, port=port).start()
        try:
            deadline = time.monotonic() + WAIT
            while time.monotonic() < deadline:
                if not rep.submit({}).result(timeout=WAIT).shed:
                    break
                time.sleep(0.05)
            else:
                pytest.fail("remote never recovered after server restart")
            assert rep.breaker.state == "closed"
            assert rep.reconnects >= 1
        finally:
            server2.close()
    finally:
        rep.close()


def test_partition_heals_and_publishes_events(fake_server):
    _, server = fake_server
    rep = _remote(server)
    captured = []

    class _Sink:
        def emit(self, kind, **fields):
            captured.append((kind, fields))

    sink = _Sink()
    events.register_ambient(sink)
    try:
        assert rep.submit({}).result(timeout=WAIT).shed is False
        # held in flight on the connection the partition drops: the
        # `wire_shed` burst below is published for it, not for a submit
        # that may find the link already down (a race with the reader)
        held = rep.submit({"delay": 5.0})
        server.partition(0.3)
        lost = held.result(timeout=WAIT)
        assert lost.shed and lost.reason == "connection_lost"
        res = rep.submit({}).result(timeout=WAIT)
        assert res.shed and res.reason in (
            "connection_lost", "remote_unreachable", "breaker_open"
        )
        deadline = time.monotonic() + WAIT
        while time.monotonic() < deadline:
            if not rep.submit({}).result(timeout=WAIT).shed:
                break
            time.sleep(0.05)
        else:
            pytest.fail("remote never recovered after partition heal")
    finally:
        events.unregister_ambient(sink)
        rep.close()
    kinds = {k for k, _ in captured}
    assert "wire_partition_heal" in kinds
    assert "wire_reconnect" in kinds
    sheds = [f for k, f in captured if k == "wire_shed"]
    assert sheds and all(f.get("reason") for f in sheds)


def test_net_fault_kinds_parse_and_fire(clean_faults):
    plan = faults.FaultPlan.from_spec(
        "wire_response@kind=net_torn_frame,"
        "wire_accept@kind=net_partition:delay_ms=120"
    )
    with pytest.raises(faults.InjectedFault) as e:
        plan.fire("wire_response", request=1)
    assert e.value.kind == "net_torn_frame"
    with pytest.raises(faults.InjectedFault) as e:
        plan.fire("wire_accept", conn=1)
    assert e.value.kind == "net_partition" and e.value.delay_ms == 120
    # net_delay stalls and continues, like slow
    plan = faults.FaultPlan.from_spec("wire_request@kind=net_delay:delay_ms=60")
    t0 = time.monotonic()
    plan.fire("wire_request", request=1)
    assert time.monotonic() - t0 >= 0.05


def test_injected_torn_response_sheds_then_connection_recovers(
    fake_server, clean_faults
):
    """net_torn_frame on the response path: the client detects the torn
    frame, sheds the in-flight request, reconnects and serves again —
    the torn frame never poisons protocol state."""
    _, server = fake_server
    rep = _remote(server)
    try:
        clean_faults.setenv(
            faults.ENV_VAR, "wire_response@kind=net_torn_frame"
        )
        res = rep.submit({}).result(timeout=WAIT)
        assert res.shed and res.reason == "connection_lost"
        clean_faults.delenv(faults.ENV_VAR)
        faults.reset_plans()
        deadline = time.monotonic() + WAIT
        while time.monotonic() < deadline:
            if not rep.submit({}).result(timeout=WAIT).shed:
                break
            time.sleep(0.05)
        else:
            pytest.fail("remote never recovered after torn frame")
    finally:
        rep.close()


def test_closed_remote_sheds_closed(fake_server):
    _, server = fake_server
    rep = _remote(server)
    rep.close()
    res = rep.submit({}).result(timeout=WAIT)
    assert res.shed and res.reason == "closed"
    rep.close()  # idempotent


def test_router_fails_over_from_killed_remote_to_live_remote():
    svc_a, svc_b = FakeService(), FakeService()
    server_a = WireServer(svc_a).start()
    server_b = WireServer(svc_b).start()
    rep_a = _remote(server_a)
    rep_b = _remote(server_b)
    try:
        router = ReplicaRouter([rep_a, rep_b], hedge_ms=0)
        assert not router.query({"tag": "warm"}, timeout=WAIT).shed
        fut = rep_a.submit({"delay": 10.0})  # park one in flight
        server_a.kill()
        assert fut.result(timeout=WAIT).shed  # sheds, frees the router
        res = router.query({"tag": "after"}, timeout=WAIT)
        assert not res.shed and res.matches == [("after", 0.5)]
    finally:
        rep_a.close()
        rep_b.close()
        server_a.kill()
        server_b.close()


def test_remote_latency_summary_feeds_hedger(fake_server):
    _, server = fake_server
    rep = _remote(server)
    try:
        for _ in range(5):
            assert not rep.submit({}).result(timeout=WAIT).shed
        summary = rep.latency_summary()
        assert summary["p95_ms"] > 0
        assert summary["served"] == 5
    finally:
        rep.close()


# ---------------------------------------------------------------------------
# obs: summarize rendering + flight transition registration
# ---------------------------------------------------------------------------


def test_summarize_renders_wire_events():
    from splink_tpu.obs.cli import summarize_events

    evs = [
        {"type": "wire_connect", "mono": 1.0, "server": "wire:serve",
         "peer": "127.0.0.1:5", "conn": 1},
        {"type": "wire_shed", "mono": 2.0, "replica": "remote:h:1",
         "reason": "connection_lost", "n": 3},
        {"type": "wire_reconnect", "mono": 3.0, "replica": "remote:h:1",
         "attempts": 4, "downtime_s": 1.25},
        {"type": "wire_partition_heal", "mono": 4.0, "server": "wire:serve",
         "duration_s": 0.5, "dropped": 2},
    ]
    out = summarize_events(evs)
    assert ("wire tier: 1 connect(s), 0 disconnect(s), 1 reconnect(s), "
            "1 shed burst(s), 1 partition heal(s)") in out
    assert "shed remote:h:1: 3 x connection_lost" in out
    assert "reconnect remote:h:1: 4 attempt(s), 1.25s down" in out
    assert "partition heal wire:serve: 0.5s, 2 connection(s) dropped" in out


def test_summarize_tolerates_torn_wire_records():
    from splink_tpu.obs.cli import summarize_events

    evs = [
        {"type": "wire_shed", "mono": 1.0},
        {"type": "wire_reconnect", "mono": 2.0},
        {"type": "wire_partition_heal", "mono": 3.0},
    ]
    out = summarize_events(evs)
    assert "wire tier" in out
    assert "shed ?: 0 x ?" in out
    assert "reconnect ?: 0 attempt(s), 0s down" in out


def test_wire_reconnect_is_a_flight_transition():
    from splink_tpu.obs.flight import TRANSITION_TYPES, FlightRecorder

    assert "wire_reconnect" in TRANSITION_TYPES
    rec = FlightRecorder(8)
    rec.emit("wire_reconnect", replica="r", attempts=1, downtime_s=0.1)
    assert any(
        r.get("type") == "wire_reconnect" for r in rec.snapshot()
    )


# ---------------------------------------------------------------------------
# Parity tier: remote answers bit-identical to local (real engine)
# ---------------------------------------------------------------------------


def people_df(n=80, seed=11):
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


@pytest.fixture(scope="module")
def trained():
    settings = {
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
        "max_iterations": 4,
    }
    df = people_df()
    linker = Splink(settings, df=df)
    linker.estimate_parameters()
    index = linker.export_index()
    return df, index


def test_remote_answers_bit_identical_to_local(trained):
    """The parity acceptance criterion: every (query, match, probability)
    triple served over the wire equals the locally served one exactly —
    same matches, same order, same float bits."""
    df, index = trained
    engine = QueryEngine(index, policy=BucketPolicy((16,), (64, 256)))
    engine.warmup()
    svc = LinkageService(engine, deadline_ms=None)
    server = WireServer(svc).start()
    rep = _remote(server, pool_size=2)
    try:
        records = df.to_dict(orient="records")[:40]
        local = [
            svc.query(dict(r), timeout=WAIT) for r in records
        ]
        remote = [
            f.result(timeout=WAIT)
            for f in [rep.submit(dict(r)) for r in records]
        ]
        assert sum(1 for r in local if not r.shed) == len(records)
        for lo, re in zip(local, remote):
            assert not re.shed, re.reason
            assert len(lo.matches) == len(re.matches)
            for (lu, lp), (ru, rp) in zip(lo.matches, re.matches):
                assert str(lu) == str(ru)
                assert lp == rp  # bitwise: JSON round-trips doubles exactly
            assert lo.n_candidates == re.n_candidates
            assert lo.approx == re.approx
    finally:
        rep.close()
        server.close()
        svc.close()


# ---------------------------------------------------------------------------
# Connection cap (wire_max_connections)
# ---------------------------------------------------------------------------


def _handshake(sock, req_id=0):
    """One health exchange: proves the server fully registered the
    connection (the accept loop admits sequentially)."""
    sock.sendall(encode_frame(
        {"v": WIRE_VERSION, "kind": "health", "id": req_id}))
    env = read_frame(sock)
    assert env is not None and env.get("v") == WIRE_VERSION
    return env


def test_connection_cap_sheds_with_error_frame():
    """The (cap+1)-th connection is answered with ONE machine-readable
    `server_overloaded` error envelope and closed — an explicit shed a
    client can distinguish from a partition or a crash."""
    svc = FakeService()
    server = WireServer(svc, max_connections=2).start()
    socks = []
    try:
        for _ in range(2):
            s = socket.create_connection(("127.0.0.1", server.port),
                                         timeout=WAIT)
            _handshake(s)
            socks.append(s)
        over = socket.create_connection(("127.0.0.1", server.port),
                                        timeout=WAIT)
        socks.append(over)
        over.settimeout(WAIT)
        env = read_frame(over)
        assert env == {
            "v": WIRE_VERSION, "kind": "error", "id": None,
            "reason": "server_overloaded", "health": "healthy",
        }
        assert read_frame(over) is None  # then EOF: the socket is closed
        deadline = time.monotonic() + WAIT
        while server.stats()["overloaded_total"] < 1:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        stats = server.stats()
        assert stats["overloaded_total"] == 1
        assert stats["max_connections"] == 2
        assert stats["connections_active"] == 2  # the refused conn never joined
        # the refusal rides the Prometheus exposition too
        samples = {s.name: s.value for s in server.prometheus_samples()}
        assert samples["splink_wire_overloaded_total"] == 1
        # a slot freed by a disconnect re-admits the next dial
        socks[0].close()
        deadline = time.monotonic() + WAIT
        while server.stats()["connections_active"] >= 2:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        again = socket.create_connection(("127.0.0.1", server.port),
                                         timeout=WAIT)
        socks.append(again)
        _handshake(again)
    finally:
        for s in socks:
            try:
                s.close()
            except OSError:
                pass
        server.close()


def test_remote_replica_sheds_past_the_cap():
    """A RemoteReplica dialing a full server fails its liveness handshake
    on the error envelope (no half-dead pooled socket) and submits shed
    machine-readably instead of hanging."""
    svc = FakeService()
    server = WireServer(svc, max_connections=1).start()
    holder = None
    rep = None
    try:
        holder = socket.create_connection(("127.0.0.1", server.port),
                                          timeout=WAIT)
        _handshake(holder)
        rep = _remote(server, eager_connect=False)
        res = rep.submit({"tag": "over"}).result(timeout=WAIT)
        assert res.shed and res.reason == "remote_unreachable"
        assert server.stats()["overloaded_total"] >= 1
        # the slot frees -> the same replica recovers on a later submit
        holder.close()
        holder = None
        deadline = time.monotonic() + WAIT
        while time.monotonic() < deadline:
            res = rep.submit({"tag": "retry"}).result(timeout=WAIT)
            if not res.shed:
                break
            time.sleep(0.05)
        assert not res.shed and res.matches == [("retry", 0.5)]
    finally:
        if rep is not None:
            rep.close()
        if holder is not None:
            holder.close()
        server.close()
