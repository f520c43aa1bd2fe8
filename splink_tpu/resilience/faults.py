"""Deterministic fault injection for the EM execution stack.

Every recovery path in this package (pass retry, checkpoint resume, OOM
degradation) must have a test that actually exercises it. Real device losses are not reproducible in CI, so
the execution stack carries explicit, deterministic injection points that
fire according to a plan parsed from the ``SPLINK_TPU_FAULTS`` environment
variable or the ``fault_plan`` settings key.

Plan grammar (comma-separated events)::

    <site>@key=value[:key=value...]

    batch_fetch@iter=2:batch=3            transient stream error (default kind)
    batch_fetch@iter=1:batch=0:kind=oom   simulated RESOURCE_EXHAUSTED
    em_iteration@iter=4:kind=kill         SIGKILL own process at iteration 4
    resident_em@kind=oom                  device OOM entering the resident path
    segment@iter=10:kind=transient        error at a segmented-EM boundary
    serve_batch@batch=1:kind=slow:delay_ms=400   stall one serve batch 400ms
    wire_response@kind=net_torn_frame     cut one wire reply mid-frame
    wire_accept@kind=net_partition:delay_ms=500  drop + refuse conns 500ms

Sites are the hook names the execution stack calls (`fire`); ``iter`` /
``batch`` constrain when the event matches (omitted = any). ``times``
bounds how often an event fires (default 1), so a retried pass sees the
fault exactly once and then succeeds — which is what makes bit-identical
recovery assertions possible.

The kill kind uses SIGKILL (no atexit, no finally blocks), faithfully
modelling host death for the checkpoint/resume tests; the relaunching
parent controls the environment, so a resumed process does not re-fire.
The slow kind SLEEPS ``delay_ms`` (default 250) and returns — it models a
stalled device dispatch rather than a failed one, for deadline/timeout
paths that only misbehave when work is late, not absent.

Serve-path fault sites (SERVE_SITES; exercised end to end by
``scripts/chaos_smoke.py`` / ``make chaos-smoke``):

    serve_worker    top of the micro-batch worker loop, OUTSIDE the batch
                    try block — a raise here kills the worker thread
                    (coords: batch=completed batch count), the failure the
                    service watchdog exists to recover from
    serve_batch     inside the per-batch scoring try block (coords:
                    batch=batch ordinal) — an exception here must shed
                    the batch, never escape to callers, and feeds the
                    circuit breaker; kind=slow stalls the batch instead
    swap_load       QueryEngine.swap_index, before loading the candidate
                    index (models unreadable/corrupt artifact files)
    swap_validate   QueryEngine.swap_index, before the parity-probe
                    replay commits — a raise rolls the swap back with the
                    old index still serving

Offline write-path sites (BUILD_SITES; the kill-and-resume contract of
the billion-row build — tests/test_spill_resume.py, ``make scale-smoke``):

    emit_segment    sharded spill emission (blocking_device.
                    emit_pairs_sharded), fired AFTER a segment's bytes are
                    appended + fsynced but BEFORE its manifest commit —
                    the widest window a kill can tear; a resumed driver
                    truncates the torn tail and re-emits the segment
                    byte-identically (coords: rule, shard, seq)
    build_chunk     out-of-core packed-matrix writer (serve/index.
                    _pack_table_out_of_core), fired between a chunk's
                    byte append and its build_state.json watermark commit
                    (coords: chunk)
"""

from __future__ import annotations

import logging
import os
import signal
import time

logger = logging.getLogger("splink_tpu")

ENV_VAR = "SPLINK_TPU_FAULTS"

_KINDS = (
    "transient", "oom", "kill", "slow",
    "net_drop", "net_delay", "net_torn_frame", "net_partition",
)

DEFAULT_SLOW_DELAY_MS = 250

# The serve-path injection points (documented above); chaos_smoke drives
# every one of them and asserts the service-level recovery contract.
SERVE_SITES = ("serve_worker", "serve_batch", "swap_load", "swap_validate")

# The offline write-path injection points (documented above); the
# kill-and-resume tests and scale_smoke aim these at the commit windows of
# the spill emission driver and the out-of-core index build.
BUILD_SITES = ("emit_segment", "build_chunk")

# The wire-tier injection points (serve/wire.py; exercised end to end by
# ``scripts/wire_chaos_smoke.py`` / ``make wire-smoke``). The net_* kinds
# model link failures rather than compute failures:
#
#     net_drop        the connection dies abruptly at the site (server
#                     closes the socket with no reply; the client must
#                     resolve every in-flight future as a shed)
#     net_delay       the link stalls delay_ms then continues — drives the
#                     hedger and deadline propagation, like kind=slow
#     net_torn_frame  a frame is cut mid-write (length prefix promises
#                     more bytes than arrive) — the reader must reject it
#                     without poisoning the connection state
#     net_partition   the host becomes unreachable for delay_ms: every
#                     live connection drops AND new connects are refused
#                     until the partition heals
WIRE_SITES = ("wire_accept", "wire_request", "wire_response")


class InjectedFault(RuntimeError):
    """A deliberately injected failure.

    The message embeds the marker string the retry classifier keys on for
    the requested kind, so injected faults exercise the SAME classification
    code path as real ones (``RESOURCE_EXHAUSTED`` for oom, a
    connection-drop message for transient).
    """

    def __init__(
        self, site: str, kind: str, coords: dict,
        delay_ms: int = DEFAULT_SLOW_DELAY_MS,
    ):
        self.site = site
        self.kind = kind
        self.coords = dict(coords)
        # net_partition repurposes delay_ms as the partition duration; the
        # wire server reads it off the caught fault to schedule the heal
        self.delay_ms = delay_ms
        marker = (
            "RESOURCE_EXHAUSTED: injected device OOM"
            if kind == "oom"
            else "UNAVAILABLE: Socket closed (injected connection drop)"
        )
        super().__init__(f"injected fault at {site} {coords}: {marker}")


class _Event:
    __slots__ = ("site", "kind", "match", "times", "delay_ms")

    def __init__(
        self,
        site: str,
        kind: str,
        match: dict,
        times: int,
        delay_ms: int = DEFAULT_SLOW_DELAY_MS,
    ):
        self.site = site
        self.kind = kind
        self.match = match  # {"iter": int, "batch": int, ...}
        self.times = times
        self.delay_ms = delay_ms

    def matches(self, site: str, coords: dict) -> bool:
        if self.times <= 0 or site != self.site:
            return False
        return all(coords.get(k) == v for k, v in self.match.items())


class FaultPlan:
    """A parsed, stateful fault plan. ``fire(site, **coords)`` is called at
    each injection point; matching events decrement their budget and then
    raise (or kill). An empty plan is a no-op, so the hooks cost one
    attribute check on the production path."""

    def __init__(self, events: list[_Event] | None = None, spec: str = ""):
        self.events = events or []
        self.spec = spec

    def __bool__(self) -> bool:
        return bool(self.events)

    @classmethod
    def from_spec(cls, spec: str | None) -> "FaultPlan":
        spec = (spec or "").strip()
        if not spec:
            return cls()
        events = []
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            site, _, argstr = part.partition("@")
            kind, times, match = "transient", 1, {}
            delay_ms = DEFAULT_SLOW_DELAY_MS
            for kv in filter(None, argstr.split(":")):
                key, _, value = kv.partition("=")
                key = key.strip()
                if key == "kind":
                    if value not in _KINDS:
                        raise ValueError(
                            f"fault plan {part!r}: kind must be one of {_KINDS}"
                        )
                    kind = value
                elif key == "times":
                    times = int(value)
                elif key == "delay_ms":
                    delay_ms = int(value)
                else:
                    match[key] = int(value)
            events.append(_Event(site.strip(), kind, match, times, delay_ms))
        return cls(events, spec)

    def fire(self, site: str, **coords) -> None:
        """Raise/kill/stall if an event matches this (site, coords); else
        no-op."""
        if not self.events:
            return
        for ev in self.events:
            if ev.matches(site, coords):
                ev.times -= 1
                # emit BEFORE raising/killing: the telemetry record must
                # show the fault that a kill prevents any later code from
                # reporting (the sink flushes per event)
                from ..obs.events import publish

                publish("fault", site=site, kind=ev.kind, coords=dict(coords))
                if ev.kind in ("slow", "net_delay"):
                    logger.warning(
                        "fault injection: stalling %s %s for %dms",
                        site, coords, ev.delay_ms,
                    )
                    time.sleep(ev.delay_ms / 1000.0)
                    continue  # a stall completes; later events may still fire
                if ev.kind == "kill":
                    logger.warning(
                        "fault injection: SIGKILL self at %s %s", site, coords
                    )
                    os.kill(os.getpid(), signal.SIGKILL)
                raise InjectedFault(site, ev.kind, coords, ev.delay_ms)


# One live plan per spec string: event budgets (``times``) must be shared
# by every hook in the process or a once-only fault would re-fire at each
# injection site that consults the plan.
_PLAN_CACHE: dict[str, FaultPlan] = {}


def active_plan(settings: dict | None = None) -> FaultPlan:
    """The process's active fault plan: ``SPLINK_TPU_FAULTS`` env var first,
    else the ``fault_plan`` settings key, else an empty (no-op) plan."""
    spec = os.environ.get(ENV_VAR) or (settings or {}).get("fault_plan") or ""
    if spec not in _PLAN_CACHE:
        _PLAN_CACHE[spec] = FaultPlan.from_spec(spec)
    return _PLAN_CACHE[spec]


def reset_plans() -> None:
    """Forget fired-event state (tests only)."""
    _PLAN_CACHE.clear()
