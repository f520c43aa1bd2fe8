"""Retry with bounded exponential backoff + failure classification.

Failures worth retrying (a platform that reports a transient status) are
told apart from failures that never heal (broken install, shape bug,
schema error). The classifier below encodes it: gRPC/XLA status markers
and connection errors are transient; everything else is deterministic and
propagates immediately. Three consecutive IDENTICAL failures end the retry
budget early, because an error that reproduces byte-for-byte three times
is deterministic no matter what its class says.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass

logger = logging.getLogger("splink_tpu")

# Substrings marking a transient platform failure (gRPC status names XLA
# embeds in RuntimeError text, plus connection-drop phrasing).
# RESOURCE_EXHAUSTED is transient HERE (device memory often
# frees after in-flight buffers drain); the resident EM path additionally
# treats it as a degradation trigger via is_oom().
TRANSIENT_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "UNAVAILABLE",
    "DEADLINE_EXCEEDED",
    "ABORTED",
    "INTERNAL",
    "Socket closed",
    "connection reset",
    "Connection reset",
    "failed to connect",
)

OOM_MARKERS = ("RESOURCE_EXHAUSTED", "out of memory", "Out of memory", "OOM")

TRANSIENT_TYPES = (ConnectionError, TimeoutError, BrokenPipeError)


class RetryError(RuntimeError):
    """Retry budget exhausted (the original failure rides as __cause__)."""


@dataclass
class RetryPolicy:
    """Bounded exponential backoff: delay_k = min(base * mult^k, max)."""

    max_retries: int = 4  # retries, i.e. up to 1 + max_retries attempts
    base_delay: float = 0.5
    max_delay: float = 30.0
    multiplier: float = 2.0
    max_identical_failures: int = 3

    def delay(self, attempt: int) -> float:
        return min(self.base_delay * self.multiplier**attempt, self.max_delay)


def is_oom(exc: BaseException) -> bool:
    """Whether an exception is a device out-of-memory condition — the
    trigger for resident -> streamed degradation (linker._run_em)."""
    from .faults import InjectedFault

    if isinstance(exc, InjectedFault):
        return exc.kind == "oom"
    text = f"{type(exc).__name__}: {exc}"
    return any(m in text for m in OOM_MARKERS)


def classify_error(exc: BaseException) -> str:
    """'transient' (worth retrying) or 'deterministic' (propagate now)."""
    from .faults import InjectedFault

    if isinstance(exc, InjectedFault):
        return "deterministic" if exc.kind == "kill" else "transient"
    if isinstance(exc, TRANSIENT_TYPES):
        return "transient"
    text = f"{type(exc).__name__}: {exc}"
    if any(m in text for m in TRANSIENT_MARKERS):
        return "transient"
    return "deterministic"


def retry_call(
    fn,
    *,
    policy: RetryPolicy | None = None,
    classify=classify_error,
    label: str = "",
    sleep=time.sleep,
    on_retry=None,
):
    """Call ``fn()`` with bounded-backoff retry on transient failures.

    Deterministic failures propagate immediately; so does the
    ``max_identical_failures``-th consecutive byte-identical failure
    (wrapped in RetryError so callers can tell budget exhaustion from the
    first occurrence). ``sleep`` is injectable so tests run at full speed.
    """
    policy = policy or RetryPolicy()
    last_repr = None
    identical = 0
    for attempt in range(policy.max_retries + 1):
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 - classification decides
            kind = classify(e)
            this_repr = f"{type(e).__name__}: {e}"
            identical = identical + 1 if this_repr == last_repr else 1
            last_repr = this_repr
            if kind != "transient":
                raise
            if identical >= policy.max_identical_failures:
                raise RetryError(
                    f"{label or 'operation'}: {identical} consecutive "
                    f"identical failures, aborting as deterministic: "
                    f"{this_repr}"
                ) from e
            if attempt >= policy.max_retries:
                raise RetryError(
                    f"{label or 'operation'}: retry budget exhausted after "
                    f"{attempt + 1} attempts: {this_repr}"
                ) from e
            delay = policy.delay(attempt)
            logger.warning(
                "%s: transient failure (attempt %d/%d), retrying in %.1fs: %s",
                label or "operation",
                attempt + 1,
                policy.max_retries + 1,
                delay,
                this_repr,
            )
            from ..obs.events import publish

            publish(
                "retry",
                label=label or "operation",
                attempt=attempt + 1,
                max_attempts=policy.max_retries + 1,
                delay_s=delay,
                error=this_repr[:300],
            )
            if on_retry is not None:
                on_retry(attempt, e)
            sleep(delay)
    raise AssertionError("unreachable")  # pragma: no cover
