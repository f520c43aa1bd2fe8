"""Fault tolerance for long EM runs on preemptible accelerator fleets.

The reference implementation inherits restartability from Spark (a failed
stage re-executes from the last shuffle); splink_tpu's fused device EM has
no such safety net — a device loss, OOM or host death mid-run used to throw
away the whole job. This package is the TPU-native answer, exploiting the
fact that the ENTIRE training state is a few small arrays (lambda, m, u,
histories, iteration counter):

  * :mod:`checkpoint` — atomic on-disk snapshots (write-temp + fsync +
    rename), versioned and bound to a settings/gamma-program hash so stale
    checkpoints are rejected rather than silently loaded.
  * :mod:`retry` — bounded exponential backoff around streamed batch fetch
    and device put/execute, classifying transient failures (RESOURCE_EXHAUSTED,
    RPC drops) from deterministic ones.
  * :mod:`faults` — deterministic fault injection (env/settings-driven), so
    every recovery path has a test that actually exercises it.

Degradation when a regime fails outright: resident EM -> streamed EM
(docs/resilience.md). An accelerator that does not come up raises; nothing
falls back to the CPU backend.
"""

from .checkpoint import (  # noqa: F401
    CheckpointError,
    CheckpointMismatchError,
    EMCheckpoint,
    EMCheckpointer,
    load_checkpoint,
    save_checkpoint,
    settings_state_hash,
)
from .faults import FaultPlan, InjectedFault, active_plan  # noqa: F401
from .retry import (  # noqa: F401
    RetryError,
    RetryPolicy,
    classify_error,
    is_oom,
    retry_call,
)

__all__ = [
    "CheckpointError",
    "CheckpointMismatchError",
    "EMCheckpoint",
    "EMCheckpointer",
    "load_checkpoint",
    "save_checkpoint",
    "settings_state_hash",
    "FaultPlan",
    "InjectedFault",
    "active_plan",
    "RetryError",
    "RetryPolicy",
    "classify_error",
    "is_oom",
    "retry_call",
]
